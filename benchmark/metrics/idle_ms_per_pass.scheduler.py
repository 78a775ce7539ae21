"""Chip-0 idle ms a traced pass whose innermost host span is apex_tpu.scheduler.*."""
from benchmark import spans


def read(run):
    return spans.idle_ms_per_pass(run.trace, 'scheduler')
