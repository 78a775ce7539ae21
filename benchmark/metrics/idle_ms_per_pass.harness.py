"""Chip-0 idle ms a traced pass under the benchmark's own spans (bench.*) or no span at all."""
from benchmark import spans


def read(run):
    return spans.idle_ms_per_pass(run.trace, 'harness')
