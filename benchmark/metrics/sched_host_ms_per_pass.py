"""Median over traced decoding passes of the apex_tpu.scheduler.pass span less the apex_tpu.inference.* and apex_tpu.scheduler.token_read spans inside it: the scheduler's own host work."""
from benchmark import spans


def read(run):
    return spans.sched_host_ms_per_pass(run.trace)
