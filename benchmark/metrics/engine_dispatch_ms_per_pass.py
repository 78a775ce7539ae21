"""Median over traced decoding passes of the summed apex_tpu.inference.* spans inside the pass: host time spent enqueueing programs."""
from benchmark import spans


def read(run):
    return spans.engine_dispatch_ms_per_pass(run.trace)
