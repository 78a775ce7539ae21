"""apex_tpu.inference.* spans inside traced apex_tpu.scheduler.pass spans, per traced pass."""
from benchmark import spans


def read(run):
    return spans.dispatches_per_pass(run.trace)
