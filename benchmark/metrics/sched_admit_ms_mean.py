"""Mean over the traced passes that prefilled of the apex_tpu.scheduler.admit span less the apex_tpu.inference.* spans inside it."""
from benchmark import spans


def read(run):
    return spans.sched_admit_ms_mean(run.trace)
