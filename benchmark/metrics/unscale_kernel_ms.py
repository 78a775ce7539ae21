"""Device ms per traced step of the operations named %apex_amp_unscale* (the loss-scale unscale Pallas kernel, by its name=)."""
from benchmark import reduce


def read(run):
    return reduce.ops_ms_per_step(run, r'^%apex_amp_unscale')
