"""Median time from one step's end to the next's, as the host sees them end."""
from benchmark import reduce


def read(run):
    return reduce.train_step_ms_p50(run.facts)
