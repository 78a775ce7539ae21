"""Median host wall of one step: batch, dispatch and sync."""
from benchmark import reduce


def read(run):
    return reduce.train_step_ms_p50(run.facts)
