"""Median host wall of a scheduler pass that only decoded (dispatch plus token read)."""
from benchmark import reduce


def read(run):
    return reduce.decode_step_ms_p50(run.facts)
