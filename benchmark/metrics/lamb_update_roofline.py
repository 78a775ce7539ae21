"""Least bytes a LAMB update streams (28 B/parameter) over HBM bandwidth, over lamb_update_ms."""
from benchmark import reduce


def read(run):
    return reduce.lamb_update_roofline(run)
