"""Least bytes of the traced decode steps (weights once a step + live K/V) over HBM bandwidth, over the device time of the jit_decode* programs."""
from benchmark import reduce


def read(run):
    return reduce.decode_roofline(run, r'^jit_decode')
