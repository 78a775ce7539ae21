"""Least bytes of the traced decode steps (resident weights + experts hit once a step + K/V attended, window layers capped) over HBM bandwidth, over the device time of jit_decode*."""
from benchmark import counts_moe


def read(run):
    return counts_moe.decode_roofline(run)
