"""Least bytes of the traced decode steps (resident weights + experts hit once a step + 128 B a position scored + 2,048 B a position picked, a layer) over HBM bandwidth, over the device time of jit_decode*."""
from benchmark import counts_dsa


def read(run):
    return counts_dsa.decode_roofline(run)
