"""Least bytes of the traced decode steps (resident weights + held experts hit once a step + one latent row a layer a position attended) over HBM bandwidth, over the device time of jit_decode*."""
from benchmark import counts_mla


def read(run):
    return counts_mla.decode_roofline(run)
