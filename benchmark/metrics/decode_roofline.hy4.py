"""Least bytes of the traced decode steps (resident weights with the float32 head, held experts hit, index keys scored on full layers, latent rows picked) over their device time."""
from benchmark import counts_hy4


def read(run):
    return counts_hy4.decode_roofline(run)
