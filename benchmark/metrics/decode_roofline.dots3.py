"""Least bytes of the traced decode steps (resident weights, held experts hit, index keys scored and latent rows picked on full layers, 2,176 B window rows on window layers) over their device time."""
from benchmark import counts_dots3


def read(run):
    return counts_dots3.decode_roofline(run)
