"""The whole serving loop's share of the chip's bf16 peak over the work the window completed."""
from benchmark import reduce


def read(run):
    return reduce.serve_step_mfu(run)
