"""The whole serving loop's share of the chip's bf16 peak over the work the window completed, by counts_moe (active matrices only)."""
from benchmark import counts_moe


def read(run):
    return counts_moe.serve_step_mfu(run)
