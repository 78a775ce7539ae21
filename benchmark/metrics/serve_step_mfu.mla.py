"""The whole serving loop's share of the chip's bf16 peak over the work the window completed, by counts_mla (matrices a token is active in, assignments that LANDED from the counter, attention over the contexts attended)."""
from benchmark import counts_mla


def read(run):
    return counts_mla.serve_step_mfu(run)
