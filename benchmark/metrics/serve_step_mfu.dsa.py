"""Share of the chip's bf16 peak over the work the window completed: 2 FLOPs a parameter of the matrices a token is active in (attention, indexer, router, 8 experts), index scores over the contexts scored, attention over the PICKED positions only, a head row-block a sampled token."""
from benchmark import counts_dsa


def read(run):
    return counts_dsa.serve_step_mfu(run)
