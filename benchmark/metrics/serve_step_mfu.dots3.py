"""Share of the chip's bf16 peak over the work the window completed: 2 FLOPs a parameter of the matrices a token is active in (both MLA geometries with their gates, the indexer on full layers, router, shared expert, dense FFN), the experts that landed, index scores over the contexts scored, attention over the PICKED positions of full layers and the WINDOW of window layers, a head row-block a sampled token."""
from benchmark import counts_dots3


def read(run):
    return counts_dots3.serve_step_mfu(run)
