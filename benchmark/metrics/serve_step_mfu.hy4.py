"""Share of the chip's bf16 peak over the work the window completed: 2 FLOPs a parameter of the matrices a token is active in (MLA, gate, indexer on full layers, the streams' mixes, router, shared expert, dense FFN), the experts that landed, the streams' mixing, index scores over the contexts scored, attention over the PICKED positions only, a head row-block a sampled token."""
from benchmark import counts_hy4


def read(run):
    return counts_hy4.serve_step_mfu(run)
