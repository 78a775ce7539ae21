"""Device ms a traced pass of the held experts' grouped products (XLA's %ragged-dot-* and the fusions that read them) and the router (rank-2 float32, int32 or bool results [tokens, 192]; attention's [.., heads, 192] is not found); sort, gather, combine and shared expert are not found."""
from benchmark import counts_mla


def read(run):
    return counts_mla.moe_products_ms_per_pass(run)
