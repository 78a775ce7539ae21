"""Device ms a traced pass of the expert FFN's grouped products (XLA's %ragged-dot-* and the fusions that read them) and router (ops experts wide); sort, gather, combine and shared expert are not found."""
from benchmark import counts_moe


def read(run):
    return counts_moe.moe_products_ms_per_pass(run)
