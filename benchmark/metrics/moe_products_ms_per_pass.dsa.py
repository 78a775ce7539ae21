"""Device ms a traced pass of the experts' grouped products (%ragged-dot-* and the fusions that read them) and the router (rank-2 float32 / int32 / bool results [tokens, 128])."""
from benchmark import counts_dsa


def read(run):
    return counts_dsa.moe_products_ms_per_pass(run)
