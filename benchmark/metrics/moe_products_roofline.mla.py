"""Least time of the traced grouped products (held experts hit read once a step, activations, 2*3*hidden*width FLOPs an assignment that LANDED) over the device time moe_products_ms_per_pass.mla reads."""
from benchmark import counts_mla


def read(run):
    return counts_mla.moe_products_roofline(run)
