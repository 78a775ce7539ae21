"""Least time of the traced grouped products (experts hit read once a step, activations, 2*3*hidden*width FLOPs an assignment) over the device time moe_products_ms_per_pass reads."""
from benchmark import counts_moe


def read(run):
    return counts_moe.moe_products_roofline(run)
