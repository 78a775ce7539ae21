"""Least time of the grouped products of the traced steps (experts hit read once a step, 2*3*2048*768 FLOPs an assignment) over the device time moe_products_ms_per_pass.dsa reads."""
from benchmark import counts_dsa


def read(run):
    return counts_dsa.moe_products_roofline(run)
