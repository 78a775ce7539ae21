"""Busiest expert's tokens over the mean expert's, over the prefill passes (program counter): the straggler."""
from benchmark import counts_moe


def read(run):
    return counts_moe.moe_load_max_over_mean(run)
