"""Over the prefill passes: the busiest expert's tokens over the mean expert's (program counters)."""
from benchmark import counts_dsa


def read(run):
    return counts_dsa.moe_load_max_over_mean(run)
