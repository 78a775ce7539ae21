"""Over the prefill passes, the busiest held expert's tokens over the mean held expert's: landed assignments over (held experts x expert layers) (program counter)."""
from benchmark import counts_mla


def read(run):
    return counts_mla.moe_load_max_over_mean(run)
