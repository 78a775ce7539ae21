"""peak_bytes_in_use of the fullest chip after the window, before the reference runs."""
from benchmark import reduce


def read(run):
    return reduce.peak_hbm_gib(run.facts)
