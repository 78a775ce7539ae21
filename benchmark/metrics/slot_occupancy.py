"""Active slots summed over decode steps over steps times slots, from the program's counters."""
from benchmark import reduce


def read(run):
    return reduce.slot_occupancy(run.facts)
