"""Mean over the window's passes of the pages the allocator held out (reservations + what the prefix cache keeps)."""
import numpy as np

from benchmark import reduce


def read(run):
    return reduce.pool_pages(run.facts, 0, np.mean)
