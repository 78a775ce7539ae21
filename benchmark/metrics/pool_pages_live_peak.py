"""Most pages the allocator held out after any pass of the window (reservations + what the prefix cache keeps)."""
from benchmark import reduce


def read(run):
    return reduce.pool_pages(run.facts, 0, max)
