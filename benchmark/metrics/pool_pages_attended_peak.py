"""Most pages that held a token some active slot attended, after any pass of the window."""
from benchmark import reduce


def read(run):
    return reduce.pool_pages(run.facts, 1, max)
