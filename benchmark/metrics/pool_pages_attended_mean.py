"""Mean over the window's passes of the pages that held a token some active slot attended."""
import numpy as np

from benchmark import reduce


def read(run):
    return reduce.pool_pages(run.facts, 1, np.mean)
