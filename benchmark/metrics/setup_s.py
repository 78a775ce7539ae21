"""Process start to the first timed unit of work, by the host's clock."""
from benchmark import reduce


def read(run):
    return run.setup_s
