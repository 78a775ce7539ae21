"""95th percentile of admitted (start of the admitting pass) minus due."""
from benchmark import reduce


def read(run):
    return reduce.percentile(reduce.queue_wait_ms(run.facts), 95)
