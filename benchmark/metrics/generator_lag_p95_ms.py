"""95th percentile of sent minus due: how late the load generator ran."""
from benchmark import reduce


def read(run):
    return reduce.percentile(reduce.generator_lag_ms(run.facts), 95)
