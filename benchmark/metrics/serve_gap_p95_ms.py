"""95th percentile of all gaps between consecutive tokens of one request, pooled."""
from benchmark import reduce


def read(run):
    return reduce.percentile(reduce.serve_gaps_ms(run.facts), 95)
