"""95th percentile over all requests due in the window of first token minus due time (swings with the order of arrivals: per-layer, not bounded)."""
from benchmark import reduce


def read(run):
    return reduce.percentile(reduce.serve_ttft_ms(run.facts), 95)
