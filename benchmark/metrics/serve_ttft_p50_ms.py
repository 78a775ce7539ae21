"""Median over all requests due in the window of first token on the host minus the time the request was due."""
from benchmark import reduce


def read(run):
    return reduce.percentile(reduce.serve_ttft_ms(run.facts), 50)
