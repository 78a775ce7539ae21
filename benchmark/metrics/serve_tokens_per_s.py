"""Prompt tokens prefilled plus tokens generated inside the window, over the window."""
from benchmark import reduce


def read(run):
    return reduce.serve_tokens_per_s(run.facts)
