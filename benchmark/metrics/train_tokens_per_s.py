"""Tokens of all steps completed in the window over first start to last end."""
from benchmark import reduce


def read(run):
    return reduce.train_tokens_per_s(run.facts)
