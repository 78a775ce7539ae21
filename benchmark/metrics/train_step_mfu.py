"""The whole step's share of the chip's bf16 peak: least FLOPs per token times tokens per second."""
from benchmark import reduce


def read(run):
    return reduce.train_step_mfu(run)
