"""XLA compile events counted inside the measured window (must read 0)."""
from benchmark import reduce


def read(run):
    return float(run.facts['compiles_in_window'])
