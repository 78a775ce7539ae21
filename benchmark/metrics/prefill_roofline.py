"""Least time of the traced prefills (max of FLOPs/peak and bytes/bandwidth) over the device time of the jit_prefill* programs."""
from benchmark import reduce


def read(run):
    return reduce.prefill_roofline(run, r'^jit_prefill')
