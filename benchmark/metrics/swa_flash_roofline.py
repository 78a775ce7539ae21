"""The prefill's windowed flash kernel: 2x64x(256+128) FLOPs a (query, key in its window of 513) pair at the real prompt lengths and q/k/v/out once, over the prefill part of apex_swa_attend's time."""
from benchmark import counts_dots3


def read(run):
    return counts_dots3.swa_flash_roofline(run)
