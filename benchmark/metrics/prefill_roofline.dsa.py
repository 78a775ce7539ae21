"""Per traced prefill the larger of FLOPs over peak and bytes over bandwidth at its real prompt length (picked positions only), summed, over the device time of jit_prefill*."""
from benchmark import counts_dsa


def read(run):
    return counts_dsa.prefill_roofline(run)
