"""Per traced prefill max(FLOPs / peak, bytes / bandwidth) by counts_moe, over the device time of jit_prefill*."""
from benchmark import counts_moe


def read(run):
    return counts_moe.prefill_roofline(run)
