"""Per traced prefill max(FLOPs / peak, bytes / bandwidth) by counts_mla (expanded form), over the device time of jit_prefill*."""
from benchmark import counts_mla


def read(run):
    return counts_mla.prefill_roofline(run)
