"""The prefill attention kernel at 192-wide scores and 128-wide values: causal pairs counted once, max(FLOPs / peak, bytes / bandwidth) over the device time of apex_flash_fwd."""
from benchmark import counts_mla


def read(run):
    return counts_mla.mla_flash_roofline(run)
