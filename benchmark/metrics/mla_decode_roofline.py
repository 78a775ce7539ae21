"""The latent decode kernel's own least time (each position attended read once a layer, 2*heads*(576+512) FLOPs a position, the larger) over the device time of apex_paged_decode_latent."""
from benchmark import counts_mla


def read(run):
    return counts_mla.mla_decode_roofline(run)
