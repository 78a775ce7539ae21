"""apex_dsa_attend_latent against the least work: one 1,152 B latent row and 2x64x(576+512) FLOPs a position picked a layer; reads low by design while the walk reads every live page."""
from benchmark import counts_hy4


def read(run):
    return counts_hy4.dsa_attend_latent_roofline(run)
