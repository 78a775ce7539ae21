"""apex_dsa_attend_latent on the full layers against the least work: one 1,152 B latent row and 2x128x(576+512) FLOPs a position picked; reads low by design while the walk reads every live page."""
from benchmark import counts_dots3


def read(run):
    return counts_dots3.dsa_attend_latent_roofline(run)
