"""The decode's ring attention: 2,176 B and 2x64x(1,088+1,024) FLOPs a ring position attended by a window layer (swa_attended), over the decode part of apex_swa_attend's time."""
from benchmark import counts_dots3


def read(run):
    return counts_dots3.swa_latent_decode_roofline(run)
