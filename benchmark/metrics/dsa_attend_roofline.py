"""The decode's attention kernel against the LEAST work: 2,048 B and 2*32*256 FLOPs a PICKED position a layer, over the device time of %apex_dsa_attend*."""
from benchmark import counts_dsa


def read(run):
    return counts_dsa.dsa_attend_roofline(run)
