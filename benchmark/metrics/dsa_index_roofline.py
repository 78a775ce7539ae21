"""The decode's index kernel against its least time: 128 B and 2*16*64 FLOPs a position scored a layer, over the device time of %apex_dsa_index."""
from benchmark import counts_dsa


def read(run):
    return counts_dsa.dsa_index_roofline(run)
