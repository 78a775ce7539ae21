"""The decode's apex_dsa_index against its least time: 256 B and 2x32x128 FLOPs a position scored a FULL layer."""
from benchmark import counts_hy4


def read(run):
    return counts_hy4.dsa_index_roofline(run)
