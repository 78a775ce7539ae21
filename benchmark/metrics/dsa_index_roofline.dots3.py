"""The decode's apex_dsa_index on the full layers against its least time: one 256 B index key and 2x64x128 FLOPs a position scored."""
from benchmark import counts_dots3


def read(run):
    return counts_dots3.dsa_index_roofline(run)
