"""Device ms a traced pass of the selection, which is XLA's: the operations that read or write a rank-2 uint32 array (the scores' order-preserving image)."""
from benchmark import counts_dsa


def read(run):
    return counts_dsa.dsa_select_ms_per_pass(run)
