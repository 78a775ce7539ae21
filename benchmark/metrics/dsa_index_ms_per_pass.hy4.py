"""Device ms a traced pass of the index kernels (ops named %apex_dsa_index*: decode's over the paged index keys, prefill's over a block of rows; the full layers only)."""
from benchmark import counts_hy4


def read(run):
    return counts_hy4.dsa_index_ms_per_pass(run)
