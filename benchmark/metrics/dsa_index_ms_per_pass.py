"""Device ms a traced pass of the index kernels (ops named %apex_dsa_index*: decode's over the paged index keys, prefill's over a block of rows)."""
from benchmark import counts_dsa


def read(run):
    return counts_dsa.dsa_index_ms_per_pass(run)
