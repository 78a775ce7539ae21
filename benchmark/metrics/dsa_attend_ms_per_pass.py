"""Device ms a traced pass of the decode's attention over the picked rows (ops named %apex_dsa_attend*)."""
from benchmark import counts_dsa


def read(run):
    return counts_dsa.dsa_attend_ms_per_pass(run)
