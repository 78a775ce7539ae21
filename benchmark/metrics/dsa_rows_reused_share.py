"""Of the query rows x layers that attended a selection (both phases), the share that attended a pick set carried from an earlier layer (program counter dsa_rows_reused)."""
from benchmark import counts_hy4


def read(run):
    return counts_hy4.dsa_rows_reused_share(run)
