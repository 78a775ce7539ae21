"""Of the positions the counted queries could have attended (their contexts, both phases), the share they did (program counter dsa_selected): how much of the cache the steps really read."""
from benchmark import counts_dsa


def read(run):
    return counts_dsa.dsa_selected_share(run)
