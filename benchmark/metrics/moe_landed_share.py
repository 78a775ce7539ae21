"""Of the (token, expert) assignments of both phases (8 a token an expert layer), the share that LANDED on a held expert (program counter): 6.25% under even routing."""
from benchmark import counts_mla


def read(run):
    return counts_mla.moe_landed_share(run)
