"""Device ms a traced pass of apex_dsa_attend_latent, the decode's latent attention over the picked positions, all layers."""
from benchmark import counts_hy4


def read(run):
    return counts_hy4.dsa_attend_latent_ms_per_pass(run)
