"""Device ms a traced pass of apex_paged_decode_latent, all layers."""
from benchmark import counts_mla


def read(run):
    return counts_mla.mla_decode_ms_per_pass(run)
