"""Device ms a traced pass under apex_swa_attend: the window layers' latent attention, both phases (ring decode, windowed flash prefill)."""
from benchmark import counts_dots3


def read(run):
    return counts_dots3.swa_latent_ms_per_pass(run)
