"""Device ms a traced pass under the residual streams' scopes (apex_hc_pre, apex_hc_post, apex_hc_head), both phases."""
from benchmark import counts_hy4


def read(run):
    return counts_hy4.hc_ms_per_pass(run)
