"""Device ms a traced pass of the whole expert FFN: every operation under an apex_moe_* scope (route, sort, experts, combine, shared), both phases."""
from benchmark import scopes


def read(run):
    return scopes.ms_per_pass(run, scopes.MOE_STAGES)
