"""Device ms a traced pass of the expert FFN's routing and data movement: the apex_moe_route, apex_moe_sort and apex_moe_combine scopes, both phases."""
from benchmark import scopes


def read(run):
    return scopes.ms_per_pass(run, scopes.MOE_DISPATCH)
