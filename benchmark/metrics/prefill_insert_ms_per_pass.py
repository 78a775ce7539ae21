"""Device ms a traced pass of writing prompts' cache rows into the pool (and rings, index keys): the apex_prefill_cache_insert scope."""
from benchmark import scopes


def read(run):
    return scopes.ms_per_pass(run, ("apex_prefill_cache_insert",))
