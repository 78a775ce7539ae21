"""Device ms a traced step of the train step's backward: the apex_train_forward scope's transpose."""
from benchmark import scopes


def read(run):
    return scopes.ms_per_step(run, ("apex_train_forward",), backward=True)
