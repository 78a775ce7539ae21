"""Device ms a traced step of the train step's forward: the apex_train_forward scope, not its backward."""
from benchmark import scopes


def read(run):
    return scopes.ms_per_step(run, ("apex_train_forward",), backward=False)
