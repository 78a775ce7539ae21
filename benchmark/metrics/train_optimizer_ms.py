"""Device ms a traced step of the optimizer update and the loss-scale update: the apex_train_optimizer scope."""
from benchmark import scopes


def read(run):
    return scopes.ms_per_step(run, ("apex_train_optimizer",))
