"""1 minus the union of device-operation intervals over the traced window (busiest-idle chip)."""
from benchmark import reduce


def read(run):
    return reduce.device_idle_share(run)
