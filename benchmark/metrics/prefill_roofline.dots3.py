"""Per traced prefill the larger of FLOPs over peak and bytes over bandwidth (resident weights, held experts hit, the rows and index keys written), summed, over the device time of the prefill programs."""
from benchmark import counts_dots3


def read(run):
    return counts_dots3.prefill_roofline(run)
