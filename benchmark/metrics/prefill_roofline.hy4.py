"""Per traced prefill the larger of FLOPs over peak and bytes over bandwidth (resident weights, held experts hit, latent rows and index keys written), summed, over the device time of those programs."""
from benchmark import counts_hy4


def read(run):
    return counts_hy4.prefill_roofline(run)
