"""Device ms per step of the LayerNorm kernels: custom-calls of the step whose type does not hold the optimizer's flat length."""
from benchmark import reduce


def read(run):
    return reduce.layernorm_ms(run)
