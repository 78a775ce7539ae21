"""Device ms per step of all operations over the flat fp32/bf16 optimizer buffers (matched by the flat length in the op's type)."""
from benchmark import reduce


def read(run):
    return reduce.lamb_update_ms(run)
