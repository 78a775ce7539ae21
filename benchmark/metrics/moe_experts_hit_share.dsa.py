"""Of the 128 experts a layer, those a decode step gave a token to, as a share (program counter): what decode_roofline.dsa counts as read."""
from benchmark import counts_dsa


def read(run):
    return counts_dsa.moe_experts_hit_share(run)
