"""Held experts that received a token in a decode step, of the experts held in all expert layers (program counter): what decode_roofline.mla counts as read once a step."""
from benchmark import counts_mla


def read(run):
    return counts_mla.moe_experts_hit_share(run)
