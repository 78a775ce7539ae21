"""Experts that received a token in a decode step, of all experts of all expert layers (program counter)."""
from benchmark import counts_moe


def read(run):
    return counts_moe.moe_experts_hit_share(run)
