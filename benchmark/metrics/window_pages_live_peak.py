"""Window-ring pages holding a live position at the peak (program counter); never above slots x ring pages."""
from benchmark import counts_moe


def read(run):
    return counts_moe.window_pages_live_peak(run)
