from .common import (
    interpret_mode,
    round_up,
    pad_rows,
    cdiv,
    tree_ravel,
)
from .prefetcher import DevicePrefetcher

__all__ = [
    "interpret_mode",
    "round_up",
    "pad_rows",
    "cdiv",
    "tree_ravel",
    "DevicePrefetcher",
]
