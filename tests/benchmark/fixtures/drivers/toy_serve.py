"""A driver added as a file: the serving driver's own loop, by import."""
from __future__ import annotations

from . import serve


def run(**kw) -> dict:
    return serve.run(**kw)
