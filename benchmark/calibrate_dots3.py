#!/usr/bin/env python3
"""``calibrate_dsa.py`` for a kind whose window layers attend latent rows in
rings beside full layers that select (``bindings/mla_swa_dots3.py``): the
same readings — one short window a seed at the cell's own load, the served
tokens of its sampled requests judged against the reference, then the
controls' tokens on the control seeds — with that binding's five controls by
default: the precision below (``fp8``), two wrong selections of the full
layers in full precision (``attend_all``, ``recent_topk``), window layers
that attend their whole context (``swa_all``) and a router that chooses
without its selection bias (``no_bias``).  Run on the chip by hand, never by
the benchmark's own runs:

    python3 benchmark/calibrate_dots3.py --workload W --seeds 1,2 \\
        --seconds 20 [--control-seeds 1,2] [--controls fp8,swa_all]

Every control has to fail a limit; the limits lie between the bands.  Each
reading is one JSON line.
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from benchmark import calibrate_dsa                         # noqa: E402
from benchmark.bindings.mla_swa_dots3 import CONTROLS       # noqa: E402


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not any(a.split("=")[0] == "--controls" for a in argv):
        argv += ["--controls", ",".join(c for c in CONTROLS if c)]
    return calibrate_dsa.main(argv)


if __name__ == "__main__":
    sys.exit(main())
