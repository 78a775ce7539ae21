#!/usr/bin/env python3
"""Readings that the limits of a ``moe_serve`` cell are set from — run on
the chip by hand, never by the benchmark's own runs (``calibrate.py serve``
reads ``drivers/serve.py``'s widest gap alone; this cell judges two other
numbers of the same distribution, ``PERF.md`` section 4).

    python3 benchmark/calibrate_moe.py --workload W --seeds 1,2,3 --seconds 10 [--control-seeds 1,2,3]

Per seed one short window at the cell's own load, then over the served
tokens of its sampled requests the widest gap, the mean gap and the share
of tokens more than each step of ``moe_serve.LADDER`` under the reference's
best (the LOWER readings); for the control seeds the same numbers of the
token the fp8 reference puts first (the UPPER readings).  ``correct.tail_gap``
is the step at which the two bands lie farthest apart; the limits lie
between the bands.  Each reading is one JSON line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from benchmark import harness as H                   # noqa: E402
from benchmark import traffic                        # noqa: E402
from benchmark.calibrate import ints                 # noqa: E402
from benchmark.drivers import moe_serve as M         # noqa: E402
from benchmark.drivers import serve as D             # noqa: E402


def reading(kind, seed, gap, **kw):
    print(json.dumps(dict(
        kind=kind, seed=seed, widest=gap["widest"], mean=gap["mean"],
        tail_share=gap["tail_share"], tokens=gap["tokens"],
        shares_above=M.shares_above(gap["gaps"]),
        percentiles={str(q): float(np.percentile(gap["gaps"], q))
                     for q in (90, 95, 99)}, **kw)), flush=True)


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", default="")
    p.add_argument("--seconds", type=float, default=10.0)
    args = p.parse_args(argv)
    cell = H.load_cell(args.workload, H.ROOT)
    devices = H.find_devices(cell.chips, False)
    H.enable_compile_cache(H.ROOT)
    for seed in ints(args.seeds):
        t0 = time.perf_counter()
        requests = traffic.serve_requests(cell.mix, seed, args.seconds,
                                          cell.config["token_ids"])
        engine, sched, shapes = D.build(cell, seed)
        D.warm_up(sched, cell, traffic.rng_for(seed, stream=2))
        out = D.measure(cell, sched, requests, args.seconds)
        del engine, sched
        D.free_device(devices.platform)
        seqs = D.sample_sequences(cell, seed, requests, out["by_uid"],
                                  out["served"])
        reading("program", seed, M.served_token_gaps(cell, shapes, seed, seqs),
                requests=len(out["by_uid"]),
                longest=max(len(p) + len(o) for p, o in seqs),
                seconds=time.perf_counter() - t0)
        if seed in ints(args.control_seeds):
            reading("control_fp8", seed, M.served_token_gaps(
                cell, shapes, seed, seqs, quant="fp8"))
        D.free_device(devices.platform)
    return 0


if __name__ == "__main__":
    sys.exit(main())
