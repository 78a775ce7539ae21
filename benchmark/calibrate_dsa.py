#!/usr/bin/env python3
"""Readings that the limits of a ``dsa_serve`` cell are set from — run on the
chip by hand, never by the benchmark's own runs.  ``calibrate_moe.py`` for a
kind whose layers SELECT the positions they attend: beside the fp8 control
(the precision below) it reads two WRONG-SELECTION controls in full
precision — the reference attending EVERY causal position (what a program
that skipped the indexer serves) and the reference attending the most recent
``topk`` (what a program that took a window for the selection serves).

    python3 benchmark/calibrate_dsa.py --workload W --seeds 1,2,3 --seconds 10 \\
        [--control-seeds 1,2,3] [--controls fp8,attend_all,recent_topk]

Per seed one short window at the cell's own load, then over the served
tokens of its sampled requests the widest gap, the mean gap and the share of
tokens more than each step of ``moe_serve.LADDER`` under the reference's best
(the LOWER readings); for the control seeds the same numbers of the token
each control puts first (the UPPER readings: every control has to fail a
limit).  The limits lie between the bands.  Each reading is one JSON line.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from benchmark import harness as H                   # noqa: E402
from benchmark import traffic                        # noqa: E402
from benchmark.bindings.dsa_keye import CONTROLS         # noqa: E402
from benchmark.calibrate import ints                 # noqa: E402
from benchmark.calibrate_moe import reading          # noqa: E402
from benchmark.drivers import moe_serve as M         # noqa: E402
from benchmark.drivers import serve as D             # noqa: E402


def remember_the_reference(binding) -> dict:
    """``served_token_gaps`` runs the reference proper once a control (the
    gap of the control's token is measured in it): keep a seed's, on the
    host, so that three controls cost three passes a sequence more, not
    six.  Returns the store; the caller empties it between seeds."""
    plain, kept = binding.reference_logits, {}

    def reference_logits(cfg, w, padded, first, rows, quant=None):
        if quant is not None:
            return plain(cfg, w, padded, first, rows, quant=quant)
        key = (first, rows, padded.tobytes())
        if key not in kept:
            kept[key] = np.asarray(plain(cfg, w, padded, first, rows))
        return kept[key]

    binding.reference_logits = reference_logits
    return kept


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", default="")
    p.add_argument("--controls", default=",".join(
        c for c in CONTROLS if c is not None))
    p.add_argument("--seconds", type=float, default=10.0)
    args = p.parse_args(argv)
    cell = H.load_cell(args.workload, H.ROOT)
    devices = H.find_devices(cell.chips, False)
    H.enable_compile_cache(H.ROOT)
    controls = [c for c in args.controls.split(",") if c]
    kept = remember_the_reference(H.load_binding(cell))
    for seed in ints(args.seeds):
        t0 = time.perf_counter()
        kept.clear()
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
            for control in controls:
                reading("control_" + control, seed, M.served_token_gaps(
                    cell, shapes, seed, seqs, quant=control))
        D.free_device(devices.platform)
    return 0


if __name__ == "__main__":
    sys.exit(main())
