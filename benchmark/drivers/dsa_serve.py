"""Driver of the serving cells of a model kind whose layers SELECT the
positions they attend (a learned indexer, an index-key pool beside the K/V
pool) over an expert FFN: ``drivers/moe_serve.py``'s reference pass and
checks by import (``served_token_gaps``, judged on the mean gap and the tail
share as Laguna's and A.X-K1's cells are), ``drivers/serve.py``'s loop under
them, plus the counters such a kind's steps report behind the expert ones
(``ServeTelemetry``'s ``dsa_*`` families).  ``moe_serve.run`` reads its
counters from a fixed table round the one ``serve.measure`` call, so this
file makes that call itself and reads both tables:

    facts["moe"] = {"prefill": {...}, "decode": {...}, "window_pages_live_peak"}
    facts["dsa"] = {"prefill": {"rows", "rows_sparse", "selected"}, "decode": {...}}

per phase the query rows x layers that went through the indexer, those whose
context exceeded the selection's size, and the positions attended, each
summed over the window and its drain like ``facts["counters"]``.  A program
whose telemetry lacks a family leaves its entry out, and the readers then
find nothing to read.
"""
from __future__ import annotations

import time

from .. import harness as H
from .. import traffic
from . import moe_serve as M
from . import serve
from .train import free_device

#: facts["dsa"][phase] key -> the telemetry's counter
FAMILIES = {"rows": "dsa_rows", "rows_sparse": "dsa_rows_sparse",
            "selected": "dsa_selected"}


def select_counters(tel):
    """The telemetry's selection counters per phase, or None where the
    program has none."""
    if not all(hasattr(tel, attr) for attr in FAMILIES.values()):
        return None
    return {ph: {k: float(getattr(tel, attr).value(phase=ph))
                 for k, attr in FAMILIES.items()} for ph in M.PHASES}


def _delta(before, after, keys):
    return {ph: {k: after[ph][k] - before[ph][k] for k in keys}
            for ph in M.PHASES}


def run(*, cell, devices, seed, seconds, profiler, t_process) -> dict:
    cfg = cell.config
    requests = traffic.serve_requests(cell.mix, seed, seconds,
                                      cfg["token_ids"])
    H.note(t_process, "imports done, building the engine")
    engine, sched, shapes = serve.build(cell, seed)
    H.note(t_process, "engine built, warming every shape of the mix")
    serve.warm_up(sched, cell, traffic.rng_for(seed, stream=2))
    H.note(t_process, "warm; the window opens")
    setup_s = time.perf_counter() - t_process
    tel = sched.telemetry
    before = M.expert_counters(tel), select_counters(tel)
    out = serve.measure(cell, sched, requests, seconds, profiler)
    facts, served, by_uid = out["facts"], out["served"], out["by_uid"]
    after = M.expert_counters(tel), select_counters(tel)
    if before[0] is not None and after[0] is not None:
        facts["moe"] = _delta(before[0], after[0], M.FAMILIES)
        facts["moe"]["window_pages_live_peak"] = \
            tel.window_pages_live_peak.value()
    if before[1] is not None and after[1] is not None:
        facts["dsa"] = _delta(before[1], after[1], FAMILIES)
    peak = devices.memory_peak_bytes()
    facts["memory_peak_bytes"] = peak
    del engine, sched, tel
    free_device(devices.platform)
    unfinished = [r for r in by_uid.values() if r["reason"] != "length"]
    wrong_count = [uid for uid, toks in served.items() if uid in by_uid
                   and len(toks) != by_uid[uid]["new_tokens"]]
    seqs = serve.sample_sequences(cell, seed, requests, by_uid, served)
    gap = M.served_token_gaps(cell, shapes, seed, seqs)
    # the widest gap is said, not judged, as in the other expert cells
    H.note(t_process, f"widest served-token gap {gap['widest']:.6g} over "
                      f"{gap['tokens']} tokens (not judged); shares of them "
                      f"above {M.shares_above(gap['gaps'])}")
    limits = cfg["correct"]["limits"]
    checks = [
        {"name": "served_token_gap_mean", "value": gap["mean"],
         "limit": limits["served_token_gap_mean"]},
        {"name": "served_token_gap_tail_share", "value": gap["tail_share"],
         "limit": limits["served_token_gap_tail_share"]},
        {"name": "requests_unfinished", "value": float(len(unfinished)),
         "limit": 0.0},
        {"name": "token_count_wrong", "value": float(len(wrong_count)),
         "limit": 0.0},
    ]
    return {"facts": facts, "setup_s": setup_s, "memory_peak_bytes": peak,
            "correct": all(c["value"] <= c["limit"] for c in checks),
            "attempted": len(by_uid), "failed": len(unfinished),
            "checks": checks}
