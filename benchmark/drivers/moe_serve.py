"""Driver of the serving cells of a model kind with an expert FFN and
window layers: ``drivers/serve.py``'s loop, stamps, sample and checks,
unchanged and by import, plus the counters such a kind's steps report with
their tokens (``ServeTelemetry``'s ``moe_*`` and ``window_pages_live*``
families).  ``serve.measure`` takes its counters from a fixed list, so this
file reads the new ones round the same call and adds them to ``facts``:

    facts["moe"] = {"prefill": {...}, "decode": {...}, "window_pages_live_peak"}

per phase the passes that ran an expert FFN, the (token, expert)
assignments, the experts hit and the busiest expert's tokens, each summed
over the window and its drain like ``facts["counters"]``.  A program whose
telemetry lacks the families (a kind without experts) leaves ``facts["moe"]``
out, and the readers then find nothing to read.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import harness as H
from .. import traffic, weights
from . import serve
from .train import free_device

PHASES = ("prefill", "decode")
FAMILIES = {"passes": "moe_passes", "assignments": "moe_assignments",
            "experts_hit": "moe_experts_hit",
            "load_max": "moe_expert_load_max"}


#: steps of the served-token gap at which the share of tokens beyond is
#: said with every run, so that ``correct.tail_gap`` can be set again from
#: the runs' own lines
LADDER = (0.1, 0.2, 0.3, 0.5)


def shares_above(gaps) -> dict:
    return {str(step): float(np.mean(gaps > step)) if len(gaps)
            else float("inf") for step in LADDER}


def expert_counters(tel):
    """The telemetry's expert counters per phase, or None where the
    program has none."""
    if not all(hasattr(tel, attr) for attr in FAMILIES.values()):
        return None
    return {ph: {k: float(getattr(tel, attr).value(phase=ph))
                 for k, attr in FAMILIES.items()} for ph in PHASES}


def served_token_gaps(cell, shapes, seed: int, seqs, *, quant=None) -> dict:
    """``serve.served_token_gap`` with the whole distribution behind the
    widest gap: ``gaps``, for every served token of ``seqs`` the gap by
    which its reference logit lies below the reference's best, and of
    them the ``widest``, the ``mean`` and the ``tail_share`` — the share
    of tokens more than the configuration's ``correct.tail_gap`` under
    the best.  The widest gap is an extreme of a few hundred tokens;
    where experts are routed, a token now and then takes another 8th
    expert than the float32 reference and lands far from its best, in
    any precision: on the chip a sound run's widest gap reaches the fp8
    control's (``PERF.md`` section 4 has the bands), so it cannot decide
    ``correct``.  The mean is what the precision moves (ten times); the
    tail share is what a fault in a few positions moves (a stale ring
    row, one slot of many) and the mean hardly sees.  This driver judges
    both.  One reference pass per sequence gives all."""
    cfg = cell.config
    if not seqs:
        return {"widest": float("inf"), "mean": float("inf"),
                "tail_share": float("inf"), "tokens": 0,
                "gaps": np.zeros((0,), np.float32)}
    binding = H.load_binding(cell)
    w = binding.reference_weights(cfg, weights.make(shapes, seed))
    pad = cfg["correct"]["reference_pad_to"]
    rows = max(cell.mix["new_tokens"]["max"], max(len(o) for _, o in seqs))

    @jax.jit
    def gap_rows(ref_rows, judged):
        best = jnp.max(ref_rows, axis=-1)
        got = jnp.take_along_axis(ref_rows, judged[:, None], axis=-1)[:, 0]
        return best - got

    gaps = []
    for prompt, out in seqs:
        full = np.concatenate([prompt, out[:-1]]).astype(np.int32)
        if len(full) > pad:
            raise H.Refused(f"a served sequence of {len(full)} tokens is "
                            f"longer than reference_pad_to {pad}")
        padded = np.zeros((pad,), np.int32)
        padded[:len(full)] = full
        first = len(prompt) - 1
        ref = binding.reference_logits(cfg, w, padded, first, rows)
        judged = np.zeros((rows,), np.int32)
        if quant is None:
            judged[:len(out)] = out
        else:
            low = binding.reference_logits(cfg, w, padded, first, rows,
                                           quant=quant)
            judged[:len(out)] = np.asarray(
                jnp.argmax(low, axis=-1))[:len(out)]
        gaps.append(np.asarray(gap_rows(ref, jnp.asarray(judged)))[:len(out)])
    gaps = np.concatenate(gaps)
    return {"widest": float(gaps.max()), "mean": float(gaps.mean()),
            "tail_share": float(np.mean(gaps > cfg["correct"]["tail_gap"])),
            "tokens": len(gaps), "gaps": gaps}


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
    before = expert_counters(sched.telemetry)
    out = serve.measure(cell, sched, requests, seconds, profiler)
    facts, served, by_uid = out["facts"], out["served"], out["by_uid"]
    after = expert_counters(sched.telemetry)
    if before is not None and after is not None:
        facts["moe"] = {ph: {k: after[ph][k] - before[ph][k]
                             for k in FAMILIES} for ph in PHASES}
        facts["moe"]["window_pages_live_peak"] = \
            sched.telemetry.window_pages_live_peak.value()
    peak = devices.memory_peak_bytes()
    facts["memory_peak_bytes"] = peak
    del engine, sched
    free_device(devices.platform)
    unfinished = [r for r in by_uid.values() if r["reason"] != "length"]
    wrong_count = [uid for uid, toks in served.items() if uid in by_uid
                   and len(toks) != by_uid[uid]["new_tokens"]]
    seqs = serve.sample_sequences(cell, seed, requests, by_uid, served)
    gap = served_token_gaps(cell, shapes, seed, seqs)
    # the widest gap is said, not judged: its bands touch (PERF.md sec. 4)
    H.note(t_process, f"widest served-token gap {gap['widest']:.6g} over "
                      f"{gap['tokens']} tokens (not judged); shares of them "
                      f"above {shares_above(gap['gaps'])}")
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
