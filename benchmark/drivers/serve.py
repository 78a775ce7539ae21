"""Driver of the serving cells: the program's ``InferenceEngine`` (paged)
under its ``SlotScheduler``, fed by one host loop.

Open loop (``arrivals.kind == "poisson"``): a request is submitted as soon
as the loop gets round to it after it was due; its times are measured from
when it was DUE.  Backlog: the queue is kept ``depth`` deep for the whole
window (callers wait; there is no arrival schedule).

The scheduler gives no token callback, so tokens are stamped when the pass
that produced them returns — the first moment a caller of ``run_pass`` can
see them.  Once the window has closed, requests in flight are drained
(never longer than ``drain_seconds``), the peak memory is read, the engine
is freed, and the plain reference scores a seeded sample of the finished
requests, the longest among them.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import harness as H
from .. import traffic, weights
from .train import free_device


def build(cell, seed: int):
    """The program's engine and scheduler over the benchmark's weights.
    What is particular to the model kind is the configuration's binding."""
    from apex_tpu.inference import SlotScheduler

    cfg, mix = cell.config, cell.mix
    if not traffic.greedy_sampling(mix):
        raise H.Refused("only greedy mixes can be checked against the "
                        "reference; mix greedy requests in")
    binding = H.load_binding(cell)
    binding.check_supported(cfg)         # before any weight is made
    model_cfg, shapes = binding.model_of(cfg)
    params = weights.make(shapes, seed)
    engine = binding.engine(cfg, model_cfg, mix, params, seed)
    del params
    return engine, SlotScheduler(engine), shapes


class Loop:
    """One wave of the scheduler, driven pass by pass, with the
    benchmark's own stamps."""

    def __init__(self, sched):
        self.sched = sched
        self.req = {}          # uid -> the request's record
        self.inflight = {}     # uid -> tokens seen so far
        self.passes = []       # (start, end, first tokens, tokens, active)
        self.pool = []         # per pass: (allocator's live pages, pages
        #                        holding a token some active slot attends)
        sched.begin_run()

    def submit(self, r, due: float) -> None:
        with H.span("submit"):
            uid = self.sched.submit(r.prompt, max_new_tokens=r.new_tokens)
        self.req[uid] = {
            "index": r.index, "due": due, "sent": time.perf_counter(),
            "prompt_len": len(r.prompt), "new_tokens": r.new_tokens,
            "admitted": None, "token_times": [], "reason": None}
        self.inflight[uid] = 0

    def one_pass(self) -> None:
        sched = self.sched
        t0 = time.perf_counter()
        with H.span("run_pass"):
            sched.run_pass()
        t1 = time.perf_counter()
        with H.span("stamp_tokens"):
            seen, firsts, tokens = {}, 0, 0
            for st in sched.slot_states():
                if st is not None:
                    seen[st.uid] = len(st.generated)
            for uid in list(self.inflight):
                rec = self.req[uid]
                if uid in seen:
                    n = seen[uid]
                elif uid in sched.finish_reasons:
                    rec["reason"] = sched.finish_reasons[uid]
                    n = (rec["new_tokens"] if rec["reason"] == "length"
                         else self.inflight[uid])
                else:
                    continue               # still queued
                if rec["admitted"] is None:
                    rec["admitted"] = t0
                new = n - self.inflight[uid]
                if new > 0:
                    firsts += self.inflight[uid] == 0
                    tokens += new
                    rec["token_times"].extend([t1] * new)
                    self.inflight[uid] = n
                if rec["reason"] is not None:
                    del self.inflight[uid]
            self.passes.append((t0, t1, firsts, tokens,
                                sum(1 for s in seen.values() if s)))
            page = sched.alloc.page_size
            self.pool.append((sched.alloc.live_pages, sum(
                -(-(self.req[u]["prompt_len"] + n) // page)
                for u, n in seen.items() if u in self.req)))

    def pending(self) -> bool:
        return self.sched.run_pending()

    def close(self) -> dict:
        return self.sched.finish_run()


def warm_up(sched, cell, rng) -> None:
    """Every shape the mix can reach: one request in each prefill bucket
    between the mix's shortest and longest prompt, decoded together."""
    mix, cfg = cell.mix, cell.config
    eng = sched.engine
    lo, hi = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    lengths, n = [], lo
    while True:
        bucket = eng.bucket_for(n)
        lengths.append(min(bucket, hi))
        if bucket >= hi:
            break
        n = bucket + 1
    for n in lengths:
        sched.submit(rng.randint(0, cfg["token_ids"], size=n),
                     max_new_tokens=3)
    sched.run()


def measure(cell, sched, requests, seconds: float, profiler=None) -> dict:
    """One measured window over a warm scheduler: submit, pass, stamp,
    close, drain.  Returns the stamps and what was served."""
    from apex_tpu.observability.timers import compile_count

    mix = cell.mix
    tel = sched.telemetry
    counters0 = _counters(tel)
    backlog = mix["arrivals"]["kind"] == "backlog"
    trace_s, drain_s = mix["trace_seconds"], mix["drain_seconds"]
    loop = Loop(sched)
    nxt, tracing, closed = 0, False, False
    compiles0 = compile_count()
    t0 = time.perf_counter()
    close = t0 + seconds
    while True:
        now = time.perf_counter()
        if now < close:
            if backlog:
                # ``requests`` laps (traffic.Backlog): it never runs dry
                while len(sched.queue) < mix["arrivals"]["depth"]:
                    loop.submit(requests[nxt], t0)
                    nxt += 1
            else:
                while nxt < len(requests) \
                        and t0 + requests[nxt].due_s <= now:
                    loop.submit(requests[nxt], t0 + requests[nxt].due_s)
                    nxt += 1
            if profiler is not None and not tracing \
                    and now >= close - trace_s:
                profiler.start()
                tracing = True
        elif not closed:
            closed = True
            if tracing:
                profiler.stop()
            if backlog:
                # callers still waiting were never started: not attempted
                for r in list(sched.queue):
                    loop.inflight.pop(r.uid, None)
                    loop.req.pop(r.uid, None)
                sched.queue.clear()
        if not loop.pending():
            if closed:
                break
            due = (close if backlog or nxt >= len(requests)
                   else min(close, t0 + requests[nxt].due_s))
            with H.span("wait_for_request"):
                time.sleep(max(0.0, min(due - time.perf_counter(), 0.05)))
            continue
        if closed and now > close + drain_s and sched.queue:
            # the drain gave up: what is still queued never gets served
            # (it counts as failed); what is in flight is let finish
            sched.queue.clear()
        loop.one_pass()
    compiles = compile_count() - compiles0
    t_drained = time.perf_counter()
    served = loop.close()
    facts = {
        "window": (t0, close), "drained": t_drained,
        "requests": list(loop.req.values()), "passes": loop.passes,
        "pool": loop.pool,
        "slots": mix["slots"], "compiles_in_window": compiles,
        "counters": {k: v - counters0[k]
                     for k, v in _counters(tel).items()},
        "trace_started": profiler.started if profiler else None,
        "trace_stopped": profiler.stopped if profiler else None,
    }
    return {"facts": facts, "served": served, "by_uid": loop.req}


def sample_sequences(cell, seed: int, requests, by_uid, served) -> list:
    """``(prompt, served tokens)`` of a seeded sample of the finished
    requests, the longest always among them."""
    finished = [uid for uid, r in by_uid.items()
                if r["reason"] == "length" and uid in served]
    sample = _sample(finished, by_uid,
                     cell.config["correct"]["sample_requests"],
                     traffic.rng_for(seed, stream=3))
    # a request's index is its place in ``requests``, whatever its lap
    return [(requests[by_uid[u]["index"]].prompt, np.asarray(served[u]))
            for u in sample]


def run(*, cell, devices, seed, seconds, profiler, t_process) -> dict:
    cfg = cell.config
    requests = traffic.serve_requests(cell.mix, seed, seconds,
                                      cfg["token_ids"])
    H.note(t_process, "imports done, building the engine")
    engine, sched, shapes = build(cell, seed)
    H.note(t_process, "engine built, warming every shape of the mix")
    warm_up(sched, cell, traffic.rng_for(seed, stream=2))
    H.note(t_process, "warm; the window opens")
    setup_s = time.perf_counter() - t_process
    out = measure(cell, sched, requests, seconds, profiler)
    facts, served, by_uid = out["facts"], out["served"], out["by_uid"]
    peak = devices.memory_peak_bytes()
    facts["memory_peak_bytes"] = peak
    del engine, sched
    free_device(devices.platform)
    unfinished = [r for r in by_uid.values() if r["reason"] != "length"]
    wrong_count = [uid for uid, toks in served.items() if uid in by_uid
                   and len(toks) != by_uid[uid]["new_tokens"]]
    seqs = sample_sequences(cell, seed, requests, by_uid, served)
    gap = served_token_gap(cell, shapes, seed, seqs)
    checks = [
        {"name": "served_token_gap", "value": gap["widest"],
         "limit": cfg["correct"]["limits"]["served_token_gap"]},
        {"name": "requests_unfinished", "value": float(len(unfinished)),
         "limit": 0.0},
        {"name": "token_count_wrong", "value": float(len(wrong_count)),
         "limit": 0.0},
    ]
    return {"facts": facts, "setup_s": setup_s, "memory_peak_bytes": peak,
            "correct": all(c["value"] <= c["limit"] for c in checks),
            "attempted": len(by_uid), "failed": len(unfinished),
            "checks": checks}


def _counters(tel) -> dict:
    return {"decode_steps": float(tel.decode_steps.total()),
            "idle_slot_tokens": float(tel.idle_slot_tokens.total())}


def _sample(finished: list, by_uid: dict, k: int, rng) -> list:
    """``k`` finished requests drawn from the seed, the longest (prompt +
    served tokens) always among them."""
    if not finished:
        return []
    longest = max(finished, key=lambda u: (by_uid[u]["prompt_len"]
                                           + by_uid[u]["new_tokens"], u))
    rest = sorted(u for u in finished if u != longest)
    rng.shuffle(rest)
    return [longest] + rest[:max(0, k - 1)]


def served_token_gap(cell, shapes, seed: int, seqs, *, quant=None) -> dict:
    """The widest gap by which a served token's reference logit lies below
    the reference's best, over every served token of ``seqs`` — a list of
    ``(prompt, served tokens)``.  With ``quant`` (the control) the token
    judged at each position is the one the lower precision puts first."""
    cfg = cell.config
    if not seqs:
        return {"widest": float("inf"), "tokens": 0}
    binding = H.load_binding(cell)
    w = binding.reference_weights(cfg, weights.make(shapes, seed))
    pad = cfg["correct"]["reference_pad_to"]
    # the rows judged, one shape for every sequence: the mix's longest answer
    rows = max(cell.mix["new_tokens"]["max"], max(len(o) for _, o in seqs))

    @jax.jit
    def gaps(ref_rows, judged, count):
        best = jnp.max(ref_rows, axis=-1)
        got = jnp.take_along_axis(ref_rows, judged[:, None], axis=-1)[:, 0]
        live = jnp.arange(judged.shape[0]) < count
        return jnp.max(jnp.where(live, best - got, 0.0))

    widest, tokens = 0.0, 0
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
        widest = max(widest, float(gaps(ref, jnp.asarray(judged),
                                        len(out))))
        tokens += len(out)
    return {"widest": widest, "tokens": tokens}
