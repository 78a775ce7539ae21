"""From a run's facts (host stamps, counters) and its reduced trace to
metric values.  The readers under ``metrics/`` are one call each into this
file; a reader that finds nothing to read returns ``None``.
"""
from __future__ import annotations

import numpy as np

from . import counts
from . import trace as trace_mod


def percentile(values, q: float):
    values = np.asarray(list(values), np.float64)
    return float(np.percentile(values, q)) if values.size else None


# -- training ----------------------------------------------------------------

def _untraced_steps(facts):
    """Steps of the window measured as the end-to-end rate measures them:
    all of them, or, in a traced run, those before the profiler came on."""
    n = len(facts["step_starts"]) - facts.get("traced_steps", 0)
    return facts["step_starts"][:n], facts["step_ends"][:n]


def train_tokens_per_s(facts):
    starts, ends = _untraced_steps(facts)
    if not starts:
        return None
    return facts["tokens_per_step"] * len(starts) / (ends[-1] - starts[0])


def train_step_ms_p50(facts):
    """Median time between the host's sight of one finished step and of the
    next: with steps dispatched ahead, the pace at which the chip ends
    them."""
    _, ends = _untraced_steps(facts)
    if len(ends) < 2:
        return None
    return float(np.median(np.diff(ends))) * 1e3


def train_flops_per_token(run) -> float:
    cfg, mix = run.cell.config, run.cell.mix
    return counts.train_flops_per_token(
        hidden=cfg["hidden_size"], ffn=cfg["intermediate_size"],
        layers=cfg["num_hidden_layers"], seq=mix["seq"],
        vocab=cfg["vocab_size"],
        head_share=run.facts["labels_per_row"] / mix["seq"])


def train_step_mfu(run):
    rate = train_tokens_per_s(run.facts)
    if rate is None:
        return None
    return 100.0 * train_flops_per_token(run) * rate / (
        run.cell.chips * run.peaks["bf16_flops_per_s"])


def ops_ms_per_step(run, pattern: str):
    """Device milliseconds per traced step of the operations whose name
    matches ``pattern``, on the chip that spent most."""
    steps = run.facts.get("traced_steps", 0)
    if run.trace is None or not steps:
        return None
    worst = max((trace_mod.matching_seconds(evs, pattern)[0]
                 for evs in run.trace.ops.values()), default=0.0)
    return worst * 1e3 / steps if worst else None


def flat_buffer_pattern(run) -> str:
    """Operations over the flat optimizer buffers, by the one thing a
    rename cannot change: the flat length in the operation's own type
    (``f32[<n_params>]`` or ``bf16[<n_params>]`` in the head of its HLO
    text — result or first operands)."""
    return r"(f32|bf16)\[%d\]" % run.facts["n_params"]


def lamb_update_ms(run):
    """Device time per step of every operation that reads or writes a
    whole flat buffer: the LAMB and unscale kernels, the norm reductions,
    and XLA's selects, copies, pads and converts around them."""
    return ops_ms_per_step(run, flat_buffer_pattern(run))


def layernorm_ms(run):
    """Device time per step of the Pallas kernels that are not the
    optimizer's: custom-calls whose type does not hold the flat length.
    In the train step those are the LayerNorm kernels, forward and
    backward (PERF.md section 5 has the names one trace showed)."""
    n = run.facts["n_params"]
    return ops_ms_per_step(
        run, r"^(?!.*\[%d\])%%\S+ = \S.* custom-call\(" % n)


def lamb_update_roofline(run):
    ms = lamb_update_ms(run)
    if not ms:
        return None
    least = counts.lamb_update_bytes(run.facts["n_params"]) / (
        run.cell.chips * run.peaks["hbm_bytes_per_s"])
    return 100.0 * least * 1e3 / ms


# -- serving -----------------------------------------------------------------

def _model(run) -> dict:
    cfg = run.cell.config
    return dict(hidden=cfg["hidden_size"], ffn=cfg["intermediate_size"],
                layers=cfg["num_hidden_layers"], vocab=cfg["vocab_size"])


def _tokens_between(facts, lo: float, hi: float):
    """``(prefills, decodes)`` stamped in ``[lo, hi]``: prompt lengths of
    the requests whose first token fell there, and the context each later
    token attended."""
    prefills, decodes = [], []
    for r in facts["requests"]:
        for j, t in enumerate(r["token_times"]):
            if lo <= t <= hi:
                if j == 0:
                    prefills.append(r["prompt_len"])
                else:
                    decodes.append(r["prompt_len"] + j)
    return prefills, decodes


def serve_tokens_per_s(facts):
    lo, hi = facts["window"]
    prefills, decodes = _tokens_between(facts, lo, hi)
    done = sum(prefills) + len(prefills) + len(decodes)
    return done / (hi - lo) if done else None


def serve_ttft_ms(facts) -> list:
    """Per request due in the window: first token on the host minus the
    time it was due; a request that never got one counts as worst (it
    waited until the drain gave up)."""
    return [((r["token_times"][0] if r["token_times"]
              else facts["drained"]) - r["due"]) * 1e3
            for r in facts["requests"]]


def serve_gaps_ms(facts) -> list:
    out = []
    for r in facts["requests"]:
        out.extend(np.diff(r["token_times"]) * 1e3)
    return out


def generator_lag_ms(facts) -> list:
    return [(r["sent"] - r["due"]) * 1e3 for r in facts["requests"]]


def queue_wait_ms(facts) -> list:
    return [((r["admitted"] if r["admitted"] is not None
              else facts["drained"]) - r["due"]) * 1e3
            for r in facts["requests"]]


def decode_step_ms_p50(facts):
    walls = [(t1 - t0) * 1e3 for t0, t1, firsts, tokens, _ in
             facts["passes"] if not firsts and tokens]
    return float(np.median(walls)) if walls else None


def serve_step_mfu(run):
    lo, hi = run.facts["window"]
    if run.facts.get("trace_started"):
        hi = min(hi, run.facts["trace_started"])
    prefills, decodes = _tokens_between(run.facts, lo, hi)
    m = _model(run)
    flops = (sum(counts.prefill_flops(n, **m) for n in prefills)
             + sum(counts.decode_flops(c, **m) for c in decodes))
    if not flops:
        return None
    return 100.0 * flops / (hi - lo) / (
        run.cell.chips * run.peaks["bf16_flops_per_s"])


def _module_seconds(run, pattern: str):
    if run.trace is None or not run.trace.modules:
        return 0.0, 0
    chip = min(run.trace.modules)
    return trace_mod.matching_seconds(run.trace.modules[chip], pattern)


def _traced_tokens(run):
    lo, hi = run.facts.get("trace_started"), run.facts.get("trace_stopped")
    if lo is None or hi is None:
        return [], []
    return _tokens_between(run.facts, lo, hi)


def decode_roofline(run, pattern: str):
    """Least time of the decode steps traced (weights once a step, the
    keys and values each generated token attended) over the device time of
    the decode programs."""
    seconds, steps = _module_seconds(run, pattern)
    _, decodes = _traced_tokens(run)
    if not seconds or not decodes:
        return None
    m = _model(run)
    least = (steps * counts.weight_stream_bytes(**m)
             + sum(decodes) * counts.kv_bytes_per_token(
                 hidden=m["hidden"], layers=m["layers"])
             ) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds


def traced_prefills(facts, calls: int):
    """Prompt lengths of the ``calls`` prefill programs the device ran in
    the traced window, or ``None`` where the host's stamps cannot name
    them.  A first token is stamped when its pass returns, after its
    prefill ran: so at the trace's edges the device may have run one call
    whose stamp falls after the stop (taken in), or not have run the
    first one stamped (left out).  Any other difference is a fault of the
    stamps or of the pattern, and nothing is read."""
    lo, hi = facts.get("trace_started"), facts.get("trace_stopped")
    if lo is None or hi is None:
        return None
    firsts = sorted((r["token_times"][0], r["prompt_len"])
                    for r in facts["requests"] if r["token_times"])
    inside = [n for t, n in firsts if lo <= t <= hi]
    after = [n for t, n in firsts if t > hi]
    if calls == len(inside) + 1 and after:
        return inside + after[:1]
    if calls == len(inside) - 1:
        return inside[1:]
    return inside if calls == len(inside) else None


def prefill_roofline(run, pattern: str):
    """Least time of the prefills the device ran in the traced window
    (the larger of FLOPs over peak and bytes over bandwidth, each from
    its prompt's length) over the device time of those programs."""
    seconds, calls = _module_seconds(run, pattern)
    prefills = traced_prefills(run.facts, calls) if calls else None
    if not prefills:
        return None
    m = _model(run)
    least = sum(counts.roofline_seconds(
        counts.prefill_flops(n, **m), counts.prefill_bytes(n, **m),
        run.peaks) for n in prefills)
    return 100.0 * least / seconds


def slot_occupancy(facts):
    c = facts["counters"]
    if not c["decode_steps"]:
        return None
    return 100.0 * (1.0 - c["idle_slot_tokens"]
                    / (c["decode_steps"] * facts["slots"]))


def pool_pages(facts, column: int, stat):
    """``stat`` (``max``, ``numpy.mean``) over the passes that ended inside
    the window of the pool's pages in use: column 0 what the allocator
    holds out (reservations of admitted requests and pages the prefix
    cache keeps), column 1 the pages that hold a token some active slot
    attends."""
    hi = facts["window"][1]
    rows = [p[column] for (_, end, *_), p in zip(facts["passes"],
                                                facts["pool"]) if end <= hi]
    return float(stat(rows)) if rows else None


# -- device ------------------------------------------------------------------

def device_idle_share(run):
    if run.trace is None or not run.trace.ops:
        return None
    lo, hi = trace_mod.window(run.trace)
    busy = min(trace_mod.busy_seconds(run.trace).values())
    return 100.0 * (1.0 - busy / ((hi - lo) * 1e-9))


def peak_hbm_gib(facts):
    peak = facts.get("memory_peak_bytes")
    return peak / 2 ** 30 if peak else None
