"""What the program's own host spans say about one scheduler pass.

The program opens ``apex_tpu.scheduler.*`` spans inside
``SlotScheduler.run_pass`` and ``apex_tpu.inference.*`` spans round each
dispatch of the engine (``jax.profiler.TraceAnnotation``: on the profiler's
clock, beside the device planes).  The harness keeps them in
``run.trace.host``; this file is arithmetic over them with ``trace.py``'s
intervals as they are.  A program without those spans, a run without a
trace, or a trace in which no pass lies wholly inside the traced window
reads ``None`` everywhere.

* a *traced pass* is an ``apex_tpu.scheduler.pass`` span that lies wholly
  inside the traced window (first device operation to last);
* a *decoding pass* is a traced pass that holds an
  ``apex_tpu.scheduler.decode`` span;
* ``apex_tpu.scheduler.token_read`` is the one span in which the host only
  waits for the device, so a pass less its ``apex_tpu.inference.*`` and
  ``token_read`` spans is the scheduler's own host work.
"""
from __future__ import annotations

import statistics

from . import trace as trace_mod

SCHEDULER = "apex_tpu.scheduler."
ENGINE = "apex_tpu.inference."
PASS = SCHEDULER + "pass"
ADMIT = SCHEDULER + "admit"
PREFILL = SCHEDULER + "prefill"
DECODE = SCHEDULER + "decode"
TOKEN_READ = SCHEDULER + "token_read"


def traced_passes(trace) -> list:
    """``[(pass span, [the host spans inside it]), ...]`` for the traced
    passes, in order."""
    if trace is None:
        return []
    win = trace_mod.window(trace)
    if win is None:
        return []
    lo, hi = win
    out = []
    for p in trace.host:
        if p.name == PASS and lo <= p.start and p.end <= hi:
            out.append((p, [e for e in trace.host if e is not p
                            and p.start <= e.start and e.end <= p.end]))
    return out


def _named(events, name: str) -> list:
    return [e for e in events if e.name == name]


def _engine(events) -> list:
    return [e for e in events if e.name.startswith(ENGINE)]


def _covered_ms(events) -> float:
    """Milliseconds that spans, which may overlap, cover together."""
    return trace_mod.total(trace_mod.union(
        (e.start, e.end) for e in events)) * 1e-6


def _decoding(trace) -> list:
    return [(p, inside) for p, inside in traced_passes(trace)
            if _named(inside, DECODE)]


def sched_host_ms_per_pass(trace):
    """Median over decoding passes of the pass less every span in which
    the engine dispatches or the host waits for a token."""
    rows = [p.seconds * 1e3
            - _covered_ms(_engine(inside) + _named(inside, TOKEN_READ))
            for p, inside in _decoding(trace)]
    return statistics.median(rows) if rows else None


def engine_dispatch_ms_per_pass(trace):
    """Median over decoding passes of the summed length of the engine's
    dispatch spans: host time spent enqueueing programs."""
    rows = [sum(e.seconds for e in _engine(inside)) * 1e3
            for _, inside in _decoding(trace)]
    return statistics.median(rows) if rows else None


def sched_admit_ms_mean(trace):
    """Mean, over the traced passes that prefilled, of the admit span less
    the engine's dispatches inside it (copy-on-write, swap-in)."""
    rows = []
    for _, inside in traced_passes(trace):
        if not _named(inside, PREFILL):
            continue
        for a in _named(inside, ADMIT):
            rows.append(a.seconds * 1e3 - _covered_ms(
                e for e in _engine(inside)
                if a.start <= e.start and e.end <= a.end))
    return statistics.fmean(rows) if rows else None


def dispatches_per_pass(trace):
    """The engine's dispatch spans inside traced passes, per traced pass."""
    passes = traced_passes(trace)
    if not passes:
        return None
    return sum(len(_engine(inside)) for _, inside in passes) / len(passes)


def layer_of(span_name: str) -> str:
    """Whose host code a span is: the scheduler's, the engine's, or the
    benchmark's own loop (``bench.*`` and what no span covers)."""
    if span_name.startswith(SCHEDULER):
        return "scheduler"
    if span_name.startswith(ENGINE):
        return "engine"
    return "harness"


def idle_ms_per_pass(trace, layer: str):
    """Idle milliseconds of chip 0 a traced pass whose innermost host span
    belongs to ``layer`` (``trace.idle_gaps_by_host_span``, every row
    kept); the three layers sum to all idle time of the traced window
    over the traced passes."""
    passes = traced_passes(trace)
    if not passes:
        return None
    rows = trace_mod.idle_gaps_by_host_span(trace, n=len(trace.host) + 1)
    return sum(seconds for name, seconds in rows
               if layer_of(name) == layer) * 1e3 / len(passes)
