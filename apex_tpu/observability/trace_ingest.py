"""Profiler-trace ingestion: the MEASURED truth source (ISSUE 14).

PR 10 gave the repo *compiled* truth (``xla_stats``: what XLA says an
executable costs) and PR 8 armed ``profile_capture()`` on every bench
leg — but nothing ever read the traces it wrote.  This module is the
reader: it finds the trace-viewer ``*.trace.json.gz`` event streams
``jax.profiler.start_trace``/``stop_trace`` drop under
``APEX_TPU_PROFILE_DIR`` (globbing the session directory, because the
layout differs per backend/version — ``plugins/profile/<session>/
<host>.trace.json.gz`` today), normalizes the Chrome-trace events into
pinned :class:`TraceEvent` records, and buckets each XLA op into the
attribution categories :mod:`apex_tpu.observability.attribution` prices
wall time against:

* ``dot`` — dot/convolution (the MXU work measured MFU divides into),
* ``collective:all_gather`` / ``collective:all_reduce`` (psum) /
  ``collective:reduce_scatter`` / ``collective:ppermute`` /
  ``collective:all_to_all`` — per-type collective time,
* ``fusion`` — XLA fusions (the elementwise/reduction bulk),
* ``copy`` — copies, infeed/outfeed, host transfers, send/recv,
* ``other`` — every remaining leaf op (tanh, reduce, broadcast, …).

Op-event selection is layout-tolerant: an event counts as an XLA op
when its ``args`` carry ``hlo_op``/``hlo_module`` (the CPU backend's
convention) or when it sits on a ``/device:``-named process outside
the known non-op lanes ("XLA Modules", "Steps", …).  Wrapper ops
(``call``/``while``/``conditional``) are skipped — their leaves are
traced individually and counting both would double-attribute.

Degradation contract (PR 10 discipline): an empty directory, a
malformed file, or a trace with no recognizable op events yields a
:class:`RankTrace` whose ``provenance`` is ``unavailable:<reason>`` and
carries NO events — never fabricated zeros.  A healthy parse is
``measured:trace``.

Each trace FILE is one rank: a multi-host capture (or several per-rank
profile dirs passed together) merges into the cross-rank straggler/skew
report in :mod:`attribution`.

CLI::

    python -m apex_tpu.observability.trace_ingest <profile_dir> [...]
        [--steps N] [--flops-per-step F] [--chip KIND]
        [--model-exposed-comm-us X] [--out attribution.json]

prints the attribution record as JSON — the same record ``bench.py``
stamps into captures and ``report --attribution`` renders.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = ["TraceEvent", "RankTrace", "PROVENANCE_MEASURED",
           "UNAVAILABLE_PREFIX", "CATEGORIES", "categorize",
           "find_trace_files", "parse_trace_file", "load_profile_dirs",
           "main"]

PROVENANCE_MEASURED = "measured:trace"
UNAVAILABLE_PREFIX = "unavailable:"

#: the pinned attribution categories (order = report/table order).
CATEGORIES: Tuple[str, ...] = (
    "dot", "fusion",
    "collective:all_gather", "collective:all_reduce",
    "collective:reduce_scatter", "collective:ppermute",
    "collective:all_to_all",
    "copy", "other")

#: collective HLO base names (dash-normalized) -> canonical type.
_COLLECTIVE_BASES: Dict[str, str] = {
    "all-gather": "all_gather",
    "all-reduce": "all_reduce",
    "psum": "all_reduce",
    "reduce-scatter": "reduce_scatter",
    "psum-scatter": "reduce_scatter",
    "collective-permute": "ppermute",
    "ppermute": "ppermute",
    "all-to-all": "all_to_all",
    "alltoall": "all_to_all",
}

#: wrapper ops whose leaves are traced individually — counting the
#: wrapper too would attribute the same wall time twice.
_WRAPPER_BASES = frozenset({"call", "while", "conditional"})

#: device-process thread lanes that carry module/step aggregates, not
#: leaf ops (xprof's trace-viewer export) — a module-level span covers
#: compute AND collectives, so admitting it would dissolve the
#: exposed-comm overlap math.
_NON_OP_THREAD_PREFIXES = ("XLA Modules", "Steps", "Framework",
                           "Source code", "TensorFlow Name Scope")


@dataclass(frozen=True)
class TraceEvent:
    """One normalized XLA op occurrence (times in microseconds, in the
    trace's own clock)."""

    name: str                    # HLO op name, e.g. "dot.6"
    category: str                # one of CATEGORIES
    start_us: float
    dur_us: float
    pid: int = 0
    tid: int = 0

    @property
    def end_us(self) -> float:
        return self.start_us + self.dur_us


@dataclass
class RankTrace:
    """One rank's (= one trace file's) normalized op-event stream."""

    source: str                  # file path (or synthetic label)
    provenance: str              # measured:trace | unavailable:<reason>
    events: List[TraceEvent] = field(default_factory=list)

    @property
    def degraded(self) -> bool:
        return self.provenance != PROVENANCE_MEASURED


def categorize(name: str) -> Optional[str]:
    """Attribution category for one HLO op name (``None`` = skip: a
    wrapper op whose leaves are traced individually).

    The base is the segment before the first ``.`` (``"dot.6"`` ->
    ``"dot"``, ``"tanh.4.clone"`` -> ``"tanh"``), dash-normalized; the
    async ``-start``/``-done`` halves of a collective both file under
    its type (the interval union absorbs their overlap).
    """
    base = name.split(".", 1)[0].strip().lstrip("%").lower()
    base = base.replace("_", "-")
    if base in _WRAPPER_BASES:
        return None
    for suffix in ("-start", "-done"):
        if base.endswith(suffix):
            base = base[:-len(suffix)]
    coll = _COLLECTIVE_BASES.get(base)
    if coll is not None:
        return f"collective:{coll}"
    if base.startswith("fusion") or base.endswith("fusion"):
        return "fusion"
    if base.startswith(("dot", "convolution", "cudnn-conv")):
        return "dot"
    if base.startswith(("copy", "memcpy", "transfer", "infeed",
                        "outfeed", "send", "recv",
                        "dynamic-update-slice-copy")):
        return "copy"
    return "other"


# ---------------------------------------------------------------------------
# discovery + parsing
# ---------------------------------------------------------------------------

_TRACE_GLOBS = ("*.trace.json.gz", "*.trace.json", "trace.json.gz",
                "trace.json")


def find_trace_files(profile_dir: str) -> List[str]:
    """Every trace-viewer JSON file under ``profile_dir`` (recursive —
    the session-dir layout differs per backend/jax version), sorted for
    a deterministic rank order."""
    found = set()
    for pattern in _TRACE_GLOBS:
        found.update(glob.glob(os.path.join(profile_dir, pattern)))
        found.update(glob.glob(os.path.join(profile_dir, "**", pattern),
                               recursive=True))
    return sorted(found)


def _unavailable(source: str, reason: str) -> RankTrace:
    return RankTrace(source=source,
                     provenance=UNAVAILABLE_PREFIX + reason)


def parse_trace_file(path: str) -> RankTrace:
    """Parse one ``trace.json(.gz)`` into a :class:`RankTrace`.

    Never raises: malformed gzip/JSON, a missing ``traceEvents`` list,
    or a stream with no recognizable XLA op events all return the
    ``unavailable:<reason>`` marker (empty event list)."""
    try:
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "rt", encoding="utf-8") as fh:
            doc = json.load(fh)
    except Exception as e:  # noqa: BLE001 — surfaced in the provenance
        return _unavailable(path, f"parse-failed:{type(e).__name__}")
    raw = doc.get("traceEvents") if isinstance(doc, dict) else None
    if not isinstance(raw, list) or not raw:
        return _unavailable(path, "no-trace-events")

    # metadata pass: process/thread names drive the device-lane selector
    proc_names: Dict[int, str] = {}
    thread_names: Dict[Tuple[int, int], str] = {}
    for e in raw:
        if not isinstance(e, dict) or e.get("ph") != "M":
            continue
        args = e.get("args") or {}
        if e.get("name") == "process_name":
            proc_names[e.get("pid", 0)] = str(args.get("name", ""))
        elif e.get("name") == "thread_name":
            thread_names[(e.get("pid", 0), e.get("tid", 0))] = \
                str(args.get("name", ""))

    events: List[TraceEvent] = []
    for e in raw:
        if not isinstance(e, dict) or e.get("ph") != "X":
            continue
        ts, dur = e.get("ts"), e.get("dur")
        if not isinstance(ts, (int, float)) \
                or not isinstance(dur, (int, float)) or dur <= 0:
            continue
        args = e.get("args") or {}
        name = str(args.get("hlo_op") or e.get("name") or "")
        if not name:
            continue
        pid, tid = e.get("pid", 0), e.get("tid", 0)
        is_op = "hlo_op" in args or "hlo_module" in args
        if not is_op:
            pname = proc_names.get(pid, "")
            if "/device:" not in pname and not pname.startswith(
                    ("TPU", "GPU")):
                continue
            tname = thread_names.get((pid, tid), "")
            if tname.startswith(_NON_OP_THREAD_PREFIXES):
                continue
        cat = categorize(name)
        if cat is None:
            continue
        events.append(TraceEvent(name=name, category=cat,
                                 start_us=float(ts), dur_us=float(dur),
                                 pid=pid, tid=tid))
    if not events:
        return _unavailable(path, "no-op-events")
    events.sort(key=lambda ev: (ev.start_us, ev.end_us, ev.name))
    return RankTrace(source=path, provenance=PROVENANCE_MEASURED,
                     events=events)


def load_profile_dirs(profile_dirs: Sequence[str]) -> List[RankTrace]:
    """Ingest one or more profile directories; each discovered trace
    FILE is one rank (multi-host captures drop one per host).  A
    directory with no trace files contributes a single
    ``unavailable:no-trace-files`` rank so the degradation is explicit,
    never an empty silence."""
    ranks: List[RankTrace] = []
    for d in profile_dirs:
        files = find_trace_files(d)
        if not files:
            ranks.append(_unavailable(d, "no-trace-files"))
            continue
        ranks.extend(parse_trace_file(f) for f in files)
    return ranks


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser(
        prog="python -m apex_tpu.observability.trace_ingest",
        description="ingest jax.profiler trace dirs and print the "
                    "measured attribution record (per-category time, "
                    "exposed comm, measured MFU, cross-rank skew) as "
                    "JSON")
    p.add_argument("profile_dirs", nargs="+",
                   help="APEX_TPU_PROFILE_DIR capture directories "
                        "(several = merged as ranks)")
    p.add_argument("--steps", type=int, default=None,
                   help="step dispatches inside the captured window "
                        "(enables per-step time + measured MFU)")
    p.add_argument("--flops-per-step", type=float, default=None,
                   help="compiled FLOPs per step (xla_stats) for "
                        "measured MFU")
    p.add_argument("--chip", default=None,
                   help="device_kind the trace was captured on, for the "
                        "chip-spec peak (measured MFU is unavailable "
                        "without it)")
    p.add_argument("--model-exposed-comm-us", type=float, default=None,
                   help="comm_model.step_time_estimate exposed_comm_us "
                        "prediction to compare against")
    p.add_argument("--out", default=None,
                   help="write the JSON record here instead of stdout")
    args = p.parse_args(argv)

    for d in args.profile_dirs:
        if not os.path.isdir(d):
            p.error(f"profile dir not found: {d}")

    from apex_tpu.observability.attribution import attribute
    record = attribute(
        load_profile_dirs(args.profile_dirs),
        steps=args.steps, flops_per_step=args.flops_per_step,
        device_kind=args.chip,
        model_exposed_comm_us=args.model_exposed_comm_us)
    text = json.dumps(record, indent=1, sort_keys=True) + "\n"
    if args.out:
        with open(args.out, "w", encoding="utf-8") as fh:
            fh.write(text)
        print(f"attribution written: {args.out}")
    else:
        print(text, end="")
    return 0


if __name__ == "__main__":  # pragma: no cover
    raise SystemExit(main())
