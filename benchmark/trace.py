"""From a profiler trace to numbers: the benchmark's own reader and reducer.

The JAX profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``;
``jax.profiler.ProfileData`` reads it with nothing but JAX.  What the TPU
planes look like under jax 0.9 on a "TPU v5 lite" (read by hand, PERF.md
"Hand-read traces"):

* one plane per chip, ``/device:TPU:<n>``; its line ``XLA Modules`` holds one
  event per executed program (``jit_step(<fingerprint>)``), its line
  ``XLA Ops`` one event per HLO operation (fusions, custom calls, copies);
* the plane ``/host:CPU`` holds the host threads; ``TraceAnnotation`` spans
  sit on the line of the thread that opened them.

Intervals are ``(start, end)`` pairs in nanoseconds.  The arithmetic (union,
clipping, gaps) was copied from ``apex_tpu/observability/attribution.py`` so
that a change there cannot move a benchmark number.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Iterable, Optional

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float
    end: float

    @property
    def seconds(self) -> float:
        return (self.end - self.start) * 1e-9


@dataclasses.dataclass
class Trace:
    """What the reducers read: per chip its operations and its programs,
    and the host's annotation spans."""
    ops: dict            # chip index -> [Event]
    modules: dict        # chip index -> [Event]
    host: list           # [Event] — TraceAnnotation spans of the benchmark
    #                      and of the program (names with a '.' prefix kept)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str, host_prefixes: Iterable[str]) -> Trace:
    """Read the device planes whole and, of the host plane, the spans whose
    name starts with one of ``host_prefixes``."""
    from jax.profiler import ProfileData

    prefixes = tuple(host_prefixes)
    ops, modules, host = {}, {}, []
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[chip] = _events(line)
                elif line.name == MODULES_LINE:
                    modules[chip] = _events(line)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(e for e in _events(line)
                            if e.name.startswith(prefixes))
    host.sort(key=lambda e: e.start)
    return Trace(ops=ops, modules=modules, host=host)


#: an operation's event is named by its whole HLO text; its head — name,
#: result type, kind of operation — is what the reducers match
NAME_CHARS = 240


def _events(line) -> list:
    out = [Event(e.name[:NAME_CHARS], float(e.start_ns),
                 float(e.start_ns) + float(e.duration_ns))
           for e in line.events]
    out.sort(key=lambda e: e.start)
    return out


# -- interval arithmetic -----------------------------------------------------

def union(intervals) -> list:
    """Merge overlapping ``(start, end)`` pairs; sorted, disjoint."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals) -> float:
    """Sum of lengths of DISJOINT intervals, in the unit they came in."""
    return float(sum(e - s for s, e in intervals))


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a, b) -> list:
    """The part of disjoint sorted ``a`` that no interval of disjoint
    sorted ``b`` covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy, lo: float, hi: float) -> list:
    """The idle intervals of ``[lo, hi]`` given disjoint sorted ``busy``."""
    return subtract([(lo, hi)], clip(busy, lo, hi))


# -- reductions --------------------------------------------------------------

def window(trace: Trace) -> Optional[tuple]:
    """``(lo, hi)`` of the traced window on the device clock: first start to
    last end over every chip's operations."""
    evs = [e for chip in trace.ops.values() for e in chip]
    if not evs:
        return None
    return min(e.start for e in evs), max(e.end for e in evs)


def busy_seconds(trace: Trace) -> dict:
    """Per chip, seconds in which at least one operation ran."""
    return {chip: total(union((e.start, e.end) for e in evs)) * 1e-9
            for chip, evs in trace.ops.items()}


def seconds_by_name(events) -> dict:
    """Sum of durations by event name."""
    out: dict = {}
    for e in events:
        out[e.name] = out.get(e.name, 0.0) + e.seconds
    return out


def matching_seconds(events, pattern: str) -> tuple:
    """``(seconds, count)`` over the events whose name matches."""
    rx = re.compile(pattern)
    hit = [e for e in events if rx.search(e.name)]
    return sum(e.seconds for e in hit), len(hit)


def short_name(name: str) -> str:
    """``%fusion.12 = bf16[..]{..} fusion(...)`` -> ``fusion.12 fusion``:
    the operation's own name and its kind, without shapes."""
    m = re.match(r"%?([^\s=]+) = .*?\s([a-z][a-z\-]*)\(", name)
    if m:
        return f"{m.group(1)} {m.group(2)}"
    return name.lstrip("%").split(" = ")[0][:80]


def top_ops(trace: Trace, n: int = 10) -> list:
    """The ``n`` operations with most device time, averaged over the
    chips used: ``[[name, seconds], ...]``."""
    chips = max(1, len(trace.ops))
    acc: dict = {}
    for evs in trace.ops.values():
        for name, s in seconds_by_name(evs).items():
            name = short_name(name)
            acc[name] = acc.get(name, 0.0) + s / chips
    return [[k, v] for k, v in
            sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps_by_host_span(trace: Trace, n: int = 10) -> list:
    """Idle time of chip 0 (every chip runs the same program) attributed to
    what the host was doing: each idle interval is split over the host
    spans that overlap it, innermost (latest-opened) span first; what no
    span covers is ``(unannotated)``.  ``[[name, seconds], ...]``."""
    if not trace.ops:
        return []
    chip = min(trace.ops)
    win = window(trace)
    busy = union((e.start, e.end) for e in trace.ops[chip])
    idle = gaps(busy, *win)
    acc: dict = {}
    spans = sorted(trace.host, key=lambda e: (e.start, -e.end))
    for lo, hi in idle:
        left = [(lo, hi)]
        # innermost first: later-starting spans are nested deeper
        for sp in reversed([s for s in spans
                            if s.start < hi and s.end > lo]):
            part = clip(left, sp.start, sp.end)
            if part:
                acc[sp.name] = acc.get(sp.name, 0.0) + total(part) * 1e-9
                left = subtract(left, union(part))
        if left:
            acc["(unannotated)"] = (acc.get("(unannotated)", 0.0)
                                    + total(left) * 1e-9)
    return [[k, v] for k, v in
            sorted(acc.items(), key=lambda kv: -kv[1])[:n]]
