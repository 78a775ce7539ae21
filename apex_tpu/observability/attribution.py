"""Measured-time attribution over ingested profiler traces (ISSUE 14).

The third truth source.  The analysis suite *estimates* (analytic
comm/FLOP models), ``xla_stats`` reports what the *compiler* says, and
this module reports what the hardware *did*: wall time per op category
from the normalized event stream :mod:`trace_ingest` produces, with
interval-union arithmetic so nested/parallel events never double-count.

The rollup per rank (all µs, in the trace's own clock):

* ``window_us`` — first op start to last op end (the captured extent);
* ``busy_us`` — measure of the union of ALL op intervals;
* ``host_gap_us`` — ``window_us - busy_us`` (time no op covers);
* ``categories`` — per-category interval-union time (dot, fusion,
  per-type collectives, copy, other);
* ``compute_us`` — union of the compute categories (dot+fusion+other);
* ``exposed_comm_us`` — collective time NOT overlapped by concurrent
  compute: ``measure(union(collectives) - union(compute))``.  This is
  the measured face of ``comm_model.step_time_estimate``'s
  ``exposed_comm_us`` prediction, and the pair's ratio
  (``exposed_comm_drift_ratio``) is what the bench watch trends;
* ``coverage`` — ``(sum(categories) + host_gap_us) / window_us``.  On a
  serialized device queue this is exactly 1.0; a thread-pool backend
  (CPU) runs ops concurrently, so categories can overlap each other and
  coverage drifts above 1 — the documented tolerance is **±0.25**
  (asserted by the acceptance test): outside it the trace is suspect.

With a caller-supplied ``steps`` (dispatches inside the window) the
record adds ``step_us = window_us / steps`` and, with compiled
``flops_per_step`` (``xla_stats.CompiledStats.flops``) and a chip spec,
the **measured MFU**: ``steps * flops_per_step / compute_seconds /
chip_peak`` — compiled FLOPs over measured compute time, where the
train gauge's MFU divides by the step *wall* time.

Multiple ranks (one per trace file) merge into the straggler report
multi-chip serving needs: headline times come from the SLOWEST rank
(the straggler sets the global step), and ``skew`` carries
``slowest_over_median`` (per-rank window ratio), the per-rank windows,
and per-collective-type cross-rank start spreads (k-th occurrence,
rebased to each rank's first op — clocks are per-host).

Degradation (PR 10 discipline): no usable rank -> a record holding
ONLY ``{"provenance": "unavailable:<reason>", "ranks": 0, "sources"}``
— numeric fields are absent, never zero.  :func:`publish` mirrors a
record into the pinned ``trace_*`` metric families and the
``attribution`` JSONL event (absent values stay ``null``).
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from apex_tpu.observability.trace_ingest import (PROVENANCE_MEASURED,
                                                 UNAVAILABLE_PREFIX,
                                                 RankTrace, TraceEvent)

__all__ = ["ATTRIBUTION_METRIC_FAMILIES", "ATTRIBUTION_EVENTS",
           "COMPUTE_CATEGORIES", "COVERAGE_TOLERANCE",
           "merge_intervals", "interval_measure", "subtract_intervals",
           "attribute", "publish"]

#: schema families this module writes (guard-test pattern, like
#: ``spans.TRACE_METRIC_FAMILIES``).
ATTRIBUTION_METRIC_FAMILIES: Tuple[str, ...] = (
    "trace_window_us", "trace_step_time_us", "trace_mfu",
    "trace_exposed_comm_us", "trace_category_time_us",
    "trace_rank_step_skew", "trace_collective_start_spread_us")
ATTRIBUTION_EVENTS: Tuple[str, ...] = ("attribution",)

#: categories whose union is "compute" for the exposed-comm overlap
#: (copies are transfers — comm hiding under a copy is still hidden
#: from the compute roofline, so copy does NOT count as cover).
COMPUTE_CATEGORIES: Tuple[str, ...] = ("dot", "fusion", "other")

#: documented tolerance on ``coverage`` (see module docstring).
COVERAGE_TOLERANCE = 0.25


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def merge_intervals(intervals: Iterable[Tuple[float, float]]) \
        -> List[Tuple[float, float]]:
    """Sorted disjoint union of ``(start, end)`` intervals (empty and
    inverted inputs are dropped)."""
    ivs = sorted((s, e) for s, e in intervals if e > s)
    out: List[Tuple[float, float]] = []
    for s, e in ivs:
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def interval_measure(merged: Sequence[Tuple[float, float]]) -> float:
    """Total length of a disjoint interval list."""
    return sum(e - s for s, e in merged)


def subtract_intervals(target: Sequence[Tuple[float, float]],
                       cover: Sequence[Tuple[float, float]]) \
        -> List[Tuple[float, float]]:
    """``target - cover`` for two disjoint sorted interval lists: the
    parts of ``target`` no ``cover`` interval overlaps (the
    exposed-comm primitive: collectives minus concurrent compute)."""
    out: List[Tuple[float, float]] = []
    j = 0
    for s, e in target:
        lo = s
        while j < len(cover) and cover[j][1] <= lo:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < e:
            cs, ce = cover[k]
            if cs > lo:
                out.append((lo, cs))
            lo = max(lo, ce)
            if lo >= e:
                break
            k += 1
        if lo < e:
            out.append((lo, e))
    return out


# ---------------------------------------------------------------------------
# per-rank rollup
# ---------------------------------------------------------------------------

def _r(v: float, digits: int = 3) -> float:
    return round(float(v), digits)


def _attribute_rank(events: Sequence[TraceEvent]) -> dict:
    by_cat: Dict[str, List[Tuple[float, float]]] = {}
    for ev in events:
        by_cat.setdefault(ev.category, []).append(
            (ev.start_us, ev.end_us))
    merged = {cat: merge_intervals(ivs) for cat, ivs in by_cat.items()}
    categories = {cat: _r(interval_measure(m))
                  for cat, m in merged.items()}
    all_union = merge_intervals(iv for ivs in by_cat.values()
                                for iv in ivs)
    busy = interval_measure(all_union)
    window = (max(ev.end_us for ev in events)
              - min(ev.start_us for ev in events))
    compute_union = merge_intervals(
        iv for cat in COMPUTE_CATEGORIES for iv in by_cat.get(cat, ()))
    coll_union = merge_intervals(
        iv for cat, ivs in by_cat.items()
        if cat.startswith("collective:") for iv in ivs)
    exposed = interval_measure(
        subtract_intervals(coll_union, compute_union))
    collectives = {}
    for cat in sorted(by_cat):
        if not cat.startswith("collective:"):
            continue
        kind = cat.split(":", 1)[1]
        collectives[kind] = {
            "time_us": categories[cat],
            "count": len(by_cat[cat]),
        }
    return {
        "window_us": _r(window),
        "busy_us": _r(busy),
        "host_gap_us": _r(window - busy),
        "categories": categories,
        "collectives": collectives,
        "compute_us": _r(interval_measure(compute_union)),
        "exposed_comm_us": _r(exposed),
        "coverage": (_r((sum(categories.values()) + (window - busy))
                        / window, 4) if window > 0 else None),
    }


def _skew_report(rank_rollups: Sequence[dict],
                 ranks: Sequence[RankTrace]) -> dict:
    """Cross-rank straggler skew: per-rank windows, slowest/median, and
    per-collective start spreads (k-th occurrence of each type, starts
    rebased to each rank's first op event — per-host clocks never
    share an epoch)."""
    windows = [rr["window_us"] for rr in rank_rollups]
    ordered = sorted(windows)
    # lower median: on an even rank count the straggler must not BE
    # the median (2 ranks would always report skew 1.0)
    median = ordered[(len(ordered) - 1) // 2]
    slowest = max(windows)
    spread: Dict[str, float] = {}
    starts_by_rank: List[Dict[str, List[float]]] = []
    for tr in ranks:
        base = min(ev.start_us for ev in tr.events)
        per_type: Dict[str, List[float]] = {}
        for ev in sorted(tr.events, key=lambda e: e.start_us):
            if ev.category.startswith("collective:"):
                per_type.setdefault(ev.category.split(":", 1)[1],
                                    []).append(ev.start_us - base)
        starts_by_rank.append(per_type)
    for kind in sorted({k for per in starts_by_rank for k in per}):
        seqs = [per.get(kind, []) for per in starts_by_rank]
        depth = min(len(s) for s in seqs)
        if depth == 0 or len(seqs) < 2:
            continue
        spread[kind] = _r(max(
            max(s[k] for s in seqs) - min(s[k] for s in seqs)
            for k in range(depth)))
    out = {
        "per_rank_window_us": [_r(w) for w in windows],
        "slowest_rank": windows.index(slowest),
        "slowest_over_median": (_r(slowest / median, 4)
                                if median > 0 else None),
    }
    if spread:
        out["collective_start_spread_us"] = spread
    return out


# ---------------------------------------------------------------------------
# the public record
# ---------------------------------------------------------------------------

def attribute(ranks: Sequence[RankTrace], *,
              steps: Optional[int] = None,
              flops_per_step: Optional[float] = None,
              device_kind: Optional[str] = None,
              model_exposed_comm_us: Optional[float] = None) -> dict:
    """The measured-attribution record for one capture (see the module
    docstring for every field).  Degraded ingestion yields the
    ``unavailable:`` record — provenance + sources only, no numbers."""
    sources = [tr.source for tr in ranks]
    usable = [tr for tr in ranks if not tr.degraded]
    if not usable:
        reasons = sorted({tr.provenance[len(UNAVAILABLE_PREFIX):]
                          for tr in ranks}) or ["no-ranks"]
        return {
            "provenance": UNAVAILABLE_PREFIX + ",".join(reasons),
            "ranks": 0,
            "sources": sources,
        }
    rollups = [_attribute_rank(tr.events) for tr in usable]
    # the straggler sets the global step: headline numbers are the
    # slowest rank's (single-rank captures: the only rank's)
    head = rollups[max(range(len(rollups)),
                       key=lambda i: rollups[i]["window_us"])]
    record = dict(head)
    record["provenance"] = PROVENANCE_MEASURED
    record["ranks"] = len(usable)
    record["sources"] = sources
    if len(rollups) > 1:
        record["skew"] = _skew_report(rollups, usable)

    if steps and steps > 0:
        record["steps"] = int(steps)
        record["step_us"] = _r(head["window_us"] / steps)
        record["step_exposed_comm_us"] = _r(
            head["exposed_comm_us"] / steps)
    from apex_tpu.chip_specs import match_spec
    spec = match_spec(device_kind)
    if steps and steps > 0 and flops_per_step \
            and head["compute_us"] > 0 and spec is not None:
        peak = spec.bf16_tflops * 1e12
        record["mfu"] = round(
            steps * flops_per_step / (head["compute_us"] * 1e-6) / peak,
            6)
        record["mfu_provenance"] = PROVENANCE_MEASURED
    else:
        # a device kind outside the chip table has no peak to divide
        # by: the MFU is absent, never priced against another chip's
        record["mfu_provenance"] = UNAVAILABLE_PREFIX + (
            "no-step-count" if not steps
            else "no-compiled-flops" if not flops_per_step
            else "no-compute-time" if not head["compute_us"] > 0
            else "device-kind-not-in-chip-specs")
    if model_exposed_comm_us is not None:
        record["model_exposed_comm_us"] = _r(model_exposed_comm_us)
        measured_per_step = record.get("step_exposed_comm_us")
        if measured_per_step is not None and model_exposed_comm_us > 0:
            record["exposed_comm_drift_ratio"] = round(
                measured_per_step / model_exposed_comm_us, 4)
    return record


# ---------------------------------------------------------------------------
# registry publishing
# ---------------------------------------------------------------------------

def publish(record: dict, profile_dir: str, registry=None) -> None:
    """Mirror an attribution record into the pinned ``trace_*`` metric
    families and emit the ``attribution`` JSONL event.  A degraded
    record emits the event (provenance + nulls) and sets NO gauges —
    a dashboard must read the marker, not a fabricated zero."""
    if registry is None:
        from apex_tpu.observability import configure_from_env
        registry = configure_from_env()
    gauges = (("window_us", "trace_window_us"),
              ("step_us", "trace_step_time_us"),
              ("mfu", "trace_mfu"),
              ("exposed_comm_us", "trace_exposed_comm_us"))
    for key, fam in gauges:
        v = record.get(key)
        if v is not None:
            registry.declared(fam).set(v)
    for cat, us in (record.get("categories") or {}).items():
        registry.declared("trace_category_time_us").set(us, category=cat)
    host_gap = record.get("host_gap_us")
    if host_gap is not None:
        registry.declared("trace_category_time_us").set(
            host_gap, category="host_gap")
    skew = record.get("skew") or {}
    if skew.get("slowest_over_median") is not None:
        registry.declared("trace_rank_step_skew").set(
            skew["slowest_over_median"])
    for kind, us in (skew.get("collective_start_spread_us")
                     or {}).items():
        registry.declared("trace_collective_start_spread_us").set(
            us, collective=kind)
    registry.emit_event(
        "attribution",
        profile_dir=profile_dir,
        provenance=record["provenance"],
        ranks=record.get("ranks", 0),
        window_us=record.get("window_us"),
        busy_us=record.get("busy_us"),
        host_gap_us=record.get("host_gap_us"),
        compute_us=record.get("compute_us"),
        exposed_comm_us=record.get("exposed_comm_us"),
        coverage=record.get("coverage"),
        steps=record.get("steps"),
        step_us=record.get("step_us"),
        mfu=record.get("mfu"),
        mfu_provenance=record.get("mfu_provenance"),
        model_exposed_comm_us=record.get("model_exposed_comm_us"),
        exposed_comm_drift_ratio=record.get("exposed_comm_drift_ratio"),
        categories=record.get("categories") or {},
        collectives=record.get("collectives") or {},
        skew=record.get("skew"),
    )
