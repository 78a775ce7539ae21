"""Analytic comm-bytes + peak-live-buffer model over jaxprs.

The SPMD auditor (:mod:`apex_tpu.analysis.spmd_audit`) prices every
collective in a registered executable with the standard ring-algorithm
per-chip byte counts — the same arithmetic PERF.md round-6 carries by
hand for the ZeRO RS+AG==AR argument, now machine-applied:

===============  ==========================================  ============
primitive        per-chip bytes (axis size n, payload B)     B measured at
===============  ==========================================  ============
psum/pmax/pmin   ``2 * (n-1)/n * B``  (ring all-reduce)      input
all_gather       ``(n-1) * B``  (== (n-1)/n * output)        input shard
reduce_scatter   ``(n-1)/n * B``                             input
all_to_all       ``(n-1)/n * B``                             input
ppermute         ``B``  (one neighbor hop)                   input
===============  ==========================================  ============

Multi-axis collectives (``psum(x, ("data", "expert"))``) price at the
PRODUCT of the axis sizes — one logical ring over the combined group.

The peak-live-buffer estimate is a linear-scan liveness walk over the
eqn sequence: at each program point the live set is every value already
produced (or an input) whose last consumer is still ahead, plus the
values the current eqn materializes; the peak is the max over points.
It deliberately ignores XLA fusion/rematerialization — the number is an
upper-bound *shape* metric whose job is to be deterministic and to move
when someone adds a full-size temporary to a registered executable, not
to predict an HBM high-water mark.

Both reports are pure functions of the jaxpr (+ static axis sizes), so
they are stable across runs and machines — the property the committed
``.analysis_budget.json`` ratchet needs.
"""
from __future__ import annotations

from typing import Dict, Optional

__all__ = ["COLLECTIVE_PRIMS", "collective_axes", "eqn_comm_bytes",
           "comm_report", "peak_live_bytes", "ring_allreduce_bytes",
           "jaxpr_dot_flops", "step_time_estimate"]

# Collective primitive name -> pricing kind.  ``psum_scatter`` traces as
# ``reduce_scatter`` on current jax; both spellings are kept so the
# walker survives either.
COLLECTIVE_PRIMS: Dict[str, str] = {
    "psum": "allreduce",
    "pmax": "allreduce",
    "pmin": "allreduce",
    "all_gather": "allgather",
    "reduce_scatter": "reducescatter",
    "psum_scatter": "reducescatter",
    "all_to_all": "alltoall",
    "ppermute": "ppermute",
}


def _aval_bytes(aval) -> int:
    size = 1
    for d in getattr(aval, "shape", ()):
        size *= int(d)
    return size * getattr(aval, "dtype", None).itemsize


def collective_axes(eqn) -> tuple:
    """The mesh axis name(s) a collective eqn reduces/reshards over.

    jax spells the parameter ``axes`` (psum/pmax/pmin) or ``axis_name``
    (all_gather/reduce_scatter/ppermute/all_to_all); either may be a
    bare name or a tuple.
    """
    axes = eqn.params.get("axes", eqn.params.get("axis_name"))
    if axes is None:
        return ()
    if isinstance(axes, (tuple, list)):
        return tuple(axes)
    return (axes,)


def ring_allreduce_bytes(n: int, payload: int) -> int:
    """Per-chip bytes of a ring all-reduce of ``payload`` bytes."""
    return 0 if n <= 1 else int(2 * (n - 1) * payload // n)


def eqn_comm_bytes(eqn, axis_sizes: Dict[str, int]) -> int:
    """Per-chip bytes for one collective eqn (0 for non-collectives).

    ``axis_sizes`` maps mesh axis name -> size; an axis the executable
    never declared prices at size 1 (zero bytes) — the *soundness* of
    such an axis is the auditor's APX211 check, not the price model's.
    """
    kind = COLLECTIVE_PRIMS.get(eqn.primitive.name)
    if kind is None:
        return 0
    n = 1
    for ax in collective_axes(eqn):
        n *= int(axis_sizes.get(ax, 1))
    if n <= 1:
        return 0
    payload = sum(_aval_bytes(v.aval) for v in eqn.invars
                  if getattr(v, "aval", None) is not None)
    if kind == "allreduce":
        return ring_allreduce_bytes(n, payload)
    if kind == "allgather":
        return (n - 1) * payload
    if kind in ("reducescatter", "alltoall"):
        return (n - 1) * payload // n
    return payload  # ppermute: one neighbor hop


def _subjaxpr_items(eqn, axis_sizes: Optional[Dict[str, int]] = None,
                    all_branches: bool = False):
    """(jaxpr, multiplier) pairs nested under one eqn.

    * ``scan`` bodies run ``length`` times — comm inside multiplies.
    * ``while`` bodies have an unknown trip count — priced ONCE (a
      lower bound; the budget ratchet still moves when the per-trip
      comm grows).
    * ``cond`` branches are alternatives — for comm the report prices
      the MOST expensive branch (a budget is a worst case, and pricing
      all branches would double-count mutually exclusive collectives);
      ``all_branches=True`` yields every branch instead, for callers
      that take a max over the yields themselves (the peak-live walk —
      selecting by comm bytes there would just pick branch 0).
    """
    import jax.extend.core as jex_core

    name = eqn.primitive.name
    if name == "scan":
        length = int(eqn.params.get("length", 1))
        yield eqn.params["jaxpr"], length
        return
    if name == "cond":
        if all_branches:
            for br in eqn.params.get("branches", ()):
                yield br, 1
            return
        best, best_bytes = None, -1
        for br in eqn.params.get("branches", ()):
            b = _jaxpr_comm_bytes(br, axis_sizes or {})
            if b > best_bytes:
                best, best_bytes = br, b
        if best is not None:
            yield best, 1
        return
    for v in eqn.params.values():
        items = v if isinstance(v, (list, tuple)) else [v]
        for item in items:
            if isinstance(item, (jex_core.Jaxpr, jex_core.ClosedJaxpr)):
                yield item, 1


def _open(jaxpr):
    return getattr(jaxpr, "jaxpr", jaxpr)


def _jaxpr_comm_bytes(jaxpr, axis_sizes) -> int:
    total = 0
    for eqn in _open(jaxpr).eqns:
        total += eqn_comm_bytes(eqn, axis_sizes)
        for sub, mult in _subjaxpr_items(eqn, axis_sizes):
            total += mult * _jaxpr_comm_bytes(sub, axis_sizes)
    return total


def comm_report(closed_jaxpr, axis_sizes: Dict[str, int]) -> dict:
    """``{"total_bytes", "by_collective": {"prim@axes": bytes},
    "counts": {"prim@axes": n}}`` for one traced executable.

    ``by_collective`` keys are ``"all_gather@data"``-style so the
    committed budget stays human-readable.  cond branches contribute
    their most expensive alternative; scan bodies multiply by length.
    """
    by: Dict[str, int] = {}
    counts: Dict[str, int] = {}

    def walk(jaxpr, mult):
        for eqn in _open(jaxpr).eqns:
            b = eqn_comm_bytes(eqn, axis_sizes)
            if b or eqn.primitive.name in COLLECTIVE_PRIMS:
                key = (f"{eqn.primitive.name}@"
                       f"{','.join(collective_axes(eqn))}")
                by[key] = by.get(key, 0) + mult * b
                counts[key] = counts.get(key, 0) + mult
            for sub, m in _subjaxpr_items(eqn, axis_sizes):
                walk(sub, mult * m)

    walk(closed_jaxpr, 1)
    return {"total_bytes": sum(by.values()), "by_collective": by,
            "counts": counts}


def _jaxpr_dot_flops(jaxpr, mult: int = 1) -> int:
    """Per-chip matmul FLOPs over a jaxpr (2·M·N·K per ``dot_general``,
    nested jaxprs included, scan bodies × length, cond = max branch).
    Conv/Pallas work is not counted — the number feeds a RELATIVE
    step-time model, and every registered executable's hot loops are
    dot-shaped."""
    total = 0
    for eqn in _open(jaxpr).eqns:
        if eqn.primitive.name == "dot_general":
            (lc, _), (lb, _) = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval
            out = eqn.outvars[0].aval
            k = 1
            for d in lc:
                k *= int(lhs.shape[d])
            size = 1
            for d in out.shape:
                size *= int(d)
            total += 2 * size * k
        subs = list(_subjaxpr_items(eqn, {}, all_branches=True))
        if eqn.primitive.name == "cond":
            total += max((_jaxpr_dot_flops(s) for s, _ in subs),
                         default=0)
        else:
            for sub, m in subs:
                total += m * _jaxpr_dot_flops(sub)
    return mult * total


def jaxpr_dot_flops(closed_jaxpr) -> int:
    """Public face of the analytic matmul-FLOP count — what the APX218
    drift ledger compares against the compiled ``cost_analysis()``
    truth (which counts EVERY op, so the pinned ratio also records how
    dot-dominated each executable is)."""
    return _jaxpr_dot_flops(_open(closed_jaxpr))


def step_time_estimate(closed_jaxpr, axis_sizes: Dict[str, int], *,
                       tflops: Optional[float] = None,
                       ici_gbps: float = 100.0) -> dict:
    """Analytic overlap-aware step-time model for one executable.

    Prices the jaxpr's ``dot_general`` FLOPs against ``tflops`` and its
    collective bytes (the APX215 ring formulas) against ``ici_gbps``,
    then reports both scheduling disciplines:

    * ``sequential_us`` — comm SERIAL with compute (every collective on
      the critical path): ``t_compute + t_comm``;
    * ``overlap_us`` — comm hidden under compute (the restructured
      prefetch/ring pipelines): ``max(t_compute, t_comm)`` per step,
      i.e. only the EXPOSED comm ``max(t_comm - t_compute, 0)`` adds to
      the roofline.

    The absolute numbers inherit the bandwidth constants' optimism —
    the pair is a MODEL whose job is the ratio (the step-time win a
    bench capture records next to the measured legs as
    ``overlap_step_time_model_us``), not a wall-clock prediction.

    ``tflops=None`` resolves to the :mod:`apex_tpu.chip_specs` default
    generation's bf16 peak — the one chip-spec table (callers with a
    live device pass ``find_spec(device_kind).bf16_tflops``).
    """
    if tflops is None:
        from apex_tpu.chip_specs import default_spec
        tflops = default_spec().bf16_tflops
    report = comm_report(closed_jaxpr, axis_sizes)
    flops = _jaxpr_dot_flops(closed_jaxpr)
    t_compute = flops / (tflops * 1e12)
    t_comm = report["total_bytes"] / (ici_gbps * 1e9)
    return {
        "compute_us": round(t_compute * 1e6, 3),
        "comm_us": round(t_comm * 1e6, 3),
        "comm_bytes": int(report["total_bytes"]),
        "dot_flops": int(flops),
        "sequential_us": round((t_compute + t_comm) * 1e6, 3),
        "overlap_us": round(max(t_compute, t_comm) * 1e6, 3),
        "exposed_comm_us": round(max(t_comm - t_compute, 0.0) * 1e6, 3),
    }


def peak_live_bytes(closed_jaxpr) -> int:
    """Linear-scan liveness upper bound on live buffer bytes.

    Inputs are live from entry until their last use; each eqn's outputs
    become live at its position; jaxpr outputs stay live to the end.
    An eqn carrying subjaxprs (cond/scan/pjit/custom_vjp) contributes
    the max of its branches' internal peaks as a transient at its
    position — nested intermediates don't outlive the eqn.
    """
    import jax.extend.core as jex_core

    jaxpr = _open(closed_jaxpr)
    eqns = jaxpr.eqns
    last_use: dict = {}
    for i, eqn in enumerate(eqns):
        for v in eqn.invars:
            if not isinstance(v, jex_core.Literal):
                last_use[v] = i
    n_eqns = len(eqns)
    for v in jaxpr.outvars:
        if not isinstance(v, jex_core.Literal):
            last_use[v] = n_eqns

    live = 0
    for v in list(jaxpr.invars) + list(jaxpr.constvars):
        if v in last_use:
            live += _aval_bytes(v.aval)
    peak = live
    born_at: dict = {}
    for i, eqn in enumerate(eqns):
        transient = 0
        for sub, _ in _subjaxpr_items(eqn, all_branches=True):
            transient = max(transient, peak_live_bytes(sub))
        for v in eqn.outvars:
            if v in last_use:
                live += _aval_bytes(v.aval)
                born_at[v] = i
        peak = max(peak, live + transient)
        # free everything whose last consumer was this eqn
        for v in list(eqn.invars) + list(eqn.outvars):
            if not isinstance(v, jex_core.Literal) \
                    and last_use.get(v) == i and born_at.get(v, -1) <= i:
                live -= _aval_bytes(v.aval)
                last_use.pop(v)
    return peak
