"""Compiled-truth statistics: what XLA says an executable costs.

Every capacity number elsewhere in the repo is a hand-built estimate —
APX215's peak-live is a linear liveness scan over the jaxpr,
``comm_model`` prices only ``dot_general`` FLOPs, bench MFU divides by
an analytic ``6*N + attention`` FLOPs/token.  The compiler already
knows the truth: ``jit(...).lower(...).compile()`` exposes
``cost_analysis()`` (FLOPs, bytes accessed) and ``memory_analysis()``
(argument/output/alias/temp buffer bytes) per executable.  This module
is the one place that truth is extracted, so the SPMD auditor's APX218
drift ledger, the ``train_mfu`` gauge, bench capture stamps, and the
flight-recorder report all read the SAME numbers.

Degradation contract: a backend without a cost model or without memory
accounting yields a :class:`CompiledStats` whose missing fields are
``None`` and whose ``provenance`` string says exactly what degraded —
never a fabricated zero.  The three provenance markers:

* ``"xla:cost+memory"`` — both analyses landed;
* ``"xla:cost-only:memory_analysis-unavailable"`` — FLOPs/bytes are
  compiled truth, peak HBM is unknown (``peak_hbm_bytes is None``);
* ``"unavailable:<reason>"`` — nothing compiled (trace/compile failure,
  no cost model): every numeric field is ``None``.

CLI: ``python -m apex_tpu.observability.xla_stats [--execs a,b]
[--out stats.json]`` dumps the ledger-executable stats the flight
recorder consumes.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from typing import Dict, Optional, Sequence

__all__ = ["CompiledStats", "PROVENANCE_FULL", "PROVENANCE_COST_ONLY",
           "PROVENANCE_UNAVAILABLE_PREFIX", "provenance_rank",
           "stats_from_compiled", "compile_and_stats", "ledger_stats",
           "main"]

PROVENANCE_FULL = "xla:cost+memory"
PROVENANCE_COST_ONLY = "xla:cost-only:memory_analysis-unavailable"
PROVENANCE_UNAVAILABLE_PREFIX = "unavailable:"


def provenance_rank(provenance: str) -> int:
    """Order on the degradation ladder: full=2 > cost-only=1 >
    unavailable=0.  The one place the ladder lives — the APX218
    degradation check and the flight recorder's source-selection both
    rank through here."""
    if provenance.startswith(PROVENANCE_UNAVAILABLE_PREFIX):
        return 0
    return 2 if provenance == PROVENANCE_FULL else 1


@dataclass(frozen=True)
class CompiledStats:
    """One executable's compiled-truth numbers (``None`` = the backend
    did not report it — see the module degradation contract)."""

    provenance: str
    flops: Optional[int] = None
    bytes_accessed: Optional[int] = None
    argument_bytes: Optional[int] = None
    output_bytes: Optional[int] = None
    alias_bytes: Optional[int] = None
    temp_bytes: Optional[int] = None
    peak_hbm_bytes: Optional[int] = None   # arg + out - alias + temp
    generated_code_bytes: Optional[int] = None

    @property
    def degraded(self) -> bool:
        return self.provenance != PROVENANCE_FULL

    def asdict(self) -> dict:
        """JSON-ready dict; ``None`` fields are DROPPED (a missing key
        is the explicit absence — serializing ``null`` would invite
        ``or 0`` fabrication downstream), provenance always present."""
        out = {"provenance": self.provenance}
        for k in ("flops", "bytes_accessed", "argument_bytes",
                  "output_bytes", "alias_bytes", "temp_bytes",
                  "peak_hbm_bytes", "generated_code_bytes"):
            v = getattr(self, k)
            if v is not None:
                out[k] = int(v)
        return out


def _unavailable(reason: str) -> CompiledStats:
    return CompiledStats(
        provenance=PROVENANCE_UNAVAILABLE_PREFIX + reason)


def _compiled_cost_analysis(compiled):
    """``Compiled.cost_analysis()`` as one flat dict, or None.  A
    missing method or a backend that raises (some PJRT plugins ship no
    cost model) -> None — callers must treat None as "unavailable",
    never as zero."""
    fn = getattr(compiled, "cost_analysis", None)
    if fn is None:
        return None
    try:
        out = fn()
    except Exception:  # noqa: BLE001 — unimplemented on this backend
        return None
    return dict(out) if out else None


def _compiled_memory_analysis(compiled):
    """``Compiled.memory_analysis()``: the backend's
    ``CompiledMemoryStats`` (argument/output/alias/temp byte fields) or
    None when the method is missing, raises, or returns nothing — the
    degraded-backend case the caller must mark explicitly."""
    fn = getattr(compiled, "memory_analysis", None)
    if fn is None:
        return None
    try:
        out = fn()
    except Exception:  # noqa: BLE001 — unimplemented on this backend
        return None
    if out is None or not hasattr(out, "argument_size_in_bytes"):
        return None
    return out


def stats_from_compiled(compiled) -> CompiledStats:
    """Extract :class:`CompiledStats` from an already-compiled
    ``jax.stages.Compiled`` (or anything exposing the same analysis
    methods)."""
    cost = _compiled_cost_analysis(compiled)
    if cost is None or "flops" not in cost:
        return _unavailable("no-cost-analysis-on-this-backend")
    flops = int(cost["flops"])
    # a cost model without the bytes key reports None (dropped), not a
    # fabricated 0 — same contract as the memory fields
    bytes_accessed = (int(cost["bytes accessed"])
                      if "bytes accessed" in cost else None)

    mem = _compiled_memory_analysis(compiled)
    if mem is None:
        return CompiledStats(provenance=PROVENANCE_COST_ONLY,
                             flops=flops, bytes_accessed=bytes_accessed)
    arg = int(mem.argument_size_in_bytes)
    out = int(mem.output_size_in_bytes)
    alias = int(mem.alias_size_in_bytes)
    temp = int(mem.temp_size_in_bytes)
    # a backend without the code-size field gets None (dropped from the
    # dict), not a fabricated 0 — same contract as every other field
    gcs = getattr(mem, "generated_code_size_in_bytes", None)
    return CompiledStats(
        provenance=PROVENANCE_FULL,
        flops=flops,
        bytes_accessed=bytes_accessed,
        argument_bytes=arg,
        output_bytes=out,
        alias_bytes=alias,
        temp_bytes=temp,
        peak_hbm_bytes=arg + out - alias + temp,
        generated_code_bytes=None if gcs is None else int(gcs),
    )


def compile_and_stats(fn, args, donate_argnums: tuple = ()) \
        -> CompiledStats:
    """``jit(fn, donate_argnums).lower(*args).compile()`` then extract.

    Never raises: a trace/compile failure returns the ``unavailable:``
    marker carrying the exception class — the caller decides whether
    that is a finding (the SPMD auditor) or a skipped stamp (bench).
    """
    import jax

    try:
        compiled = jax.jit(fn, donate_argnums=donate_argnums or ()) \
            .lower(*args).compile()
    except Exception as e:  # noqa: BLE001 — surfaced in the provenance
        return _unavailable(f"compile-failed:{type(e).__name__}")
    return stats_from_compiled(compiled)


def ledger_stats(execs: Optional[Sequence[str]] = None) \
        -> Dict[str, dict]:
    """Compiled stats for every (or the named) SPMD-ledger executable,
    as ``{name: CompiledStats.asdict()}`` — the standalone route to the
    same numbers ``apex-tpu-analyze --spmd`` embeds in
    ``.analysis_budget.json``, for the flight recorder and ad-hoc
    inspection.  Builders whose optional dependency is absent are
    skipped entirely (matching the auditor)."""
    from apex_tpu.analysis.spmd_audit import ensure_devices, exec_specs
    from apex_tpu.transformer import parallel_state as ps

    ensure_devices()
    specs = exec_specs()
    if execs:
        wanted = set(execs)
        missing = wanted - {s.name for s in specs}
        if missing:
            raise ValueError(f"unknown executable(s): {sorted(missing)}")
        specs = [s for s in specs if s.name in wanted]

    # same topology save/restore set as run_spmd_audit — the builders
    # destroy/reinit parallel_state freely, including the VPP globals
    saved_mesh = ps._MESH
    saved_vpp_rank = ps._VIRTUAL_PIPELINE_MODEL_PARALLEL_RANK
    saved_vpp_world = ps._VIRTUAL_PIPELINE_MODEL_PARALLEL_WORLD_SIZE
    out: Dict[str, dict] = {}
    try:
        for spec in specs:
            try:
                fn, args, _ = spec.build()
            except ImportError:
                continue            # optional dependency absent
            except Exception as e:  # noqa: BLE001 — marked, not raised
                out[spec.name] = _unavailable(
                    f"build-failed:{type(e).__name__}").asdict()
                continue
            out[spec.name] = compile_and_stats(
                fn, args, spec.donate_argnums).asdict()
    finally:
        ps._MESH = saved_mesh
        ps._VIRTUAL_PIPELINE_MODEL_PARALLEL_RANK = saved_vpp_rank
        ps._VIRTUAL_PIPELINE_MODEL_PARALLEL_WORLD_SIZE = saved_vpp_world
    return out


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser(
        prog="python -m apex_tpu.observability.xla_stats",
        description="dump compiled-truth stats (FLOPs, bytes, peak "
                    "HBM) for the registered SPMD-ledger executables")
    p.add_argument("--execs", default=None,
                   help="comma-separated executable names (default: "
                        "all registered)")
    p.add_argument("--out", default=None,
                   help="write JSON here instead of stdout")
    args = p.parse_args(argv)
    stats = ledger_stats(args.execs.split(",") if args.execs else None)
    text = json.dumps({"version": 1, "executables": stats}, indent=1,
                      sort_keys=True) + "\n"
    if args.out:
        with open(args.out, "w", encoding="utf-8") as fh:
            fh.write(text)
        print(f"compiled stats written: {args.out} "
              f"({len(stats)} executable(s))")
    else:
        print(text, end="")
    return 0


if __name__ == "__main__":  # pragma: no cover
    raise SystemExit(main())
