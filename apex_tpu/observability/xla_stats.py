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

**Op -> scope tables** (ISSUE 38).  A v5e profile names each device
operation by its HLO text WITHOUT metadata, so the ``jax.named_scope``
stages the program sets (``apex_prefill_cache_insert``,
``apex_moe_experts``, ``apex_train_forward`` ...) reach no event.  The
optimized HLO of the executable that runs carries every instruction's
``op_name``; :func:`op_scopes` reads it into ``{instruction: (scope,
backward)}``, and :func:`capture` keeps that table — and nothing else of
the executable — in a process-wide registry that a trace reader joins
on the instruction names the events start with (:func:`scope_tables`).
"""
from __future__ import annotations

import hashlib
import json
import re
import threading
import time
from collections import defaultdict
from dataclasses import dataclass
from typing import Dict, Mapping, Optional, Sequence, Tuple

__all__ = ["CompiledStats", "PROVENANCE_FULL", "PROVENANCE_COST_ONLY",
           "PROVENANCE_UNAVAILABLE_PREFIX", "provenance_rank",
           "stats_from_compiled", "compile_and_stats", "ledger_stats",
           "ScopeTable", "op_scopes", "capture", "capture_when_read",
           "scope_tables", "main"]

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


# -- op -> scope tables (ISSUE 38) -------------------------------------------

#: an instruction line: ``  [ROOT ]%name = <result type> <opcode>(...)``
_INSTR = re.compile(r"^\s+(?:ROOT )?%([^\s=]+) = (.*)$")
#: the opcode: the first `` word(`` after the result type (a layout's
#: ``T(8,128)`` / ``S(1)`` follow no space)
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_OPERAND = re.compile(r"%([^\s,()]+)")
_CALLED = re.compile(r"(calls|body|condition|to_apply|true_computation|"
                     r"false_computation)=%([^\s,)}]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%([^\s(]+) ")
#: a transform round a name-stack component: ``jvp(...)``,
#: ``transpose(...)``, ``vmap(...)``, ``remat(...)``, ``jit(...)``
_WRAPPED = re.compile(r"^[A-Za-z_][\w\-]*\((.*)\)$")
_SCOPE_PREFIX = "apex_"


def _components(op_name: str) -> list:
    """The name stack's components, split at ``/`` outside parentheses,
    each stripped of the transforms round it (recursively, so a
    ``transpose(jvp(a/b))`` gives ``a``, ``b``)."""
    out, depth, start = [], 0, 0
    for i, ch in enumerate(op_name):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == "/" and depth == 0:
            out.append(op_name[start:i])
            start = i + 1
    out.append(op_name[start:])
    flat = []
    for comp in out:
        m = _WRAPPED.match(comp)
        while m:
            comp = m.group(1)
            m = _WRAPPED.match(comp)
        flat.extend(_components(comp) if "/" in comp else [comp])
    return flat


def _chain_of(op_name: str) -> tuple:
    """``(apex_* components outer to inner, backward)``; a component
    repeated at once (a kernel called inside the scope of its own name, a
    nested jit's stack) counts once."""
    chain = []
    for c in _components(op_name):
        if c.startswith(_SCOPE_PREFIX) and (not chain or chain[-1] != c):
            chain.append(c)
    return tuple(chain), "transpose(" in op_name


def _primitive_chains(closed_jaxpr) -> dict:
    """``{first word of a primitive's name: {(chain, backward)}}`` over
    every equation of a traced program, sub-programs included (their name
    stacks are relative to the equation that holds them)."""
    out = defaultdict(set)

    def walk(jaxpr, outer):
        for eqn in jaxpr.eqns:
            stack = str(eqn.source_info.name_stack)
            chain, back = _chain_of(stack)
            chain, back = outer[0] + chain, outer[1] or back
            out[eqn.primitive.name.split("_")[0]].add((chain, back))
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub, (chain, back))

    walk(closed_jaxpr.jaxpr, ((), False))
    return out


def _parse(text: str, closed_jaxpr=None):
    """One pass over an executable's HLO text.  Returns ``(module,
    chains, types, inferred)`` over the instructions that can be device
    events: those of fused and reducer computations are left out (a
    fusion is one event and carries its root's metadata).
    ``closed_jaxpr``, the traced program, names what a compiler pass
    rewrote (:func:`_infer`)."""
    module = ""
    comp = None
    rows = {}                    # name -> (op_name | None, operands, comp)
    renamed = {}                 # name -> the op_name a compiler pass gave
    types = {}
    inner, callers = set(), {}
    for line in text.splitlines():
        if not line.startswith(" "):
            if line.startswith("HloModule "):
                module = line[10:].split(",", 1)[0].strip()
            else:
                m = _COMPUTATION.match(line)
                if m:
                    comp = m.group(1)
            continue
        m = _INSTR.match(line)
        if m is None:
            continue
        name, rest = m.groups()
        cut = rest.find(", backend_config=")
        if cut >= 0:
            rest = rest[:cut]
        op = _OPCODE.search(rest)
        if op is None:
            continue
        close = rest.find(")", op.end())
        operands = _OPERAND.findall(rest[op.end():close if close >= 0
                                         else len(rest)])
        if "=%" in rest:
            for attr, called in _CALLED.findall(rest):
                if attr == "to_apply" or op.group(1) == "fusion":
                    inner.add(called)
                else:
                    callers.setdefault(called, name)
        if "branch_computations=" in rest:
            for group in _BRANCHES.findall(rest):
                for called in _OPERAND.findall(group):
                    callers.setdefault(called, name)
        at = rest.find('op_name="')
        on = rest[at + 9:rest.find('"', at + 9)] if at >= 0 else None
        if on is not None and "/" not in on and op.group(1) != "parameter":
            # not a name stack: what a compiler pass called the
            # instruction it made (``ragged-dot-none``)
            renamed[name] = on
            on = None
        rows[name] = (on, operands, comp)
        types[name] = rest[:op.start()]
    memo: dict = {}
    chains = {}
    for name, (on, _, _) in rows.items():
        if on is not None:
            if on not in memo:
                memo[on] = _chain_of(on)
            chains[name] = memo[on]
    keep = [n for n, row in rows.items() if row[2] not in inner]
    prims = (_primitive_chains(closed_jaxpr)
             if closed_jaxpr is not None and any(
                 n in renamed for n in keep) else {})
    inferred = _infer(rows, chains, callers, renamed, prims)
    return (module, {n: chains.get(n, ((), False)) for n in keep},
            {n: types[n] for n in keep}, frozenset(inferred) & set(keep))


def _infer(rows: dict, scopes: dict, callers: dict, renamed: dict,
           prims: dict) -> set:
    """Instructions the compiler made carry no name stack: async copies
    and slices and buffer allocations have no ``op_name`` at all, and a
    pass that rewrites an operation names what it makes after itself
    (``jax.lax.ragged_dot`` becomes ``ragged-dot-metadata`` and
    ``ragged-dot-none`` custom calls, ``op_name`` their own names).  A
    rewritten instruction takes the chain of the traced program's
    equations of the primitive it came from (``ragged_dot_general``:
    matched on the first word), where those all share one; otherwise,
    and for the rest, the chain of the nearest instruction that has one:
    first among their users, then among their operands, in the same
    computation, through other such instructions only; failing both,
    that of the instruction that calls their computation (a ``while``
    body's).  Fills ``scopes`` in place; returns the names it filled."""
    users = defaultdict(list)
    for name, (_, operands, _) in rows.items():
        for o in operands:
            users[o].append(name)

    def search(start, step):
        seen, frontier = {start}, [start]
        while frontier:
            nxt = []
            for n in frontier:
                for m in step(n):
                    if m in seen or m not in rows:
                        continue
                    seen.add(m)
                    if rows[m][0] is not None:
                        return scopes[m]
                    nxt.append(m)
            frontier = nxt
        return None

    def caller_scope(name, depth=0):
        call = callers.get(rows[name][2])
        if call is None or depth > 16:
            return None
        if call in scopes:
            return scopes[call]
        return caller_scope(call, depth + 1)

    filled = set()
    for name, (on, _, _) in rows.items():
        if on is not None:
            continue
        got = None
        if name in renamed:
            same = prims.get(renamed[name].split("-")[0], ())
            got = next(iter(same)) if len(same) == 1 else None
        got = (got or search(name, lambda n: users.get(n, ()))
               or search(name, lambda n: rows[n][1])
               or caller_scope(name))
        if got is not None:
            scopes[name] = got
            filled.add(name)
    return filled


def op_scopes(text: str) -> Dict[str, Tuple[Optional[str], bool]]:
    """``{instruction: (scope, backward)}`` from an executable's optimized
    HLO text (``jit(f).lower(...).compile().as_text()``).

    ``scope`` is the innermost ``apex_*`` component of the instruction's
    ``op_name`` with the transforms round each component stripped
    (``transpose(jvp(apex_train_forward))`` is ``apex_train_forward``);
    ``None`` where the name stack holds no ``apex_*`` component.
    ``backward`` is true when the ``op_name`` holds ``transpose(``.  A
    fusion takes its own metadata, which is its root's.  An instruction
    the compiler added with no ``op_name`` at all takes its neighbours'
    (:func:`_infer`)."""
    return {name: (chain[-1] if chain else None, backward)
            for name, (chain, backward) in _parse(text)[1].items()}


@dataclass(frozen=True)
class ScopeTable:
    """What the registry keeps of one executable: plain strings only (no
    executable, no array).  ``scopes`` maps an instruction to ``(chain,
    backward)``, ``chain`` its ``apex_*`` components outer to inner (a
    kernel's name sits inside the stage that calls it:
    ``("apex_train_optimizer", "apex_lamb_stage1")``; ``chain[-1]`` is
    :func:`op_scopes`'s scope, ``()`` none).  ``types`` (instruction ->
    result type, as a profile event's name gives it after ``%name = ``)
    tells two executables of one jit name apart — prefill buckets share
    their instruction names, not their shapes; ``inferred`` names the
    instructions whose scope came from a neighbour; ``seconds`` is what
    reading the text and parsing it cost."""
    module: str
    fingerprint: str
    scopes: Mapping[str, Tuple[Tuple[str, ...], bool]]
    types: Mapping[str, str]
    inferred: frozenset
    seconds: float = 0.0


_TABLES: Dict[Tuple[str, str], ScopeTable] = {}
_PENDING: list = []              # [(key, fn, specs, donate_argnums)]
_LOCK = threading.Lock()
_RESOLVING = threading.local()   # set while pending functions re-trace


def _register(text: str, fingerprint, t0: float,
              closed_jaxpr=None) -> ScopeTable:
    module, scopes, types, inferred = _parse(text, closed_jaxpr)
    fp = (fingerprint.hex() if isinstance(fingerprint, bytes)
          else str(fingerprint) if fingerprint is not None
          else hashlib.sha1(text.encode()).hexdigest())
    table = ScopeTable(module, fp, scopes, types, inferred,
                       time.perf_counter() - t0)
    with _LOCK:
        _TABLES[(module, fp)] = table
    return table


def _register_compiled(traced, compiled) -> ScopeTable:
    t0 = time.perf_counter()
    fingerprint = None
    try:
        fingerprint = compiled.runtime_executable().fingerprint
    except Exception:  # noqa: BLE001 — the text's hash stands in
        pass
    return _register(compiled.as_text(), fingerprint, t0, traced.jaxpr)


def capture(jitted, *args) -> Optional[ScopeTable]:
    """Lower and compile ``jitted`` at ``args`` and keep its op -> scope
    table.  The executable is the one the jitted call runs: lowering and
    compiling ahead of the call, or after it, share ONE backend compile
    (jax caches both steps on the traced function), so the capture adds
    none — only the text and its parse.  Call it once per compiled
    shape, before the call that donates ``args``.  Never raises: a
    failure leaves no table and returns ``None``."""
    try:
        traced = jitted.trace(*args)
        return _register_compiled(traced, traced.lower().compile())
    except Exception:  # noqa: BLE001 — a table is never worth a failure
        return None


def capture_when_read(fn, *args, donate_argnums: tuple = ()) -> None:
    """For a function that its CALLER jits (``make_train_step``'s step,
    jitted by a training loop the program does not own): called while
    ``fn`` is traced, it keeps ``fn`` and the arguments' shapes — no
    array — and :func:`scope_tables` captures ``jax.jit(fn,
    donate_argnums)`` at them when the tables are first read.  The same
    function, shapes and donation lower to the same program, and XLA
    compiles a program to the same instructions every time, so the table
    names what ran.  That compile keys the persistent cache with the
    program's metadata (``jax_compilation_cache_include_metadata_in_key``
    for its duration): a cache entry compiled from the same program
    under OTHER names — the same step before it had its scopes — is not
    taken for it."""
    import jax

    if getattr(_RESOLVING, "on", False):
        return
    specs = jax.tree.map(
        lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype), args)
    key = (fn, jax.tree.structure(specs),
           tuple((s.shape, str(s.dtype)) for s in jax.tree.leaves(specs)),
           tuple(donate_argnums))
    with _LOCK:
        if any(p[0] == key for p in _PENDING):
            return
        _PENDING.append((key, fn, specs, tuple(donate_argnums)))


def _resolve_pending() -> None:
    import jax

    with _LOCK:
        pending = list(_PENDING)
        _PENDING.clear()
    if not pending:
        return
    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    jax.config.update(flag, True)
    _RESOLVING.on = True
    try:
        for _, fn, specs, donate in pending:
            capture(jax.jit(fn, donate_argnums=donate), *specs)
    finally:
        _RESOLVING.on = False
        jax.config.update(flag, before)


def scope_tables() -> Tuple[ScopeTable, ...]:
    """Every table captured in this process (a function left for
    :func:`capture_when_read` is captured now)."""
    _resolve_pending()
    with _LOCK:
        return tuple(_TABLES.values())


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
