"""Pallas kernel VMEM auditor: the fourth analysis engine.

The other three engines stop at the ``pallas_call`` boundary — the AST
lint sees the call site, the jaxpr audit walks the kernel body's
arithmetic, the SPMD audit prices the collectives around it — but none
of them can answer the question the ROADMAP's hottest open items turn
on: *does this kernel's working set fit in VMEM?*  The fused block
decode is capped at hidden ≲ 2048 by a VMEM envelope that existed only
as a PERF.md comment; weight-TILE streaming (item 6) and TP-sharded
fused decode (item 1) are both justified by shrinking that envelope.
This engine makes the constraint machine-checked instead of folklore.

Every registered Pallas kernel is traced abstractly (``jax.make_jaxpr``
— zero FLOPs, CPU milliseconds) and each ``pallas_call`` equation is
decomposed into its grid, BlockSpec block shapes + index maps, VMEM
scratch shapes and scalar-prefetch operands.  From those pieces a
static per-grid-step VMEM footprint is modeled:

* **prefetch operands** — SMEM-resident whole arrays, counted once;
* **operand/output blocks** — ``prod(block_shape) · itemsize`` per
  buffer; a block whose index map *varies* with the grid is DMA'd per
  step and double-buffered (×2 — compute on buffer A while step i+1
  lands in buffer B), a block with a *constant* index map is fetched
  once and stays resident (×1 — the fused decode's weight blocks);
* **scratch** — full shapes, resident for the kernel's lifetime (the
  fp32 online-softmax accumulators).

The footprint is priced against per-core VMEM capacity from
:mod:`apex_tpu.chip_specs` and committed to the
``.analysis_kernel_budget.json`` ledger with the same ratchet /
no-suppression / conscious-re-pin discipline as the SPMD comm budget.

Checks:

* **APX300** — kernel trace failure (a refactor that breaks an op's
  signature cannot silently drop it from the audit; mirrors APX200/210).
* **APX301** — VMEM envelope: a kernel's modeled footprint exceeds the
  chip's VMEM capacity, or GREW past its committed ledger entry.
* **APX302** — reduction-kernel accumulator discipline: a kernel
  declared ``reduction`` in its module's ``PALLAS_AUDIT`` hook whose
  VMEM scratch (or revisited constant-index-map output block) is not
  fp32 — the online-softmax/wgrad rule, previously enforced only by
  convention.
* **APX303** — grid/BlockSpec divisibility: a block dim that doesn't
  divide its operand dim silently masks (or zero-pads) a remainder;
  flagged unless the kernel declares ``masked_tail`` in its module's
  ``PALLAS_AUDIT`` hook (the paged kernels' beyond-length page masking,
  the fused-update kernels' lane-padded single block).
* **APX304** — traced-value use in a BlockSpec index map: index maps
  must resolve from grid indices + scalar-prefetch operands only.  jax
  rejects a captured tracer at trace time, so in the wild this
  surfaces as a classified trace failure; the record-level check also
  covers captured non-grid constants.
* **APX305** — ledger completeness: a Pallas kernel reachable from a
  registered op with no kernel-budget entry (mirrors APX215's
  unbudgeted-executable check; the tier-1 exact-set guard catches the
  stale direction).

Ops modules declare the properties the trace can't reveal in a
module-level ``PALLAS_AUDIT`` dict (kernel name → ``{"reduction":
bool, "masked_tail": bool}``) — a registration hook only, no behavior
change.

``fused_block_envelope`` / ``predict_fusion_max_hidden`` expose the
model for the fused decode block directly: the hidden-size sweep that
must bracket the observed ~2048 fusion cap (tier-1 test; tolerance
documented in PERF.md round-16), and the ``--mesh tp=N`` mode pricing
the 1/tp-sharded weight-block envelope for ROADMAP item 1.
"""
from __future__ import annotations

import importlib
import linecache
import re
from dataclasses import dataclass
from typing import Callable, Optional, Sequence

from apex_tpu.analysis.finding import Finding
from apex_tpu.chip_specs import CHIP_SPECS, DEFAULT_CHIP, ChipSpec

__all__ = [
    "BUDGET_NAME", "DOUBLE_BUFFER", "KernelOpSpec", "BlockRecord",
    "KernelRecord", "kernel_specs", "kernel_function_name",
    "extract_kernels",
    "check_kernel_record", "audit_kernel_op", "run_kernel_audit",
    "compare_kernel_budget", "fused_block_envelope",
    "predict_fusion_max_hidden", "FUSION_SWEEP",
]

BUDGET_NAME = ".analysis_kernel_budget.json"

#: buffer factor for grid-varying (DMA'd) blocks: the Pallas pipeline
#: overlaps step i's compute with step i+1's DMA, so two copies of the
#: block are live; constant-index-map blocks are fetched once (×1).
DOUBLE_BUFFER = 2

#: the default hidden-size sweep for the fused-decode crossover model
#: (all multiples of the flagship head_dim 64, heads even so tp=2
#: shards cleanly).
FUSION_SWEEP = (512, 768, 1024, 1536, 2048, 3072, 4096, 6144, 8192)


# ---------------------------------------------------------------------------
# records
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class BlockRecord:
    """One BlockSpec'd operand/output of a ``pallas_call``."""
    role: str               # "in" | "out"
    block_shape: tuple
    full_shape: tuple
    dtype: str
    block_bytes: int        # one buffer: prod(block_shape) * itemsize
    constant: bool          # constant index map -> resident, single copy
    traced_consts: int      # values the index map captured by closure
    nondividing: tuple      # dims where block_shape doesn't divide full

    @property
    def bytes_per_step(self) -> int:
        return self.block_bytes * (1 if self.constant else DOUBLE_BUFFER)


@dataclass(frozen=True)
class KernelRecord:
    """One ``pallas_call`` equation, decomposed for the VMEM model."""
    kernel: str             # kernel function name
    grid: tuple
    prefetch_bytes: int     # scalar-prefetch operands (SMEM), whole
    blocks: tuple           # BlockRecords, inputs then outputs
    scratch: tuple          # ((shape, dtype, bytes), ...)

    @property
    def block_bytes(self) -> int:
        return sum(b.bytes_per_step for b in self.blocks)

    @property
    def resident_bytes(self) -> int:
        return sum(b.block_bytes for b in self.blocks if b.constant)

    @property
    def scratch_bytes(self) -> int:
        return sum(s[2] for s in self.scratch)

    @property
    def vmem_bytes(self) -> int:
        """The modeled per-grid-step VMEM footprint."""
        return self.prefetch_bytes + self.block_bytes + self.scratch_bytes

    def entry(self) -> dict:
        """The ledger shape committed per kernel."""
        return {
            "grid": list(self.grid),
            "vmem_bytes": self.vmem_bytes,
            "resident_bytes": self.resident_bytes,
            "scratch_bytes": self.scratch_bytes,
            "prefetch_bytes": self.prefetch_bytes,
            "blocks": len(self.blocks),
        }


@dataclass(frozen=True)
class KernelOpSpec:
    """One registered kernel-bearing op: how to trace it + where its
    module's ``PALLAS_AUDIT`` declarations live."""
    name: str
    path: str                     # module path findings anchor to
    module: str                   # dotted module carrying PALLAS_AUDIT
    build: Callable[[], tuple]    # () -> (fn, args tuple)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def _builders():
    """Lazy fixtures (importing this module stays jax-free).  Every
    fixture with an XLA twin pins the PALLAS path explicitly
    (``xla_max_seq=0``) — the auditor prices kernels, not the XLA
    twins the crossover knobs would otherwise dispatch these tiny
    shapes to.  Norm/attention ops trace fwd+bwd via ``jax.vjp`` so
    the backward kernels (the wgrad accumulators) are covered."""
    import jax
    import jax.numpy as jnp

    bf16 = jnp.bfloat16
    f32 = jnp.float32

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    def layer_norm():
        from apex_tpu.ops import layer_norm as op

        def fn(x, w, b):
            y, vjp = jax.vjp(op, x, w, b)
            return vjp(y)
        return fn, (s((128, 256), bf16), s((256,), bf16), s((256,), bf16))

    def rms_norm():
        from apex_tpu.ops import rms_norm as op

        def fn(x, w):
            y, vjp = jax.vjp(op, x, w)
            return vjp(y)
        return fn, (s((128, 256), bf16), s((256,), bf16))

    def flash_attention():
        from apex_tpu.ops import flash_attention as op

        def fn(q, k, v):
            y, vjp = jax.vjp(
                lambda *a: op(*a, causal=True, xla_max_seq=0), q, k, v)
            return vjp(y)
        qkv = s((1, 2, 256, 64), bf16)
        return fn, (qkv, qkv, qkv)

    def decode_attention():
        from apex_tpu.ops import decode_attention as op
        return (lambda q, k, v, n: op(q, k, v, n, xla_max_seq=0),
                (s((2, 4, 1, 64), bf16), s((2, 2, 128, 64), bf16),
                 s((2, 2, 128, 64), bf16), s((2,), jnp.int32)))

    def paged_decode_attention():
        from apex_tpu.ops import paged_decode_attention as op
        pool = s((9, 2, 4, 16, 64), bf16)
        return (lambda q, kp, vp, pt, n: op(q, kp, vp, pt, n, layer=1),
                (s((2, 4, 64), bf16), pool, pool,
                 s((2, 4), jnp.int32), s((2,), jnp.int32)))

    def paged_decode_latent():
        # ISSUE 34: the latent form — one pool with no KV-head axis, the
        # values a row's leading columns
        from apex_tpu.ops import paged_decode_attention as op
        return (lambda q, pool, pt, n: op(q, pool, None, pt, n, layer=1,
                                          sm_scale=0.1, values=128),
                (s((2, 4, 192), bf16), s((9, 2, 192, 16), bf16),
                 s((2, 4), jnp.int32), s((2,), jnp.int32)))

    def dsa_index_scores():
        # ISSUE 36: a block of query rows' index scores against the keys
        from apex_tpu.ops.attention import index_scores as op
        return (op, (s((128, 4, 64), bf16), s((128, 4), jnp.float32),
                     s((256, 64), bf16)))

    def paged_index_scores():
        # ISSUE 36: index scores of the live positions along the work
        # list, over the index-key pool (a page's positions minor)
        from apex_tpu.ops.paged_attention import (
            paged_index_scores as op, paged_work_list)
        return (lambda qi, wi, ik, pt, n: op(
            qi, wi, ik, paged_work_list(pt, n, page_size=16), layer=1),
                (s((2, 4, 64), bf16), s((2, 4), jnp.float32),
                 s((9, 2, 64, 16), bf16), s((2, 4), jnp.int32),
                 s((2,), jnp.int32)))

    def paged_select_attention():
        # ISSUE 36: attention over the picked positions only
        from apex_tpu.ops.paged_attention import (
            paged_select_attention as op, paged_work_list)
        pool = s((9, 2, 4, 16, 64), bf16)
        return (lambda q, kp, vp, picked, pt, n: op(
            q, kp, vp, picked, paged_work_list(pt, n, page_size=16),
            layer=1),
                (s((2, 4, 64), bf16), pool, pool, s((2, 64), bool),
                 s((2, 4), jnp.int32), s((2,), jnp.int32)))

    def paged_select_attention_latent():
        # latent attention over the picked positions only, with
        # a sink a head
        from apex_tpu.ops.paged_attention import (
            paged_select_attention_latent as op, paged_work_list)
        return (lambda q, pool, picked, sink, pt, n: op(
            q, pool, picked, paged_work_list(pt, n, page_size=16), layer=1,
            sm_scale=0.1, values=128, sink=sink),
                (s((2, 4, 192), bf16), s((9, 2, 192, 16), bf16),
                 s((2, 64), bool), s((4,), jnp.float32),
                 s((2, 4), jnp.int32), s((2,), jnp.int32)))

    def fused_block_decode():
        # the jaxpr-audit fixture geometry (hidden 64, GPT kind); the
        # flagship-shape envelope rides fused_block_envelope, not the
        # ledger entry
        return _fused_block_fixture(hidden=64, head_dim=16,
                                    page_size=16, max_pages=4, slots=2,
                                    pages=9)

    def fused_block_decode_tp2():
        # ISSUE 17: the tp=2 SERVING shard of the same fixture — the
        # --mesh pricing as a committed ledger row.  fuse_mlp off and
        # partial_out on, exactly the variant the sharded decode
        # dispatches (the out-proj psum + MLP tail run outside)
        return _fused_block_fixture(hidden=64, head_dim=16,
                                    page_size=16, max_pages=4, slots=2,
                                    pages=9, tp=2, partial_out=True)

    def fused_update():
        from apex_tpu.ops.fused_update import (
            fused_adagrad_flat, fused_adam_flat, fused_axpby,
            fused_l2norm, fused_l2norm_scale, fused_lamb_phase1_flat,
            fused_scale, fused_sgd_flat)

        def fn(p, g, m, v):
            out = [fused_scale(p, 0.5),
                   fused_axpby(1.0, p, 2.0, g),
                   fused_l2norm(p),
                   fused_l2norm_scale(p, 0.5)]
            out.extend(fused_adam_flat(
                p, g, m, v, lr=1e-3, beta1=0.9, beta2=0.999, eps=1e-8,
                weight_decay=0.0, step=1))
            out.extend(fused_adagrad_flat(
                p, g, m, lr=1e-2, eps=1e-10, weight_decay=0.0))
            out.extend(fused_sgd_flat(
                p, g, m, lr=1e-2, momentum=0.9, dampening=0.0,
                weight_decay=0.0, nesterov=False))
            out.extend(fused_lamb_phase1_flat(
                p, g, m, v, beta1=0.9, beta2=0.999, eps=1e-8,
                weight_decay=0.01, step=1))
            return out
        p = s((2048,), f32)
        return fn, (p, p, p, p)

    def xentropy():
        # XLA-lowered (no pallas_call) — the zero-kernel entry
        # documents that; a Pallas rewrite lands in the ledger here
        from apex_tpu.ops import softmax_cross_entropy_loss as op
        return (lambda l, y: op(l, y),
                (s((8, 128), bf16), s((8,), jnp.int32)))

    def fused_lm_xent():
        from apex_tpu.ops import fused_lm_head_cross_entropy as op
        return (lambda h, w, y: op(h, w, y, token_chunk=32,
                                   vocab_chunk=0),
                (s((96, 64), bf16), s((512, 64), bf16),
                 s((96,), jnp.int32)))

    ops = "apex_tpu.ops."
    return {
        "layer_norm": (layer_norm, "apex_tpu/ops/layer_norm.py",
                       ops + "layer_norm"),
        "rms_norm": (rms_norm, "apex_tpu/ops/layer_norm.py",
                     ops + "layer_norm"),
        "flash_attention": (flash_attention, "apex_tpu/ops/attention.py",
                            ops + "attention"),
        "decode_attention": (decode_attention, "apex_tpu/ops/attention.py",
                             ops + "attention"),
        "paged_decode_attention": (paged_decode_attention,
                                   "apex_tpu/ops/paged_attention.py",
                                   ops + "paged_attention"),
        "paged_decode_latent": (paged_decode_latent,
                                "apex_tpu/ops/paged_attention.py",
                                ops + "paged_attention"),
        "dsa_index_scores": (dsa_index_scores,
                             "apex_tpu/ops/attention.py",
                             ops + "attention"),
        "paged_index_scores": (paged_index_scores,
                               "apex_tpu/ops/paged_attention.py",
                               ops + "paged_attention"),
        "paged_select_attention": (paged_select_attention,
                                   "apex_tpu/ops/paged_attention.py",
                                   ops + "paged_attention"),
        "paged_select_attention_latent": (paged_select_attention_latent,
                                          "apex_tpu/ops/paged_attention.py",
                                          ops + "paged_attention"),
        "fused_block_decode": (fused_block_decode,
                               "apex_tpu/ops/paged_attention.py",
                               ops + "paged_attention"),
        "fused_block_decode_tp2": (fused_block_decode_tp2,
                                   "apex_tpu/ops/paged_attention.py",
                                   ops + "paged_attention"),
        "fused_update": (fused_update, "apex_tpu/ops/fused_update.py",
                         ops + "fused_update"),
        "xentropy": (xentropy, "apex_tpu/ops/xentropy.py",
                     ops + "xentropy"),
        "fused_lm_xent": (fused_lm_xent, "apex_tpu/ops/fused_lm_xent.py",
                          ops + "fused_lm_xent"),
    }


def kernel_specs() -> list:
    return [KernelOpSpec(name, path, module, build)
            for name, (build, path, module) in _builders().items()]


def _op_meta(spec: KernelOpSpec) -> dict:
    """The op module's ``PALLAS_AUDIT`` declarations ({} if absent)."""
    try:
        mod = importlib.import_module(spec.module)
    except ImportError:
        return {}
    return getattr(mod, "PALLAS_AUDIT", {}) or {}


# ---------------------------------------------------------------------------
# extraction
# ---------------------------------------------------------------------------

def _itemsize(dtype) -> int:
    import numpy as np
    return int(np.dtype(dtype).itemsize)


def _prod(shape) -> int:
    out = 1
    for d in shape:
        out *= int(d)
    return out


def kernel_function_name(eqn) -> str:
    """The name of the kernel FUNCTION of a ``pallas_call`` equation —
    what ``PALLAS_AUDIT`` and the budget ledger key on.
    ``pallas_call(name=)`` (the kernel's stable name in profiler traces)
    takes the function's place in the kernel jaxpr's debug info but
    leaves the file and line of its ``def``: read the name there."""
    info = eqn.params["jaxpr"].debug_info
    if eqn.params.get("name") and info.func_filename and info.func_lineno:
        m = re.match(r"\s*def\s+(\w+)", linecache.getline(
            info.func_filename, info.func_lineno))
        if m:
            return m.group(1)
    return info.func_name


def _record_from_eqn(eqn) -> KernelRecord:
    import jax.extend.core as jex_core

    gm = eqn.params["grid_mapping"]
    kname = kernel_function_name(eqn)

    npre = gm.num_index_operands
    prefetch = sum(_prod(sh.shape) * _itemsize(sh.dtype)
                   for sh in list(gm.in_shapes)[:npre])

    blocks = []
    for i, bm in enumerate(gm.block_mappings):
        full = bm.array_aval
        # Blocked(block_size=n) dims carry their size; squeezed/None
        # dims contribute one element to the block
        bshape = tuple(int(getattr(b, "block_size", 1))
                       for b in bm.block_shape)
        imj = bm.index_map_jaxpr
        constant = (not imj.jaxpr.eqns) and all(
            isinstance(v, jex_core.Literal) for v in imj.jaxpr.outvars)
        nondiv = tuple(
            d for d, (b, n) in enumerate(zip(bshape, full.shape))
            if b > 0 and int(n) % b)
        blocks.append(BlockRecord(
            role="in" if i < gm.num_inputs else "out",
            block_shape=bshape,
            full_shape=tuple(int(n) for n in full.shape),
            dtype=str(full.dtype),
            block_bytes=_prod(bshape) * _itemsize(full.dtype),
            constant=constant,
            traced_consts=len(imj.consts),
            nondividing=nondiv))

    kj = eqn.params["jaxpr"]
    nscr = gm.num_scratch_operands
    scratch = tuple(
        (tuple(int(d) for d in v.aval.shape), str(v.aval.dtype),
         _prod(v.aval.shape) * _itemsize(v.aval.dtype))
        for v in (kj.invars[len(kj.invars) - nscr:] if nscr else []))

    grid = tuple(int(g) if isinstance(g, int) else -1 for g in gm.grid)
    return KernelRecord(kname, grid, prefetch, tuple(blocks), scratch)


def extract_kernels(closed) -> list:
    """Every ``pallas_call`` reachable from a closed jaxpr (including
    inside ``custom_vjp`` branches / nested ``pjit`` bodies), as
    :class:`KernelRecord` s in trace order."""
    from apex_tpu.analysis.jaxpr_audit import _iter_jaxprs
    records = []
    for jaxpr in _iter_jaxprs(closed.jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                records.append(_record_from_eqn(eqn))
    return records


# ---------------------------------------------------------------------------
# checks
# ---------------------------------------------------------------------------

def _chip(chip: Optional[str]) -> ChipSpec:
    key = chip or DEFAULT_CHIP
    if key not in CHIP_SPECS:
        raise ValueError(
            f"unknown chip {key!r}; known: {sorted(CHIP_SPECS)}")
    return CHIP_SPECS[key]


def check_kernel_record(rec: KernelRecord, meta: dict, chip: ChipSpec,
                        op_name: str, path: str) -> list:
    """The per-kernel check battery (APX301 capacity half, APX302,
    APX303, APX304) over one extracted record.  ``meta`` is the op
    module's ``PALLAS_AUDIT`` dict."""
    findings: list = []
    decl = meta.get(rec.kernel, {})

    def emit(rule, msg):
        findings.append(Finding(
            rule, path, 0, 0, msg,
            line_text=f"{op_name}:{rec.kernel}:{rule}"))

    if rec.vmem_bytes > chip.vmem_bytes:
        emit("APX301",
             f"{op_name}: kernel {rec.kernel} models {rec.vmem_bytes} B "
             f"of VMEM per grid step ({rec.resident_bytes} resident + "
             f"{rec.block_bytes - rec.resident_bytes} streamed + "
             f"{rec.scratch_bytes} scratch) against {chip.key}'s "
             f"{chip.vmem_bytes} B capacity — shrink the blocks or "
             f"stream the resident operands through the grid")

    if decl.get("reduction"):
        for shape, dtype, _ in rec.scratch:
            if dtype != "float32":
                emit("APX302",
                     f"{op_name}: reduction kernel {rec.kernel} "
                     f"accumulates in {dtype} scratch {shape} — online-"
                     f"softmax/wgrad accumulators must be fp32")
        for b in rec.blocks:
            if b.role == "out" and b.constant and b.dtype != "float32":
                emit("APX302",
                     f"{op_name}: reduction kernel {rec.kernel} "
                     f"revisits output block {b.block_shape} across the "
                     f"grid (constant index map) in {b.dtype} — the "
                     f"accumulated output must be fp32")

    if not decl.get("masked_tail"):
        for b in rec.blocks:
            if b.nondividing:
                emit("APX303",
                     f"{op_name}: kernel {rec.kernel} {b.role}-block "
                     f"{b.block_shape} does not divide operand "
                     f"{b.full_shape} on dim(s) {list(b.nondividing)} — "
                     f"the remainder is silently masked/zero-padded; "
                     f"handle the tail in-kernel and declare "
                     f"masked_tail in the module's PALLAS_AUDIT")

    for b in rec.blocks:
        if b.traced_consts:
            emit("APX304",
                 f"{op_name}: kernel {rec.kernel} {b.role}-block index "
                 f"map captures {b.traced_consts} closure value(s) — "
                 f"index maps must resolve from grid indices + scalar-"
                 f"prefetch operands only")
    return findings


# jax's own trace-time rejection of a tracer captured by an index map
# (the APX304 condition caught upstream) — classify it, don't bury it
# in a generic APX300.
_INDEX_MAP_CAPTURE = ("Index map function", "capture")


def audit_kernel_op(spec: KernelOpSpec, chip: Optional[str] = None):
    """Audit one registered op; -> ``(findings, ledger entry | None)``."""
    import jax

    chip_spec = _chip(chip)
    try:
        fn, args = spec.build()
    except ImportError:
        return [], None  # optional dependency absent — op not in build
    try:
        closed = jax.make_jaxpr(fn)(*args)
    except Exception as e:  # noqa: BLE001 — any trace failure is a finding
        msg = f"{type(e).__name__}: {e}"
        if all(t in str(e) for t in _INDEX_MAP_CAPTURE):
            return [Finding(
                "APX304", spec.path, 0, 0,
                f"{spec.name}: a BlockSpec index map captured a traced "
                f"value — index maps must resolve from grid indices + "
                f"scalar-prefetch operands only ({msg})",
                line_text=f"{spec.name}:APX304")], None
        return [Finding(
            "APX300", spec.path, 0, 0,
            f"{spec.name}: tracing the kernel fixture failed: {msg}",
            line_text=f"{spec.name}:APX300")], None

    findings: list = []
    meta = _op_meta(spec)
    kernels: dict = {}
    for rec in extract_kernels(closed):
        findings.extend(check_kernel_record(
            rec, meta, chip_spec, spec.name, spec.path))
        key, n = rec.kernel, 2
        while key in kernels:
            key, n = f"{rec.kernel}#{n}", n + 1
        kernels[key] = rec.entry()

    entry = {
        "kernels": kernels,
        "max_kernel_vmem_bytes": max(
            (k["vmem_bytes"] for k in kernels.values()), default=0),
    }
    return findings, entry


def run_kernel_audit(ops: Optional[Sequence[str]] = None,
                     chip: Optional[str] = None) -> tuple:
    """Audit every (or the named) registered Pallas kernel op.

    Returns ``(findings, report)`` where ``report`` is the ledger shape
    committed as ``.analysis_kernel_budget.json``: ``{"version": 1,
    "chip", "vmem_capacity_bytes", "ops": {name: {kernels: {kernel:
    {grid, vmem_bytes, resident_bytes, scratch_bytes, prefetch_bytes,
    blocks}}, max_kernel_vmem_bytes}}}``.
    """
    chip_spec = _chip(chip)
    specs = kernel_specs()
    if ops:
        wanted = set(ops)
        missing = wanted - {s.name for s in specs}
        if missing:
            raise ValueError(f"unknown kernel op(s): {sorted(missing)}")
        specs = [s for s in specs if s.name in wanted]

    findings: list = []
    entries: dict = {}
    for spec in specs:
        f, entry = audit_kernel_op(spec, chip=chip)
        findings.extend(f)
        if entry is not None:
            entries[spec.name] = entry
    report = {
        "version": 1,
        "chip": chip_spec.key,
        "vmem_capacity_bytes": chip_spec.vmem_bytes,
        "ops": entries,
    }
    return findings, report


def compare_kernel_budget(report: dict, committed: Optional[dict]) -> list:
    """Ratchet: APX301 for every kernel whose modeled VMEM footprint
    GREW vs the committed budget, APX305 for kernels/ops the budget has
    never seen.  Shrinkage is silent — re-pin with ``--kernels
    --write-budget``.  (The stale direction — a budgeted kernel that no
    longer exists — is the tier-1 exact-set guard's job, mirroring the
    SPMD ledger.)"""
    findings: list = []
    paths = {s.name: s.path for s in kernel_specs()}

    def emit(rule, op_name, key, msg):
        findings.append(Finding(
            rule, paths.get(op_name, "<pallas_audit>"), 0, 0, msg,
            line_text=f"{op_name}:{key}:{rule}"))

    base = (committed or {}).get("ops", {})
    for op_name, entry in report.get("ops", {}).items():
        pinned = base.get(op_name)
        if pinned is None:
            emit("APX305", op_name, "<op>",
                 f"{op_name}: registered Pallas op has no committed "
                 f"kernel-budget entry — run apex-tpu-analyze --kernels "
                 f"--write-budget to pin its VMEM ledger")
            continue
        pk = pinned.get("kernels", {})
        for key, k in entry.get("kernels", {}).items():
            kp = pk.get(key)
            if kp is None:
                emit("APX305", op_name, key,
                     f"{op_name}: kernel {key} is reachable from the "
                     f"registered op but has no kernel-budget entry — "
                     f"pin it with --kernels --write-budget")
                continue
            if k["vmem_bytes"] > kp.get("vmem_bytes", 0):
                emit("APX301", op_name, key,
                     f"{op_name}: kernel {key} VMEM footprint grew "
                     f"{kp.get('vmem_bytes', 0)} -> {k['vmem_bytes']} "
                     f"B/grid-step — justify and re-pin with --kernels "
                     f"--write-budget, or shrink the block/scratch "
                     f"footprint")
    return findings


# ---------------------------------------------------------------------------
# the fused-decode envelope model (--mesh tp=N / crossover prediction)
# ---------------------------------------------------------------------------

def _fused_block_fixture(hidden: int, head_dim: int = 64,
                         kv_heads: Optional[int] = None,
                         page_size: int = 64, max_pages: int = 8,
                         slots: int = 8, pages: Optional[int] = None,
                         tp: int = 1, partial_out: bool = False):
    """Abstract GPT fused-block fixture at the given geometry, with the
    head and ffn dims sharded 1/tp (the TP layout: wq/wk/wv shard
    out-features, wo in-features, wu/wd the ffn dim — each chip holds
    its heads' slice, exactly ROADMAP item 1's shard)."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.ops.paged_attention import fused_block_decode as op

    bf16 = jnp.bfloat16

    def s(shape, dtype=bf16):
        return jax.ShapeDtypeStruct(shape, dtype)

    if hidden % head_dim:
        raise ValueError(f"hidden {hidden} must be a multiple of "
                         f"head_dim {head_dim}")
    heads = hidden // head_dim
    kvh = kv_heads or heads
    ffn = 4 * hidden
    if heads % tp or kvh % tp or ffn % tp:
        raise ValueError(
            f"tp={tp} must divide heads ({heads}), kv_heads ({kvh}) "
            f"and ffn ({ffn})")
    hd = (heads // tp) * head_dim
    kvd = (kvh // tp) * head_dim
    ffn //= tp
    npages = pages if pages is not None else slots * max_pages + 1
    blk = {
        "ln1_w": s((1, hidden)), "ln1_b": s((1, hidden)),
        "wq": s((hidden, hd)), "bq": s((1, hd)),
        "wk": s((hidden, kvd)), "bk": s((1, kvd)),
        "wv": s((hidden, kvd)), "bv": s((1, kvd)),
        "wo": s((hd, hidden)), "bo": s((1, hidden)),
        "ln2_w": s((1, hidden)), "ln2_b": s((1, hidden)),
        "wu": s((hidden, ffn)), "bu": s((1, ffn)),
        "wd": s((ffn, hidden)), "bd": s((1, hidden)),
    }
    pg = s((npages, kvh // tp, page_size, head_dim))
    args = (s((slots, hidden)), blk, pg, pg,
            s((slots, max_pages), jnp.int32),
            s((slots,), jnp.int32))
    if partial_out:
        # the SERVED tp shard (ISSUE 17): MLP out of the kernel, the
        # rank-partial out-proj product emitted for the external psum
        return (lambda x, b, kp, vp, pt, ln: op(
            x, b, kp, vp, pt, ln, kind="gpt", eps=1e-5,
            fuse_mlp=False, partial_out=True), args)
    return (lambda x, b, kp, vp, pt, ln: op(x, b, kp, vp, pt, ln,
                                            kind="gpt", eps=1e-5),
            args)


def fused_block_envelope(hidden: int, *, tp: int = 1,
                         chip: Optional[str] = None,
                         head_dim: int = 64,
                         kv_heads: Optional[int] = None,
                         page_size: int = 64, max_pages: int = 8,
                         slots: int = 8,
                         pages: Optional[int] = None) -> dict:
    """Price the fused decode block's VMEM envelope at a geometry.

    Traces the real ``fused_block_decode`` abstractly with the weight
    dims sharded 1/tp and runs the extractor over the resulting
    ``pallas_call`` — the model and the kernel cannot drift apart.
    Returns the envelope dict (``vmem_bytes``, ``resident_bytes``,
    ``scratch_bytes``, ``capacity_bytes``, ``fits``)."""
    import jax

    chip_spec = _chip(chip)
    fn, args = _fused_block_fixture(
        hidden, head_dim=head_dim, kv_heads=kv_heads,
        page_size=page_size, max_pages=max_pages, slots=slots,
        pages=pages, tp=tp)
    records = extract_kernels(jax.make_jaxpr(fn)(*args))
    if len(records) != 1:
        raise RuntimeError(
            f"expected exactly one pallas_call in fused_block_decode, "
            f"found {len(records)}")
    rec = records[0]
    return {
        "hidden": hidden,
        "tp": tp,
        "chip": chip_spec.key,
        "vmem_bytes": rec.vmem_bytes,
        "resident_bytes": rec.resident_bytes,
        "scratch_bytes": rec.scratch_bytes,
        "capacity_bytes": chip_spec.vmem_bytes,
        "fits": rec.vmem_bytes <= chip_spec.vmem_bytes,
    }


def predict_fusion_max_hidden(*, tp: int = 1, chip: Optional[str] = None,
                              sweep: Optional[Sequence[int]] = None) -> dict:
    """Sweep hidden sizes through the envelope model: the largest
    hidden whose fused block fits the chip's VMEM, and the first that
    doesn't (the crossover the tier-1 test asserts brackets the
    observed ~2048 cap; see PERF.md round-16 for the tolerance)."""
    sizes = tuple(sweep or FUSION_SWEEP)
    priced: dict = {}
    max_hidden = None
    crossover = None
    for hidden in sizes:
        env = fused_block_envelope(hidden, tp=tp, chip=chip)
        priced[hidden] = env["vmem_bytes"]
        if env["fits"]:
            if max_hidden is None or hidden > max_hidden:
                max_hidden = hidden
        elif crossover is None or hidden < crossover:
            crossover = hidden
    return {
        "tp": tp,
        "chip": _chip(chip).key,
        "sweep": priced,
        "max_hidden": max_hidden,
        "crossover_hidden": crossover,
    }
