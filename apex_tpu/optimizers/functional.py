"""Flat-native functional optimizer core.

Pure ``init(params) -> FlatState`` / ``update(state, flat_grads, ...) ->
FlatState`` pairs for the five fused rules (Adam, LAMB, SGD, NovoGrad,
Adagrad), each backed by the same Pallas kernels in
:mod:`apex_tpu.ops.fused_update` that the class API drives.

Why this exists (PERF.md r5): the class API's ``step(grads)`` takes a
grad *pytree*, re-ravels it (a 297-leaf ``concatenate`` on BERT-large)
and returns unraveled params every step — ~40 ms of the 112.7 ms BERT
step was this repacking plus the host-driven dispatch of unscale /
update as separate executables.  The functional core removes the
structural overhead instead of the kernel cost (which is already
HBM-bound): state is ONE flat fp32 master plus flat slot buffers,
``update`` is a pure function over them, and a whole train step —
forward, backward, scaler, fused update — composes into a single
donated XLA program (see :mod:`apex_tpu.train_step`).  Keep the flat
master as the *differentiation variable* (``jax.value_and_grad(lambda
flat: loss(state.unravel(flat)))``) and autodiff produces flat grads
directly: no re-ravel concatenate exists in the program at all, and the
per-leaf unravel slices fuse into the forward.

Contracts:

* **Scan-carryable.** ``update`` returns ``state.replace(...)`` — the
  treedef (including the static layout fields) is preserved, so a
  ``FlatState`` is a valid ``lax.scan`` carry.
* **Donation-safe.** All mutable state is arrays (master + slots);
  static fields are hashable aux data.  ``jax.jit(update,
  donate_argnums=(0,))`` donates every buffer the kernels alias.
* **Class-interchangeable.** Slot names match the class API's
  ``state_dict()["groups"][i]["state"]`` keys exactly, and
  ``FusedOptimizerBase`` subclasses are thin stateful wrappers over
  these transforms — N steps through either path are bitwise identical
  (tests/L0/run_optimizers/test_functional_core.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import flax.struct
import jax
import jax.numpy as jnp

from apex_tpu.ops.fused_update import (
    fused_adagrad_flat,
    fused_adam_flat,
    fused_lamb_phase1_flat,
    fused_sgd_flat,
)
from apex_tpu.utils import cdiv, tree_ravel

__all__ = [
    "FlatState",
    "fused_adam",
    "fused_lamb",
    "fused_sgd",
    "fused_novograd",
    "fused_adagrad",
    "shard_flat_grads",
    "export_params",
    "prefetch_span_layout",
]


def prefetch_span_layout(sizes, k: int) -> tuple:
    """Group ``len(sizes)`` leaves into at most ``k`` gather spans of
    roughly equal element counts, aligned to leaf boundaries (the
    layered-prefetch split of the flat master along ``leaf_offsets``).

    Returns a tuple of per-span LEAF COUNTS (``sum == len(sizes)``) —
    the static ``FlatState.spans`` layout.  Greedy: close a span once it
    reaches ``total/k`` elements, so homogeneous stacks of layers land
    one layer per span."""
    sizes = [int(s) for s in sizes]
    k = max(1, min(int(k), len(sizes)))
    target = sum(sizes) / k
    counts, run, acc = [], 0, 0
    for i, s in enumerate(sizes):
        run += 1
        acc += s
        remaining_leaves = len(sizes) - i - 1
        if (acc >= target and len(counts) < k - 1) \
                or remaining_leaves < (k - 1 - len(counts)):
            counts.append(run)
            run, acc = 0, 0
    if run:
        counts.append(run)
    return tuple(counts)


def _normalize_prefetch(prefetch, sizes) -> tuple:
    """Resolve a ``prefetch=`` argument to the static ``FlatState.spans``
    tuple: a tuple of per-span leaf counts passes through, an int > 1 is
    grouped along leaf boundaries by :func:`prefetch_span_layout`, and
    ``None``/0/1 mean the contiguous block layout (``()``).  The single
    place this rule lives — ``_init_state`` and
    ``train_step.init_zero_train_state`` both go through it."""
    if prefetch is None:
        return ()
    if isinstance(prefetch, tuple):
        spans = tuple(int(c) for c in prefetch)
        if spans and (min(spans) <= 0 or sum(spans) != len(sizes)):
            raise ValueError(
                f"prefetch span layout {spans} must be positive leaf "
                f"counts summing to the number of leaves "
                f"({len(sizes)}); got sum {sum(spans)}")
        return spans
    return (prefetch_span_layout(sizes, int(prefetch))
            if int(prefetch) > 1 else ())


def _layout_master(master, *, sizes, spans, dp: int):
    """Pad a GLOBAL unpadded flat buffer to its dp-shardable layout:
    zero-pad to the dp multiple (block layout), or per-span pad and
    rank-major permute (:func:`_enspan`, prefetch layout)."""
    if spans:
        span_sizes, leaf = [], 0
        for count in spans:
            span_sizes.append(sum(sizes[leaf:leaf + count]))
            leaf += count
        span_padded = tuple(cdiv(s, dp) * dp for s in span_sizes)
        return _enspan(master, tuple(span_sizes), span_padded, dp)
    n = int(master.shape[0])
    padded = cdiv(n, dp) * dp
    if padded != n:
        return jnp.concatenate(
            [master, jnp.zeros((padded - n,), master.dtype)])
    return master


def _f32(x):
    return jnp.asarray(x, jnp.float32)


@flax.struct.dataclass
class FlatState:
    """Flat optimizer state: fp32 master + per-rule slot buffers.

    ``sizes``/``flat_dtype``/``unravel`` are static aux data (treedef,
    not leaves): per-leaf layout for rules that need tensor boundaries
    (LAMB trust ratios, NovoGrad per-tensor moments) and the pytree
    round-trip for checkpoint/eval boundaries.  ``update`` never touches
    them, so carrying a FlatState through ``lax.scan`` keeps the treedef
    stable.

    ``shard`` is the ZeRO-1/2 mode: ``()`` (dense, the default) or
    ``(axis_name, dp)`` — the flat master was padded to a ``dp``
    multiple and THIS state holds one ``1/dp`` shard of master and
    slots, owned by one rank of the named mesh axis.  Element-wise
    rules update the shard unchanged; per-leaf rules (LAMB trust
    ratios, NovoGrad per-tensor moments) compute shard-local partial
    norms over the static leaf-span layout and ``psum`` them global
    (see :mod:`apex_tpu.optimizers.base`).  Because the flat master is
    ONE contiguous buffer, sharding it is a static slice — not a
    297-leaf bucketing problem.

    ``spans`` is the layered-prefetch layout (ISSUE 7 comm/compute
    overlap): ``()`` (the contiguous-block shard above, default) or a
    tuple of per-span LEAF COUNTS.  Each span — a group of consecutive
    leaves, padded to a ``dp`` multiple INDIVIDUALLY — is sharded
    ``1/dp``, and the rank's shard is the concatenation of its slice of
    every span.  The param gather then decomposes into one independent
    ``all_gather`` per span, so XLA's scheduler can prefetch span k+1
    while span k's layers compute; autodiff's transpose produces the
    matching per-span ``psum_scatter``, the grads arrive flat in the
    same shard layout, and the fused update kernels are untouched.
    """
    master: jax.Array               # fp32 flat master buffer (or shard)
    count: jax.Array                # f32 scalar: completed update count
    slots: dict                     # rule buffers, keyed like state_dict
    sizes: tuple = flax.struct.field(pytree_node=False, default=())
    flat_dtype: str = flax.struct.field(pytree_node=False,
                                        default="float32")
    unravel: Optional[Callable] = flax.struct.field(pytree_node=False,
                                                    default=None)
    shard: tuple = flax.struct.field(pytree_node=False, default=())
    spans: tuple = flax.struct.field(pytree_node=False, default=())

    @property
    def offsets(self) -> tuple:
        out, off = [], 0
        for s in self.sizes:
            out.append(off)
            off += s
        return tuple(out)

    # -- ZeRO shard layout (all static Python ints) --------------------------
    @property
    def shard_axis(self) -> Optional[str]:
        return self.shard[0] if self.shard else None

    @property
    def shard_dp(self) -> int:
        return int(self.shard[1]) if self.shard else 1

    @property
    def global_numel(self) -> int:
        """Unpadded element count of the GLOBAL flat master."""
        return sum(self.sizes)

    @property
    def span_sizes(self) -> tuple:
        """Unpadded element count of each prefetch span (``()`` for the
        block layout)."""
        out, leaf = [], 0
        for count in self.spans:
            out.append(sum(self.sizes[leaf:leaf + count]))
            leaf += count
        return tuple(out)

    @property
    def span_padded(self) -> tuple:
        """Per-span dp-padded element counts."""
        dp = self.shard_dp
        return tuple(cdiv(s, dp) * dp for s in self.span_sizes)

    @property
    def padded_numel(self) -> int:
        if self.spans:
            return sum(self.span_padded)
        return cdiv(self.global_numel, self.shard_dp) * self.shard_dp

    @property
    def shard_len(self) -> int:
        """Per-rank shard length: ``ceil(P_padded / dp)`` elements."""
        return self.padded_numel // self.shard_dp

    def _despan(self, flat):
        """Reassemble the GLOBAL unpadded flat master from a rank-major
        span-layout padded buffer (static slices + one concat)."""
        dp, lt = self.shard_dp, self.shard_len
        parts, off = [], 0
        for size_k, padded_k in zip(self.span_sizes, self.span_padded):
            lk = padded_k // dp
            span = jnp.concatenate(
                [jax.lax.slice_in_dim(flat, r * lt + off, r * lt + off + lk)
                 for r in range(dp)]) if dp > 1 else \
                jax.lax.slice_in_dim(flat, off, off + lk)
            parts.append(span[:size_k] if padded_k != size_k else span)
            off += lk
        return jnp.concatenate(parts) if len(parts) > 1 else parts[0]

    def _full_master(self, dtype=None):
        """GLOBAL unpadded flat master.  For a sharded LOCAL view this
        all-gathers over the shard axis (call inside the mapped region);
        a sharded GLOBAL view (buffers already full-size, e.g. a state
        passed OUT of shard_map with a dp-sharded out-spec) and the
        dense case just slice.  A prefetch-layout buffer (local or
        global view) is rank-major per span and is statically
        reassembled after the gather."""
        flat = self.master
        if dtype is not None:
            flat = flat.astype(dtype)
        if self.shard and self.shard_dp > 1 \
                and flat.shape[0] != self.padded_numel:
            flat = jax.lax.all_gather(flat, self.shard_axis, axis=0,
                                      tiled=True)
        if self.spans and self.shard_dp > 1:
            return self._despan(flat)
        n = self.global_numel
        return flat[:n] if flat.shape[0] != n else flat

    def params(self, dtype=None):
        """Materialize the params pytree (construction dtypes).

        This is the checkpoint/eval boundary — inside a jitted train
        step the unravel slices fuse into the consumer instead.  A
        sharded state all-gathers its master (in the construction
        dtype, so bf16 params cost bf16 comm bytes).

        ``dtype`` is the inference-export knob: floating leaves are cast
        to it after the unravel (``dtype=jnp.bfloat16`` is the serving
        regime — the engine consumes bf16 weights regardless of how the
        fp32 master was trained); integer leaves pass through."""
        if self.unravel is None:
            raise ValueError(
                "FlatState was initialized from a flat buffer (no "
                "unravel); call .master directly or init from a pytree")
        tree = self.unravel(self._full_master(self.flat_dtype))
        return tree if dtype is None else _cast_floating(tree, dtype)


def _cast_floating(tree, dtype):
    return jax.tree.map(
        lambda x: x.astype(dtype)
        if jnp.issubdtype(jnp.result_type(x), jnp.floating) else x, tree)


def export_params(flat, params_template, *, dtype=None):
    """Inference weight export from a FULL flat master buffer.

    ``flat`` is the reassembled fp32 master — ``FlatState.master`` for a
    dense state, or the ``"master"`` entry of a contrib
    ``DistributedFused*`` shard-aware ``state_dict()`` (written at ANY
    dp; trailing ZeRO padding is sliced off here).  ``params_template``
    supplies the leaf layout/dtypes (the model's ``init`` tree — shapes
    only are read, values untouched); ``dtype`` optionally casts the
    floating leaves for serving (bf16).
    """
    tmpl_flat, unravel = tree_ravel(params_template)
    n = int(tmpl_flat.size)
    flat = jnp.asarray(flat)
    if flat.shape[0] < n:
        raise ValueError(
            f"flat master has {flat.shape[0]} elements < the template's "
            f"{n} — wrong template, or a single SHARD was passed instead "
            "of the reassembled full master")
    tree = unravel(flat[:n].astype(tmpl_flat.dtype))
    return tree if dtype is None else _cast_floating(tree, dtype)


def _enspan(flat, span_sizes, span_padded, dp):
    """Permute a GLOBAL unpadded flat buffer into the rank-major
    prefetch layout: each span zero-padded to its dp multiple, then the
    per-rank slices concatenated rank-major (the exact buffer a
    ``P(axis)`` block split hands each rank as its span-layout shard).
    Inverse of :meth:`FlatState._despan`."""
    padded_spans, off = [], 0
    for size_k, padded_k in zip(span_sizes, span_padded):
        span = jax.lax.slice_in_dim(flat, off, off + size_k)
        if padded_k != size_k:
            span = jnp.concatenate(
                [span, jnp.zeros((padded_k - size_k,), span.dtype)])
        padded_spans.append(span)
        off += size_k
    blocks = []
    for r in range(dp):
        for span, padded_k in zip(padded_spans, span_padded):
            lk = padded_k // dp
            blocks.append(jax.lax.slice_in_dim(span, r * lk, (r + 1) * lk))
    return jnp.concatenate(blocks) if len(blocks) > 1 else blocks[0]


def shard_flat_grads(flat_grads: jax.Array, state: FlatState, *,
                     mean: bool = True) -> jax.Array:
    """Reduce-scatter a FULL per-rank flat grad buffer into MY shard's
    window (the ZeRO-2 grad reduction): zero-pad to the padded length,
    ``psum_scatter`` over the shard axis, and (by default) divide by dp
    for data-parallel mean semantics.  Comm bytes equal the old
    all-reduce's reduce-scatter half; the all-gather half moves to the
    params side (:meth:`FlatState.params` / the zero train step).  A
    prefetch-layout state permutes the grads rank-major per span first,
    so the scatter lands each rank exactly its span-layout shard.

    No-op (beyond the mean) when ``state`` is dense or dp == 1 — so the
    same step code serves every topology."""
    if not state.shard or state.shard_dp == 1:
        return flat_grads
    if state.spans:
        flat_grads = _enspan(flat_grads, state.span_sizes,
                             state.span_padded, state.shard_dp)
    else:
        pad = state.padded_numel - state.global_numel
        if pad:
            flat_grads = jnp.concatenate(
                [flat_grads, jnp.zeros((pad,), flat_grads.dtype)])
    gshard = jax.lax.psum_scatter(
        flat_grads, state.shard_axis, scatter_dimension=0, tiled=True)
    return gshard / state.shard_dp if mean else gshard


def _shard_of(flat: jax.Array, shard_len: int, rank):
    return jax.lax.dynamic_slice_in_dim(
        flat, jnp.asarray(rank, jnp.int32) * shard_len, shard_len)


def _init_state(tx, params, shard=None, prefetch=None) -> FlatState:
    """Shared init: ravel a pytree (or accept an already-flat buffer)
    into a donation-safe fp32 master + the rule's zero slots.

    ``shard=(axis_name, dp[, rank])`` materializes only rank's
    ``1/dp`` shard of the dp-padded master (and slots).  ``rank``
    defaults to ``lax.axis_index(axis_name)`` — the in-``shard_map``
    case; pass an explicit int to build one rank's shard eagerly
    (checkpoint resharding, tests).

    ``prefetch`` (with ``shard``) selects the layered-prefetch layout:
    an int asks for that many gather spans (grouped along leaf
    boundaries by :func:`prefetch_span_layout`); a tuple of per-span
    leaf counts is used as-is.  ``None``/0/1 keep the contiguous block
    layout."""
    if hasattr(params, "ndim") and params.ndim == 1:
        flat, unravel = params, None
        sizes = (int(flat.size),)
        flat_dtype = str(flat.dtype)
    else:
        flat, unravel = tree_ravel(params)
        sizes = tuple(int(x.size)
                      for x in jax.tree_util.tree_leaves(params))
        flat_dtype = str(flat.dtype)
    # Explicit copy: the master is donated every step, and ravel of a
    # single fp32 leaf can alias the caller's param array.
    master = jnp.array(flat, dtype=jnp.float32, copy=True)
    shard_static: tuple = ()
    spans: tuple = ()
    if shard is not None:
        axis_name, dp, *rank_opt = shard
        dp = int(dp)
        shard_static = (axis_name, dp)
        spans = _normalize_prefetch(prefetch, sizes)
        master = _layout_master(master, sizes=sizes, spans=spans, dp=dp)
        if dp > 1:
            rank = rank_opt[0] if rank_opt \
                else jax.lax.axis_index(axis_name)
            master = _shard_of(master, int(master.shape[0]) // dp, rank)
    return FlatState(
        master=master,
        count=jnp.zeros((), jnp.float32),
        slots=tx.init_slots(master, sizes=sizes),
        sizes=sizes,
        flat_dtype=flat_dtype,
        unravel=unravel,
        shard=shard_static,
        spans=spans)


@dataclasses.dataclass(frozen=True)
class _AdamTx:
    """Functional FusedAdam(W) (kernel: :func:`fused_adam_flat`)."""
    lr: float = 1e-3
    beta1: float = 0.9
    beta2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.0
    adam_w_mode: bool = True
    bias_correction: bool = True

    def init(self, params, shard=None, prefetch=None) -> FlatState:
        return _init_state(self, params, shard=shard, prefetch=prefetch)

    def init_slots(self, master, *, sizes) -> dict:
        return {"exp_avg": jnp.zeros_like(master),
                "exp_avg_sq": jnp.zeros_like(master)}

    def update(self, state: FlatState, flat_grads, *, noop_flag=0.0,
               grad_scale=1.0, lr=None, beta1=None, beta2=None, eps=None,
               weight_decay=None) -> FlatState:
        t = state.count + 1.0
        p, m, v = fused_adam_flat(
            state.master, flat_grads,
            state.slots["exp_avg"], state.slots["exp_avg_sq"],
            lr=_f32(self.lr if lr is None else lr),
            beta1=_f32(self.beta1 if beta1 is None else beta1),
            beta2=_f32(self.beta2 if beta2 is None else beta2),
            eps=_f32(self.eps if eps is None else eps),
            weight_decay=_f32(self.weight_decay if weight_decay is None
                              else weight_decay),
            step=t, adam_w_mode=self.adam_w_mode,
            bias_correction=self.bias_correction,
            noop_flag=_f32(noop_flag), grad_scale=_f32(grad_scale))
        return state.replace(
            master=p, count=t,
            slots={"exp_avg": m, "exp_avg_sq": v})


def _broadcast_leaf_scalars(scalars, sizes):
    # late import: base.py imports this module
    from apex_tpu.optimizers.base import broadcast_leaf_scalars
    return broadcast_leaf_scalars(scalars, sizes)


@dataclasses.dataclass(frozen=True)
class _LambTx:
    """Functional FusedLAMB (phase-1 kernel + per-tensor trust ratios).

    Per-leaf norms need the tensor boundaries — ``state.sizes`` — so the
    state must have been built by ``init`` from a pytree (or a flat
    buffer treated as one tensor)."""
    lr: float = 1e-3
    beta1: float = 0.9
    beta2: float = 0.999
    eps: float = 1e-6
    weight_decay: float = 0.01
    max_grad_norm: float = 1.0
    bias_correction: bool = True
    grad_averaging: bool = True
    use_nvlamb: bool = False

    def init(self, params, shard=None, prefetch=None) -> FlatState:
        return _init_state(self, params, shard=shard, prefetch=prefetch)

    def init_slots(self, master, *, sizes) -> dict:
        return {"exp_avg": jnp.zeros_like(master),
                "exp_avg_sq": jnp.zeros_like(master)}

    def update(self, state: FlatState, flat_grads, *, noop_flag=0.0,
               grad_scale=1.0, lr=None, beta1=None, beta2=None, eps=None,
               weight_decay=None, max_grad_norm=None) -> FlatState:
        t = state.count + 1.0
        p = state.master
        m = state.slots["exp_avg"]
        v = state.slots["exp_avg_sq"]
        offsets, sizes = state.offsets, state.sizes
        sharded = bool(state.shard) and state.shard_dp > 1
        axis, dp = state.shard_axis, state.shard_dp
        mgn = _f32(self.max_grad_norm if max_grad_norm is None
                   else max_grad_norm)
        g32 = flat_grads.astype(jnp.float32)    # no-op for fp32 grads
        gscale = _f32(grad_scale)
        # global grad norm clip (reference: first multi_tensor_l2norm
        # launch); under ZeRO each rank holds one grad shard, so the
        # shard-local sum of squares is psum'd into the global norm.
        # The product with grad_scale is only reduced here: the kernel
        # reads the buffer as given and applies grad_scale with the
        # clip, so no scaled copy of the flat buffer is written
        gsq = jnp.sum(jnp.square(g32 * gscale))
        if sharded:
            gsq = jax.lax.psum(gsq, axis)
        gnorm = jnp.sqrt(gsq)
        clip = jnp.where((mgn > 0) & (gnorm > mgn), mgn / (gnorm + 1e-6),
                         1.0)
        # the kernel predicates the moments on noop_flag in place
        m, v, u = fused_lamb_phase1_flat(
            p, g32, m, v,
            beta1=_f32(self.beta1 if beta1 is None else beta1),
            beta2=_f32(self.beta2 if beta2 is None else beta2),
            eps=_f32(self.eps if eps is None else eps),
            weight_decay=_f32(self.weight_decay if weight_decay is None
                              else weight_decay),
            step=t, bias_correction=self.bias_correction,
            grad_scale=clip * gscale, grad_averaging=self.grad_averaging,
            noop_flag=_f32(noop_flag))

        if sharded:
            # EXACT per-tensor trust ratios across shards (reference:
            # DistributedFusedLAMB's multi_tensor_l2norm + group
            # allreduce): shard-local per-tensor partial sq-sums over
            # the static leaf-span layout (lax.switch over ranks — no
            # per-element gathers), psum'd over dp.
            from apex_tpu.optimizers.base import (
                sharded_leaf_broadcast, sharded_leaf_sq_norms)
            rank = jax.lax.axis_index(axis)
            sq = sharded_leaf_sq_norms(
                (p, u), sizes, dp=dp, shard_len=state.shard_len,
                rank=rank, spans=state.spans)
            sq = jax.lax.psum(sq, axis)
            w_norm, u_norm = jnp.sqrt(sq[0]), jnp.sqrt(sq[1])
        else:
            def sq_norms(flat):
                return jnp.stack([
                    jnp.sum(jnp.square(
                        jax.lax.dynamic_slice_in_dim(flat, off, size)))
                    for off, size in zip(offsets, sizes)])

            w_norm = jnp.sqrt(sq_norms(p))
            u_norm = jnp.sqrt(sq_norms(u))
        # NVLAMB applies the trust ratio to every param; default LAMB
        # skips params with zero norm (reference kernel's `use_nvlamb`).
        ratio = jnp.where((w_norm > 0) & (u_norm > 0), w_norm / u_norm,
                          jnp.float32(1.0))
        if self.use_nvlamb:
            ratio = w_norm / jnp.maximum(u_norm, 1e-12)
        if sharded:
            scale = sharded_leaf_broadcast(
                ratio, sizes, dp=dp, shard_len=state.shard_len,
                rank=rank, spans=state.spans)
        else:
            scale = _broadcast_leaf_scalars(ratio, sizes)
        p_new = p - _f32(self.lr if lr is None else lr) * scale * u

        # the master keeps its select (one fusion with the apply): on an
        # overflowed step u holds inf/nan and lr*0*inf would be nan
        skip = _f32(noop_flag) > 0
        return state.replace(
            master=jnp.where(skip, p, p_new), count=t,
            slots={"exp_avg": m, "exp_avg_sq": v})


@dataclasses.dataclass(frozen=True)
class _SgdTx:
    """Functional FusedSGD (kernel: :func:`fused_sgd_flat`).

    ``slots["seeded"]`` replicates the class API's first-effective-step
    tracking: torch clones the grad into a FRESH buffer on the first
    step that actually applies (a noop-skipped step must not seed)."""
    lr: float = 1e-3
    momentum: float = 0.0
    dampening: float = 0.0
    weight_decay: float = 0.0
    nesterov: bool = False
    wd_after_momentum: bool = False

    def init(self, params, shard=None, prefetch=None) -> FlatState:
        return _init_state(self, params, shard=shard, prefetch=prefetch)

    def init_slots(self, master, *, sizes) -> dict:
        return {"momentum_buffer": jnp.zeros_like(master),
                "seeded": jnp.zeros((), jnp.float32)}

    def update(self, state: FlatState, flat_grads, *, noop_flag=0.0,
               grad_scale=1.0, lr=None, momentum=None, dampening=None,
               weight_decay=None) -> FlatState:
        t = state.count + 1.0
        seeded = state.slots["seeded"]
        noop = _f32(noop_flag)
        p, buf = fused_sgd_flat(
            state.master, flat_grads, state.slots["momentum_buffer"],
            lr=_f32(self.lr if lr is None else lr),
            momentum=_f32(self.momentum if momentum is None else momentum),
            dampening=_f32(self.dampening if dampening is None
                           else dampening),
            weight_decay=_f32(self.weight_decay if weight_decay is None
                              else weight_decay),
            nesterov=self.nesterov,
            wd_after_momentum=self.wd_after_momentum,
            first_run=1.0 - seeded, noop_flag=noop,
            grad_scale=_f32(grad_scale))
        return state.replace(
            master=p, count=t,
            slots={"momentum_buffer": buf,
                   "seeded": jnp.maximum(
                       seeded, jnp.where(noop > 0.0, 0.0, 1.0))})


@dataclasses.dataclass(frozen=True)
class _NovoGradTx:
    """Functional FusedNovoGrad: per-tensor ||g||²-EMA second moments
    (``exp_avg_sq`` has one scalar per leaf — needs ``state.sizes``)."""
    lr: float = 1e-3
    beta1: float = 0.9
    beta2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.0
    bias_correction: bool = True
    grad_averaging: bool = True
    init_zero: bool = False

    def init(self, params, shard=None, prefetch=None) -> FlatState:
        return _init_state(self, params, shard=shard, prefetch=prefetch)

    def init_slots(self, master, *, sizes) -> dict:
        return {"exp_avg": jnp.zeros_like(master),
                "exp_avg_sq": jnp.zeros((len(sizes),), jnp.float32)}

    def update(self, state: FlatState, flat_grads, *, noop_flag=0.0,
               grad_scale=1.0, lr=None, beta1=None, beta2=None, eps=None,
               weight_decay=None) -> FlatState:
        t = state.count + 1.0
        p = state.master
        m = state.slots["exp_avg"]
        v = state.slots["exp_avg_sq"]
        offsets, sizes = state.offsets, state.sizes
        sharded = bool(state.shard) and state.shard_dp > 1
        b1 = _f32(self.beta1 if beta1 is None else beta1)
        b2 = _f32(self.beta2 if beta2 is None else beta2)
        g32 = flat_grads.astype(jnp.float32) * _f32(grad_scale)
        if sharded:
            # per-tensor ||g||² from grad SHARDS: static-span partial
            # sums, psum'd global (the exp_avg_sq slot is one scalar
            # per leaf — replicated, NOT sharded)
            from apex_tpu.optimizers.base import (
                sharded_leaf_broadcast, sharded_leaf_sq_norms)
            rank = jax.lax.axis_index(state.shard_axis)
            gsq = jax.lax.psum(
                sharded_leaf_sq_norms(
                    (g32,), sizes, dp=state.shard_dp,
                    shard_len=state.shard_len, rank=rank,
                    spans=state.spans)[0],
                state.shard_axis)
        else:
            gsq = jnp.stack([
                jnp.sum(jnp.square(
                    jax.lax.dynamic_slice_in_dim(g32, off, size)))
                for off, size in zip(offsets, sizes)])
        first = t <= 1.0
        v_init = jnp.zeros_like(gsq) if self.init_zero else gsq
        v_new = jnp.where(first, v_init, b2 * v + (1.0 - b2) * gsq)
        denom_scalars = (jnp.sqrt(v_new)
                         + _f32(self.eps if eps is None else eps))
        if sharded:
            denom = sharded_leaf_broadcast(
                denom_scalars, sizes, dp=state.shard_dp,
                shard_len=state.shard_len, rank=rank, spans=state.spans)
        else:
            denom = _broadcast_leaf_scalars(denom_scalars, sizes)
        ghat = g32 / denom + _f32(self.weight_decay if weight_decay is None
                                  else weight_decay) * p
        coef = (1.0 - b1) if self.grad_averaging else 1.0
        m_new = b1 * m + coef * ghat
        lr_ = _f32(self.lr if lr is None else lr)
        if self.bias_correction:
            step_size = lr_ / (1.0 - jnp.power(b1, t))
        else:
            step_size = lr_
        p_new = p - step_size * m_new
        skip = _f32(noop_flag) > 0
        return state.replace(
            master=jnp.where(skip, p, p_new), count=t,
            slots={"exp_avg": jnp.where(skip, m, m_new),
                   "exp_avg_sq": jnp.where(skip, v, v_new)})


@dataclasses.dataclass(frozen=True)
class _AdagradTx:
    """Functional FusedAdagrad (kernel: :func:`fused_adagrad_flat`)."""
    lr: float = 1e-2
    eps: float = 1e-10
    weight_decay: float = 0.0
    w_mode: bool = False

    def init(self, params, shard=None, prefetch=None) -> FlatState:
        return _init_state(self, params, shard=shard, prefetch=prefetch)

    def init_slots(self, master, *, sizes) -> dict:
        return {"sum": jnp.zeros_like(master)}

    def update(self, state: FlatState, flat_grads, *, noop_flag=0.0,
               grad_scale=1.0, lr=None, eps=None,
               weight_decay=None) -> FlatState:
        t = state.count + 1.0
        p, h = fused_adagrad_flat(
            state.master, flat_grads, state.slots["sum"],
            lr=_f32(self.lr if lr is None else lr),
            eps=_f32(self.eps if eps is None else eps),
            weight_decay=_f32(self.weight_decay if weight_decay is None
                              else weight_decay),
            w_mode=self.w_mode, noop_flag=_f32(noop_flag),
            grad_scale=_f32(grad_scale))
        return state.replace(master=p, count=t, slots={"sum": h})


# -- factories (constructor-parity argument names) ---------------------------

def fused_adam(lr=1e-3, betas=(0.9, 0.999), eps=1e-8, weight_decay=0.0,
               adam_w_mode=True, bias_correction=True) -> _AdamTx:
    return _AdamTx(lr=float(lr), beta1=float(betas[0]),
                   beta2=float(betas[1]), eps=float(eps),
                   weight_decay=float(weight_decay),
                   adam_w_mode=bool(adam_w_mode),
                   bias_correction=bool(bias_correction))


def fused_lamb(lr=1e-3, betas=(0.9, 0.999), eps=1e-6, weight_decay=0.01,
               max_grad_norm=1.0, bias_correction=True,
               grad_averaging=True, use_nvlamb=False) -> _LambTx:
    return _LambTx(lr=float(lr), beta1=float(betas[0]),
                   beta2=float(betas[1]), eps=float(eps),
                   weight_decay=float(weight_decay),
                   max_grad_norm=float(max_grad_norm or 0.0),
                   bias_correction=bool(bias_correction),
                   grad_averaging=bool(grad_averaging),
                   use_nvlamb=bool(use_nvlamb))


def fused_sgd(lr, momentum=0.0, dampening=0.0, weight_decay=0.0,
              nesterov=False, wd_after_momentum=False) -> _SgdTx:
    return _SgdTx(lr=float(lr), momentum=float(momentum),
                  dampening=float(dampening),
                  weight_decay=float(weight_decay),
                  nesterov=bool(nesterov),
                  wd_after_momentum=bool(wd_after_momentum))


def fused_novograd(lr=1e-3, betas=(0.9, 0.999), eps=1e-8,
                   weight_decay=0.0, bias_correction=True,
                   grad_averaging=True, init_zero=False) -> _NovoGradTx:
    return _NovoGradTx(lr=float(lr), beta1=float(betas[0]),
                       beta2=float(betas[1]), eps=float(eps),
                       weight_decay=float(weight_decay),
                       bias_correction=bool(bias_correction),
                       grad_averaging=bool(grad_averaging),
                       init_zero=bool(init_zero))


def fused_adagrad(lr=1e-2, eps=1e-10, weight_decay=0.0,
                  adagrad_w_mode=False) -> _AdagradTx:
    return _AdagradTx(lr=float(lr), eps=float(eps),
                      weight_decay=float(weight_decay),
                      w_mode=bool(adagrad_w_mode))
