"""Flat-native train step: forward, backward, scaler, and fused update
as ONE donated XLA program.

The structural insight (PERF.md r5, ISSUE 2): keep the flat fp32 master
buffer as the *differentiation variable* —

    jax.value_and_grad(lambda flat: loss(unravel(flat)))

— and autodiff *produces* flat gradients.  The per-leaf ``unravel``
slices fuse into the forward, their transpose is a pad+add chain XLA
fuses over the flat cotangent, and the 297-leaf grad re-ravel
``concatenate`` plus the host-driven unscale/update dispatches disappear
from the step entirely.  Full pytree materialization happens only at
checkpoint/eval boundaries (``TrainState.params()``).

amp is carried in-program: the loss is scaled before the backward, the
flat grads stay scaled and ``1/scale`` rides the optimizer's
``grad_scale`` (the multiplier its kernel already applies), a read-only
reduction flags a non-finite grad
(:func:`apex_tpu.amp.scaler.check_flat_grads`), and that flag feeds the
update kernel's ``noop_flag`` predicate — no host sync anywhere between
backward and update, and no unscaled copy of the flat grads.

Typical use (the shape ``examples/bert/pretrain_bert.py`` runs)::

    tx = functional.fused_lamb(lr=1e-3, weight_decay=0.01)
    state = init_train_state(tx, params, loss_scale="dynamic")
    run = train_loop(loss_fn, tx)          # jitted scan, state donated
    state, losses = run(state, batches)    # batches: [iters, ...] leaves
    final_params = state.params()          # checkpoint/eval boundary
"""
from __future__ import annotations

import functools
import os
from typing import Callable, Optional

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.amp.scaler import (
    LossScaleState,
    check_flat_grads,
    init_loss_scale,
    update_scale,
)
from apex_tpu.observability import xla_stats
from apex_tpu.optimizers.functional import (FlatState, _layout_master,
                                            _normalize_prefetch)

__all__ = ["TrainState", "init_train_state", "init_zero_train_state",
           "make_train_step", "train_loop", "instrumented_train_loop",
           "leaf_offsets", "zero_prefetch_default"]


def zero_prefetch_default() -> int:
    """Effective ``APEX_TPU_ZERO_PREFETCH`` value: the number of
    layered-prefetch gather spans a ZeRO state is built with when
    ``prefetch`` is not passed explicitly.  0/1 keep the monolithic
    gather (today's layout); stamped into ZeRO bench captures."""
    return int(os.environ.get("APEX_TPU_ZERO_PREFETCH", "0"))


@flax.struct.dataclass
class TrainState:
    """Scan-carryable train-loop state: flat optimizer state + (optional)
    loss-scaler state."""
    opt: FlatState
    scaler: Optional[LossScaleState] = None

    def params(self):
        """Materialize the params pytree (checkpoint/eval boundary)."""
        return self.opt.params()


def init_train_state(tx, params, loss_scale=None, shard=None,
                     prefetch=None) -> TrainState:
    """Build a TrainState from a params pytree.

    ``loss_scale``: None (no amp scaling), "dynamic", or a fixed float —
    the same contract as :class:`apex_tpu.amp.scaler.LossScaler`.

    ``shard=(axis_name, dp[, rank])`` builds a ZeRO dp-sharded optimizer
    state (see :class:`~apex_tpu.optimizers.functional.FlatState`);
    without an explicit rank this must run inside ``shard_map`` with the
    axis bound.  Pair with ``make_train_step(..., zero=True)``.

    ``prefetch`` (with ``shard``) selects the layered-prefetch shard
    layout: the flat master is split along leaf boundaries into this
    many gather spans so the zero step's param all-gather decomposes
    into independent per-span gathers XLA can overlap with the layers
    consuming them.  ``None`` reads ``APEX_TPU_ZERO_PREFETCH``
    (default 0 = monolithic gather); a tuple of per-span leaf counts is
    used as-is.
    """
    scaler = None if loss_scale is None else init_loss_scale(loss_scale)
    if shard is not None and prefetch is None:
        prefetch = zero_prefetch_default()
    return TrainState(opt=tx.init(params, shard=shard, prefetch=prefetch),
                      scaler=scaler)


def init_zero_train_state(tx, params, axis_name: str, dp: int,
                          loss_scale=None, prefetch=None):
    """GLOBAL-view ZeRO state + its PartitionSpec tree, for the
    init-outside / step-inside pattern.

    Returns ``(state, specs)``: ``state`` is a :class:`TrainState` whose
    dp-shardable buffers are FULL (padded) length, and ``specs`` is a
    matching pytree of ``PartitionSpec`` — pass the state through
    ``shard_map(..., in_specs=(specs, ...), out_specs=(specs, ...))``
    and each rank's inside view is exactly its local ``1/dp`` shard.
    The state that comes back OUT is again the global view:
    ``state.params()`` / checkpointing see the reassembled flat master
    with no extra code.

    ``prefetch`` selects the layered-prefetch layout (see
    :func:`init_train_state`): the padded global buffers are laid out
    rank-major per span, so the same ``P(axis_name)`` specs hand each
    rank exactly its span-layout shard."""
    from jax.sharding import PartitionSpec as P

    # dense init first (it makes the donation-safe copy of the raveled
    # params), then stamp the shard layout and pad — no throwaway
    # per-rank slicing, and the padding arithmetic lives in the
    # FlatState properties
    state = init_train_state(tx, params, loss_scale=loss_scale)
    if prefetch is None:
        prefetch = zero_prefetch_default()
    opt = state.opt.replace(
        shard=(axis_name, int(dp)),
        spans=_normalize_prefetch(prefetch, state.opt.sizes))
    padded = opt.padded_numel
    if opt.spans or padded != opt.global_numel:
        master = _layout_master(opt.master, sizes=opt.sizes,
                                spans=opt.spans, dp=opt.shard_dp)
        opt = opt.replace(
            master=master, slots=tx.init_slots(master, sizes=opt.sizes))
    state = state.replace(opt=opt)

    def spec_of(leaf):
        return (P(axis_name)
                if hasattr(leaf, "ndim") and leaf.ndim == 1
                and leaf.shape[0] == padded else P())

    specs = jax.tree.map(spec_of, state)
    return state, specs


def _pmean_float_leaves(aux, axis):
    """pmean the float leaves of an aux pytree over ``axis``; integer/
    bool leaves pass through (dtype dispatch is static)."""
    def leaf(a):
        if jnp.issubdtype(jnp.result_type(a), jnp.inexact):
            return jax.lax.pmean(a, axis)
        return a
    return jax.tree.map(leaf, aux)


def make_train_step(loss_fn, tx, *, has_aux: bool = False,
                    grad_transform: Optional[Callable] = None,
                    zero: bool = False, numerics: bool = False):
    """Build a pure ``step(state, batch) -> (state, metrics)``.

    ``loss_fn(params, batch)`` takes the MATERIALIZED params pytree (the
    unravel slices fuse into the forward) and returns a scalar loss (or
    ``(loss, aux)`` with ``has_aux=True``).  ``metrics`` is the UNSCALED
    loss (or ``(loss, aux)``).

    ``grad_transform(flat_grads)`` runs between backward and the
    overflow check — the hook for data-parallel ``pmean`` or per-leaf
    collective fixups (see :func:`leaf_offsets`); it must stay on-device
    and flat.  Under ``zero=True`` it receives the local grad SHARD
    (already dp-meaned), so per-leaf offset fixups do not apply there.

    ``zero=True`` is the ZeRO-sharded step: the state's optimizer must
    be dp-sharded (``init_train_state(..., shard=(axis, dp))``) and the
    step must run inside ``shard_map`` with the axis bound.  The flat
    master SHARD stays the differentiation variable: the forward
    consumes ``all_gather(shard.astype(bf16))`` — so autodiff's
    transpose IS the ``psum_scatter`` of the flat bf16 grads (comm
    bytes match the old all-reduce: RS(2N) + AG(2N) vs AR(4N) in ring
    terms) — the overflow flag is reduced over the shard and pmax'd
    replica-uniform, and the Pallas fused update touches only the local
    ``1/dp`` of master/slots.  Per-chip optimizer state,
    update FLOPs, and update HBM traffic all drop dp×; everything still
    composes into ONE donated XLA program.  A state built with
    ``prefetch`` spans (``init_train_state(..., prefetch=K)`` /
    ``APEX_TPU_ZERO_PREFETCH``) decomposes that gather into independent
    per-span all-gathers so comm overlaps the consuming layers' compute
    — same bytes, same ONE executable.  The reported loss — and
    every float leaf of ``aux`` — is ``pmean``'d over the axis (the
    global-batch metric); integer/bool aux diagnostics stay rank-local.

    ``numerics=True`` (ISSUE 11) adds the in-program numerics health
    probes: the step returns ``(state, (metrics, probes))`` where
    ``probes`` is a :class:`~apex_tpu.observability.numerics.
    NumericsProbes` — global flat-grad/param/update sq-norms plus the
    per-leaf grad sq-norms and nonfinite counts that power the overflow
    autopsy, computed over the unscaled grads the update consumed.
    Everything still composes into the same ONE donated executable;
    under ZeRO the probes add exactly one ``(2*n_leaves+2)``-element
    f32 ``psum`` (replica-uniform, APX213-clean — pinned by the
    ``train_step_zero_numerics`` budget twin).

    The result is a valid ``lax.scan`` body; jit it (or the scan around
    it) with ``donate_argnums=(0,)`` — the whole state is donation-safe.
    """

    def step(state: TrainState, batch):
        # its caller jits it: the op -> scope table is captured when the
        # tables are read (ISSUE 38), from the shapes of this trace
        xla_stats.capture_when_read(step, state, batch, donate_argnums=(0,))
        opt, scaler = state.opt, state.scaler
        scale = (scaler.loss_scale if scaler is not None
                 else jnp.float32(1.0))
        if zero and not opt.shard:
            raise ValueError(
                "make_train_step(zero=True) needs a dp-sharded state: "
                "init_train_state(tx, params, shard=(axis_name, dp))")
        axis = opt.shard_axis if zero else None
        dp = opt.shard_dp if zero else 1
        n, padded = opt.global_numel, (opt.padded_numel if zero else 0)

        def flat_loss(flat):
            # the backward of everything in here is named
            # transpose(jvp(apex_train_forward)) in the compiled program
            with jax.named_scope("apex_train_forward"):
                return forward(flat)

        def forward(flat):
            full = flat.astype(opt.flat_dtype)
            if zero and dp > 1:
                if opt.spans:
                    # layered prefetch: one INDEPENDENT all_gather per
                    # leaf span.  Each gather feeds only its own
                    # leaves' unravel slices (the slice-of-concat
                    # simplifies away), so XLA's scheduler issues span
                    # k+1's gather while span k's layers compute —
                    # machine-verified by APX217.  The transpose of
                    # each gather is the matching per-span psum_scatter
                    # of the flat bf16 grads; total comm bytes are the
                    # monolithic gather's (modulo per-span padding).
                    parts, off = [], 0
                    for size_k, padded_k in zip(opt.span_sizes,
                                                opt.span_padded):
                        lk = padded_k // dp
                        g = jax.lax.all_gather(
                            jax.lax.slice_in_dim(full, off, off + lk),
                            axis, axis=0, tiled=True)
                        parts.append(g[:size_k] if padded_k != size_k
                                     else g)
                        off += lk
                    full = (jnp.concatenate(parts) if len(parts) > 1
                            else parts[0])
                else:
                    # params all-gather in the CONSTRUCTION dtype (bf16
                    # comm for bf16 models); the [:n] unpad's transpose
                    # is a zero-pad of the flat cotangent
                    full = jax.lax.all_gather(full, axis, axis=0,
                                              tiled=True)
                    if padded != n:
                        full = full[:n]
            params = opt.unravel(full)
            out = loss_fn(params, batch)
            loss, aux = out if has_aux else (out, None)
            # the scaled loss drives the backward; the raw loss is the
            # reported metric
            return loss * scale.astype(loss.dtype), (loss, aux)

        (_, (loss, aux)), flat_g = jax.value_and_grad(
            flat_loss, has_aux=True)(opt.master)
        if zero and dp > 1:
            # autodiff already psum_scatter'd (all_gather's transpose):
            # flat_g is my SUM-reduced shard; take the dp mean
            flat_g = flat_g / dp
        if grad_transform is not None:
            flat_g = grad_transform(flat_g)
        grad_scale = jnp.float32(1.0)
        if scaler is not None:
            # the grads stay scaled: 1/scale rides the update's own
            # grad_scale, so no unscaled copy of the flat buffer is
            # written.  found_inf (a read-only reduction, pmax'd
            # replica-uniform under ZeRO) feeds the update kernel's
            # noop predicate in-program
            grad_scale = 1.0 / scaler.loss_scale
            with jax.named_scope("apex_train_unscale"):
                scaler = check_flat_grads(
                    flat_g, scaler,
                    axis_name=axis if zero and dp > 1 else None)
            with jax.named_scope("apex_train_optimizer"):
                new_opt = tx.update(opt, flat_g,
                                    noop_flag=scaler.found_inf,
                                    grad_scale=grad_scale)
                scaler = update_scale(scaler)
        else:
            with jax.named_scope("apex_train_optimizer"):
                new_opt = tx.update(opt, flat_g)
        probes = None
        if numerics:
            # in-program numerics probes over the UNSCALED grads the
            # update consumed and the pre/post masters — extra scalar
            # outputs of the same ONE donated executable (the probes
            # only reduce, so the multiply fuses into them)
            from apex_tpu.observability.numerics import compute_probes
            probes = compute_probes(
                opt, new_opt.master, flat_g * grad_scale,
                axis_name=axis if zero and dp > 1 else None)
        new_state = state.replace(opt=new_opt, scaler=scaler)
        if zero and dp > 1:
            loss = jax.lax.pmean(loss, axis)
            # aux floats get the same global-batch semantics as the
            # loss next to them (a rank-local metric beside a pmean'd
            # loss reads as global and silently is not); integer/bool
            # diagnostics stay rank-local — averaging would corrupt
            # their dtype/meaning
            if aux is not None:
                aux = _pmean_float_leaves(aux, axis)
        metrics = (loss, aux) if has_aux else loss
        return new_state, ((metrics, probes) if numerics else metrics)

    return step


def train_loop(loss_fn, tx, **step_kwargs):
    """Jitted ``run(state, batches) -> (state, metrics)``: every step
    inside one ``lax.scan``, the carried state donated, ONE compiled
    executable for the whole run.  ``batches`` leaves are stacked along
    a leading [iters] axis (the scan axis)."""
    step = make_train_step(loss_fn, tx, **step_kwargs)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def run(state: TrainState, batches):
        return jax.lax.scan(step, state, batches)

    return run


def instrumented_train_loop(loss_fn, tx, *, telemetry=None,
                            tokens_per_batch: Optional[int] = None,
                            mfu_from_compiled: bool = False,
                            numerics: Optional[bool] = None,
                            numerics_every: Optional[int] = None,
                            **step_kwargs):
    """Telemetry-instrumented ``run(state, batches) -> (state, metrics)``
    (ISSUE 8): the same pure step as :func:`train_loop`, jitted ONCE
    with the state donated, but driven host-side one step at a time so
    runtime signals exist — the scanned loop is a single opaque
    executable with nothing observable between steps.

    Invariants preserved (and pinned by ``tests/L1/test_observability``):
    the step stays ONE donated executable (steps after the first add
    zero compiles — the telemetry's recompile counter stays 0), and no
    host sync is added anywhere — the
    :class:`~apex_tpu.observability.train.TrainTelemetry` only brackets
    the dispatch with the dispatch-aware timer and ENQUEUES the step's
    device scalars (loss, ``found_inf``, ``loss_scale``), which resolve
    one step late via the deferred collector, after the next step has
    been dispatched.

    ``metrics`` is the per-step metrics list (device values; stack or
    ``telemetry.flush()`` at the boundary).  Step-loop overhead is the
    per-step dispatch the scan amortizes — use :func:`train_loop` when
    nothing needs observing.

    ``mfu_from_compiled=True`` (ISSUE 10) arms the telemetry's
    ``train_mfu`` gauge from the COMPILED step's own
    ``cost_analysis()`` FLOPs (one extra AOT compile at run start —
    outside every step bracket, so the recompile counter still pins 0;
    the degraded-backend case simply leaves the gauge unarmed, never a
    fabricated number).

    ``numerics`` (ISSUE 11) builds the numerics-probed step
    (``make_train_step(numerics=True)``) and arms the telemetry's
    :class:`~apex_tpu.observability.numerics.NumericsAccountant` —
    grad/param-norm and update-ratio gauges, the grad-norm histogram,
    loss-scale backoff/growth counters, and the overflow autopsy that
    names the parameter leaves whose grads went nonfinite, all
    resolved one step late (zero added syncs, zero recompiles, the
    step still ONE donated executable).  ``None`` reads
    ``APEX_TPU_NUMERICS`` (default off).  ``numerics_every`` samples
    the NORM probes every Nth step (``None`` reads
    ``APEX_TPU_NUMERICS_EVERY``, default 1) — the per-leaf nonfinite
    vector rides every step so an overflow is never sampled away; the
    compiled step is identical at every sampling value.
    """
    from apex_tpu.observability import TrainTelemetry
    from apex_tpu.observability.numerics import (numerics_default,
                                                 numerics_every_default)

    if telemetry is None:
        telemetry = TrainTelemetry()
    if numerics is None:
        numerics = numerics_default()
    numerics = bool(numerics)
    if numerics_every is None:
        numerics_every = numerics_every_default()
    numerics_every = max(1, int(numerics_every))
    step = make_train_step(loss_fn, tx, numerics=numerics,
                           **step_kwargs)

    def _step_with_overflow(state, batch):
        new_state, out = step(state, batch)
        m, probes = out if numerics else (out, None)
        sc_in, sc_out = state.scaler, new_state.scaler
        overflow = None
        if sc_out is not None:
            # found_inf is consumed in-program (the update kernel's
            # noop_flag) and cleared by update_scale, so it cannot be
            # read back.  A dynamic scale strictly DECREASES only on an
            # overflow backoff, so this compare recovers the flag as a
            # FRESH in-program value (unlike a passthrough of a donated
            # buffer, it can never be aliased away by the next step's
            # donation).  Saturates at the min_scale floor and is
            # always-False for fixed scales — both already-broken or
            # skip-free regimes.
            overflow = sc_out.loss_scale < sc_in.loss_scale
        return new_state, (m, overflow, probes)

    jstep = jax.jit(_step_with_overflow, donate_argnums=(0,))

    def snap(x):
        # the scaler scalars live INSIDE the donated state: the NEXT
        # dispatch consumes their buffers, so the deferred read would
        # find them deleted.  jnp.copy is an async device-side copy to
        # an independent buffer — no host sync, one tiny executable
        # compiled once.  (The loss needs none of this: metrics outputs
        # are not donated.)
        return None if x is None else jnp.copy(x)

    def run(state: TrainState, batches):
        n = jax.tree.leaves(batches)[0].shape[0]
        if numerics and not telemetry.numerics_armed:
            from apex_tpu.observability.numerics import flat_leaf_names
            telemetry.arm_numerics(flat_leaf_names(state.opt),
                                   every=numerics_every)
        if mfu_from_compiled and not telemetry.mfu_armed and n > 0:
            from apex_tpu.observability.xla_stats import compile_and_stats
            batch0 = jax.tree.map(lambda x: x[0], batches)
            stats = compile_and_stats(_step_with_overflow,
                                      (state, batch0),
                                      donate_argnums=(0,))
            if stats.flops:
                telemetry.arm_mfu(stats.flops)
        metrics = []
        for i in range(n):
            batch = jax.tree.map(lambda x: x[i], batches)
            with telemetry.step(tokens=tokens_per_batch):
                state, (m, overflow, probes) = jstep(state, batch)
            loss = m[0] if isinstance(m, tuple) else m
            sc = state.scaler
            # probe sampling is a host-side choice of what to ENQUEUE —
            # the executable computed them either way, so no recompile
            # can ride the interval knob.  The per-leaf nonfinite
            # vector (the autopsy's attribution signal) rides EVERY
            # step regardless: an overflow on an unsampled step must
            # still name its leaf
            sampled = i % numerics_every == 0
            telemetry.observe_device(
                loss=loss,
                found_inf=overflow,
                loss_scale=None if sc is None else snap(sc.loss_scale),
                probes=probes if sampled else None,
                leaf_nonfinite=(probes.leaf_nonfinite
                                if probes is not None and not sampled
                                else None))
            metrics.append(m)
        telemetry.flush()          # end-of-run boundary: blocking is fine
        return state, metrics

    run.telemetry = telemetry
    return run


def leaf_offsets(tree) -> "dict[str, tuple[int, int, tuple]]":
    """``{keystr: (offset, size, shape)}`` of each leaf inside the
    raveled flat buffer (``ravel_pytree`` order = ``tree_leaves``
    order).

    The flat-native escape hatch for per-leaf grad fixups (tied
    embeddings, replicated-kv psums): ``lax.dynamic_slice_in_dim`` the
    leaf out of the flat grads, fix it, ``dynamic_update_slice_in_dim``
    it back — no tree round-trip, no re-ravel concatenate."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out, off = {}, 0
    for path, leaf in flat:
        size = int(np.prod(leaf.shape)) if np.ndim(leaf) else 1
        out[jax.tree_util.keystr(path)] = (off, size,
                                           tuple(np.shape(leaf)))
        off += size
    return out
