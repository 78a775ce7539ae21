"""Loss scaling — functional core + imperative parity wrapper.

Reference: ``apex/amp/scaler.py :: LossScaler`` with the classic dynamic
schedule — init scale 2**16, x2 growth every 2000 clean steps, x0.5 backoff
on overflow — and ``_has_inf_or_nan`` overflow detection.

TPU-native design: the scaler is a pytree (``LossScaleState``) carried
through the jitted train step; overflow detection is the fused non-finite
flag from :func:`apex_tpu.ops.fused_update.fused_scale`, or in the
flat-native step a read-only reduction with the unscale folded into the
optimizer's ``grad_scale`` (:func:`check_flat_grads`) — no device→host
sync either way, the classic CUDA perf trap called out in SURVEY §3.1;
skip-on-overflow is the ``noop_flag`` predicate inside the fused
optimizer kernel.
"""
from __future__ import annotations

import flax.struct
import jax
import jax.numpy as jnp

from apex_tpu.ops.fused_update import fused_scale
from apex_tpu.utils import tree_ravel

__all__ = ["LossScaleState", "init_loss_scale", "scale_loss_value",
           "unscale_grads", "check_flat_grads",
           "nonfinite_leaf_counts", "update_scale", "LossScaler"]

# Reference constants (apex/amp/scaler.py)
DEFAULT_INIT_SCALE = 2.0 ** 16
DEFAULT_GROWTH_FACTOR = 2.0
DEFAULT_BACKOFF_FACTOR = 0.5
DEFAULT_GROWTH_INTERVAL = 2000
MAX_SCALE = 2.0 ** 24
MIN_SCALE = 1.0


@flax.struct.dataclass
class LossScaleState:
    """Jit-carried scaler state (pytree; ``dynamic`` is static aux data)."""
    loss_scale: jax.Array          # f32 scalar
    growth_tracker: jax.Array      # i32 scalar: clean steps since last growth
    found_inf: jax.Array           # f32 scalar: overflow flag of last unscale
    dynamic: bool = flax.struct.field(pytree_node=False, default=True)


def init_loss_scale(loss_scale="dynamic") -> LossScaleState:
    """Build scaler state.  ``loss_scale``: "dynamic" or a fixed float."""
    dynamic = loss_scale == "dynamic"
    scale = DEFAULT_INIT_SCALE if dynamic else float(loss_scale)
    return LossScaleState(
        loss_scale=jnp.asarray(scale, jnp.float32),
        growth_tracker=jnp.asarray(0, jnp.int32),
        found_inf=jnp.asarray(0.0, jnp.float32),
        dynamic=dynamic)


def scale_loss_value(loss, state: LossScaleState):
    """loss * scale (the body of the reference's ``scale_loss`` ctx mgr)."""
    return loss * state.loss_scale.astype(loss.dtype)


def unscale_grads(grads, state: LossScaleState):
    """Unscale a grad pytree by 1/scale with fused overflow detection.

    Returns (unscaled_grads, new_state with found_inf set).
    Parity: ``LossScaler.unscale_`` (amp_C.multi_tensor_scale path).
    """
    flat, unravel = tree_ravel(grads)
    out, flag = fused_scale(flat, 1.0 / state.loss_scale)
    return unravel(out), state.replace(found_inf=flag)


def check_flat_grads(flat_grads, state: LossScaleState, axis_name=None):
    """Overflow detection of a SCALED flat grad buffer, without writing
    the unscaled copy: ``found_inf`` flags a non-finite element of
    ``flat_grads * (1 / scale)``, the product :func:`fused_scale` would
    write, and nothing else is produced.  The flat-native train step
    hands the optimizer the scaled buffer as the backward wrote it with
    ``grad_scale=1/scale``, which every fused update folds into the
    multiplier its kernel already applies; this is a read-only
    reduction that XLA fuses with the optimizer's own read of the
    buffer.  An empty buffer reads clean.

    ``axis_name`` reduces the overflow flag across a mesh axis (pmax):
    under ZeRO each rank checks only its own grad SHARD, but the skip
    decision must be replica-uniform — a rank whose shard happens to be
    finite must still skip when any peer overflowed, or the ranks'
    masters diverge silently.

    Returns the new state with found_inf set.
    """
    inv_scale = 1.0 / state.loss_scale
    # a float max, not a boolean any: the v5e's compiler then merges it
    # with the optimizer's float sum of squares (and the backward's
    # build of the flat buffer) into ONE pass over the grads; a pred
    # reduce stays a pass of its own
    flag = jnp.max((~jnp.isfinite(flat_grads * inv_scale)).astype(
        jnp.float32), initial=0.0)
    if axis_name is not None:
        flag = jax.lax.pmax(flag, axis_name)
    return state.replace(found_inf=flag)


def nonfinite_leaf_counts(flat_grads, sizes, *, axis_name=None, dp=1,
                          shard_len=None, rank=None, spans=None):
    """Per-leaf counts of nonfinite (inf/nan) elements of a flat grad
    buffer — WHICH parameter overflowed, next to
    :func:`check_flat_grads`'s scalar ``found_inf`` that only says
    THAT one did.  This is the overflow autopsy's attribution signal
    (ISSUE 11): computed in-program as one more scalar-vector output of
    the donated step, resolved one step late by the telemetry, so the
    attribution costs no host sync and no recompile.

    Dense (``dp == 1``): ``flat_grads`` is the full flat buffer and
    ``sizes`` its per-leaf layout.  Under ZeRO pass the grad SHARD with
    the state's static layout (``dp``/``shard_len``/``spans``) and
    ``rank = lax.axis_index(axis_name)``; ``axis_name`` psums the
    partial counts replica-uniform — every rank reports the same
    autopsy, the same APX213 discipline as ``found_inf``'s pmax.

    Returns an ``[n_leaves]`` f32 count vector (0.0 everywhere on a
    clean step)."""
    from apex_tpu.optimizers.base import sharded_leaf_nonfinite_counts
    if axis_name is not None and int(dp) <= 1:
        # psum of per-rank counts is only correct over SHARDS; on
        # replicated grads every rank already holds the global counts
        # and the psum would overcount by the replica count (found_inf
        # sidesteps the same hazard with pmax)
        raise ValueError(
            "axis_name without a sharded layout (dp <= 1): replicated "
            "grads would psum to replica_count x the true counts — "
            "drop axis_name (every rank already holds the global "
            "counts) or pass the shard layout (dp/shard_len/rank)")
    sizes = tuple(int(s) for s in sizes)
    if shard_len is None:
        shard_len = int(flat_grads.shape[0])
    if rank is None:
        rank = jnp.int32(0)
    counts = sharded_leaf_nonfinite_counts(
        (flat_grads,), sizes, dp=int(dp), shard_len=int(shard_len),
        rank=rank, spans=spans)[0]
    if axis_name is not None:
        counts = jax.lax.psum(counts, axis_name)
    return counts


def update_scale(state: LossScaleState,
                 growth_factor=DEFAULT_GROWTH_FACTOR,
                 backoff_factor=DEFAULT_BACKOFF_FACTOR,
                 growth_interval=DEFAULT_GROWTH_INTERVAL,
                 min_scale=MIN_SCALE, max_scale=MAX_SCALE) -> LossScaleState:
    """Post-step scale update (parity: ``LossScaler.update_scale``)."""
    if not state.dynamic:
        return state.replace(found_inf=jnp.asarray(0.0, jnp.float32))
    overflow = state.found_inf > 0
    tracker = jnp.where(overflow, 0, state.growth_tracker + 1)
    grow = tracker >= growth_interval
    scale = jnp.where(
        overflow,
        jnp.maximum(state.loss_scale * backoff_factor, min_scale),
        jnp.where(grow,
                  jnp.minimum(state.loss_scale * growth_factor, max_scale),
                  state.loss_scale))
    tracker = jnp.where(grow, 0, tracker)
    return LossScaleState(scale.astype(jnp.float32),
                          tracker.astype(jnp.int32),
                          jnp.asarray(0.0, jnp.float32),
                          state.dynamic)


class LossScaler:
    """Imperative parity wrapper (reference: ``apex/amp/scaler.py``).

    Holds a :class:`LossScaleState` and mirrors the reference's method
    surface for eager-style training loops.  Inside fully-jitted steps use
    the functional API directly.
    """

    def __init__(self, loss_scale="dynamic", init_scale=None,
                 scale_factor=DEFAULT_GROWTH_FACTOR,
                 scale_window=DEFAULT_GROWTH_INTERVAL,
                 min_loss_scale=MIN_SCALE, max_loss_scale=MAX_SCALE):
        if init_scale is not None:
            loss_scale = "dynamic" if loss_scale == "dynamic" else init_scale
        self.state = init_loss_scale(loss_scale)
        if init_scale is not None and self.state.dynamic:
            self.state = self.state.replace(
                loss_scale=jnp.asarray(init_scale, jnp.float32))
        self._scale_factor = scale_factor
        self._scale_window = scale_window
        self._min_scale = MIN_SCALE if min_loss_scale is None \
            else float(min_loss_scale)
        self._max_scale = MAX_SCALE if max_loss_scale is None \
            else float(max_loss_scale)

    def loss_scale(self) -> float:
        return float(self.state.loss_scale)

    def scale_loss(self, loss):
        return scale_loss_value(loss, self.state)

    def unscale_(self, grads):
        out, self.state = unscale_grads(grads, self.state)
        return out

    def update_scale(self):
        self.state = update_scale(
            self.state, growth_factor=self._scale_factor,
            growth_interval=self._scale_window,
            min_scale=self._min_scale, max_scale=self._max_scale)

    @property
    def found_inf(self):
        return self.state.found_inf

    # checkpoint parity: apex persists these via amp.state_dict()
    def state_dict(self) -> dict:
        return {"loss_scale": float(self.state.loss_scale),
                "unskipped": int(self.state.growth_tracker),
                "dynamic": self.state.dynamic}

    def load_state_dict(self, sd: dict) -> None:
        self.state = LossScaleState(
            loss_scale=jnp.asarray(sd["loss_scale"], jnp.float32),
            growth_tracker=jnp.asarray(sd.get("unskipped", 0), jnp.int32),
            found_inf=jnp.asarray(0.0, jnp.float32),
            dynamic=bool(sd.get("dynamic", True)))
