"""MoE layer: dense dispatch/combine + all_to_all expert parallelism.

Beyond reference parity (SURVEY.md §2.4 marks EP "No"); design is the
canonical TPU MoE of GShard (Lepikhin et al. 2020) / Switch (Fedus et
al. 2021), with Megatron-core's layer naming.

Why dense einsum dispatch and not gather/scatter: XLA wants static
shapes, and the MXU wants matmuls.  Routing decisions become a one-hot
``dispatch`` tensor ``[tokens, E, capacity]``; moving tokens into the
expert-major buffer is then ``einsum('sec,sh->ech')`` — a matmul with a
0/1 operand that XLA tiles onto the MXU — and returning them is the
transpose einsum weighted by the gates.  No dynamic indexing anywhere,
so the whole layer jits once regardless of routing.

Expert parallelism: with ``E`` global experts over ``ep`` ranks, each
rank dispatches its local tokens into the GLOBAL ``[E, C, h]`` buffer,
then one ``lax.all_to_all`` over the ``expert`` mesh axis reshards it so
each rank holds its ``E/ep`` local experts' slots from EVERY source
rank (``[E_local, ep*C, h]``).  After the expert FFNs, the inverse
``all_to_all`` routes tokens home.  Exactly two collectives per layer,
both riding ICI; ``lax.all_to_all`` is differentiable so the backward
is the mirrored pair automatically.

Capacity per expert defaults to ``ceil(capacity_factor * S * k / E)``
rounded up to a multiple of 8 (TPU lane-friendly; the pad slots carry
zero weight through both einsums).
"""
from __future__ import annotations

import math
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu.transformer.moe.experts import GroupedMLP, expert_init
from apex_tpu.transformer.moe.router import TopKRouter
from apex_tpu.transformer.parallel_state import (DATA_AXIS, EXPERT_AXIS,
                                                 TENSOR_AXIS)
from apex_tpu.transformer.tensor_parallel import mappings
from apex_tpu.utils import round_up

__all__ = ["MoELayer", "compute_dispatch_and_combine",
           "compute_dispatch_indices", "reduce_moe_grads"]


def reduce_moe_grads(grads, *, dense_axes=None, expert_axes=None):
    """Average an MoE layer's grad tree over each param's replica axes.

    MoE splits the data-parallel reduction (the analog of Megatron's
    allreduce over _DATA_MODULO_EXPERT_PARALLEL_GROUP vs the full DP
    group):

    * subtrees under an ``experts`` key (the GroupedMLP weights) are
      replicated along ``data`` only — the ``expert`` axis holds
      *different* experts — so they reduce over ``expert_axes``;
    * everything else (router + any dense params reached through the
      same tree) is replicated along both, reducing over ``dense_axes``.

    :class:`MoELayer` with ``tensor_parallel_size=tp`` +
    ``sequence_parallel=True`` needs NO tensor-axis reduction here: the
    in-layer gather makes every TP rank route the same tokens (router
    grads replica-consistent) and the expert ffn shards are rank-local.
    Only when running a tp=1 MoELayer directly on sequence-sharded
    activations does the MoE region act data-parallel along the tensor
    axis — append that axis to BOTH tuples there (the same obligation
    Megatron's ``allreduce_sequence_parallel_gradients`` covers for SP
    LayerNorm params).

    With the default ``None`` axes, both tuples are resolved from the
    live mesh: dense = ``(data, expert[, context])``, expert =
    ``(data[, context])`` — the ``context`` axis joins both whenever
    context parallelism is active, because each cp rank routes a
    different sequence shard through replicated weights (the same
    dp-cp reduction Megatron applies to all non-attention params).

    Uses ``pmean`` (grads averaged, matching the DDP predivide
    convention elsewhere in the package).  Expert leaves additionally
    divide by the expert-parallel world size: the loss is averaged over
    ``dense_axes`` shards but an expert weight has replicas only along
    ``expert_axes``, so a bare pmean normalizes by the smaller replica
    count and returns ep x the true gradient — expert params would
    silently train at ``lr * ep`` relative to dense params (Megatron
    applies the same 1/ep expert-grad scaling; caught by the r4
    multichip equivalence dryrun, which compares against a dense ep=1
    replay).
    """
    import jax.tree_util as jtu

    if dense_axes is None or expert_axes is None:
        from apex_tpu.transformer import parallel_state as ps
        live = ps.model_parallel_is_initialized()
        if dense_axes is None:
            # expert axis always included (pmean over a size-1 axis is
            # identity); context joins when active
            dense_axes = (ps.get_data_parallel_group(
                with_expert_parallel=True,
                with_context_parallel=(
                    ps.get_context_parallel_world_size() > 1))
                if live else (DATA_AXIS, EXPERT_AXIS))
        if expert_axes is None:
            expert_axes = (ps.get_expert_param_grad_axes() if live
                           else (DATA_AXIS,))

    from apex_tpu.parallel.distributed import _axes_size as world

    def f(path, g):
        names = {p.key for p in path if isinstance(p, jtu.DictKey)}
        if "experts" in names:
            if expert_axes:
                g = jax.lax.pmean(g, expert_axes)
            # pmean(expert_axes) * |expert| / |dense| == psum / |dense|:
            # normalize by the LOSS replica count, not the (smaller)
            # expert replica count
            scale = (world(expert_axes) if expert_axes else 1) / \
                (world(dense_axes) if dense_axes else 1)
            return g * scale if scale != 1.0 else g
        return jax.lax.pmean(g, dense_axes) if dense_axes else g
    return jtu.tree_map_with_path(f, grads)


def _slot_positions(expert_index, num_experts: int):
    """Shared slot-assignment prelude for BOTH dispatch forms: GShard
    priority — (k-slot, token) order, one cumsum over the k-major
    flattened one-hot.  Returns ``(onehot [S,k,E], pos [S,k,E])`` where
    ``pos`` counts the higher-priority claims on each expert.  Keeping
    this in one place is what makes the one-hot and gather dispatch
    modes provably route identically."""
    s, k = expert_index.shape
    onehot = jax.nn.one_hot(expert_index, num_experts,
                            dtype=jnp.float32)          # [S, k, E]
    km = onehot.transpose(1, 0, 2).reshape(k * s, num_experts)
    pos = jnp.cumsum(km, axis=0) - km                    # slots before me
    pos = pos.reshape(k, s, num_experts).transpose(1, 0, 2)  # [S, k, E]
    return onehot, pos


def compute_dispatch_and_combine(gates, expert_index, num_experts: int,
                                 capacity: int):
    """Turn top-k routing decisions into dense dispatch/combine tensors.

    ``gates``/``expert_index``: [S, k].  Returns ``(dispatch, combine)``
    with shapes [S, E, C]: ``dispatch`` is 0/1 (token s occupies slot c
    of expert e), ``combine = gate * dispatch``.

    Slot assignment is GShard's: priority order is (k-slot, token) — all
    top-1 choices beat all top-2 choices, ties broken by token position —
    computed with ONE cumsum over the k-major flattened one-hot, no loop
    over experts.  Tokens past an expert's capacity are dropped (zero
    rows in both tensors).
    """
    onehot, pos = _slot_positions(expert_index, num_experts)
    within = onehot * (pos < capacity)                   # kept choices
    # An expert appears at most once in a token's top-k, so the k axis
    # collapses to [S, E] before the capacity one-hot — the biggest
    # intermediate is [S, E, C], never [S, k, E, C].
    kept = within.sum(axis=1)                            # [S, E] in {0,1}
    pos_se = (pos * within).sum(axis=1)                  # [S, E]
    gate_se = (gates[..., None] * within).sum(axis=1)    # [S, E]
    dispatch = kept[..., None] * jax.nn.one_hot(
        pos_se.astype(jnp.int32), capacity, dtype=jnp.float32)
    combine = gate_se[..., None] * dispatch
    return dispatch, combine


def compute_dispatch_indices(gates, expert_index, num_experts: int,
                             capacity: int):
    """Index-form routing: the SAME slot assignment as
    :func:`compute_dispatch_and_combine` (GShard priority, identical
    drops), emitted as gather indices instead of [S, E, C] one-hots.

    The dense formulation's dispatch/combine einsums do
    ``2*S*E*C*h`` MACs each against a 0/1 operand — linear in E at
    fixed per-expert capacity, which is exactly what the bench's
    ``moe_dispatch_sweep`` shows degrading at Switch-scale E.  The
    index form moves only the O(E*C*h) rows that exist.

    Returns:

    * ``slot_token`` [E, C] int32 — token id feeding each slot, or S
      (a sentinel one past the last token) for empty slots;
    * ``token_slot`` [S, k] int32 — flat slot ``e*C + c`` of each
      routing choice, or E*C (sentinel) when dropped;
    * ``token_gate`` [S, k] — the gate, 0 when dropped.
    """
    s, k = gates.shape
    onehot, pos = _slot_positions(expert_index, num_experts)
    kept = ((onehot * (pos < capacity)).sum(-1) > 0)     # [S, k] bool
    c_sk = (pos * onehot).sum(-1).astype(jnp.int32)      # [S, k]
    flat = expert_index.astype(jnp.int32) * capacity + c_sk
    token_slot = jnp.where(kept, flat, num_experts * capacity)
    token_gate = gates * kept
    tok_ids = jnp.broadcast_to(
        jnp.arange(s, dtype=jnp.int32)[:, None], (s, k))
    # kept slots are unique, so the scatter has no collisions except at
    # the sentinel row (sliced off)
    slot_token = jnp.full((num_experts * capacity + 1,), s, jnp.int32) \
        .at[token_slot.reshape(-1)].set(tok_ids.reshape(-1))
    return (slot_token[:num_experts * capacity].reshape(
        num_experts, capacity), token_slot, token_gate)


#: auto-dispatch crossover (``dispatch_mode="auto"``): gather from this
#: many experts, one-hot below.  Pinned at 64, cross-checked against the
#: r5/r6 capture record (PERF.md "MoE auto-dispatch policy" has the full
#: numbers; the policy is also pinned literally in
#: ``tests/L0/run_transformer/test_moe.py``):
#:  * r5 on-chip ONE-HOT E-sweep ([8192 tok, h 1024, ffn 4096], top-2;
#:    ``r5_watch_capture_001.json :: moe_dispatch_sweep``): 7722 us at
#:    E=8, 3567 us at E=32, 7155 us at E=64 — total expert GEMM work is
#:    E-independent at fixed top-k, so the ~2x jump from 32 to 64 is
#:    the dispatch side degrading: the measured one-hot inflection
#:    lands the crossover in (32, 64];
#:  * the CPU-mesh sweep (E in {4..128}, tokens=256, h=64): gather won
#:    at EVERY E (1.1-2.3x) — an upper bound on where gather can win,
#:    since interpret-mode lacks the MXU advantage that makes the dense
#:    [S,E,C] one-hot einsums cheap at small E on TPU, so it cannot
#:    justify dropping the threshold below the measured inflection;
#:  * r6 added no on-chip gather timings (the r5 gather legs collapsed
#:    to ``us_gather: 0.0`` inside the subtracted dispatch round trip
#:    and were scrubbed; r6 chip
#:    time went to the ZeRO captures) — a clean gather sweep could
#:    still tighten 64 toward 33, but cannot move it above 64.
_AUTO_GATHER_MIN_E = 64


def resolve_dispatch_mode(dispatch_mode: str, num_experts: int,
                          tokens: int, capacity: int,
                          hidden: int) -> str:
    """Resolve ``"auto"`` to a concrete dispatch mode from the shape.

    The decision variable is the dense one-hot volume ``S*E*C*h`` (what
    the GShard formulation einsums through) against the gather path's
    ``(S + E*C)*h`` row movement; at the capacity formula's
    ``C ~ f*S*k/E`` the ratio reduces to growing with E, so the policy
    is an expert-count threshold (``_AUTO_GATHER_MIN_E`` — see its
    provenance note).  ``tokens``/``capacity``/``hidden`` are accepted
    so a measured on-chip crossover can refine the policy without
    changing call sites."""
    if dispatch_mode != "auto":
        return dispatch_mode
    del tokens, capacity, hidden   # reserved for the on-chip refinement
    return "gather" if num_experts >= _AUTO_GATHER_MIN_E else "onehot"


class MoELayer(nn.Module):
    """Sparsely-activated FFN (Megatron-core: ``MoELayer``).

    Call with ``x`` of shape ``[..., hidden]``; leading dims are
    flattened into a token axis.  Returns ``(y, aux)``: the LOSS terms
    ``aux["load_balancing_loss"]`` / ``aux["z_loss"]`` (scale by your
    coefficients and add to the task loss; under data/expert
    parallelism, mean them over those axes), plus stop-gradiented
    DIAGNOSTICS for the metrics subsystem — ``aux["expert_load"]``
    ([E] capacity-fill fractions) and ``aux["dropped_fraction"]``
    (scalar) — which must NOT be added to the loss.

    Parallel composition (all static config; >1 requires running inside
    ``shard_map`` with the named axis bound):

    * ``expert_parallel_size`` — experts shard over ``expert_axis``;
      token exchange is the ``all_to_all`` round trip.
    * ``tensor_parallel_size`` — each expert's FFN shards its ffn dim
      over ``tensor_axis`` (the Column->Row parallel pattern collapsed
      into the expert einsums, Megatron's MoE+TP): the router and
      dispatch replicate, each rank computes a partial output with its
      ``ffn/tp`` slice, and ONE psum (or reduce-scatter under SP)
      finishes the layer.  Experts are bias-free under TP (a per-rank
      output bias would be summed tp times), the Megatron/Mixtral
      convention.
    * ``sequence_parallel`` — input arrives sequence-sharded on dim 0
      (Megatron ``[s/tp, b, h]`` layout); it is all-gathered over
      ``tensor_axis`` so every TP rank routes the SAME token set (router
      grads stay replica-consistent) and the output is reduce-scattered
      back.  Exactly the ColumnParallelLinear-under-SP collective pair.

    With all sizes 1 (default) the layer is a plain single-shard MoE —
    identical math, zero collectives.
    """
    num_experts: int
    hidden_size: int
    ffn_hidden_size: int
    top_k: int = 2
    capacity_factor: float = 1.25
    capacity: Optional[int] = None            # override the formula
    expert_parallel_size: int = 1
    expert_axis: str = EXPERT_AXIS
    tensor_parallel_size: int = 1
    tensor_axis: str = TENSOR_AXIS
    sequence_parallel: bool = False
    activation: Callable = nn.gelu
    params_dtype: Any = jnp.float32
    jitter_eps: float = 0.0
    load_balancing_type: str = "aux_loss"     # | "sinkhorn" | "none"
    # "onehot": GShard dense dispatch/combine einsums (MXU-friendly,
    # O(S*E*C*h) MACs — best at small E).  "gather": index-based
    # dispatch (same routing, same drops) moving only O(E*C*h) rows —
    # wins at Switch-scale E; measured crossover in PERF.md /
    # moe_dispatch_sweep.  "auto" (the default) picks from the shape
    # via :func:`resolve_dispatch_mode` — an expert-count threshold
    # pinned at the r5-measured one-hot inflection (see
    # ``_AUTO_GATHER_MIN_E``'s provenance note); both modes share one
    # slot-assignment rule, so the choice changes data movement only,
    # not routing.
    dispatch_mode: str = "auto"               # | "onehot" | "gather"

    def _expert_init(self, init: Callable) -> Callable:
        """Fold the expert-axis and tensor-axis ranks into the init key
        so each rank draws DIFFERENT local experts / ffn shards (same
        trick as the TP layers' shard init — reference inits the full
        master weight then scatters)."""
        ep, tp = self.expert_parallel_size, self.tensor_parallel_size
        if ep == 1 and tp == 1:
            return init

        def f(key, shape, dtype):
            if ep > 1:
                key = jax.random.fold_in(
                    key, jax.lax.axis_index(self.expert_axis))
            if tp > 1:
                key = jax.random.fold_in(
                    key, jax.lax.axis_index(self.tensor_axis) + 1)
            return init(key, shape, dtype)
        return f

    @nn.compact
    def __call__(self, x, *, deterministic: bool = True):
        ep, tp = self.expert_parallel_size, self.tensor_parallel_size
        if self.num_experts % ep:
            raise ValueError(f"num_experts ({self.num_experts}) not "
                             f"divisible by expert_parallel_size ({ep})")
        if self.ffn_hidden_size % tp:
            raise ValueError(f"ffn_hidden_size ({self.ffn_hidden_size}) "
                             f"not divisible by tensor_parallel_size ({tp})")
        if self.dispatch_mode not in ("auto", "onehot", "gather"):
            raise ValueError(
                f"dispatch_mode must be 'auto', 'onehot' or 'gather', "
                f"got {self.dispatch_mode!r}")
        if self.sequence_parallel:
            # gather the sequence shards so all TP ranks route the same
            # tokens.  tensor_parallel_output_grad=False: by the time
            # the cotangent reaches this gather it is already FULL and
            # replicated on every rank (the router path is replicated
            # and the dispatch path was psummed by copy_to's backward
            # around the expert MLP below), so the backward must SLICE,
            # not reduce-scatter — a sum here would count each
            # contribution tp times.
            x = mappings.gather_from_sequence_parallel_region(
                x, self.tensor_axis, tensor_parallel_output_grad=False)
        lead, h = x.shape[:-1], x.shape[-1]
        tokens = x.reshape(-1, h)
        s = tokens.shape[0]
        cap = self.capacity if self.capacity is not None else round_up(
            max(1, math.ceil(self.capacity_factor * s * self.top_k /
                             self.num_experts)), 8)

        gates, expert_index, aux = TopKRouter(
            num_experts=self.num_experts, top_k=self.top_k,
            jitter_eps=self.jitter_eps,
            load_balancing_type=self.load_balancing_type, name="router")(
                tokens, deterministic=deterministic)
        dt = tokens.dtype
        gather = resolve_dispatch_mode(
            self.dispatch_mode, self.num_experts, s, cap, h) == "gather"
        if gather:
            slot_token, token_slot, token_gate = compute_dispatch_indices(
                gates, expert_index, self.num_experts, cap)
            # one zero pad row: empty slots (sentinel index s) read it,
            # and its gradient is discarded by the slice in take's VJP
            pad = jnp.concatenate([tokens, jnp.zeros((1, h), dt)])
            buf = jnp.take(pad, slot_token, axis=0)          # [E, C, h]
            slots = jax.lax.stop_gradient(
                (slot_token < s).sum(axis=1).astype(jnp.float32))
        else:
            dispatch, combine = compute_dispatch_and_combine(
                gates, expert_index, self.num_experts, cap)
            slots = jax.lax.stop_gradient(dispatch.sum(axis=(0, 2)))
        # routing statistics for the metrics/logging subsystem
        # (Megatron-core logs the same per-expert load + drop counters);
        # stop_gradient: diagnostics must not leak into the loss
        aux["expert_load"] = slots / cap          # fill fraction per expert
        aux["dropped_fraction"] = 1.0 - slots.sum() / (s * self.top_k)

        if not gather:
            buf = jnp.einsum("sec,sh->ech", dispatch.astype(dt), tokens)
        e_local = self.num_experts // ep
        if ep > 1:
            # [E, C, h] -> rows grouped by destination rank -> exchange ->
            # [E_local, ep*C, h]: my experts' slots from every source rank
            buf = buf.reshape(ep, e_local, cap, h)
            buf = jax.lax.all_to_all(buf, self.expert_axis,
                                     split_axis=0, concat_axis=0)
            buf = buf.transpose(1, 0, 2, 3).reshape(e_local, ep * cap, h)
        if tp > 1:
            # The TP boundary wraps ONLY the expert MLP (Megatron: each
            # expert is a Column->Row parallel pair).  copy_to: identity
            # forward / psum backward — the rank-partial d(buf) from the
            # ffn shards must be summed, while the replicated router/
            # dispatch paths outside this region keep their replicated
            # (already-full) cotangents untouched.
            buf = mappings.copy_to_tensor_model_parallel_region(
                buf, self.tensor_axis)
        expert_out = GroupedMLP(
            num_local_experts=e_local, hidden_size=h,
            ffn_hidden_size=self.ffn_hidden_size // tp,
            activation=self.activation, use_bias=(tp == 1),
            params_dtype=self.params_dtype,
            init_method=self._expert_init(expert_init),
            name="experts")(buf)
        if tp > 1:
            # psum the ffn-shard partials BEFORE combine (Megatron: the
            # per-expert RowParallel allreduce).  Reducing after combine
            # would move fewer bytes ([S,h] vs [E,C,h] ~ k*cf larger)
            # but would leave the router's gate grads rank-partial —
            # each rank's combine cotangent would see only its local
            # partial expert output — silently desyncing router
            # replicas; here combine sees the FULL expert output, so
            # router grads are replica-consistent by construction.
            expert_out = mappings.reduce_from_tensor_model_parallel_region(
                expert_out, self.tensor_axis)
        if ep > 1:
            expert_out = expert_out.reshape(e_local, ep, cap, h)
            expert_out = expert_out.transpose(1, 0, 2, 3)
            expert_out = jax.lax.all_to_all(expert_out, self.expert_axis,
                                            split_axis=0, concat_axis=0)
            expert_out = expert_out.reshape(self.num_experts, cap, h)
        if gather:
            out_pad = jnp.concatenate([
                expert_out.reshape(self.num_experts * cap, h),
                jnp.zeros((1, h), expert_out.dtype)])
            picked = jnp.take(out_pad, token_slot, axis=0)   # [S, k, h]
            y = jnp.einsum("skh,sk->sh", picked,
                           token_gate.astype(picked.dtype))
        else:
            y = jnp.einsum("sec,ech->sh", combine.astype(dt), expert_out)
        y = y.reshape(*lead, h)
        if self.sequence_parallel:
            # output is already full (tensor psum above): just slice my
            # sequence shard back out; backward all-gathers
            y = mappings.scatter_to_sequence_parallel_region(
                y, self.tensor_axis)
        return y, aux
