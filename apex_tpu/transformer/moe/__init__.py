"""Mixture-of-Experts with expert parallelism (beyond reference parity).

SURVEY.md §2.4 marks EP (expert/MoE) "No — out of scope for parity" in
the reference; the task spec lists ``ep`` among the first-class sharding
axes, so the rebuild provides it natively.  The design follows the
GShard/Switch TPU lineage (Lepikhin et al. 2020; Fedus et al. 2021) and
Megatron-core's module naming so Megatron MoE users find the pieces
where they expect them:

* :class:`~apex_tpu.transformer.moe.router.TopKRouter` — top-k softmax
  gating with capacity, load-balancing aux loss, and router z-loss;
* :class:`~apex_tpu.transformer.moe.experts.GroupedMLP` — the local
  experts' FFNs evaluated as ONE batched einsum (expert-major operands
  keep the MXU busy; no per-expert Python loop);
* :class:`~apex_tpu.transformer.moe.layer.MoELayer` — dense
  dispatch/combine einsums (static shapes — no dynamic gather/scatter,
  the canonical TPU MoE formulation) around an ``all_to_all`` over the
  ``expert`` mesh axis.

* :func:`~apex_tpu.transformer.moe.dropless.dropless_moe_ffn` — the
  formulation serving needs (ISSUE 30): no capacity, no dropped token,
  SwiGLU experts + a shared expert, grouped products
  (``jax.lax.ragged_dot``) over the experts that received tokens.

Everything is differentiable through plain jnp ops + ``lax.all_to_all``
(whose transpose is the inverse resharding), so no custom VJPs are
needed; ep=1 degrades to a single-host MoE with zero collectives.
"""
from apex_tpu.transformer.moe.router import (TopKRouter,
                                             load_balancing_loss, sinkhorn)
from apex_tpu.transformer.moe.experts import GroupedMLP
from apex_tpu.transformer.moe.dropless import (dropless_moe_ffn,
                                               route_group_limited,
                                               route_top_k)
from apex_tpu.transformer.moe.layer import (MoELayer, reduce_moe_grads,
                                            resolve_dispatch_mode)

__all__ = ["TopKRouter", "GroupedMLP", "MoELayer", "load_balancing_loss",
           "reduce_moe_grads", "resolve_dispatch_mode", "sinkhorn",
           "dropless_moe_ffn", "route_top_k", "route_group_limited"]
