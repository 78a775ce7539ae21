"""A top-k expert FFN that drops no token (ISSUE 30).

:class:`~apex_tpu.transformer.moe.layer.MoELayer` is the GShard
formulation: capacity slots per expert, static dispatch/combine einsums,
tokens past capacity dropped.  This is the other formulation — the one a
served mixture-of-experts decoder needs, where an answer must not depend
on which other requests share the batch:

    route -> sort the ``tokens x k`` assignments by expert -> grouped
    products over the experts that received tokens -> weighted combine
    (+ the shared expert)

There is no capacity anywhere, so nothing can be dropped whatever the
imbalance (every token on the same ``k`` experts is just one long group).
The grouped products are :func:`jax.lax.ragged_dot` over the expert-major
stacked weights ``[experts, in, out]``; on the TPU XLA lowers it to a
grouped-matmul custom call (``%ragged-dot-*`` in a trace) that visits
only the (row tile, expert) pairs that hold rows — in decode, where a
step carries a few hundred assignments, it therefore reads the experts
HIT and not the experts held.  Everything else is plain XLA.

Experts are SwiGLU (``down(silu(gate(x)) * up(x))``) and the routing
weight is applied to the expert's OUTPUT.  Forward only is exercised by
serving; every op used is differentiable, so training can call it too.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

__all__ = ["route_top_k", "dropless_moe_ffn", "swiglu", "fold_stats"]


def swiglu(x, w_gate, w_up, w_down):
    """SwiGLU over ``[out, in]`` weights (the TP layers' layout)."""
    return jnp.matmul(jax.nn.silu(jnp.matmul(x, w_gate.T))
                      * jnp.matmul(x, w_up.T), w_down.T)


def route_top_k(x, router_w, top_k: int, scale: float):
    """Softmax router: ``(weights [T, k] float32, experts [T, k] int32)``.

    The probabilities are a float32 softmax over ALL experts; the ``k``
    largest are renormalised to sum to one and multiplied by ``scale``.
    No selection bias, no soft cap on the logits."""
    logits = jnp.matmul(x, router_w.T, preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, top_k)
    weights = scale * top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return weights, top_e.astype(jnp.int32)


def dropless_moe_ffn(x, router_w, w_gate, w_up, w_down, *, top_k: int,
                     scale: float = 1.0, shared: Optional[dict] = None,
                     valid=None):
    """``x [T, hidden]`` -> ``(y [T, hidden], stats)``.

    ``router_w [E, hidden]``; ``w_gate``/``w_up`` ``[E, hidden, ffn]`` and
    ``w_down [E, ffn, hidden]`` expert-major stacks.  ``shared`` (optional)
    holds a shared expert's ``gate_proj``/``up_proj``/``down_proj``
    ``{"weight": [out, in]}`` — added ungated.  ``valid [T]`` (bool,
    optional) marks the rows that carry a token: padding rows are routed
    nowhere (they cost no expert work, hit no expert and return only the
    shared expert's output).

    ``stats`` are int32 scalars computed on the device: ``assignments``
    (valid tokens x ``top_k``), ``experts_hit`` (experts with at least one
    token), ``load_max`` (the busiest expert's tokens)."""
    t, hidden = x.shape
    n_exp = router_w.shape[0]
    with jax.named_scope("apex_moe_route"):
        weights, experts = route_top_k(x, router_w, top_k, scale)
    with jax.named_scope("apex_moe_sort"):
        flat = experts.reshape(-1)
        if valid is not None:
            # a padding row's assignments sort behind every expert's group
            flat = jnp.where(jnp.repeat(valid, top_k), flat, n_exp)
        order = jnp.argsort(flat, stable=True)        # assignment ids
        group_sizes = jnp.bincount(flat, length=n_exp + 1)[:n_exp].astype(
            jnp.int32)
        xs = jnp.take(x, order // top_k, axis=0)      # [T*k, hidden]
    with jax.named_scope("apex_moe_experts"):
        act = jax.nn.silu(jax.lax.ragged_dot(xs, w_gate, group_sizes)) \
            * jax.lax.ragged_dot(xs, w_up, group_sizes)
        ys = jax.lax.ragged_dot(act.astype(x.dtype), w_down, group_sizes)
    with jax.named_scope("apex_moe_combine"):
        # back to token order: row order[i] of the flat assignments is ys[i]
        inverse = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=order.dtype))
        y = jnp.take(ys, inverse, axis=0).reshape(
            t, top_k, hidden).astype(jnp.float32)
        if valid is not None:
            # a padding row's assignments lie behind the last group, where
            # the grouped product writes nothing: take none of it
            y = jnp.where(valid[:, None, None], y, 0.0)
        y = jnp.sum(y * weights[..., None], axis=1).astype(x.dtype)
    if shared is not None:
        with jax.named_scope("apex_moe_shared"):
            y = y + swiglu(x, shared["gate_proj"]["weight"],
                           shared["up_proj"]["weight"],
                           shared["down_proj"]["weight"])
    stats = {"assignments": jnp.sum(group_sizes),
             "experts_hit": jnp.sum((group_sizes > 0).astype(jnp.int32)),
             "load_max": jnp.max(group_sizes)}
    return y, stats


def fold_stats(acc, stats):
    """Fold one expert layer's ``stats`` into a step's: assignments and
    experts hit add up over the layers, the busiest expert is a max.
    ``None`` on either side (no expert layer yet, a dense layer) passes
    the other through."""
    if stats is None or acc is None:
        return acc if stats is None else dict(stats)
    return {"assignments": acc["assignments"] + stats["assignments"],
            "experts_hit": acc["experts_hit"] + stats["experts_hit"],
            "load_max": jnp.maximum(acc["load_max"], stats["load_max"])}
