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
serving; every op of the path above is differentiable, so training can
call it too — NOT the ``held=`` path below, whose loop of dynamic length
has no reverse-mode derivative (forward only).

**The experts held here (ISSUE 34).**  Under expert parallelism a chip
holds a contiguous share of a layer's experts.  ``held=(first, count)``
tells the layer so: the router still scores ALL experts (its weight keeps
every row), the stacks are ``[count, ...]``, and an assignment to an
expert that is not held is computed by nobody here and adds nothing — the
result is this chip's PART of the routed sum (plus the shared expert,
which every chip holds), and the parts of all the shares add up to the
uncut layer.  The cost follows the assignments that LAND: they sort ahead
of the others and are consumed in fixed blocks of rows under a dynamic
trip count, so no ``[tokens x k, hidden]`` array of rows no held expert
takes is ever made, and however many land — all of them on one expert —
none is dropped.  ``held=None`` is the path above, text for text.

Two routers: :func:`route_top_k` (softmax, top-k renormalised) and
:func:`route_group_limited` (sigmoid scores, the best groups kept, top-k
among their experts, optionally chosen under a selection bias);
``router=`` takes any ``(x, router_w) -> (weights, experts)``.
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["route_top_k", "route_group_limited", "dropless_moe_ffn",
           "swiglu", "fold_stats", "HELD_ROW_BLOCK"]

#: rows of landed assignments one trip of the held-experts loop takes
HELD_ROW_BLOCK = 512


def _gated(gate, up: Callable, limit: Optional[float]):
    """``silu(gate) * up()``, the up projection made after the gate's
    activation; ``limit`` (``swiglu_limit``) clamps the gate from
    above and the up projection both ways, and ``None`` traces nothing."""
    if limit is not None:
        gate = jnp.minimum(gate, limit)
    act = jax.nn.silu(gate)
    up = up()
    if limit is not None:
        up = jnp.clip(up, -limit, limit)
    return act * up


def swiglu(x, w_gate, w_up, w_down, limit: Optional[float] = None):
    """SwiGLU over ``[out, in]`` weights (the TP layers' layout)."""
    return jnp.matmul(_gated(jnp.matmul(x, w_gate.T),
                             lambda: jnp.matmul(x, w_up.T), limit), w_down.T)


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


def route_group_limited(x, router_w, top_k: int, scale: float, *,
                        n_group: int, topk_group: int, bias=None):
    """Group-limited sigmoid router: ``(weights [T, k] float32, experts
    [T, k] int32)``.

    ``sigma = sigmoid(x W^T)`` in float32 over ALL experts, which lie in
    ``n_group`` groups of consecutive experts; a group's score is the sum
    of its two largest ``sigma``; the ``topk_group`` best groups are
    kept; the ``top_k`` largest ``sigma`` among their experts are the
    token's experts, weighted ``scale * sigma_e / (sum of the chosen +
    1e-20)``.

    ``bias [E]`` (optional) is a SELECTION bias (``topk_method:
    noaux_tc``, ``e_score_correction_bias``): groups and experts are
    CHOSEN by ``sigma + bias``, and the chosen are still weighted by their
    ``sigma``.  ``None`` traces none of it."""
    n_exp = router_w.shape[0]
    logits = jnp.matmul(x, router_w.T, preferred_element_type=jnp.float32)
    sig = jax.nn.sigmoid(logits.astype(jnp.float32))            # [T, E]
    choose = sig if bias is None else sig + bias.astype(jnp.float32)
    grouped = choose.reshape(-1, n_group, n_exp // n_group)
    group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
    _, keep = jax.lax.top_k(group_score, topk_group)            # [T, g]
    kept = jnp.any(keep[..., None] == jnp.arange(n_group), axis=1)
    # sigma > 0: never chosen; a biased score may lie below -1
    masked = jnp.where(jnp.repeat(kept, n_exp // n_group, axis=-1),
                       choose, -1.0 if bias is None else -jnp.inf)
    top_s, top_e = jax.lax.top_k(masked, top_k)
    if bias is not None:
        top_s = jnp.take_along_axis(sig, top_e, axis=-1)
    weights = scale * top_s / (jnp.sum(top_s, axis=-1, keepdims=True)
                               + 1e-20)
    return weights, top_e.astype(jnp.int32)


def _held_products(x, weights, experts, w_gate, w_up, w_down,
                   held: Tuple[int, int], valid, limit=None):
    """The routed part of the experts ``[first, first + count)`` — see the
    module docstring — as ``(y [T, hidden] float32, group_sizes
    [count])``."""
    first, count = held
    t, hidden = x.shape
    top_k = experts.shape[1]
    n = t * top_k
    block = min(HELD_ROW_BLOCK, n)
    with jax.named_scope("apex_moe_sort"):
        local = experts.reshape(-1) - first
        landed = (local >= 0) & (local < count)
        if valid is not None:
            landed = landed & jnp.repeat(valid, top_k)
        # what lands sorts by held expert, ahead of everything else
        key = jnp.where(landed, local, count)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        group_sizes = jnp.bincount(key, length=count + 1)[:count].astype(
            jnp.int32)
        ends = jnp.cumsum(group_sizes)
        n_landed = ends[-1]
        # a whole number of blocks to slice from, whatever n
        pad = -n % block
        order = jnp.concatenate([order, jnp.zeros((pad,), jnp.int32)])
        w_flat = weights.reshape(-1)

    def trip(i, y):
        lo = i * block
        ids = jax.lax.dynamic_slice(order, (lo,), (block,))
        live = lo + jnp.arange(block, dtype=jnp.int32) < n_landed
        rows = ids // top_k
        xs = jnp.take(x, rows, axis=0)                  # [block, hidden]
        # the block's rows of each held expert's group
        sizes = jnp.clip(ends, lo, lo + block) \
            - jnp.clip(ends - group_sizes, lo, lo + block)
        act = _gated(jax.lax.ragged_dot(xs, w_gate, sizes),
                     lambda: jax.lax.ragged_dot(xs, w_up, sizes), limit)
        ys = jax.lax.ragged_dot(act.astype(x.dtype), w_down, sizes)
        w = jnp.where(live, jnp.take(w_flat, ids), 0.0)
        # rows past the landed ones belong to no group: take none of them
        ys = jnp.where(live[:, None], ys.astype(jnp.float32), 0.0)
        return y.at[rows].add(ys * w[:, None])

    with jax.named_scope("apex_moe_experts"):
        y = jax.lax.fori_loop(0, (n_landed + block - 1) // block, trip,
                              jnp.zeros((t, hidden), jnp.float32))
    return y, group_sizes


def dropless_moe_ffn(x, router_w, w_gate, w_up, w_down, *, top_k: int,
                     scale: float = 1.0, shared: Optional[dict] = None,
                     valid=None, held: Optional[Tuple[int, int]] = None,
                     router: Optional[Callable] = None,
                     limit: Optional[float] = None):
    """``x [T, hidden]`` -> ``(y [T, hidden], stats)``.

    ``router_w [E, hidden]``; ``w_gate``/``w_up`` ``[E, hidden, ffn]`` and
    ``w_down [E, ffn, hidden]`` expert-major stacks.  ``shared`` (optional)
    holds a shared expert's ``gate_proj``/``up_proj``/``down_proj``
    ``{"weight": [out, in]}`` — added ungated.  ``valid [T]`` (bool,
    optional) marks the rows that carry a token: padding rows are routed
    nowhere (they cost no expert work, hit no expert and return only the
    shared expert's output).

    ``router`` (optional) is ``(x, router_w) -> (weights [T, k] float32,
    experts [T, k] int32)``; left out, :func:`route_top_k`.  ``held``
    (optional) is ``(first, count)``, the experts whose stacks ``w_gate``
    / ``w_up`` / ``w_down`` ``[count, ...]`` are: ``y`` is then their part
    of the routed sum (module docstring) plus the shared expert.
    ``limit`` (optional) clamps every SwiGLU's gate and up projection,
    the shared expert's too (``swiglu_limit``).

    ``stats`` are int32 scalars computed on the device: ``assignments``
    (valid tokens x ``top_k``; with ``held``, those that LAND on a held
    expert), ``experts_hit`` (experts — of those held — with at least one
    token), ``load_max`` (the busiest of them's tokens)."""
    t, hidden = x.shape
    n_exp = router_w.shape[0]
    with jax.named_scope("apex_moe_route"):
        weights, experts = (
            route_top_k(x, router_w, top_k, scale) if router is None
            else router(x, router_w))
    if held is not None:
        y, group_sizes = _held_products(x, weights, experts, w_gate, w_up,
                                        w_down, held, valid, limit)
        y = y.astype(x.dtype)
        return _finish(x, y, shared, group_sizes, limit)
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
        act = _gated(jax.lax.ragged_dot(xs, w_gate, group_sizes),
                     lambda: jax.lax.ragged_dot(xs, w_up, group_sizes),
                     limit)
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
    return _finish(x, y, shared, group_sizes, limit)


def _finish(x, y, shared, group_sizes, limit=None):
    """The shared expert on top of the routed sum, and the step's stats."""
    if shared is not None:
        with jax.named_scope("apex_moe_shared"):
            y = y + swiglu(x, shared["gate_proj"]["weight"],
                           shared["up_proj"]["weight"],
                           shared["down_proj"]["weight"], limit)
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
