"""Plain reference of the ``bert-large-phase1`` configuration: masked-LM
loss, its gradient, and LAMB, for the first steps of training.

Model (Devlin et al. 2018, section 3, in Megatron-LM's pre-LN arrangement,
see ``transformer.py``): token + position embeddings (no segment input is
fed, so no segment embedding is added), a bidirectional stack, a final
LayerNorm, the MLM head (dense, GELU, LayerNorm) and logits through the
transposed token embedding, with no output bias.  The loss is the mean
cross-entropy over the positions that carry a label (label >= 0).

Optimizer (You et al. 2019, Algorithm 2, as NVIDIA's FusedLAMB states it):
the gradient is first scaled so that its GLOBAL norm is at most
``max_grad_norm``; Adam moments with bias correction; the update direction
``u = m_hat / (sqrt(v_hat) + eps) + weight_decay * w``; per TENSOR the
trust ratio ``|w| / |u|`` (1 where either norm is 0); ``w -= lr * ratio *
u``.  Weight decay applies to every tensor, biases and gains included.

Weights: ``{"wte", "wpe", "lnf_g", "lnf_b", "head_w" [out, in], "head_b",
"head_ln_g", "head_ln_b", "layers": {<LAYER_KEYS>: [L, ...]}}``, float32.
A "tensor" of ``layers`` is one layer's slice, so norms of those leaves are
vectors of length L.  This file imports nothing of the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import transformer as T


def loss_sum(weights, tokens, labels, heads: int, quant=None):
    """Sum of cross-entropies over labelled positions of ``tokens`` [b, s]."""
    s = tokens.shape[1]
    x = weights["wte"][tokens] + weights["wpe"][:s]
    x = T.stack(x, weights["layers"], heads, False, quant)
    x = T.layer_norm(x, weights["lnf_g"], weights["lnf_b"])
    t = T.gelu(T.matmul(x, weights["head_w"], quant) + weights["head_b"])
    t = T.layer_norm(t, weights["head_ln_g"], weights["head_ln_b"])
    logits = T.matmul(t, weights["wte"], quant)
    valid = labels >= 0
    safe = jnp.where(valid, labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    return jnp.sum(jnp.where(valid, logz - picked, 0.0))


def loss_and_grad(weights, tokens, labels, heads: int, block_rows: int,
                  quant=None):
    """Mean loss over the batch's labelled positions and its gradient,
    accumulated over blocks of ``block_rows`` rows so that it fits."""
    b = tokens.shape[0]
    nb = b // block_rows
    tk = tokens.reshape(nb, block_rows, -1)
    lb = labels.reshape(nb, block_rows, -1)
    count = jnp.maximum(jnp.sum(labels >= 0), 1).astype(jnp.float32)
    vg = jax.value_and_grad(loss_sum)

    def body(acc, xs):
        l, g = vg(weights, xs[0], xs[1], heads, quant)
        return (acc[0] + l, jax.tree.map(jnp.add, acc[1], g)), None

    zero = (jnp.float32(0.0), jax.tree.map(jnp.zeros_like, weights))
    (l, g), _ = jax.lax.scan(body, zero, (tk, lb))
    return l / count, jax.tree.map(lambda x: x / count, g)


def tensor_sq(tree):
    """Sum of squares per tensor: scalars, and [L] for ``layers`` leaves."""
    out = {k: jnp.sum(jnp.square(v)) for k, v in tree.items()
           if k != "layers"}
    out["layers"] = {k: jnp.sum(jnp.square(v).reshape(v.shape[0], -1), -1)
                     for k, v in tree["layers"].items()}
    return out


def _per_tensor(scalars, tree):
    """Broadcast per-tensor scalars back over the tensors of ``tree``."""
    out = {k: scalars[k] for k in tree if k != "layers"}
    out["layers"] = {
        k: scalars["layers"][k].reshape((-1,) + (1,) * (v.ndim - 1))
        for k, v in tree["layers"].items()}
    return out


def lamb(w, g, m, v, t, hp):
    """One LAMB step.  Returns ``(w, m, v, g_clipped)``."""
    gsq = sum(jnp.sum(x) for x in jax.tree.leaves(tensor_sq(g)))
    gnorm = jnp.sqrt(gsq)
    mgn = hp["max_grad_norm"]
    clip = jnp.where((mgn > 0) & (gnorm > mgn), mgn / (gnorm + 1e-6), 1.0)
    g = jax.tree.map(lambda x: x * clip, g)
    b1, b2 = hp["beta1"], hp["beta2"]
    m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
    v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
    bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
    u = jax.tree.map(
        lambda mm, vv, ww: (mm / bc1) / (jnp.sqrt(vv / bc2) + hp["eps"])
        + hp["weight_decay"] * ww, m, v, w)
    wn = jax.tree.map(jnp.sqrt, tensor_sq(w))
    un = jax.tree.map(jnp.sqrt, tensor_sq(u))
    ratio = jax.tree.map(
        lambda a, c: jnp.where((a > 0) & (c > 0), a / jnp.where(
            c > 0, c, 1.0), 1.0), wn, un)
    w = jax.tree.map(lambda ww, r, uu: ww - hp["lr"] * r * uu,
                     w, _per_tensor(ratio, w), u)
    return w, m, v, g


@functools.partial(jax.jit,
                   static_argnames=("heads", "block_rows", "quant"),
                   donate_argnums=(0, 1, 2))
def train_step(w, m, v, t, tokens, labels, hp, *, heads: int,
               block_rows: int, quant=None):
    loss, g = loss_and_grad(w, tokens, labels, heads, block_rows, quant)
    w, m, v, gc = lamb(w, g, m, v, t, hp)
    return w, m, v, loss, jax.tree.map(jnp.sqrt, tensor_sq(gc))


def first_steps(weights, batches, hp, *, heads: int, block_rows: int,
                quant=None, rows=None):
    """Drive ``len(batches)`` steps from ``weights``.  Returns the losses,
    the per-tensor norm of the first gradient as LAMB gets it (after the
    global clip) and the per-tensor norm of the parameters' change over
    all the steps.  ``rows`` keeps only the first ``rows`` of each batch
    (the planted "half of the batch left out" fault)."""
    hp = {k: jnp.float32(x) for k, x in hp.items()}
    w0 = weights
    w = jax.tree.map(jnp.copy, weights)
    m = jax.tree.map(jnp.zeros_like, weights)
    v = jax.tree.map(jnp.zeros_like, weights)
    losses, g1 = [], None
    for i, batch in enumerate(batches):
        tokens, labels = batch["tokens"], batch["labels"]
        if rows is not None:
            tokens, labels = tokens[:rows], labels[:rows]
        w, m, v, loss, gn = train_step(
            w, m, v, jnp.float32(i + 1), jnp.asarray(tokens),
            jnp.asarray(labels), hp, heads=heads,
            block_rows=min(block_rows, tokens.shape[0]), quant=quant)
        losses.append(float(loss))
        if i == 0:
            g1 = jax.device_get(gn)
    delta = jax.jit(lambda a, b: jax.tree.map(
        jnp.sqrt, tensor_sq(jax.tree.map(jnp.subtract, a, b))))(w, w0)
    return {"losses": losses, "grad1": g1, "delta": jax.device_get(delta)}
