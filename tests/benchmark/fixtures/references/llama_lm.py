"""Plain reference of the toy ``llama`` configuration: the decoder of LLaMA
(Touvron et al. 2023, section 2.1) — pre-norm RMSNorm (Zhang & Sennrich
2019), rotary position embeddings in the half-split arrangement of GPT-NeoX
(Su et al. 2021; Black et al. 2022, section 2.1) on queries and keys,
grouped-query causal attention (Ainslie et al. 2023: query head ``h`` reads
key/value head ``h // group``), SwiGLU (Shazeer 2020), no biases, an untied
output projection.  One full forward pass over a whole sequence in float32
at ``Precision.HIGHEST``; no cache, no paging.  Only the rows asked for go
through the final norm and the output projection.

Weights: ``{"embed" [vocab, h], "final_norm" [h], "lm_head" [vocab, h],
"layers": {<LAYER_KEYS>: [L, ...]}}``, float32, matrices stored [out, in];
``w_kv`` orders its rows kv head by kv head as [k_h | v_h].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import transformer as T

LAYER_KEYS = ("norm1", "w_q", "w_kv", "w_o", "norm2", "w_gate", "w_up",
              "w_down")


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * gain


def rope(x, theta):
    """Rotate ``x`` [seq, heads, d]: pair ``i`` is (x[i], x[i + d/2])."""
    s, d = x.shape[0], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def block(x, lp, heads, kv_heads, theta, eps, quant):
    s, h = x.shape
    d, group = h // heads, heads // kv_heads
    y = rms_norm(x, lp["norm1"], eps)
    q = T.matmul(y, lp["w_q"], quant).reshape(s, heads, d)
    kv = T.matmul(y, lp["w_kv"], quant).reshape(s, kv_heads, 2 * d)
    q, k, v = rope(q, theta), rope(kv[..., :d], theta), kv[..., d:]
    k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    scores = jnp.einsum("qnd,knd->nqk", T._round(q, quant),
                        T._round(k, quant), precision=T.HI) / jnp.sqrt(
                            jnp.float32(d))
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None], scores,
                       -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("nqk,knd->qnd", T._round(probs, quant),
                     T._round(v, quant), precision=T.HI).reshape(s, h)
    x = x + T.matmul(ctx, lp["w_o"], quant)
    y = rms_norm(x, lp["norm2"], eps)
    y = jax.nn.silu(T.matmul(y, lp["w_gate"], quant)) * T.matmul(
        y, lp["w_up"], quant)
    return x + T.matmul(y, lp["w_down"], quant)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "theta",
                                             "eps", "quant"))
def logits(weights, tokens, rows, *, heads: int, kv_heads: int,
           theta: float, eps: float, quant=None):
    """Float32 logits [len(rows), vocab] at the positions ``rows`` of the
    one sequence ``tokens`` [seq]."""
    def body(h, lp):
        return block(h, lp, heads, kv_heads, theta, eps, quant), None
    x, _ = jax.lax.scan(body, weights["embed"][tokens], weights["layers"])
    x = rms_norm(x[rows], weights["final_norm"], eps)
    return T.matmul(x, weights["lm_head"], quant)
