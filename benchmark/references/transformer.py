"""The plain transformer stack both references share: float32 ``jax.numpy``,
every product at ``Precision.HIGHEST``, no kernel, no cache, no batching
trick.  Written from the equations of "Attention Is All You Need" (Vaswani
et al. 2017, section 3) in the pre-LayerNorm arrangement of Megatron-LM
(Shoeybi et al. 2019, section 3), which is the arrangement both
configurations are built in:

    x <- x + Attention(LN(x));   x <- x + MLP(LN(x));   out = LN_f(x)

Departures from the papers, each because the configuration states it:
GELU is the tanh approximation (Hendrycks & Gimpel 2016, eq. 2); the fused
QKV projection orders its rows head by head as [q_h | k_h | v_h]; LayerNorm
uses eps = 1e-5.  This file imports nothing of the program.

``quant`` is the CONTROL, never the reference: ``"fp8"`` rounds both
operands of every matrix product to float8_e4m3 (per-tensor scale to the
format's range) — the nearest precision below the bfloat16 both
configurations state, the step that would tempt a later PR.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
LN_EPS = 1e-5
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _round(x, quant):
    if quant is None:
        return x
    if quant == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / F8_MAX
        scale = jax.lax.stop_gradient(scale)
        return (x / scale).astype(F8).astype(jnp.float32) * scale
    raise ValueError(f"unknown control precision {quant!r}")


def matmul(x, w, quant=None):
    """``x @ w.T`` for a weight stored [out, in]."""
    return jnp.einsum("...i,oi->...o", _round(x, quant), _round(w, quant),
                      precision=HI)


def layer_norm(x, gain, bias):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * gain + bias


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def attention(x, lp, heads: int, causal: bool, quant=None):
    """Multi-head self-attention over ``x`` [batch, seq, hidden]."""
    b, s, h = x.shape
    d = h // heads
    qkv = matmul(x, lp["w_qkv"], quant) + lp["b_qkv"]
    qkv = qkv.reshape(b, s, heads, 3 * d)
    q, k, v = qkv[..., :d], qkv[..., d:2 * d], qkv[..., 2 * d:]
    scores = jnp.einsum("bqnd,bknd->bnqk", _round(q, quant),
                        _round(k, quant), precision=HI) / jnp.sqrt(
                            jnp.float32(d))
    if causal:
        keep = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(keep[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bnqk,bknd->bqnd", _round(probs, quant),
                     _round(v, quant), precision=HI).reshape(b, s, h)
    return matmul(ctx, lp["w_o"], quant) + lp["b_o"]


def block(x, lp, heads: int, causal: bool, quant=None):
    x = x + attention(layer_norm(x, lp["ln1_g"], lp["ln1_b"]), lp, heads,
                      causal, quant)
    y = layer_norm(x, lp["ln2_g"], lp["ln2_b"])
    y = gelu(matmul(y, lp["w_fc"], quant) + lp["b_fc"])
    return x + matmul(y, lp["w_proj"], quant) + lp["b_proj"]


def stack(x, layers, heads: int, causal: bool, quant=None):
    """All layers, ``layers`` holding each weight stacked on a leading
    layer axis (one scanned program instead of N inlined ones)."""
    def body(h, lp):
        return block(h, lp, heads, causal, quant), None
    out, _ = jax.lax.scan(body, x, layers)
    return out


LAYER_KEYS = ("ln1_g", "ln1_b", "w_qkv", "b_qkv", "w_o", "b_o",
              "ln2_g", "ln2_b", "w_fc", "b_fc", "w_proj", "b_proj")
