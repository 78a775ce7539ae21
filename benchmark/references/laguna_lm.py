"""Plain reference of the ``laguna-xs.2-serve`` configuration: the decoder
that poolside/Laguna-XS.2's ``config.json`` (``model_type: laguna``)
describes, written from the equations of ISSUE 30 section 1 — float32
``jax.numpy``, every product at ``Precision.HIGHEST``, no kernel, no cache,
no batching.  One sequence, one full forward; it imports nothing of the
program.

``x`` is the residual stream, ``H_l`` the layer's query heads, ``d`` the head
size, RMSNorm with a learned scale, no bias anywhere.

* Attention: ``h = RMSNorm(x)``; ``q, k, v = h W_q, h W_k, h W_v``; RoPE on
  ``q, k`` — full layers rotate the first ``rotary`` channels of a head with
  YaRN frequencies and scale cos/sin by ``attention_factor``, sliding layers
  rotate the whole head plainly; scores ``q k^T / sqrt(d)``, float32 softmax;
  query ``i`` sees key ``j`` iff ``j <= i`` (full) or ``i - window < j <= i``
  (sliding); query head ``a`` reads KV head ``a // (H_l / kv_heads)``; each
  head's output times ``sigmoid(h W_g)_a``; ``x <- x + concat(heads) W_o``.
* FFN: ``h = RMSNorm(x)``.  Dense: ``x <- x + (silu(h W_gate) * h W_up)
  W_down``.  Sparse: ``p = softmax(h W_r)``; the ``top_k`` largest; ``w_e =
  scale * p_e / sum_top p``; ``x <- x + sum_top w_e E_e(h) + E_shared(h)``,
  every ``E`` a SwiGLU.  Computed the plain way: EVERY expert over EVERY
  token, weighted by ``w`` (zero where the token did not choose it) — so no
  token can be dropped and no sort or capacity exists to get wrong.
* Head: final RMSNorm, untied ``W_head``.

What the ``config.json`` leaves open is marked ``ASSUMED (a)``..``(e)`` on
the one line that decides it, as in the program
(``apex_tpu/transformer/testing/standalone_laguna.py``,
``transformer/moe/dropless.py``) and under ``assumed`` in the configuration
file: a correction against the published modelling code is that line, here
and there.

Weights (``reference_weights`` of ``bindings/moe_laguna.py``) stay in the type
they are served in; each layer is up-cast as it is used, the routed experts
one at a time, so a 7.7 GB bfloat16 tree never becomes a 15.5 GB float32 one.

``quant`` is the CONTROL, never the reference (``"fp8"``: both operands of
every matrix product rounded to float8_e4m3, per-tensor scale).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from .transformer import HI, _round, matmul

ROW_BLOCK = 256       # query rows scored at a time: [heads, 256, seq] floats


class Spec(NamedTuple):
    """The numbers of a configuration file the equations need."""
    heads: Tuple[int, ...]          # query heads, per layer
    sliding: Tuple[bool, ...]       # window layer?  per layer
    sparse: Tuple[bool, ...]        # expert FFN?  per layer
    kv_heads: int
    head_dim: int
    window: int
    top_k: int
    scale: float
    eps: float
    rotary: int                     # channels a full layer rotates
    theta_full: float
    yarn_factor: float
    yarn_original: int
    beta_fast: float
    beta_slow: float
    attention_factor: float
    theta_sliding: float


def spec_from_config(cfg: dict) -> Spec:
    """From the published keys of a ``model_type: laguna`` configuration."""
    rope = cfg["rope_parameters"]
    full, sliding = rope["full_attention"], rope["sliding_attention"]
    assert full["rope_type"] == "yarn" and sliding["rope_type"] == "default"
    assert not cfg["moe_apply_router_weight_on_input"]
    assert not cfg["attention_bias"] and not cfg["tie_word_embeddings"]
    return Spec(
        heads=tuple(cfg["num_attention_heads_per_layer"]),
        sliding=tuple(t == "sliding_attention" for t in cfg["layer_types"]),
        sparse=tuple(t == "sparse" for t in cfg["mlp_layer_types"]),
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        window=cfg["sliding_window"], top_k=cfg["num_experts_per_tok"],
        scale=float(cfg["moe_routed_scaling_factor"]),
        eps=float(cfg["rms_norm_eps"]),
        rotary=int(cfg["head_dim"] * full["partial_rotary_factor"]),
        theta_full=float(full["rope_theta"]),
        yarn_factor=float(full["factor"]),
        yarn_original=int(full["original_max_position_embeddings"]),
        beta_fast=float(full["beta_fast"]),
        beta_slow=float(full["beta_slow"]),
        attention_factor=float(full["attention_factor"]),
        theta_sliding=float(sliding["rope_theta"]))


def yarn_inv_freq(spec: Spec) -> list:
    """YaRN (Peng et al. 2023) inverse frequencies over ``rotary`` channels:
    ``inv = interp * ramp + extrap * (1 - ramp)``, ``extrap = base^(-2i/dim)``,
    ``interp = extrap / factor``, the ramp linear between the dimension that
    turns ``beta_fast`` times over the original context and the one that
    turns ``beta_slow`` times."""
    dim, base = spec.rotary, spec.theta_full

    def c(r):
        return dim * math.log(spec.yarn_original / (2 * math.pi * r)) / (
            2 * math.log(base))

    low = max(math.floor(c(spec.beta_fast)), 0)
    high = min(math.ceil(c(spec.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    out = []
    for i in range(dim // 2):
        extrap = base ** (-2.0 * i / dim)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(extrap / spec.yarn_factor * ramp + extrap * (1 - ramp))
    return out


def rope(x, spec: Spec, sliding: bool):
    """``x [seq, heads, d]``, positions 0..seq-1.  The rotated channels pair
    ``c`` with ``c + rot/2`` (rotate-half); the rest pass through."""
    if sliding:
        rot, factor = spec.head_dim, 1.0
        inv = [spec.theta_sliding ** (-2.0 * i / rot)
               for i in range(rot // 2)]
    else:
        rot, factor = spec.rotary, spec.attention_factor
        inv = yarn_inv_freq(spec)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv, jnp.float32)[None]              # [seq, rot/2]
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    a, b, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest],
                           axis=-1)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * gain


def f32(x):
    return jnp.asarray(x, jnp.float32)


def swiglu(h, w_gate, w_up, w_down, quant):
    """``[out, in]`` weights."""
    return matmul(jax.nn.silu(matmul(h, f32(w_gate), quant))
                  * matmul(h, f32(w_up), quant), f32(w_down), quant)


def attention(x, lw, spec: Spec, heads: int, sliding: bool, quant):
    s, d, kvh = x.shape[0], spec.head_dim, spec.kv_heads
    h = rms_norm(x, f32(lw["ln1"]), spec.eps)
    q = matmul(h, f32(lw["wq"]), quant).reshape(s, heads, d)
    k = matmul(h, f32(lw["wk"]), quant).reshape(s, kvh, d)
    v = matmul(h, f32(lw["wv"]), quant).reshape(s, kvh, d)
    # ASSUMED (e): no normalisation of q, k beyond RoPE
    q, k = rope(q, spec, sliding), rope(k, spec, sliding)
    group = heads // kvh          # query head a reads KV head a // group
    q = q.reshape(s, kvh, group, d)
    kq, vq = _round(k, quant), _round(v, quant)
    cols = jnp.arange(s)[None, :]

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, ROW_BLOCK, axis=0)
        i = start + jnp.arange(ROW_BLOCK)[:, None]
        see = cols <= i
        if sliding:
            # ASSUMED (e): the Hugging Face sliding_window convention
            see = see & (cols > i - spec.window)
        sc = jnp.einsum("qngd,knd->ngqk", _round(qb, quant), kq,
                        precision=HI) / math.sqrt(d)
        pr = jax.nn.softmax(jnp.where(see[None, None], sc, -jnp.inf),
                            axis=-1)
        return jnp.einsum("ngqk,knd->qngd", _round(pr, quant), vq,
                          precision=HI)

    assert s % ROW_BLOCK == 0, (s, ROW_BLOCK)
    ctx = jax.lax.map(rows, jnp.arange(0, s, ROW_BLOCK)).reshape(
        s, heads, d)
    # ASSUMED (a): the gate is per head and a sigmoid
    gate = jax.nn.sigmoid(matmul(h, f32(lw["wg"]), quant))     # [s, heads]
    return matmul((ctx * gate[..., None]).reshape(s, heads * d),
                  f32(lw["wo"]), quant)


def expert_ffn(h, fw, spec: Spec, quant):
    # ASSUMED (c): the router is a float32 softmax over all experts, no bias;
    # ASSUMED (b): ... its logits not soft-capped
    p = jax.nn.softmax(matmul(h, f32(fw["router"]), quant), axis=-1)
    top_p, top_e = jax.lax.top_k(p, spec.top_k)
    # ASSUMED (b): the top-k weights are renormalised, then scaled
    w = spec.scale * top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    rows = jnp.arange(h.shape[0])[:, None]
    dense_w = jnp.zeros_like(p).at[rows, top_e].set(w)         # [s, E]

    def one(acc, e):
        wg, wu, wd, col = e           # [in, out] slices of the stacks
        y = matmul(jax.nn.silu(matmul(h, f32(wg).T, quant))
                   * matmul(h, f32(wu).T, quant), f32(wd).T, quant)
        return acc + col[:, None] * y, None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        fw["e_gate"], fw["e_up"], fw["e_down"], dense_w.T))
    # ASSUMED (d): the shared expert is added ungated
    return routed + swiglu(h, fw["s_gate"], fw["s_up"], fw["s_down"], quant)


@functools.partial(jax.jit, static_argnames=("spec", "heads", "sliding",
                                             "sparse", "quant"))
def layer(x, lw, *, spec: Spec, heads: int, sliding: bool, sparse: bool,
          quant=None):
    """One decoder layer over ``x [seq, hidden]`` float32."""
    x = x + attention(x, lw, spec, heads, sliding, quant)
    h = rms_norm(x, f32(lw["ln2"]), spec.eps)
    fw = lw["ffn"]
    if sparse:
        return x + expert_ffn(h, fw, spec, quant)
    return x + swiglu(h, fw["w_gate"], fw["w_up"], fw["w_down"], quant)


@functools.partial(jax.jit, static_argnames=("rows", "eps", "quant"))
def _head(x, first, gain, w_head, *, rows: int, eps: float, quant=None):
    at = jnp.clip(first + jnp.arange(rows), 0, x.shape[0] - 1)
    return matmul(rms_norm(x[at], f32(gain), eps), f32(w_head), quant)


def hidden(weights, tokens, spec: Spec, quant=None):
    """The residual stream ``[seq, hidden]`` after the last layer."""
    x = f32(weights["embed"][tokens])
    for i, lw in enumerate(weights["layers"]):
        x = layer(x, lw, spec=spec, heads=spec.heads[i],
                  sliding=spec.sliding[i], sparse=spec.sparse[i],
                  quant=quant)
    return x


def logits(weights, tokens, first, rows: int, *, spec: Spec, quant=None):
    """Float32 logits ``[rows, vocab]`` of the ``rows`` positions from
    ``first`` on (held to the last one) of the one sequence ``tokens``
    ``[seq]``, ``seq`` a multiple of ``ROW_BLOCK``."""
    x = hidden(weights, tokens, spec, quant)
    return _head(x, first, weights["final_norm"], weights["head"],
                 rows=rows, eps=spec.eps, quant=quant)
