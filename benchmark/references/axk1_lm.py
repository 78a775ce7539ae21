"""Plain reference of the ``a.x-k1-serve`` configuration: the decoder that
skt/A.X-K1's ``config.json`` (``model_type: axk1``) describes, written from
the equations of ISSUE 34 section 1 — float32 ``jax.numpy``, every product at
``Precision.HIGHEST``, no kernel, no cache, no batching.  One sequence, one
full forward; it imports nothing of the program.

``x`` is the residual stream, RMSNorm with a learned scale (eps
``rms_norm_eps``), no bias anywhere.  Pre-norm blocks: ``x <- x +
Attn(RMSNorm(x))``, ``x <- x + FFN(RMSNorm(x))``; final RMSNorm; untied head.

* Attention (MLA), ``a = RMSNorm(x)``: ``c_q = RMSNorm(a W_DQ)``; ``q = c_q
  W_UQ`` as ``H`` heads of ``(nope || rope)``; ``q_pe <- RoPE(q_pe)``.
  ``[c_raw || k_raw] = a W_DKV``; ``c = RMSNorm(c_raw)``; ``k_pe =
  RoPE(k_raw)`` — ONE vector a position, shared by all heads.  EXPANDED,
  over the whole sequence: ``[k_nope_h || v_h] = c W_UKV,h``; ``k_h = [k_nope_h
  || k_pe]``; ``o_h = softmax_causal(s q_h k_h^T) v_h``; ``out = concat(o_h)
  W_O``.  ``s = (nope + rope)^-1/2 * m^2``, ``m = 0.1 * mscale_all_dim *
  ln(factor) + 1``.  RoPE is YaRN over the ``rope`` channels: ``inv_freq =
  inter * ramp + extra * (1 - ramp)``, ``extra = theta^(-2i/rope)``, ``inter
  = extra / factor``, the ramp linear between the correction dimensions of
  ``beta_fast`` and ``beta_slow`` over the original context; cos and sin
  times ``yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)``.
* FFN.  Dense (the first ``first_k_dense_replace`` layers): ``(silu(h W_gate)
  * h W_up) W_down``.  Expert: ``sigma = sigmoid(h W_g)`` over ALL
  ``router_experts``, in ``n_group`` groups of consecutive experts; a group's
  score is the sum of its two largest ``sigma``; the ``topk_group`` best
  groups are kept; the ``top_k`` largest ``sigma`` among their experts are
  the token's experts, ``w_e = scale * sigma_e / (sum_chosen sigma + 1e-20)``;
  ``y = sum_e w_e E_e(h) + Shared(h)``.  This chip HOLDS the experts
  ``[held_first, held_first + held_count)``: the sum runs over those alone —
  every HELD expert over every token, weighted by ``w`` (zero where the
  token did not choose it) — and what the absent experts would add is left
  out, as in the program.

What the ``config.json`` leaves open is marked ``ASSUMED (a)``..``(d)`` on the
one line that decides it, as in the program
(``apex_tpu/transformer/testing/standalone_axk1.py``,
``transformer/moe/dropless.py``) and under ``assumed`` in the configuration
file.

Weights (``reference_weights`` of ``bindings/axk1.py``) stay in the type they
are served in; each layer is up-cast as it is used, the routed experts one at
a time.  ``quant`` is the CONTROL, never the reference (``"fp8"``: both
operands of every matrix product rounded to float8_e4m3, per-tensor scale).
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
    layers: int
    dense_layers: int
    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    router_experts: int             # the router's outputs (published)
    held: Tuple[int, int]           # (first, count) held on this chip
    top_k: int
    n_group: int
    topk_group: int
    scale: float
    eps: float
    theta: float
    yarn_factor: float
    yarn_original: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all_dim: float


def spec_from_config(cfg: dict) -> Spec:
    """From the published keys of a ``model_type: axk1`` configuration as
    run: ``n_routed_experts`` counts the experts HELD, ``published`` the
    router's width, ``held_experts_first`` where the share starts."""
    rs = cfg["rope_scaling"]
    assert rs["type"] == "yarn" and cfg["scoring_func"] == "sigmoid"
    assert cfg["norm_topk_prob"] and cfg["n_shared_experts"] == 1
    assert not cfg["attention_bias"] and not cfg["tie_word_embeddings"]
    assert cfg["moe_layer_freq"] == 1 and cfg["hidden_act"] == "silu"
    return Spec(
        layers=cfg["num_hidden_layers"],
        dense_layers=cfg["first_k_dense_replace"],
        heads=cfg["num_attention_heads"], q_rank=cfg["q_lora_rank"],
        kv_rank=cfg["kv_lora_rank"], nope=cfg["qk_nope_head_dim"],
        rope=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"],
        router_experts=cfg["published"]["n_routed_experts"],
        held=(cfg["held_experts_first"], cfg["n_routed_experts"]),
        top_k=cfg["num_experts_per_tok"], n_group=cfg["n_group"],
        topk_group=cfg["topk_group"],
        scale=float(cfg["routed_scaling_factor"]),
        eps=float(cfg["rms_norm_eps"]), theta=float(cfg["rope_theta"]),
        yarn_factor=float(rs["factor"]),
        yarn_original=int(rs["original_max_position_embeddings"]),
        beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
        mscale=float(rs["mscale"]),
        mscale_all_dim=float(rs["mscale_all_dim"]))


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(spec: Spec) -> float:
    # ASSUMED (d): the mscale arithmetic of the DeepSeek-V2/V3 lineage
    m = yarn_mscale(spec.yarn_factor, spec.mscale_all_dim) \
        if spec.mscale_all_dim else 1.0
    return (spec.nope + spec.rope) ** -0.5 * m * m


def yarn_inv_freq(spec: Spec) -> list:
    """YaRN (Peng et al. 2023) inverse frequencies over the ``rope``
    channels, as the family's reference code builds them."""
    dim, base = spec.rope, spec.theta

    def correction_dim(rotations):
        return dim * math.log(spec.yarn_original / (rotations * 2 * math.pi)
                              ) / (2 * math.log(base))

    low = max(math.floor(correction_dim(spec.beta_fast)), 0)
    high = min(math.ceil(correction_dim(spec.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    out = []
    for i in range(dim // 2):
        extra = base ** (-2.0 * i / dim)
        inter = extra / spec.yarn_factor
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(inter * ramp + extra * (1.0 - ramp))
    return out


def rope(x, spec: Spec):
    """``x [seq, heads, rope]``, positions 0..seq-1."""
    rot = spec.rope
    factor = yarn_mscale(spec.yarn_factor, spec.mscale) \
        / yarn_mscale(spec.yarn_factor, spec.mscale_all_dim)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * jnp.asarray(yarn_inv_freq(spec), jnp.float32)[None]
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    # ASSUMED (c): channel i pairs with i + rope/2 (no rope_interleave key)
    a, b = x[..., :rot // 2], x[..., rot // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * gain


def f32(x):
    return jnp.asarray(x, jnp.float32)


def swiglu(h, w_gate, w_up, w_down, quant):
    """``[out, in]`` weights."""
    return matmul(jax.nn.silu(matmul(h, f32(w_gate), quant))
                  * matmul(h, f32(w_up), quant), f32(w_down), quant)


def attention(x, lw, spec: Spec, quant):
    s, heads = x.shape[0], spec.heads
    a = rms_norm(x, f32(lw["ln1"]), spec.eps)
    c_q = rms_norm(matmul(a, f32(lw["w_dq"]), quant), f32(lw["q_norm"]),
                   spec.eps)
    q = matmul(c_q, f32(lw["w_uq"]), quant).reshape(
        s, heads, spec.nope + spec.rope)
    q = jnp.concatenate([q[..., :spec.nope],
                         rope(q[..., spec.nope:], spec)], axis=-1)
    ckv = matmul(a, f32(lw["w_dkv"]), quant)
    c = rms_norm(ckv[:, :spec.kv_rank], f32(lw["kv_norm"]), spec.eps)
    k_pe = rope(ckv[:, None, spec.kv_rank:], spec)            # [s, 1, rope]
    kv = matmul(c, f32(lw["w_ukv"]), quant).reshape(
        s, heads, spec.nope + spec.v_dim)
    k = jnp.concatenate([kv[..., :spec.nope],
                         jnp.broadcast_to(k_pe, (s, heads, spec.rope))],
                        axis=-1)
    v = kv[..., spec.nope:]
    kq, vq = _round(k, quant), _round(v, quant)
    cols = jnp.arange(s)[None, :]
    scale = softmax_scale(spec)

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, ROW_BLOCK, axis=0)
        see = cols <= start + jnp.arange(ROW_BLOCK)[:, None]
        sc = jnp.einsum("qhd,khd->hqk", _round(qb, quant), kq,
                        precision=HI) * scale
        pr = jax.nn.softmax(jnp.where(see[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", _round(pr, quant), vq,
                          precision=HI)

    assert s % ROW_BLOCK == 0, (s, ROW_BLOCK)
    ctx = jax.lax.map(rows, jnp.arange(0, s, ROW_BLOCK)).reshape(
        s, heads * spec.v_dim)
    return matmul(ctx, f32(lw["w_o"]), quant)


def route(h, router, spec: Spec, quant):
    """The router's weights as a dense ``[seq, router_experts]`` array:
    ``w_e`` where the token chose expert ``e``, zero elsewhere."""
    sig = jax.nn.sigmoid(matmul(h, f32(router), quant))
    per = spec.router_experts // spec.n_group
    # ASSUMED (b): a group's score is the sum of its two best
    group = jnp.sum(jax.lax.top_k(
        sig.reshape(-1, spec.n_group, per), 2)[0], axis=-1)
    # ASSUMED (a): topk_method "none" = no selection bias; n_group and
    # topk_group at face value
    _, keep = jax.lax.top_k(group, spec.topk_group)
    kept = jnp.zeros_like(group, bool).at[
        jnp.arange(h.shape[0])[:, None], keep].set(True)
    masked = jnp.where(jnp.repeat(kept, per, axis=-1), sig, -1.0)
    top_s, top_e = jax.lax.top_k(masked, spec.top_k)
    w = spec.scale * top_s / (jnp.sum(top_s, axis=-1, keepdims=True)
                              + 1e-20)
    return jnp.zeros_like(sig).at[
        jnp.arange(h.shape[0])[:, None], top_e].set(w)


def expert_ffn(h, fw, spec: Spec, quant):
    first, count = spec.held
    dense_w = route(h, fw["router"], spec, quant)[:, first:first + count]

    def one(acc, e):
        wg, wu, wd, col = e           # [in, out] slices of the held stacks
        y = matmul(jax.nn.silu(matmul(h, f32(wg).T, quant))
                   * matmul(h, f32(wu).T, quant), f32(wd).T, quant)
        return acc + col[:, None] * y, None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        fw["e_gate"], fw["e_up"], fw["e_down"], dense_w.T))
    return routed + swiglu(h, fw["s_gate"], fw["s_up"], fw["s_down"], quant)


@functools.partial(jax.jit, static_argnames=("spec", "sparse", "quant"))
def layer(x, lw, *, spec: Spec, sparse: bool, quant=None):
    """One decoder layer over ``x [seq, hidden]`` float32."""
    x = x + attention(x, lw, spec, quant)
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
        x = layer(x, lw, spec=spec, sparse=i >= spec.dense_layers,
                  quant=quant)
    return x


def logits(weights, tokens, first, rows: int, *, spec: Spec, quant=None):
    """Float32 logits ``[rows, vocab]`` of the ``rows`` positions from
    ``first`` on (held to the last one) of the one sequence ``tokens``
    ``[seq]``, ``seq`` a multiple of ``ROW_BLOCK``."""
    x = hidden(weights, tokens, spec, quant)
    return _head(x, first, weights["final_norm"], weights["head"],
                 rows=rows, eps=spec.eps, quant=quant)
