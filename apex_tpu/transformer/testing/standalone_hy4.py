"""Standalone Hy4-preview-family decoder: latent attention over
the positions a learned indexer picks, the picks of one layer reused by the
layers after it, a per-head sink, an elementwise output gate, a residual
carried in several streams, and an expert FFN that holds a share of its
experts.

What the ``hy_v4`` ``model_type`` (tencent/Hy4-preview ``config.json``) adds
to the latent attention of :mod:`standalone_axk1`, whose query, latent-row
and up-projection pieces it shares:

* **a learned indexer on the "full" layers** (``indexer_types``) — index
  queries from the query latent ``c_q``, ONE index key a position (LayerNorm,
  the cache keeps it), a weight per index head; a query attends the
  ``index_topk`` causal positions of largest ``I[t, s] = sum_j w_t[j] *
  relu(qI_t[j] . kI_s)`` and no other.  A **"shared"** layer holds no
  indexer, caches no index key and attends the set the nearest full layer
  before it picked (:attr:`HY4Config.index_sources`);
* **a sink a head** — one more logit in the softmax's denominator that
  carries no value (``attention.sink``);
* **an elementwise gate** — ``o = W_O (sigmoid(W_G a) * ctx)``, read from
  the sublayer's normed input (:func:`attn_gate`, :func:`attn_output`);
* **hyper-connections** — ``hc_mult`` residual streams; each sublayer reads
  a mix of them and writes its output into each with a mix of its own, the
  streams themselves mixed by a doubly stochastic matrix (Sinkhorn), every
  mix computed from the streams (:func:`hc_expand`, :func:`hc_pre`,
  :func:`hc_post`, :func:`hc_collapse`);
* **a clamped SwiGLU** in every FFN (``swiglu_limit``), the dense first
  layer and a sigmoid router over ALL experts, of which this chip HOLDS
  ``held = (first, count)``, plus one shared expert;
* **a float32 head**.

RMSNorm with a learned scale, no bias but the index key's LayerNorm, untied
head.  The ``init`` tree is a nested dict that the serving loops in
``inference/models.py`` consume as is; the ``hy4`` record there IS the
pieces below.  What the ``config.json`` leaves open is marked ``ASSUMED`` on
the line that decides it, as under ``assumed`` in
``benchmark/configs/hy4-preview-serve.json``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.ops import rms_norm
from apex_tpu.transformer.functional.fused_rope import (
    fused_apply_rotary_pos_emb_cached,
)
from apex_tpu.transformer.moe.dropless import (
    dropless_moe_ffn,
    route_group_limited,
    swiglu,
)
from apex_tpu.transformer.testing.standalone_axk1 import (
    attn_absorb,
    attn_expand,
    attn_value_up,
    latent_row,
)
from apex_tpu.transformer.testing.standalone_keye import _layer_norm

__all__ = ["HY4Config", "hy4_param_shapes", "rope_cos_sin",
           "index_rope_cos_sin", "softmax_scale", "attn_expand",
           "attn_absorb", "attn_value_up", "attn_gate", "attn_output",
           "attn_sink", "index_project", "ffn", "head", "hc_expand",
           "hc_pre", "hc_post", "hc_collapse", "sinkhorn", "latent_row",
           "FULL_INDEX", "SHARED_INDEX", "SINKHORN_ROUNDS"]

#: ``indexer_types`` entries: a layer that scores and picks, and one that
#: attends the picks of the full layer before it
FULL_INDEX, SHARED_INDEX = "full", "shared"
#: ASSUMED (a): mHC's t_max, the rounds of each ``H_res``'s normalisation
SINKHORN_ROUNDS = 20


@dataclasses.dataclass(frozen=True)
class HY4Config:
    """Defaults give a test-scale model whose ``index_topk`` is far under
    its contexts; the published sizes are in
    ``benchmark/configs/hy4-preview-serve.json``."""
    vocab_size: int = 512
    hidden_size: int = 64
    num_layers: int = 6
    num_heads: int = 4
    q_lora_rank: int = 48
    kv_lora_rank: int = 32
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    rope_theta: float = 10000000.0
    index_heads: int = 4
    index_head_dim: int = 16
    index_topk: int = 16
    index_q_chunk: int = 16          # query rows a prefill picks for at once
    indexer_types: Tuple[str, ...] = (FULL_INDEX, FULL_INDEX, SHARED_INDEX,
                                      SHARED_INDEX, SHARED_INDEX, FULL_INDEX)
    dense_layers: int = 1            # leading plain-SwiGLU layers
    ffn_hidden_size: int = 128
    moe_ffn_hidden_size: int = 32
    shared_ffn_hidden_size: int = 32
    num_experts: int = 16            # the router's outputs
    held: Tuple[int, int] = (0, 16)  # (first, count) held here
    experts_per_token: int = 4
    routed_scale: float = 2.827
    swiglu_limit: Optional[float] = 10.0
    hc_mult: int = 4
    hc_magnitude: float = 2.0
    hc_eps: float = 1e-6
    max_seq_length: int = 256
    rms_eps: float = 1e-5
    params_dtype: Any = jnp.float32

    def __post_init__(self):
        first, count = self.held
        if first < 0 or count < 1 or first + count > self.num_experts:
            raise ValueError(
                f"held={self.held} must lie inside the router's "
                f"{self.num_experts} experts")
        if len(self.indexer_types) != self.num_layers \
                or self.indexer_types[0] != FULL_INDEX \
                or set(self.indexer_types) - {FULL_INDEX, SHARED_INDEX}:
            raise ValueError(
                f"indexer_types {self.indexer_types} must give each of the "
                f"{self.num_layers} layers 'full' or 'shared', the first "
                f"'full' (a shared layer reuses the picks of one before it)")
        if not self.qk_rope_head_dim <= self.index_head_dim \
                or self.index_topk < 1:
            raise ValueError(
                f"the indexer ropes its leading qk_rope_head_dim "
                f"({self.qk_rope_head_dim}) channels of index_head_dim "
                f"({self.index_head_dim}); index_topk ({self.index_topk}) "
                f"must be at least 1")
        if self.hc_mult < 1:
            raise ValueError(f"hc_mult ({self.hc_mult}) must be >= 1")

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """Values one cached position holds a layer: ``[c || k_pe]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def index_sources(self) -> Tuple[int, ...]:
        """Per layer, the layer whose picks it attends: itself where it is
        full, else the nearest full layer before it."""
        out, last = [], 0
        for i, t in enumerate(self.indexer_types):
            last = i if t == FULL_INDEX else last
            out.append(last)
        return tuple(out)

    @property
    def index_layers(self) -> Tuple[int, ...]:
        """The full layers, in order: the index-key pool's layers."""
        return tuple(i for i, t in enumerate(self.indexer_types)
                     if t == FULL_INDEX)


def softmax_scale(cfg: HY4Config) -> float:
    """``qk_head_dim ** -0.5``: no YaRN, no temperature."""
    return cfg.qk_head_dim ** -0.5


def _plain_rope(theta: float, rot: int, positions):
    pairs = rot // 2
    inv = theta ** (-jnp.arange(pairs, dtype=jnp.float32) / pairs)
    freqs = positions.astype(jnp.float32)[..., None] * inv
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.cos(emb), jnp.sin(emb)


def rope_cos_sin(cfg: HY4Config, positions):
    """``(cos, sin)`` ``[*positions.shape, qk_rope_head_dim]`` float32,
    ``rope_type: default``, channel ``i`` with ``i + rot / 2``."""
    return _plain_rope(cfg.rope_theta, cfg.qk_rope_head_dim, positions)


def index_rope_cos_sin(cfg: HY4Config, positions):
    """The indexer's table over its first ``qk_rope_head_dim`` channels
    (the rest pass unroped)."""
    # ASSUMED (e): the leading 64 of the 128 channels (as many as the
    # attention ropes), same theta, the rotate-half pairing
    return _plain_rope(cfg.rope_theta, cfg.qk_rope_head_dim, positions)


# --------------------------------------------------------------------------
# attention: axk1's latent pieces, then the gate and the sink
# --------------------------------------------------------------------------

def _linear(p, x):
    return jnp.matmul(x, p["weight"].T)


def attn_gate(cfg: HY4Config, lp, h):
    """``sigmoid(W_G a)`` as ``[..., heads, v_head_dim]`` in ``h``'s type:
    the elementwise gate on each head's output."""
    # ASSUMED (c): gating_type elementwise = one gate a value channel, read
    # from the sublayer's normed input, applied after the softmax sum
    g = _linear(lp["attention"]["g_proj"], h)
    return jax.nn.sigmoid(g.astype(jnp.float32)).astype(h.dtype).reshape(
        *h.shape[:-1], cfg.num_heads, cfg.v_head_dim)


def attn_output(lp, ctx, gate):
    """``ctx [..., heads, v]`` gated, then ``W_O`` -> ``[..., hidden]``."""
    return _linear(lp["attention"]["o_proj"],
                   (ctx * gate).reshape(*ctx.shape[:-2], -1))


def attn_sink(lp):
    """Each head's sink logit ``[heads]`` float32."""
    return lp["attention"]["sink"].astype(jnp.float32)


def index_project(cfg: HY4Config, lp, h, cos, sin):
    """``h [..., hidden]`` (the sublayer's normed input) -> index queries
    ``qi [..., index_heads, di]``, their weights ``wi [..., index_heads]``
    (float32, scaled) and the ONE index key ``ki [..., di]`` the cache
    keeps; the leading ``qk_rope_head_dim`` channels of both roped."""
    att, ix = lp["attention"], lp["indexer"]
    hi, di = cfg.index_heads, cfg.index_head_dim
    # the query latent, as the attention's own query path makes it
    c_q = rms_norm(_linear(att["q_a_proj"], h), att["q_a_norm"]["weight"],
                   eps=cfg.rms_eps)
    qi = _linear(ix["q_proj"], c_q).reshape(*h.shape[:-1], hi, di)
    qi = fused_apply_rotary_pos_emb_cached(qi, cos[..., None, :],
                                           sin[..., None, :])
    ki = _layer_norm(_linear(ix["k_proj"], h), ix["k_norm"]["weight"],
                     ix["k_norm"]["bias"], cfg.rms_eps)
    ki = fused_apply_rotary_pos_emb_cached(ki, cos, sin)
    wi = _linear(ix["w_proj"], h).astype(jnp.float32) * (
        hi ** -0.5 * di ** -0.5)
    return qi, wi, ki


# --------------------------------------------------------------------------
# hyper-connections: the streams, and the mixes each sublayer reads
# --------------------------------------------------------------------------

def sinkhorn(m, iters: int, eps: float):
    """Alternate row and column normalisation of ``m [..., n, n]`` (positive)
    ``iters`` times, each denominator ``+ eps``: near doubly stochastic."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


def _hc_logits(cfg: HY4Config, hp, x, sizes):
    """``alpha * (RMSNorm(vec x) phi) + b`` of the mixes of ``hp``, float32
    ``[..., sum(sizes)]``: the streams ``x [..., n, hidden]`` normed whole
    (no gain; the norm applied to the product), ``hp["alpha"]`` one scale
    a mix, ``sizes`` each mix's rows."""
    flat = x.reshape(*x.shape[:-2], -1)
    # both sums straight off the streams' own type, accumulated in float32:
    # no float32 copy of the streams is made
    ssq = jnp.einsum("...k,...k->...", flat, flat,
                     preferred_element_type=jnp.float32)[..., None]
    inv = jax.lax.rsqrt(ssq / flat.shape[-1] + cfg.hc_eps)
    z = jnp.matmul(flat, hp["phi"].T.astype(flat.dtype),
                   preferred_element_type=jnp.float32) * inv
    alpha = jnp.repeat(hp["alpha"].astype(jnp.float32), jnp.asarray(sizes),
                       total_repeat_length=sum(sizes))
    return z * alpha + hp["bias"].astype(jnp.float32)


def _mix(w, x):
    """``sum_i w[..., i] x[..., i, :]`` float32, written out stream by
    stream: elementwise work that fuses with its consumer (a reduction over
    the stream axis would be materialized in float32 first)."""
    return sum(w[..., i, None] * x[..., i, :].astype(jnp.float32)
               for i in range(x.shape[-2]))


def hc_expand(cfg: HY4Config, h):
    """The embedding ``[..., hidden]`` copied into every stream: ``[...,
    n, hidden]``."""
    return jnp.broadcast_to(h[..., None, :], (*h.shape[:-1], cfg.hc_mult,
                                              h.shape[-1]))


def hc_pre(cfg: HY4Config, lp, which: str, x):
    """The sublayer ``which`` (``"attention"`` or ``"ffn"``) of a layer:
    ``x [..., n, hidden]`` -> its input ``u [..., hidden]`` (``sum_i H_pre[i]
    x_i``) and the mixes its output is written back with, ``(H_post [...,
    n], H_res [..., n, n])`` float32."""
    n = cfg.hc_mult
    with jax.named_scope("apex_hc_pre"):
        z = _hc_logits(cfg, lp["hc_" + which], x, (n, n, n * n))
        pre = jax.nn.sigmoid(z[..., :n])
        post = cfg.hc_magnitude * jax.nn.sigmoid(z[..., n:2 * n])
        res = sinkhorn(jnp.exp(z[..., 2 * n:].reshape(*z.shape[:-1], n, n)),
                       SINKHORN_ROUNDS, cfg.hc_eps)
        u = _mix(pre, x)
    return u.astype(x.dtype), (post, res)


def hc_post(cfg: HY4Config, mix, x, y):
    """``x' = H_res x + H_post (x) y``: the streams ``[..., n, hidden]``
    after a sublayer whose output is ``y [..., hidden]``."""
    post, res = mix
    with jax.named_scope("apex_hc_post"):
        y32 = y.astype(jnp.float32)
        out = jnp.stack([_mix(res[..., i, :], x) + post[..., i, None] * y32
                         for i in range(x.shape[-2])], axis=-2)
    # the streams are kept in their own type: without the barrier XLA may
    # hand the next sublayer the float32 sums (excess precision), and a 16k
    # prefill would hold four float32 copies of the streams, 1.6 GB each
    return jax.lax.optimization_barrier(out.astype(x.dtype))


def hc_collapse(cfg: HY4Config, p, x):
    """After the last layer: ``sum_i H_head[i] x_i`` ``[..., hidden]``."""
    # ASSUMED (a): the head-side collapse is a sigmoid mix of its own,
    # computed from the streams as the sublayers' H_pre is
    with jax.named_scope("apex_hc_head"):
        z = _hc_logits(cfg, p["hc_head"], x, (cfg.hc_mult,))
        h = _mix(jax.nn.sigmoid(z), x)
    return h.astype(x.dtype)


# --------------------------------------------------------------------------
# the FFN: clamped SwiGLU, dense first, then held experts + a shared one
# --------------------------------------------------------------------------

def ffn(cfg: HY4Config, i: int, lp, h, valid=None):
    """The layer's FFN over ``h [tokens, hidden]`` -> ``(y, stats)``;
    ``stats`` is None for a dense layer."""
    # ASSUMED (d): swiglu_limit clamps the gate from above and the up
    # projection both ways, in every SwiGLU
    limit = cfg.swiglu_limit
    if i < cfg.dense_layers:
        m = lp["mlp"]
        return swiglu(h, m["gate_proj"]["weight"], m["up_proj"]["weight"],
                      m["down_proj"]["weight"], limit=limit), None
    m = lp["moe"]

    def router(x, w):
        return route_group_limited(x, w, cfg.experts_per_token,
                                   cfg.routed_scale, n_group=1, topk_group=1)

    return dropless_moe_ffn(
        h, m["router"]["weight"], m["experts"]["w_gate"],
        m["experts"]["w_up"], m["experts"]["w_down"],
        top_k=cfg.experts_per_token, scale=cfg.routed_scale,
        shared=m["shared"], valid=valid, held=tuple(cfg.held),
        router=router, limit=limit)


def head(p, h):
    """Float32 weights and product (``enable_lm_head_fp32``)."""
    return jnp.matmul(h.astype(jnp.float32),
                      p["lm_head"]["weight"].astype(jnp.float32).T)


# --------------------------------------------------------------------------
# the param tree
# --------------------------------------------------------------------------

def hy4_param_shapes(cfg: HY4Config) -> dict:
    """The param tree's shapes.  Linear weights are ``[out, in]``; the
    routed experts are expert-major stacks ``[held count, in, out]``; the
    router keeps a row for EVERY expert; a shared layer has no
    ``indexer``; each sublayer's hyper-connection weights are ``phi [2n +
    n^2, n * hidden]`` (rows: pre, post, res), ``alpha [3]``, ``bias``."""
    hid, heads, n = cfg.hidden_size, cfg.num_heads, cfg.hc_mult
    hi, di = cfg.index_heads, cfg.index_head_dim

    def mlp(width):
        return {"gate_proj": {"weight": (width, hid)},
                "up_proj": {"weight": (width, hid)},
                "down_proj": {"weight": (hid, width)}}

    def hc(rows, mixes):
        # ASSUMED (b): phi [rows, n * hidden] and one scalar alpha a mix
        return {"phi": (rows, n * hid), "alpha": (mixes,), "bias": (rows,)}

    tree = {"embed_tokens": {"weight": (cfg.vocab_size, hid)}}
    for i in range(cfg.num_layers):
        layer = {
            "input_norm": {"weight": (hid,)},
            "attention": {
                "q_a_proj": {"weight": (cfg.q_lora_rank, hid)},
                "q_a_norm": {"weight": (cfg.q_lora_rank,)},
                "q_b_proj": {"weight": (heads * cfg.qk_head_dim,
                                        cfg.q_lora_rank)},
                "kv_a_proj": {"weight": (cfg.latent_dim, hid)},
                "kv_a_norm": {"weight": (cfg.kv_lora_rank,)},
                "kv_b_proj": {"weight": (
                    heads * (cfg.qk_nope_head_dim + cfg.v_head_dim),
                    cfg.kv_lora_rank)},
                "g_proj": {"weight": (heads * cfg.v_head_dim, hid)},
                "o_proj": {"weight": (hid, heads * cfg.v_head_dim)},
                "sink": (heads,)},
            "post_attention_norm": {"weight": (hid,)},
            "hc_attention": hc(2 * n + n * n, 3),
            "hc_ffn": hc(2 * n + n * n, 3),
        }
        if cfg.indexer_types[i] == FULL_INDEX:
            layer["indexer"] = {
                "q_proj": {"weight": (hi * di, cfg.q_lora_rank)},
                "k_proj": {"weight": (di, hid)},
                "k_norm": {"weight": (di,), "bias": (di,)},
                "w_proj": {"weight": (hi, hid)}}
        if i < cfg.dense_layers:
            layer["mlp"] = mlp(cfg.ffn_hidden_size)
        else:
            e, f = cfg.held[1], cfg.moe_ffn_hidden_size
            layer["moe"] = {
                "router": {"weight": (cfg.num_experts, hid)},
                "experts": {"w_gate": (e, hid, f), "w_up": (e, hid, f),
                            "w_down": (e, f, hid)},
                "shared": mlp(cfg.shared_ffn_hidden_size)}
        tree[f"layer_{i}"] = layer
    tree["hc_head"] = hc(n, 1)
    tree["final_norm"] = {"weight": (hid,)}
    tree["lm_head"] = {"weight": (cfg.vocab_size, hid)}
    return tree

