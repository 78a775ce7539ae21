"""Standalone A.X-K1-family decoder (ISSUE 34): latent attention and an
expert FFN that holds a share of its experts.

What the ``axk1`` ``model_type`` (skt/A.X-K1 ``config.json``) adds to the
LLaMA recipe of :mod:`standalone_llama`:

* **latent attention (MLA)** — the layer caches ONE row a position, the
  RMS-normed latent ``c`` (``kv_lora_rank``) beside the roped key channels
  ``k_pe`` every head shares (``qk_rope_head_dim``), and no per-head keys
  or values.  Two forms of the same function: *expanded*
  (:func:`attn_expand`, prefill) makes each head's keys and values from
  the latent and runs ordinary causal attention; *absorbed*
  (:func:`attn_absorb` / :func:`attn_value_up`, decode) folds the key
  up-projection into the query and the value up-projection behind the
  softmax, so a decode step reads the latent rows and nothing wider;
* **a low-rank query** (``q_lora_rank``, RMS-normed) and **YaRN** over the
  roped channels (:func:`standalone_laguna.yarn_inv_freq`, shared), with
  the softmax scale times ``mscale**2``;
* **a group-limited sigmoid router**
  (:func:`~apex_tpu.transformer.moe.dropless.route_group_limited`) over
  ALL ``num_experts``, of which this chip HOLDS ``held = (first, count)``
  (:func:`~apex_tpu.transformer.moe.dropless.dropless_moe_ffn`), one
  shared expert, the first ``dense_layers`` layers a plain SwiGLU.

RMSNorm with a learned scale, no bias anywhere, untied head.  The module
is single-chip; its ``init`` tree is a nested dict that the serving loops
in ``inference/models.py`` consume as is, and the ``axk1`` record there IS
the per-layer pieces below, so the two cannot drift.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu.ops import rms_norm
from apex_tpu.ops.attention import flash_attention
from apex_tpu.transformer.functional.fused_rope import (
    fused_apply_rotary_pos_emb_cached,
)
from apex_tpu.transformer.moe.dropless import (
    dropless_moe_ffn,
    route_group_limited,
    swiglu,
)
from apex_tpu.transformer.testing.standalone_laguna import (
    YarnRope,
    _init_subtree,
    yarn_inv_freq,
)

__all__ = ["AXK1Config", "AXK1Model", "axk1_model_provider",
           "axk1_param_shapes", "axk1_forward", "forward_hidden",
           "rope_cos_sin", "softmax_scale", "attn_expand", "attn_absorb",
           "attn_value_up", "attn_output", "ffn"]


@dataclasses.dataclass(frozen=True)
class AXK1Config:
    """Defaults give a test-scale model; the published sizes are in
    ``benchmark/configs/a.x-k1-serve.json``."""
    vocab_size: int = 512
    hidden_size: int = 64
    num_layers: int = 3
    num_heads: int = 4
    q_lora_rank: int = 48
    kv_lora_rank: int = 32
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    dense_layers: int = 1                      # leading plain-SwiGLU layers
    ffn_hidden_size: int = 128                 # their width
    moe_ffn_hidden_size: int = 32              # one routed expert
    shared_ffn_hidden_size: int = 32           # the shared expert
    num_experts: int = 16                      # the router's outputs
    held: Tuple[int, int] = (0, 16)            # (first, count) held here
    experts_per_token: int = 4
    n_group: int = 4
    topk_group: int = 2
    routed_scale: float = 2.5
    max_seq_length: int = 256
    rms_eps: float = 1e-6
    rope: YarnRope = YarnRope(theta=10000.0, rotary_dim=8, factor=32.0,
                              original_max_position=32, beta_fast=32.0,
                              beta_slow=1.0, attention_factor=1.0)
    mscale_all_dim: float = 1.0
    params_dtype: Any = jnp.float32

    def __post_init__(self):
        first, count = self.held
        if first < 0 or count < 1 or first + count > self.num_experts:
            raise ValueError(
                f"held={self.held} must lie inside the router's "
                f"{self.num_experts} experts")
        if self.num_experts % self.n_group \
                or not 1 <= self.topk_group <= self.n_group:
            raise ValueError(
                f"n_group ({self.n_group}) must divide num_experts "
                f"({self.num_experts}) and topk_group ({self.topk_group}) "
                f"lie in [1, n_group]")
        if self.rope.rotary_dim != self.qk_rope_head_dim:
            raise ValueError(
                f"rope.rotary_dim ({self.rope.rotary_dim}) must be "
                f"qk_rope_head_dim ({self.qk_rope_head_dim})")

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """Values one cached position holds a layer: ``[c || k_pe]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def expert_layers(self) -> Tuple[int, ...]:
        return tuple(range(self.dense_layers, self.num_layers))


def softmax_scale(cfg: AXK1Config) -> float:
    """``qk_head_dim ** -0.5 * m**2``, ``m = 0.1 * mscale_all_dim *
    ln(factor) + 1`` (YaRN's attention temperature, folded into the
    scale as the family's reference code does)."""
    # ASSUMED (d): the mscale arithmetic of the DeepSeek-V2/V3 lineage
    m = 1.0
    if cfg.rope.factor > 1.0 and cfg.mscale_all_dim:
        m = 0.1 * cfg.mscale_all_dim * math.log(cfg.rope.factor) + 1.0
    return cfg.qk_head_dim ** -0.5 * m * m


def rope_cos_sin(cfg: AXK1Config, positions):
    """``(cos, sin)`` ``[*positions.shape, qk_rope_head_dim]`` float32:
    YaRN frequencies, half-split ``rotate_half`` layout."""
    # ASSUMED (c): channel i pairs with i + rot/2 (no rope_interleave key)
    inv = jnp.asarray(yarn_inv_freq(cfg.rope), jnp.float32)
    freqs = positions.astype(jnp.float32)[..., None] * inv
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    f = cfg.rope.attention_factor
    return jnp.cos(emb) * f, jnp.sin(emb) * f


# --------------------------------------------------------------------------
# the per-layer pieces (shared with inference/models.py)
# --------------------------------------------------------------------------

def _linear(p, x):
    return jnp.matmul(x, p["weight"].T)


def _queries(cfg: AXK1Config, att, h, cos, sin):
    """``h [..., hidden]`` -> ``q_nope [..., H, nope]``, roped ``q_pe
    [..., H, rope]`` through the low-rank query path."""
    with jax.named_scope("apex_mla_down"):
        c_q = rms_norm(_linear(att["q_a_proj"], h),
                       att["q_a_norm"]["weight"], eps=cfg.rms_eps)
    q = _linear(att["q_b_proj"], c_q).reshape(
        *h.shape[:-1], cfg.num_heads, cfg.qk_head_dim)
    q_nope, q_pe = jnp.split(q, [cfg.qk_nope_head_dim], axis=-1)
    return q_nope, fused_apply_rotary_pos_emb_cached(q_pe, cos, sin)


def latent_row(cfg: AXK1Config, att, h, cos, sin):
    """The cache row of each position ``[..., kv_lora_rank +
    qk_rope_head_dim]``: the normed latent beside the roped shared key
    channels.  ``cos``/``sin`` broadcast against ``[..., 1, rope]``."""
    with jax.named_scope("apex_mla_down"):
        ckv = _linear(att["kv_a_proj"], h)
        c, k_pe = jnp.split(ckv, [cfg.kv_lora_rank], axis=-1)
        c = rms_norm(c, att["kv_a_norm"]["weight"], eps=cfg.rms_eps)
    k_pe = fused_apply_rotary_pos_emb_cached(k_pe[..., None, :], cos, sin)
    return jnp.concatenate([c, k_pe[..., 0, :]], axis=-1)


def _up_weights(cfg: AXK1Config, att):
    """``W_UKV`` as ``W_UK [H, nope, latent]``, ``W_UV [H, v, latent]``."""
    w = att["kv_b_proj"]["weight"].reshape(
        cfg.num_heads, cfg.qk_nope_head_dim + cfg.v_head_dim,
        cfg.kv_lora_rank)
    return w[:, :cfg.qk_nope_head_dim], w[:, cfg.qk_nope_head_dim:]


def attn_expand(cfg: AXK1Config, lp, h, cos, sin):
    """EXPANDED form (prefill): ``h [..., hidden]`` -> ``q, k [..., H,
    nope + rope]``, ``v [..., H, v]`` and the cache ``row [..., latent +
    rope]`` — keys and values made from the latent, a layer at a time."""
    att = lp["attention"]
    q_nope, q_pe = _queries(cfg, att, h, cos, sin)
    row = latent_row(cfg, att, h, cos, sin)
    c, k_pe = jnp.split(row, [cfg.kv_lora_rank], axis=-1)
    with jax.named_scope("apex_mla_expand"):
        kv = _linear(att["kv_b_proj"], c).reshape(
            *h.shape[:-1], cfg.num_heads,
            cfg.qk_nope_head_dim + cfg.v_head_dim)
    k_nope, v = jnp.split(kv, [cfg.qk_nope_head_dim], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[..., None, :],
                                  (*k_nope.shape[:-1], k_pe.shape[-1]))],
        axis=-1)
    return jnp.concatenate([q_nope, q_pe], axis=-1), k, v, row


def attn_absorb(cfg: AXK1Config, lp, h, cos, sin):
    """ABSORBED form (decode): ``h [..., hidden]`` -> the query against
    the cache rows ``[..., H, latent + rope]`` (``q_nope W_UK || q_pe``)
    and the new cache ``row``."""
    att = lp["attention"]
    q_nope, q_pe = _queries(cfg, att, h, cos, sin)
    w_uk, _ = _up_weights(cfg, att)
    with jax.named_scope("apex_mla_absorb"):
        q_lat = jnp.einsum("...hn,hnc->...hc", q_nope, w_uk)
    return (jnp.concatenate([q_lat, q_pe], axis=-1),
            latent_row(cfg, att, h, cos, sin))


def attn_value_up(cfg: AXK1Config, lp, u):
    """``u [..., H, latent]`` (softmax-weighted latents) -> each head's
    output ``[..., H, v]``: the value up-projection, behind the softmax."""
    _, w_uv = _up_weights(cfg, lp["attention"])
    with jax.named_scope("apex_mla_up"):
        return jnp.einsum("...hc,hvc->...hv", u, w_uv)


def attn_output(lp, ctx):
    """``ctx [..., H, v]`` -> ``[..., hidden]``."""
    return _linear(lp["attention"]["o_proj"],
                   ctx.reshape(*ctx.shape[:-2], -1))


def ffn(cfg: AXK1Config, i: int, lp, h, valid=None):
    """The layer's FFN over ``h [tokens, hidden]`` -> ``(y, stats)``;
    ``stats`` is None for a dense layer.  An expert layer returns the
    HELD experts' part of the routed sum plus the shared expert."""
    if i < cfg.dense_layers:
        m = lp["mlp"]
        return swiglu(h, m["gate_proj"]["weight"], m["up_proj"]["weight"],
                      m["down_proj"]["weight"]), None
    m = lp["moe"]

    # ASSUMED (a): topk_method "none" = no selection bias term, n_group /
    # topk_group taken at face value; ASSUMED (b): a group's score is the
    # sum of its two best (route_group_limited)
    def router(x, w):
        return route_group_limited(
            x, w, cfg.experts_per_token, cfg.routed_scale,
            n_group=cfg.n_group, topk_group=cfg.topk_group)

    return dropless_moe_ffn(
        h, m["router"]["weight"], m["experts"]["w_gate"],
        m["experts"]["w_up"], m["experts"]["w_down"],
        top_k=cfg.experts_per_token, scale=cfg.routed_scale,
        shared=m["shared"], valid=valid, held=tuple(cfg.held),
        router=router)


def forward_hidden(cfg: AXK1Config, p, tokens):
    """The causal stack over ``tokens [b, s]`` -> the final-normed stream
    ``[b, s, hidden]``, attention in the EXPANDED form."""
    b, s = tokens.shape
    x = jnp.take(p["embed_tokens"]["weight"], tokens, axis=0)
    cos, sin = (c[None, :, None, :] for c in rope_cos_sin(
        cfg, jnp.arange(s, dtype=jnp.int32)))
    scale = softmax_scale(cfg)
    for i in range(cfg.num_layers):
        lp = p[f"layer_{i}"]
        h1 = rms_norm(x, lp["input_norm"]["weight"], eps=cfg.rms_eps)
        q, k, v, _ = attn_expand(cfg, lp, h1, cos, sin)
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        ctx = flash_attention(q, k, v, causal=True, sm_scale=scale)
        x = x + attn_output(lp, ctx.transpose(0, 2, 1, 3))
        h2 = rms_norm(x, lp["post_attention_norm"]["weight"],
                      eps=cfg.rms_eps)
        x = x + ffn(cfg, i, lp, h2.reshape(b * s, -1))[0].reshape(b, s, -1)
    return rms_norm(x, p["final_norm"]["weight"], eps=cfg.rms_eps)


def axk1_forward(cfg: AXK1Config, p, tokens):
    """Full causal forward ``tokens [b, s]`` -> logits ``[b, s, vocab]``."""
    return _linear(p["lm_head"], forward_hidden(cfg, p, tokens))


# --------------------------------------------------------------------------
# the flax module: its init tree is what every forward consumes
# --------------------------------------------------------------------------

def axk1_param_shapes(cfg: AXK1Config) -> dict:
    """The param tree's shapes.  Linear weights are ``[out, in]``; the
    routed experts are expert-major stacks ``[held count, in, out]``; the
    router keeps a row for EVERY expert."""
    hid, heads = cfg.hidden_size, cfg.num_heads

    def mlp(width):
        return {"gate_proj": {"weight": (width, hid)},
                "up_proj": {"weight": (width, hid)},
                "down_proj": {"weight": (hid, width)}}

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
                "o_proj": {"weight": (hid, heads * cfg.v_head_dim)}},
            "post_attention_norm": {"weight": (hid,)},
        }
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
    tree["final_norm"] = {"weight": (hid,)}
    tree["lm_head"] = {"weight": (cfg.vocab_size, hid)}
    return tree


class AXK1Model(nn.Module):
    """``init`` gives ``{"params": <axk1_param_shapes tree>}``;
    ``apply(params, tokens [b, s])`` is :func:`axk1_forward`."""
    config: AXK1Config

    @nn.compact
    def __call__(self, tokens):
        cfg = self.config
        tree = {name: self.param(name, _init_subtree, sub,
                                 cfg.params_dtype)
                for name, sub in axk1_param_shapes(cfg).items()}
        return axk1_forward(cfg, tree, tokens)


def axk1_model_provider(cfg: Optional[AXK1Config] = None) -> AXK1Model:
    return AXK1Model(cfg if cfg is not None else AXK1Config())
