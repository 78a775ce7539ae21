"""Standalone dots3-note-family decoder: latent attention of TWO geometries
in one model — the full layers' under a learned indexer, the window layers'
of a rank of their own over the last ``sliding_window`` positions — each
with a headwise output gate, and an expert FFN whose sigmoid router chooses
under a selection bias and which holds a share of its experts.

What the ``dots3_note`` ``model_type`` (dots-studio/dots3-note-prev
``config.json``) adds to the latent attention of :mod:`standalone_axk1`,
whose query, latent-row and up-projection pieces it shares:

* **a geometry a layer type** (``layer_types``): a ``full_attention`` layer
  is DeepSeek-V3's latent attention (``q_lora_rank``, ``kv_lora_rank``,
  ``num_attention_heads`` of ``qk_nope + qk_rope``, values of
  ``v_head_dim``, ``rope_theta``); a ``sliding_attention`` layer the same
  function with the ``swa_*`` sizes — a latent twice as wide, half the heads,
  wider keys, another RoPE base — over the ``sliding_window`` positions up
  to and including its own (:func:`geometry`);
* **a learned indexer on the full layers** — DeepSeek-V3.2's, as
  :mod:`standalone_hy4` has it (:func:`~standalone_hy4.index_project`): a
  full layer attends the ``index_topk`` causal positions of largest index
  score and no other, every full layer picking for itself; a window layer
  has no indexer;
* **a headwise gate** on both kinds of layer — ``o = W_O concat_h
  (sigmoid(W_G a)_h ctx_h)``, one sigmoid a head read from the sublayer's
  normed input (:func:`attn_gate`);
* **a selection-biased sigmoid router** (``topk_method: noaux_tc``): the
  experts CHOSEN are the top ``experts_per_token`` of ``sigmoid(x W_r) +
  e_score_correction_bias``, weighted by their sigmoid, renormalised
  (:func:`~apex_tpu.transformer.moe.dropless.route_group_limited`'s
  ``bias``), of ALL ``num_experts`` of which this chip HOLDS ``held =
  (first, count)``, plus one shared expert; the first ``dense_layers``
  layers a plain SwiGLU.

RMSNorm with a learned scale, no bias but the index key's LayerNorm and the
router's selection bias, untied head.  The ``init`` tree is a nested dict
that the serving loops in ``inference/models.py`` consume as is; the
``dots3`` record there IS the pieces below.  What the ``config.json`` leaves
open is marked ``ASSUMED`` on the line that decides it, as under
``assumed`` in ``benchmark/configs/dots3-note-prev-serve.json``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.transformer.moe.dropless import (
    dropless_moe_ffn,
    route_group_limited,
    swiglu,
)
from apex_tpu.transformer.testing.standalone_axk1 import (
    attn_absorb,
    attn_expand,
    attn_value_up,
)
from apex_tpu.transformer.testing.standalone_hy4 import (
    _plain_rope,
    attn_output,
    index_project,
)
from apex_tpu.transformer.testing.standalone_laguna import FULL, SLIDING

__all__ = ["Dots3Config", "MLAGeometry", "dots3_param_shapes", "geometry",
           "rope_cos_sin", "index_rope_cos_sin", "softmax_scale",
           "attn_expand", "attn_absorb", "attn_value_up", "attn_gate",
           "attn_output", "index_project", "ffn", "FULL", "SLIDING"]


@dataclasses.dataclass(frozen=True)
class MLAGeometry:
    """One latent-attention geometry: what :mod:`standalone_axk1`'s pieces
    (and :func:`attn_gate`, :func:`softmax_scale`) read of a config."""
    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    rms_eps: float

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """Values one cached position holds a layer: ``[c || k_pe]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim


@dataclasses.dataclass(frozen=True)
class Dots3Config:
    """Defaults give a test-scale model whose window and ``index_topk`` are
    far under its contexts; the published sizes are in
    ``benchmark/configs/dots3-note-prev-serve.json``."""
    vocab_size: int = 512
    hidden_size: int = 64
    layer_types: Tuple[str, ...] = (FULL, FULL, SLIDING, SLIDING, SLIDING)
    # the full layers' latent attention
    num_heads: int = 4
    q_lora_rank: int = 48
    kv_lora_rank: int = 32
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    rope_theta: float = 80000000.0
    # the window layers' own
    swa_num_heads: int = 2
    swa_q_lora_rank: int = 48
    swa_kv_lora_rank: int = 64
    swa_qk_nope_head_dim: int = 24
    swa_qk_rope_head_dim: int = 8
    swa_v_head_dim: int = 16
    swa_rope_theta: float = 50000.0
    sliding_window: int = 9
    # the full layers' indexer
    index_heads: int = 4
    index_head_dim: int = 16
    index_topk: int = 16
    index_q_chunk: int = 16          # query rows a prefill picks for at once
    # the FFN
    dense_layers: int = 1            # leading plain-SwiGLU layers
    ffn_hidden_size: int = 128
    moe_ffn_hidden_size: int = 32
    shared_ffn_hidden_size: int = 32
    num_experts: int = 16            # the router's outputs
    held: Tuple[int, int] = (0, 16)  # (first, count) held here
    experts_per_token: int = 4
    routed_scale: float = 1.0
    max_seq_length: int = 256
    rms_eps: float = 1e-5
    params_dtype: Any = jnp.float32

    def __post_init__(self):
        first, count = self.held
        if first < 0 or count < 1 or first + count > self.num_experts:
            raise ValueError(
                f"held={self.held} must lie inside the router's "
                f"{self.num_experts} experts")
        if set(self.layer_types) - {FULL, SLIDING} \
                or FULL not in self.layer_types:
            raise ValueError(
                f"layer_types {self.layer_types} take {FULL!r} and "
                f"{SLIDING!r}, at least one {FULL!r}")
        if not self.qk_rope_head_dim <= self.index_head_dim \
                or self.index_topk < 1 or self.sliding_window < 1:
            raise ValueError(
                f"the indexer ropes its leading qk_rope_head_dim "
                f"({self.qk_rope_head_dim}) channels of index_head_dim "
                f"({self.index_head_dim}); index_topk ({self.index_topk}) "
                f"and sliding_window ({self.sliding_window}) must be at "
                f"least 1")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def full_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layer_types) if t == FULL)

    @property
    def window_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layer_types)
                     if t == SLIDING)


def geometry(cfg: Dots3Config, layer_type: str) -> MLAGeometry:
    """The latent attention of a layer of ``layer_type``."""
    if layer_type == FULL:
        return MLAGeometry(cfg.num_heads, cfg.q_lora_rank, cfg.kv_lora_rank,
                           cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                           cfg.v_head_dim, cfg.rope_theta, cfg.rms_eps)
    return MLAGeometry(cfg.swa_num_heads, cfg.swa_q_lora_rank,
                       cfg.swa_kv_lora_rank, cfg.swa_qk_nope_head_dim,
                       cfg.swa_qk_rope_head_dim, cfg.swa_v_head_dim,
                       cfg.swa_rope_theta, cfg.rms_eps)


def softmax_scale(g: MLAGeometry) -> float:
    """``qk_head_dim ** -0.5`` of the layer's geometry."""
    # ASSUMED (e): 1/sqrt(the q/k head width), no YaRN factor
    # (rope_scaling is null)
    return g.qk_head_dim ** -0.5


def rope_cos_sin(cfg: Dots3Config, layer_type: str, positions):
    """``(cos, sin)`` ``[*positions.shape, rope]`` float32 of the layer
    type's own base, channel ``i`` with ``i + rope / 2``."""
    g = geometry(cfg, layer_type)
    return _plain_rope(g.rope_theta, g.qk_rope_head_dim, positions)


def index_rope_cos_sin(cfg: Dots3Config, positions):
    """The indexer's table over its first ``qk_rope_head_dim`` channels
    (the rest pass unroped), the full layers' base."""
    # ASSUMED (c): DeepSeek-V3.2's indexer as hy4 has it: the leading 64
    # of the 128 channels, the full layers' theta, rotate-half
    return _plain_rope(cfg.rope_theta, cfg.qk_rope_head_dim, positions)


# --------------------------------------------------------------------------
# attention: axk1's latent pieces of the layer's geometry, then the gate
# --------------------------------------------------------------------------

def attn_gate(g: MLAGeometry, lp, h):
    """``sigmoid(W_G a)`` as ``[..., heads, 1]`` in ``h``'s type: one gate
    a head, broadcast over its value channels."""
    # ASSUMED (d): headwise = one sigmoid a head, read from the sublayer's
    # normed input, applied to the head's output before W_O
    z = jnp.matmul(h, lp["attention"]["g_proj"]["weight"].T)
    return jax.nn.sigmoid(z.astype(jnp.float32)).astype(h.dtype).reshape(
        *h.shape[:-1], g.num_heads, 1)


# ASSUMED (a): apply_mla_qkv_lora_rescale names no factor and no place; no
# rescale is applied (a constant factor ahead of the latents' RMSNorm
# changes nothing beyond eps)


# --------------------------------------------------------------------------
# the FFN: dense first, then held experts under a biased router + a shared
# one
# --------------------------------------------------------------------------

def ffn(cfg: Dots3Config, i: int, lp, h, valid=None):
    """The layer's FFN over ``h [tokens, hidden]`` -> ``(y, stats)``;
    ``stats`` is None for a dense layer."""
    if i < cfg.dense_layers:
        m = lp["mlp"]
        return swiglu(h, m["gate_proj"]["weight"], m["up_proj"]["weight"],
                      m["down_proj"]["weight"]), None
    m = lp["moe"]

    def router(x, w):
        return route_group_limited(
            x, w, cfg.experts_per_token, cfg.routed_scale, n_group=1,
            topk_group=1, bias=m["router"]["e_score_correction_bias"])

    return dropless_moe_ffn(
        h, m["router"]["weight"], m["experts"]["w_gate"],
        m["experts"]["w_up"], m["experts"]["w_down"],
        top_k=cfg.experts_per_token, scale=cfg.routed_scale,
        shared=m["shared"], valid=valid, held=tuple(cfg.held),
        router=router)


# --------------------------------------------------------------------------
# the param tree
# --------------------------------------------------------------------------

def dots3_param_shapes(cfg: Dots3Config) -> dict:
    """The param tree's shapes.  Linear weights are ``[out, in]``; each
    layer's attention is of its type's geometry, with a gate of one row a
    head; a full layer also has an ``indexer``; the routed experts are
    expert-major stacks ``[held count, in, out]``; the router keeps a row
    and a selection bias for EVERY expert."""
    hid = cfg.hidden_size
    hi, di = cfg.index_heads, cfg.index_head_dim

    def mlp(width):
        return {"gate_proj": {"weight": (width, hid)},
                "up_proj": {"weight": (width, hid)},
                "down_proj": {"weight": (hid, width)}}

    tree = {"embed_tokens": {"weight": (cfg.vocab_size, hid)}}
    for i, t in enumerate(cfg.layer_types):
        g = geometry(cfg, t)
        heads = g.num_heads
        layer = {
            "input_norm": {"weight": (hid,)},
            "attention": {
                "q_a_proj": {"weight": (g.q_lora_rank, hid)},
                "q_a_norm": {"weight": (g.q_lora_rank,)},
                "q_b_proj": {"weight": (heads * g.qk_head_dim,
                                        g.q_lora_rank)},
                "kv_a_proj": {"weight": (g.latent_dim, hid)},
                "kv_a_norm": {"weight": (g.kv_lora_rank,)},
                "kv_b_proj": {"weight": (
                    heads * (g.qk_nope_head_dim + g.v_head_dim),
                    g.kv_lora_rank)},
                "g_proj": {"weight": (heads, hid)},
                "o_proj": {"weight": (hid, heads * g.v_head_dim)}},
            "post_attention_norm": {"weight": (hid,)},
        }
        if t == FULL:
            layer["indexer"] = {
                "q_proj": {"weight": (hi * di, g.q_lora_rank)},
                "k_proj": {"weight": (di, hid)},
                "k_norm": {"weight": (di,), "bias": (di,)},
                "w_proj": {"weight": (hi, hid)}}
        if i < cfg.dense_layers:
            layer["mlp"] = mlp(cfg.ffn_hidden_size)
        else:
            e, f = cfg.held[1], cfg.moe_ffn_hidden_size
            layer["moe"] = {
                "router": {"weight": (cfg.num_experts, hid),
                           "e_score_correction_bias": (cfg.num_experts,)},
                "experts": {"w_gate": (e, hid, f), "w_up": (e, hid, f),
                            "w_down": (e, f, hid)},
                "shared": mlp(cfg.shared_ffn_hidden_size)}
        tree[f"layer_{i}"] = layer
    tree["final_norm"] = {"weight": (hid,)}
    tree["lm_head"] = {"weight": (cfg.vocab_size, hid)}
    return tree
