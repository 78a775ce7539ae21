"""Standalone Laguna-family decoder (ISSUE 30): a mixture-of-experts
decoder whose layers differ among themselves.

What the ``laguna`` ``model_type`` (poolside/Laguna-XS.2 ``config.json``)
adds to the LLaMA recipe of :mod:`standalone_llama`:

* **a head count per layer** — every layer has ``num_kv_heads`` KV heads
  of ``head_dim``, but the number of QUERY heads is a per-layer static
  (``heads_per_layer``);
* **window layers beside full ones** — ``layer_types[i]`` is ``"full"``
  (causal) or ``"sliding"`` (query ``i`` sees key ``j`` iff
  ``i - window < j <= i``), each type with its own RoPE: full layers
  rotate only the first ``rope_full.rotary_dim`` channels of a head with
  YaRN-scaled frequencies, sliding layers the whole head, plain;
* **a per-head sigmoid gate** on the attention output;
* **an expert FFN that drops no token** (``mlp_types[i] == "sparse"``):
  :func:`apex_tpu.transformer.moe.dropless.dropless_moe_ffn` — softmax
  router over all experts, top-k renormalised and scaled, SwiGLU
  experts, one shared expert; ``"dense"`` layers are a plain SwiGLU.

RMSNorm with a learned scale, no bias anywhere, untied head.  The module
is single-chip (no TP layers): its ``init`` tree is a nested dict that
the serving loops in ``inference/models.py`` consume as is, and the
``laguna`` record there IS the per-layer pieces below (``attn_project``,
``attn_output``, ``ffn``, ``rope_cos_sin``), so the two cannot drift.
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
from apex_tpu.transformer.moe.dropless import dropless_moe_ffn, swiglu

__all__ = ["LagunaConfig", "YarnRope", "LagunaModel",
           "laguna_model_provider", "laguna_param_shapes",
           "laguna_forward", "forward_hidden", "yarn_inv_freq",
           "rope_cos_sin"]

FULL, SLIDING = "full", "sliding"
DENSE, SPARSE = "dense", "sparse"


@dataclasses.dataclass(frozen=True)
class YarnRope:
    """RoPE of the full-attention layers: the first ``rotary_dim``
    channels of each head, YaRN frequencies (Peng et al. 2023), cos/sin
    scaled by ``attention_factor``."""
    theta: float = 500000.0
    rotary_dim: int = 64
    factor: float = 64.0
    original_max_position: int = 4096
    beta_fast: float = 64.0
    beta_slow: float = 1.0
    attention_factor: float = 1.4158883083359672


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    """Defaults give a test-scale model with both layer types, both FFN
    types and two head counts; the published sizes are in
    ``benchmark/configs/laguna-xs.2-serve.json``."""
    vocab_size: int = 512
    hidden_size: int = 64
    num_kv_heads: int = 2
    head_dim: int = 16
    heads_per_layer: Tuple[int, ...] = (4, 6, 6, 4)
    layer_types: Tuple[str, ...] = (FULL, SLIDING, SLIDING, FULL)
    mlp_types: Tuple[str, ...] = (DENSE, SPARSE, SPARSE, SPARSE)
    ffn_hidden_size: int = 128                 # the dense layers' SwiGLU
    moe_ffn_hidden_size: int = 32              # one routed expert
    shared_ffn_hidden_size: int = 32           # the shared expert
    num_experts: int = 8
    experts_per_token: int = 2
    routed_scale: float = 2.5
    sliding_window: int = 8
    max_seq_length: int = 256
    rms_eps: float = 1e-6
    rope_full: YarnRope = YarnRope(rotary_dim=8, original_max_position=32)
    rope_sliding_theta: float = 10000.0
    params_dtype: Any = jnp.float32

    def __post_init__(self):
        n = len(self.layer_types)
        if len(self.heads_per_layer) != n or len(self.mlp_types) != n:
            raise ValueError(
                "heads_per_layer, layer_types and mlp_types must name "
                f"the same layers; got {len(self.heads_per_layer)}, {n}, "
                f"{len(self.mlp_types)}")
        for h in self.heads_per_layer:
            if h % self.num_kv_heads:
                raise ValueError(
                    f"a layer's heads ({h}) must be a multiple of "
                    f"num_kv_heads ({self.num_kv_heads})")
        if set(self.layer_types) - {FULL, SLIDING} \
                or set(self.mlp_types) - {DENSE, SPARSE}:
            raise ValueError(
                f"layer_types take {FULL!r}/{SLIDING!r}, mlp_types "
                f"{DENSE!r}/{SPARSE!r}")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def full_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layer_types)
                     if t == FULL)

    @property
    def window_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layer_types)
                     if t == SLIDING)

    @property
    def expert_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.mlp_types)
                     if t == SPARSE)


# --------------------------------------------------------------------------
# RoPE: two tables, chosen by layer type
# --------------------------------------------------------------------------

def yarn_inv_freq(r: YarnRope):
    """YaRN inverse frequencies ``[rotary_dim / 2]`` (numpy-free python:
    the values are compile-time constants).  ``extrap`` is the plain
    RoPE frequency, ``interp = extrap / factor``; a linear ramp between
    the dimensions that turn ``beta_fast`` and ``beta_slow`` times over
    the original context blends the two."""
    dim, base = r.rotary_dim, r.theta

    def turns_dim(rot):        # the dimension that turns `rot` times
        return dim * math.log(r.original_max_position
                              / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(turns_dim(r.beta_fast)), 0)
    high = min(math.ceil(turns_dim(r.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    out = []
    for i in range(dim // 2):
        extrap = base ** (-2.0 * i / dim)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(extrap / r.factor * ramp + extrap * (1.0 - ramp))
    return out


def rope_cos_sin(cfg: LagunaConfig, layer_type: str, positions):
    """``(cos, sin)`` ``[*positions.shape, rot_dim]`` float32 for the
    layer type's RoPE at ``positions`` (half-split ``rotate_half``
    layout, what ``fused_rope._apply`` expects; channels past
    ``rot_dim`` pass through unrotated)."""
    if layer_type == FULL:
        inv = jnp.asarray(yarn_inv_freq(cfg.rope_full), jnp.float32)
        factor = cfg.rope_full.attention_factor
    else:
        d = cfg.head_dim
        inv = 1.0 / (cfg.rope_sliding_theta ** (
            jnp.arange(0, d, 2, dtype=jnp.float32) / d))
        factor = 1.0
    freqs = positions.astype(jnp.float32)[..., None] * inv
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.cos(emb) * factor, jnp.sin(emb) * factor


# --------------------------------------------------------------------------
# the per-layer pieces (shared with inference/models.py)
# --------------------------------------------------------------------------

def _linear(p, x):
    return jnp.matmul(x, p["weight"].T)


def attn_project(cfg: LagunaConfig, i: int, lp, h, cos, sin):
    """``h [..., hidden]`` -> roped ``q [..., H_i, d]``, roped ``k`` and
    ``v`` ``[..., kvh, d]``, gate logits ``[..., H_i]``.  ``cos``/``sin``
    broadcast against ``[..., heads, rot_dim]``."""
    att, d = lp["attention"], cfg.head_dim
    q = _linear(att["q_proj"], h).reshape(
        *h.shape[:-1], cfg.heads_per_layer[i], d)
    k = _linear(att["k_proj"], h).reshape(*h.shape[:-1],
                                          cfg.num_kv_heads, d)
    v = _linear(att["v_proj"], h).reshape(*h.shape[:-1],
                                          cfg.num_kv_heads, d)
    # ASSUMED (e): no normalisation of q, k beyond RoPE
    q = fused_apply_rotary_pos_emb_cached(q, cos, sin)
    k = fused_apply_rotary_pos_emb_cached(k, cos, sin)
    return q, k, v, _linear(att["g_proj"], h)


def attn_output(lp, ctx, gate):
    """``ctx [..., H_i, d]`` -> ``[..., hidden]``: each head's output
    times its gate, then the output projection."""
    # ASSUMED (a): the gate is per head and a sigmoid
    ctx = ctx * jax.nn.sigmoid(gate.astype(jnp.float32))[..., None].astype(
        ctx.dtype)
    return _linear(lp["attention"]["o_proj"],
                   ctx.reshape(*ctx.shape[:-2], -1))


def ffn(cfg: LagunaConfig, i: int, lp, h, valid=None):
    """The layer's FFN over ``h [tokens, hidden]`` -> ``(y, stats)``;
    ``stats`` is None for a dense layer."""
    if cfg.mlp_types[i] == DENSE:
        m = lp["mlp"]
        return swiglu(h, m["gate_proj"]["weight"], m["up_proj"]["weight"],
                      m["down_proj"]["weight"]), None
    m = lp["moe"]
    # ASSUMED (b), (c), (d): softmax router without bias or soft cap,
    # top-k renormalised (route_top_k); the shared expert added ungated
    return dropless_moe_ffn(
        h, m["router"]["weight"], m["experts"]["w_gate"],
        m["experts"]["w_up"], m["experts"]["w_down"],
        top_k=cfg.experts_per_token, scale=cfg.routed_scale,
        shared=m["shared"], valid=valid)


def expand_kv(t, heads: int):
    """``[b, kvh, s, d]`` -> ``[b, heads, s, d]``: query head ``a`` reads
    KV head ``a // (heads / kvh)``."""
    b, kvh, s, d = t.shape
    return jnp.broadcast_to(t[:, :, None], (b, kvh, heads // kvh, s, d)
                            ).reshape(b, heads, s, d)


def forward_hidden(cfg: LagunaConfig, p, tokens):
    """The causal stack over ``tokens [b, s]`` -> the final-normed stream
    ``[b, s, hidden]``."""
    b, s = tokens.shape
    x = jnp.take(p["embed_tokens"]["weight"], tokens, axis=0)
    pos = jnp.arange(s, dtype=jnp.int32)
    rope = {t: tuple(c[None, :, None, :] for c in rope_cos_sin(cfg, t, pos))
            for t in dict.fromkeys(cfg.layer_types)}
    for i in range(cfg.num_layers):
        lp = p[f"layer_{i}"]
        h1 = rms_norm(x, lp["input_norm"]["weight"], eps=cfg.rms_eps)
        q, k, v, g = attn_project(cfg, i, lp, h1, *rope[cfg.layer_types[i]])
        heads = cfg.heads_per_layer[i]
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        ctx = flash_attention(
            q, expand_kv(k, heads), expand_kv(v, heads), causal=True,
            window=(cfg.sliding_window
                    if cfg.layer_types[i] == SLIDING else None))
        x = x + attn_output(lp, ctx.transpose(0, 2, 1, 3), g)
        h2 = rms_norm(x, lp["post_attention_norm"]["weight"],
                      eps=cfg.rms_eps)
        x = x + ffn(cfg, i, lp, h2.reshape(b * s, -1))[0].reshape(b, s, -1)
    return rms_norm(x, p["final_norm"]["weight"], eps=cfg.rms_eps)


def laguna_forward(cfg: LagunaConfig, p, tokens):
    """Full causal forward ``tokens [b, s]`` -> logits ``[b, s, vocab]``:
    the training-shaped pass, the module's own as ``standalone_gpt`` and
    ``standalone_llama`` keep theirs.  Serving runs the same per-layer
    pieces through ``inference/models.py``'s loops."""
    return _linear(p["lm_head"], forward_hidden(cfg, p, tokens))


# --------------------------------------------------------------------------
# the flax module: its init tree is what every forward consumes
# --------------------------------------------------------------------------

def laguna_param_shapes(cfg: LagunaConfig) -> dict:
    """The param tree's shapes: ``{top-level name: nested dict of shape
    tuples}``.  Linear weights are ``[out, in]``; the routed experts are
    expert-major stacks ``[experts, in, out]``."""
    hid, d, kvh = cfg.hidden_size, cfg.head_dim, cfg.num_kv_heads

    def mlp(width):
        return {"gate_proj": {"weight": (width, hid)},
                "up_proj": {"weight": (width, hid)},
                "down_proj": {"weight": (hid, width)}}

    tree = {"embed_tokens": {"weight": (cfg.vocab_size, hid)}}
    for i in range(cfg.num_layers):
        heads = cfg.heads_per_layer[i]
        layer = {
            "input_norm": {"weight": (hid,)},
            "attention": {
                "q_proj": {"weight": (heads * d, hid)},
                "k_proj": {"weight": (kvh * d, hid)},
                "v_proj": {"weight": (kvh * d, hid)},
                "g_proj": {"weight": (heads, hid)},
                "o_proj": {"weight": (hid, heads * d)}},
            "post_attention_norm": {"weight": (hid,)},
        }
        if cfg.mlp_types[i] == DENSE:
            layer["mlp"] = mlp(cfg.ffn_hidden_size)
        else:
            e, f = cfg.num_experts, cfg.moe_ffn_hidden_size
            layer["moe"] = {
                "router": {"weight": (e, hid)},
                "experts": {"w_gate": (e, hid, f), "w_up": (e, hid, f),
                            "w_down": (e, f, hid)},
                "shared": mlp(cfg.shared_ffn_hidden_size)}
        tree[f"layer_{i}"] = layer
    tree["final_norm"] = {"weight": (hid,)}
    tree["lm_head"] = {"weight": (cfg.vocab_size, hid)}
    return tree


def _init_subtree(key, shapes, dtype):
    """normal(0, 0.02) matrices, norm scales of one."""
    leaves, treedef = jax.tree_util.tree_flatten(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    out = [jnp.ones(s, dtype) if len(s) == 1 else
           (0.02 * jax.random.normal(jax.random.fold_in(key, n), s,
                                     jnp.float32)).astype(dtype)
           for n, s in enumerate(leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


class LagunaModel(nn.Module):
    """``init`` gives ``{"params": <laguna_param_shapes tree>}``;
    ``apply(params, tokens [b, s])`` is :func:`laguna_forward`."""
    config: LagunaConfig

    @nn.compact
    def __call__(self, tokens):
        cfg = self.config
        tree = {name: self.param(name, _init_subtree, sub,
                                 cfg.params_dtype)
                for name, sub in laguna_param_shapes(cfg).items()}
        return laguna_forward(cfg, tree, tokens)


def laguna_model_provider(cfg: Optional[LagunaConfig] = None) -> LagunaModel:
    return LagunaModel(cfg if cfg is not None else LagunaConfig())
