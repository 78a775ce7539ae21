"""Standalone Keye-VL-2.0-family language model (ISSUE 36): grouped-query
attention over positions a LEARNED INDEXER picks, and an expert FFN in
every layer.

What the ``KeyeVL2`` ``model_type``'s language model (Kwai-Keye/
Keye-VL-2.0-30B-A3B ``config.json``) adds to the LLaMA recipe of
:mod:`standalone_llama`:

* **a learned sparse-attention indexer in every layer** (``sa_config``) —
  beside q, k and v a layer projects ``index_heads`` small index queries,
  ONE index key a position (which the cache keeps in a pool of its own)
  and a weight per index head; the index score of key ``s`` for query
  ``t`` is ``I[t, s] = sum_j w_t[j] * relu(qI_t[j] . kI_s)`` in float32,
  and the query attends the ``index_topk`` causal positions of largest
  score, and nothing else: the others get no probability mass
  (:func:`index_project`; the scores, the selection and the attention
  over the selection are ``ops/attention.py``'s and
  ``ops/paged_attention.py``'s);
* **per-head RMSNorm of q and k** before RoPE;
* **RoPE in three position sections** (``mrope_section``): a position is
  a triple (temporal, height, width) and each rotary pair turns by the
  axis its section names; a text token's three are equal, which is plain
  RoPE (:func:`mrope_cos_sin`, :func:`rope_cos_sin`).  The indexer ropes
  its 64 channels plainly, by the temporal position;
* **an expert FFN that drops no token in every layer**
  (:func:`~apex_tpu.transformer.moe.dropless.dropless_moe_ffn`): softmax
  router over all experts, top-k renormalised, no shared expert, every
  expert held.

RMSNorm with a learned scale, no bias on any projection, untied head.
The module is single-chip; its ``init`` tree is a nested dict that the
serving loops in ``inference/models.py`` consume as is, and the ``keye``
record there IS the per-layer pieces below, so the two cannot drift.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.ops import rms_norm
from apex_tpu.ops.attention import select_attention
from apex_tpu.transformer.functional.fused_rope import (
    fused_apply_rotary_pos_emb_cached,
)
from apex_tpu.transformer.moe.dropless import dropless_moe_ffn
from apex_tpu.transformer.testing.standalone_laguna import (
    _init_subtree,
    expand_kv,
)

__all__ = ["KeyeConfig", "KeyeModel", "keye_model_provider",
           "keye_param_shapes", "keye_forward", "forward_hidden",
           "mrope_cos_sin", "rope_cos_sin", "index_rope_cos_sin",
           "attn_project", "index_project", "attn_output", "ffn"]


@dataclasses.dataclass(frozen=True)
class KeyeConfig:
    """Defaults give a test-scale model whose ``index_topk`` is far under
    its context; the published sizes are in
    ``benchmark/configs/keye-vl-2.0-30b-a3b-serve.json``."""
    vocab_size: int = 512
    hidden_size: int = 64
    num_layers: int = 3
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 16
    mrope_section: Tuple[int, int, int] = (2, 3, 3)    # pairs per axis
    rope_theta: float = 10000000.0
    index_heads: int = 4
    index_head_dim: int = 8
    index_topk: int = 16
    index_q_chunk: int = 16        # query rows scored and selected at once
    moe_ffn_hidden_size: int = 32
    num_experts: int = 8
    experts_per_token: int = 2
    max_seq_length: int = 256
    rms_eps: float = 1e-6
    params_dtype: Any = jnp.float32

    def __post_init__(self):
        if self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"num_heads ({self.num_heads}) must be a multiple of "
                f"num_kv_heads ({self.num_kv_heads})")
        if 2 * sum(self.mrope_section) != self.head_dim:
            raise ValueError(
                f"mrope_section {self.mrope_section} must name every "
                f"rotary pair of a head of {self.head_dim}")
        if self.index_head_dim % 2 or self.index_topk < 1:
            raise ValueError(
                f"index_head_dim ({self.index_head_dim}) must be even and "
                f"index_topk ({self.index_topk}) at least 1")


# --------------------------------------------------------------------------
# RoPE: three position sections for q and k, plain for the indexer
# --------------------------------------------------------------------------

def mrope_cos_sin(cfg: KeyeConfig, pos_t, pos_h, pos_w):
    """``(cos, sin)`` ``[*pos.shape, head_dim]`` float32 for positions
    given as three equal-shaped axes: rotary pair ``i`` (channel ``i``
    with ``i + head_dim / 2``) turns by ``p_axis(i) * theta^(-i / pairs)``,
    ``axis(i)`` temporal for the first ``mrope_section[0]`` pairs, height
    for the next ``[1]``, width for the last ``[2]``."""
    pairs = cfg.head_dim // 2
    inv = cfg.rope_theta ** (-jnp.arange(pairs, dtype=jnp.float32) / pairs)
    axis = np.repeat(np.arange(3), cfg.mrope_section)       # [pairs]
    pos = jnp.stack([pos_t, pos_h, pos_w]).astype(jnp.float32)
    freqs = jnp.take(pos, axis, axis=0)             # [pairs, *pos.shape]
    freqs = jnp.moveaxis(freqs, 0, -1) * inv
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.cos(emb), jnp.sin(emb)


def rope_cos_sin(cfg: KeyeConfig, positions):
    """A text token's three axes are its index: plain RoPE."""
    return mrope_cos_sin(cfg, positions, positions, positions)


def index_rope_cos_sin(cfg: KeyeConfig, positions):
    """The indexer's RoPE over all ``index_head_dim`` channels."""
    # ASSUMED (c): the temporal position only, same theta
    pairs = cfg.index_head_dim // 2
    inv = cfg.rope_theta ** (-jnp.arange(pairs, dtype=jnp.float32) / pairs)
    freqs = positions.astype(jnp.float32)[..., None] * inv
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.cos(emb), jnp.sin(emb)


# --------------------------------------------------------------------------
# the per-layer pieces (shared with inference/models.py)
# --------------------------------------------------------------------------

def _linear(p, x):
    return jnp.matmul(x, p["weight"].T)


def _head_norm(x, weight, eps):
    """RMSNorm over a head's channels, float32 inside."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                            + eps)
    return (y * weight.astype(jnp.float32)).astype(x.dtype)


def _layer_norm(x, weight, bias, eps):
    x32 = x.astype(jnp.float32)
    xc = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    y = xc * jax.lax.rsqrt(jnp.mean(xc * xc, axis=-1, keepdims=True) + eps)
    return (y * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def attn_project(cfg: KeyeConfig, lp, h, cos, sin):
    """``h [..., hidden]`` -> normed and roped ``q [..., heads, d]`` and
    ``k [..., kvh, d]``, ``v [..., kvh, d]``.  ``cos``/``sin`` broadcast
    against ``[..., heads, d]``."""
    att, d = lp["attention"], cfg.head_dim
    q = _linear(att["q_proj"], h).reshape(*h.shape[:-1], cfg.num_heads, d)
    k = _linear(att["k_proj"], h).reshape(*h.shape[:-1], cfg.num_kv_heads,
                                          d)
    v = _linear(att["v_proj"], h).reshape(*h.shape[:-1], cfg.num_kv_heads,
                                          d)
    # ASSUMED (a): per-head RMSNorm on q and k before RoPE
    q = _head_norm(q, att["q_norm"]["weight"], cfg.rms_eps)
    k = _head_norm(k, att["k_norm"]["weight"], cfg.rms_eps)
    # ASSUMED (e): RoPE pairs channel i with i + half (rotate-half)
    q = fused_apply_rotary_pos_emb_cached(q, cos, sin)
    k = fused_apply_rotary_pos_emb_cached(k, cos, sin)
    return q, k, v


def index_project(cfg: KeyeConfig, lp, h, cos, sin):
    """``h [..., hidden]`` -> index queries ``qi [..., index_heads, di]``
    (roped), head weights ``wi [..., index_heads]`` (float32, scaled) and
    the ONE index key ``ki [..., di]`` (LayerNorm, roped) the cache keeps.
    ``cos``/``sin`` ``[..., di]`` are :func:`index_rope_cos_sin`'s."""
    ix, hi, di = lp["indexer"], cfg.index_heads, cfg.index_head_dim
    # ASSUMED (b): the lightning indexer's form, queries from the normed
    # hidden state (this model has no query latent)
    qi = _linear(ix["q_proj"], h).reshape(*h.shape[:-1], hi, di)
    qi = fused_apply_rotary_pos_emb_cached(qi, cos[..., None, :],
                                           sin[..., None, :])
    ki = _layer_norm(_linear(ix["k_proj"], h), ix["k_norm"]["weight"],
                     ix["k_norm"]["bias"], cfg.rms_eps)
    ki = fused_apply_rotary_pos_emb_cached(ki, cos, sin)
    wi = _linear(ix["w_proj"], h).astype(jnp.float32) * (
        hi ** -0.5 * di ** -0.5)
    return qi, wi, ki


def attn_output(lp, ctx):
    """``ctx [..., heads, d]`` -> ``[..., hidden]``."""
    return _linear(lp["attention"]["o_proj"],
                   ctx.reshape(*ctx.shape[:-2], -1))


def ffn(cfg: KeyeConfig, lp, h, valid=None):
    """The layer's expert FFN over ``h [tokens, hidden]`` -> ``(y,
    stats)``: softmax router, top-k renormalised, every expert held, no
    shared expert, no scaling factor."""
    m = lp["moe"]
    return dropless_moe_ffn(
        h, m["router"]["weight"], m["experts"]["w_gate"],
        m["experts"]["w_up"], m["experts"]["w_down"],
        top_k=cfg.experts_per_token, scale=1.0, valid=valid)


def forward_hidden(cfg: KeyeConfig, p, tokens):
    """The causal stack over ONE sequence ``tokens [1, s]`` -> the
    final-normed stream ``[1, s, hidden]``."""
    b, s = tokens.shape
    if b != 1:
        raise ValueError(f"the selection is a sequence's own: one "
                         f"sequence at a time, got batch {b}")
    x = jnp.take(p["embed_tokens"]["weight"], tokens[0], axis=0)
    pos = jnp.arange(s, dtype=jnp.int32)
    rope = tuple(c[:, None, :] for c in rope_cos_sin(cfg, pos))
    irope = index_rope_cos_sin(cfg, pos)
    for i in range(cfg.num_layers):
        lp = p[f"layer_{i}"]
        h1 = rms_norm(x, lp["input_norm"]["weight"], eps=cfg.rms_eps)
        q, k, v = attn_project(cfg, lp, h1, *rope)          # [s, n, d]
        qi, wi, ki = index_project(cfg, lp, h1, *irope)
        q, k, v = (t.transpose(1, 0, 2)[None] for t in (q, k, v))
        ctx, _ = select_attention(
            q, expand_kv(k, cfg.num_heads), expand_kv(v, cfg.num_heads),
            qi, wi, ki, topk=cfg.index_topk, block_q=cfg.index_q_chunk)
        x = x + attn_output(lp, ctx[0].transpose(1, 0, 2))
        h2 = rms_norm(x, lp["post_attention_norm"]["weight"],
                      eps=cfg.rms_eps)
        x = x + ffn(cfg, lp, h2)[0]
    return rms_norm(x, p["final_norm"]["weight"], eps=cfg.rms_eps)[None]


def keye_forward(cfg: KeyeConfig, p, tokens):
    """Full causal forward ``tokens [1, s]`` -> logits ``[1, s, vocab]``:
    the training-shaped pass.  Serving runs the same per-layer pieces
    through ``inference/models.py``'s loops."""
    return _linear(p["lm_head"], forward_hidden(cfg, p, tokens))


# --------------------------------------------------------------------------
# the flax module: its init tree is what every forward consumes
# --------------------------------------------------------------------------

def keye_param_shapes(cfg: KeyeConfig) -> dict:
    """The param tree's shapes.  Linear weights are ``[out, in]``; the
    routed experts are expert-major stacks ``[experts, in, out]``."""
    hid, d = cfg.hidden_size, cfg.head_dim
    hi, di = cfg.index_heads, cfg.index_head_dim
    e, f = cfg.num_experts, cfg.moe_ffn_hidden_size
    tree = {"embed_tokens": {"weight": (cfg.vocab_size, hid)}}
    for i in range(cfg.num_layers):
        tree[f"layer_{i}"] = {
            "input_norm": {"weight": (hid,)},
            "attention": {
                "q_proj": {"weight": (cfg.num_heads * d, hid)},
                "k_proj": {"weight": (cfg.num_kv_heads * d, hid)},
                "v_proj": {"weight": (cfg.num_kv_heads * d, hid)},
                "q_norm": {"weight": (d,)},
                "k_norm": {"weight": (d,)},
                "o_proj": {"weight": (hid, cfg.num_heads * d)}},
            "indexer": {
                "q_proj": {"weight": (hi * di, hid)},
                "k_proj": {"weight": (di, hid)},
                "k_norm": {"weight": (di,), "bias": (di,)},
                "w_proj": {"weight": (hi, hid)}},
            "post_attention_norm": {"weight": (hid,)},
            "moe": {
                "router": {"weight": (e, hid)},
                "experts": {"w_gate": (e, hid, f), "w_up": (e, hid, f),
                            "w_down": (e, f, hid)}},
        }
    tree["final_norm"] = {"weight": (hid,)}
    tree["lm_head"] = {"weight": (cfg.vocab_size, hid)}
    return tree


class KeyeModel(nn.Module):
    """``init`` gives ``{"params": <keye_param_shapes tree>}``;
    ``apply(params, tokens [1, s])`` is :func:`keye_forward`."""
    config: KeyeConfig

    @nn.compact
    def __call__(self, tokens):
        cfg = self.config
        tree = {name: self.param(name, _init_subtree, sub,
                                 cfg.params_dtype)
                for name, sub in keye_param_shapes(cfg).items()}
        return keye_forward(cfg, tree, tokens)


def keye_model_provider(cfg: Optional[KeyeConfig] = None) -> KeyeModel:
    return KeyeModel(cfg if cfg is not None else KeyeConfig())
