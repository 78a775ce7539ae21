"""Prefill / decode / verify forwards over the standalone model param
trees: ONE record per model kind, ONE layer loop per mode.

The training models (``transformer/testing/standalone_*``) are flax
modules built for the training shapes; inference needs the same math
split into a *prefill* (full prompt, causal flash attention, emitting
every layer's k/v for the cache), a *decode* (one token per slot against
the cache) and a speculative *verify* (a drafted slab per slot).  The
forwards consume the EXACT param pytree ``model.init`` produces — no
re-keying, no conversion step.

**A kind is a record** (:class:`Kind`, five entries in :data:`KINDS`):
its geometry (``dims`` / ``check``), its per-layer pieces (``embed``,
``rope``, ``norm``, ``project``, ``attn_out``, ``ffn``, ``head``), its
fused-block layout or ``None``, the names of the counters its steps
report (``stats``) and what is not built for it, feature by feature,
with the reason (``refuses``).  ``gpt`` and ``llama`` pieces are the
flax modules' own op sequence (same fused LayerNorm/RMSNorm kernels,
same RoPE convention, same qkv reshape/split layout), which is what
lets ``tests/L0/run_inference/test_engine_parity.py`` pin prefill +
decode against ``model.apply``; ``laguna``'s pieces ARE
``standalone_laguna``'s (``attn_project`` / ``attn_output`` / ``ffn`` /
``rope_cos_sin``), held to the benchmark's plain reference by
``test_laguna_parity.py``; ``axk1``'s are ``standalone_axk1``'s
(ISSUE 34), held to its reference by ``test_axk1_parity.py``; ``keye``'s
are ``standalone_keye``'s (ISSUE 36), held to its reference by
``test_keye_parity.py``.

**A mode is a loop** (:func:`prefill_forward`, :func:`decode_forward`,
:func:`verify_forward`): each owns its activation layout, where k/v go
(collected for the insert; appended to the pool or a ring; appended as a
slab), which attention reads them, and — in decode — the fused-block and
``tp > 1`` branches.  Whether a layer lives in the paged pool or in a
per-slot window ring is read from the record's ``layer_types``, never
from the kind's name; the engine (``engine.py``) likewise asks the
record, so adding a kind is adding a record.

**How a layer attends is the record's too.**  Most kinds project q, k
and v, cache k and v per KV head and attend them in both modes.  A kind
with LATENT attention (``Kind.latent``, ISSUE 34) caches one row a
position — the pool has no KV-head axis and no value array — and attends
it in two forms of the same function: *expanded* in prefill (keys and
values made from the latent a layer at a time, then the flash kernel with
a value width of its own) and *absorbed* in decode (the key up-projection
folded into the query, ``apex_paged_decode_latent`` over the latent pool,
the value up-projection behind it).  A kind whose layers SELECT
(``Kind.select``, ISSUE 36) projects, beside q, k and v, a few small index
queries and ONE index key a position, which the cache keeps in a pool of
its own; a query attends the ``topk`` cached positions of largest index
score and no other.  Prefill scores, picks and attends a block of query
rows at a time (``ops.attention.select_attention``); decode runs three
stages a layer over the paged pools — index scores along the step's work
list (``apex_dsa_index``), the picked set of each slot on the device
(``select_top_mask``), attention over the picked rows
(``apex_dsa_attend``).  A kind may do both (``hy4``): latent
attention over the picked positions only (``apex_dsa_attend_latent`` in
decode), with the picks of one layer REUSED by the layers after it that
hold no indexer (``Select.sources``) — so the index-key pool keeps the
picking layers' keys alone — and a residual carried in several streams
(``Kind.residual``: where the other kinds write ``h + sublayer``, the loops
ask the record for the sublayer's input and for the streams after it).  A
latent kind may give each layer TYPE a geometry of its own
(``Latent.geometry``, ``dots3``): its full layers attend through the latent
pool under a learned indexer, its window layers through per-slot rings of
latent rows of another width (``ring_decode_attention_latent`` in decode,
the windowed flash kernel in prefill).

Unsupported training-only configs (scan_layers, the capacity-slot MoE
FFN of ``transformer/moe/MoELayer``, sequence/context parallelism) fail
loudly at engine construction (``Kind.check``).

Multi-chip serving (ISSUE 17): every forward takes a static ``tp`` and,
at ``tp > 1``, runs as the per-rank body of a ``shard_map`` over the
``parallel_state`` tensor axis — the column/row partitioning of the
training ``transformer/tensor_parallel`` layers: qkv / gate / up
column-sharded over heads/ffn (no comm), out-proj and down-proj
row-sharded with ONE psum each (:func:`_row_linear`), embedding and LM
head vocab-sharded (:func:`_vocab_embed`, :func:`_gather_logits`), GQA /
MQA kv heads replicated below tp (:func:`expand_kv_for_tp`).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.inference import kv_cache
from apex_tpu.ops import layer_norm, rms_norm
from apex_tpu.ops.attention import (
    decode_attention,
    flash_attention,
    prefix_window_attention,
    ring_decode_attention,
    ring_decode_attention_latent,
    select_attend,
    select_attention,
    select_picks,
    select_top_mask,
    slab_decode_attention,
)
from apex_tpu.ops.paged_attention import (
    fused_block_decode,
    paged_decode_attention,
    paged_index_scores,
    paged_select_attention,
    paged_select_attention_latent,
    paged_slab_attention,
    paged_work_list,
)
from apex_tpu.transformer.functional.fused_rope import (
    fused_apply_rotary_pos_emb_cached,
)
from apex_tpu.transformer.moe.dropless import fold_stats
from apex_tpu.transformer.parallel_state import TENSOR_AXIS
from apex_tpu.transformer.testing import standalone_axk1 as axk1
from apex_tpu.transformer.testing import standalone_dots3 as dots3
from apex_tpu.transformer.testing import standalone_hy4 as hy4
from apex_tpu.transformer.testing import standalone_keye as keye
from apex_tpu.transformer.testing import standalone_laguna as laguna
from apex_tpu.transformer.testing.standalone_llama import _rope_cos_sin

__all__ = ["Kind", "KINDS", "model_dims", "tp_dims", "check_supported",
           "prefill_forward", "EXPERT_STATS", "SELECT_STATS", "REUSE_STATS",
           "WINDOW_STATS", "stats_tail", "Latent", "Select", "Residual",
           "cache_row_values", "cache_position_values", "ring_row_values",
           "decode_forward",
           "verify_forward",
           "fused_layer_params", "expand_kv_for_tp",
           "param_partition_specs", "fused_partition_specs"]

#: a layer of this type keeps every position, in the paged pool; any
#: other type keeps its last ``window`` positions in a per-slot ring
FULL = laguna.FULL
#: the key under which a kind that SELECTS hands the loops its indexer's
#: RoPE table, beside the layer types' tables
INDEX = "index"


# --------------------------------------------------------------------------
# what every kind's pieces are made of
# --------------------------------------------------------------------------

def _params_subtree(params):
    """Accept ``model.init``'s ``{"params": ...}`` or the bare tree."""
    return params["params"] if "params" in params and isinstance(
        params["params"], dict) else params


def _linear(p, x):
    """Column/RowParallelLinear at tp=1: ``x @ W.T (+ b)`` with the
    layers' ``[out, in]`` weight layout."""
    y = jnp.matmul(x, p["weight"].T)
    if "bias" in p:
        y = y + p["bias"]
    return y


def _row_linear(p, x, tp):
    """RowParallelLinear forward: the local in-shard matmul, ONE psum
    at the row boundary, bias added once AFTER the reduction (the
    training layers' ``reduce_from_tensor_model_parallel_region``
    discipline — a per-rank bias would add ``tp`` copies).  At tp=1
    this is :func:`_linear` op for op."""
    y = jnp.matmul(x, p["weight"].T)
    if tp > 1:
        y = jax.lax.psum(y, TENSOR_AXIS)
    if "bias" in p:
        y = y + p["bias"]
    return y


def _vocab_embed(emb_w, tokens, tp):
    """Vocab-parallel embedding lookup (the ``VocabParallelEmbedding``
    mask-clip-take-zero-psum, shared with the PR 9 vocab-parallel xent
    target pick): each rank holds rows ``[rank*vp, (rank+1)*vp)`` of
    the table, out-of-shard tokens gather row 0 and are zeroed, and the
    psum reassembles the full embedding replica-uniform."""
    if tp <= 1:
        return jnp.take(emb_w, tokens, axis=0)
    vp = emb_w.shape[0]
    start = jax.lax.axis_index(TENSOR_AXIS) * vp
    mask = (tokens < start) | (tokens >= start + vp)
    local = jnp.clip(tokens - start, 0, vp - 1)
    e = jnp.take(emb_w, local, axis=0)
    e = jnp.where(mask[..., None], jnp.zeros((), e.dtype), e)
    return jax.lax.psum(e, TENSOR_AXIS)


def _gather_logits(local, tp):
    """Reassemble vocab-sharded logits: a tiled ``all_gather`` over the
    tensor axis concatenates the rank shards along the vocab dim in
    rank-major order — which IS the original vocab order (shard ``r``
    holds rows ``[r*vp, (r+1)*vp)``), so greedy/sampled tokens off the
    gathered logits are replica-uniform with one folded key."""
    if tp <= 1:
        return local
    return jax.lax.all_gather(local, TENSOR_AXIS,
                              axis=local.ndim - 1, tiled=True)


def _merge_heads(ctx):
    """``[..., heads, head_dim]`` -> ``[..., heads * head_dim]``."""
    return ctx.reshape(*ctx.shape[:-2], -1)


def _fused_bias(p, width):
    """A linear's bias as the fused layout's ``[1, width]`` row (zeros
    when the layer was built bias-free)."""
    if "bias" in p:
        return p["bias"].reshape(1, width)
    return jnp.zeros((1, width), p["weight"].dtype)


# --------------------------------------------------------------------------
# GPT (standalone_gpt's op sequence)
# --------------------------------------------------------------------------

def _gpt_dims(cfg) -> dict:
    return {"layers": cfg.num_layers, "heads": cfg.num_attention_heads,
            "kv_heads": cfg.num_attention_heads,
            "head_dim": cfg.hidden_size // cfg.num_attention_heads,
            "layer_types": (FULL,) * cfg.num_layers,
            "pool_layers": cfg.num_layers, "window_layers": 0, "window": 0,
            "latent": 0, "index": 0}


def _check_dense(cfg) -> None:
    for flag in ("sequence_parallel", "context_parallel", "scan_layers"):
        if getattr(cfg, flag, False):
            raise ValueError(
                f"inference forwards run tp=1 unrolled; cfg.{flag} is a "
                "training-topology knob — export the weights into a "
                "plain config instead")
    if getattr(cfg, "num_moe_experts", None):
        raise ValueError("MoE FFN decode is not implemented yet")


def _gpt_embed(p, tokens, positions, tp):
    emb = p["embedding"]
    h = _vocab_embed(emb["word_embeddings"]["weight"], tokens, tp)
    tab = emb["position_embeddings"]
    if positions is None:               # a cold prefill: rows 0 .. s
        return h + tab[None, :tokens.shape[1], :]
    return h + jnp.take(tab, positions, axis=0)


def _gpt_norm(cfg, p, which, x):
    n = p[which + "_layernorm"]
    return layer_norm(x, n["weight"], n["bias"])


def _gpt_project(cfg, dims, i, lp, h, rope):
    """qkv projection + the model's reshape/split layout: q/k/v with a
    trailing ``[..., heads, head_dim]``."""
    qkv = _linear(lp["self_attention"]["query_key_value"], h)
    qkv = qkv.reshape(*h.shape[:-1], dims["heads_local"],
                      3 * dims["head_dim"])
    return (*jnp.split(qkv, 3, axis=-1), None)


def _gpt_attn_out(lp, ctx, extra, tp):
    return _row_linear(lp["self_attention"]["dense"], _merge_heads(ctx), tp)


def _gpt_ffn(cfg, i, lp, h, valid, tp):
    return _row_linear(lp["mlp"]["dense_4h_to_h"],
                       jax.nn.gelu(_linear(lp["mlp"]["dense_h_to_4h"],
                                           h)), tp), None


def _gpt_head(p, h, tp):                                    # tied head
    return jnp.einsum("...h,vh->...v", h,
                      p["embedding"]["word_embeddings"]["weight"])


def _gpt_fused_layout(cfg, dims, lp):
    """The interleaved ``query_key_value`` columns (per head: ``[q(d),
    k(d), v(d)]``) deinterleaved into ``wq``/``wk``/``wv``."""
    heads, d, hidden = dims["heads"], dims["head_dim"], cfg.hidden_size
    att = lp["self_attention"]
    w = jnp.transpose(att["query_key_value"]["weight"])
    w = w.reshape(hidden, heads, 3, d)
    b = _fused_bias(att["query_key_value"],
                    3 * heads * d).reshape(heads, 3, d)
    return {
        "ln1_w": lp["input_layernorm"]["weight"].reshape(1, hidden),
        "ln1_b": lp["input_layernorm"]["bias"].reshape(1, hidden),
        "wq": w[:, :, 0, :].reshape(hidden, heads * d),
        "bq": b[:, 0, :].reshape(1, heads * d),
        "wk": w[:, :, 1, :].reshape(hidden, heads * d),
        "bk": b[:, 1, :].reshape(1, heads * d),
        "wv": w[:, :, 2, :].reshape(hidden, heads * d),
        "bv": b[:, 2, :].reshape(1, heads * d),
        "wo": jnp.transpose(att["dense"]["weight"]),
        "bo": _fused_bias(att["dense"], hidden),
        "ln2_w": lp["post_attention_layernorm"]["weight"].reshape(
            1, hidden),
        "ln2_b": lp["post_attention_layernorm"]["bias"].reshape(
            1, hidden),
        "wu": jnp.transpose(lp["mlp"]["dense_h_to_4h"]["weight"]),
        "bu": _fused_bias(lp["mlp"]["dense_h_to_4h"], cfg.ffn),
        "wd": jnp.transpose(lp["mlp"]["dense_4h_to_h"]["weight"]),
        "bd": _fused_bias(lp["mlp"]["dense_4h_to_h"], hidden),
    }


def _gpt_fused_tail(cfg, blk, x, part):
    x2 = x + jax.lax.psum(part, TENSOR_AXIS) + blk["bo"]
    h2 = layer_norm(x2, blk["ln2_w"].reshape(-1), blk["ln2_b"].reshape(-1))
    u = jax.nn.gelu(jnp.matmul(h2, blk["wu"]) + blk["bu"])
    y = jax.lax.psum(jnp.matmul(u, blk["wd"]), TENSOR_AXIS)
    return x2 + (y + blk["bd"])


# --------------------------------------------------------------------------
# LLaMA (standalone_llama's op sequence; GQA/MQA cached once per kv head);
# its embedding, RMSNorm and untied head are laguna's too
# --------------------------------------------------------------------------

def _llama_dims(cfg) -> dict:
    return dict(_gpt_dims(cfg), kv_heads=cfg.kv_heads)


def _token_embed(p, tokens, positions, tp):
    return _vocab_embed(p["embed_tokens"]["weight"], tokens, tp)


def _llama_rope(cfg, dims, positions, n):
    """The model's ``_rope_cos_sin`` values as flat ``[n, head_dim]``
    tables, indexed at ``positions`` where there are any."""
    d = dims["head_dim"]
    cos, sin = (t.reshape(n, d)
                for t in _rope_cos_sin(n, d, cfg.rope_theta))
    if positions is not None:
        cos = jnp.take(cos, positions, axis=0)
        sin = jnp.take(sin, positions, axis=0)
    return {FULL: (cos, sin)}


def _rms_norm(cfg, p, which, x):
    return rms_norm(x, p[which + "_norm"]["weight"], eps=cfg.rms_eps)


def _llama_project(cfg, dims, i, lp, h, rope):
    d = dims["head_dim"]
    q = _linear(lp["attention"]["q_proj"], h)
    kv = _linear(lp["attention"]["kv_proj"], h)
    q = q.reshape(*h.shape[:-1], dims["heads_local"], d)
    k, v = jnp.split(kv.reshape(*h.shape[:-1], dims["kv_heads_local"],
                                2 * d), 2, axis=-1)
    q = fused_apply_rotary_pos_emb_cached(q, *rope)
    k = fused_apply_rotary_pos_emb_cached(k, *rope)
    return q, k, v, None


def _llama_attn_out(lp, ctx, extra, tp):
    return _row_linear(lp["attention"]["o_proj"], _merge_heads(ctx), tp)


def _llama_ffn(cfg, i, lp, h, valid, tp):
    gate = _linear(lp["mlp"]["gate_proj"], h)
    up = _linear(lp["mlp"]["up_proj"], h)
    return _row_linear(lp["mlp"]["down_proj"],
                       jax.nn.silu(gate) * up, tp), None


def _untied_head(p, h, tp):
    return _linear(p["lm_head"], h)


def _llama_fused_layout(cfg, dims, lp):
    """The packed ``kv_proj`` split into ``wk``/``wv`` planes."""
    d, hidden = dims["head_dim"], cfg.hidden_size
    att = lp["attention"]
    kvw = jnp.transpose(att["kv_proj"]["weight"])
    # kv-head count from the WEIGHT, not the config: a kv-expanded tree
    # (expand_kv_for_tp) carries kvh*rep heads
    kvh_w = kvw.shape[1] // (2 * d)
    kvw = kvw.reshape(hidden, kvh_w, 2, d)
    return {
        "ln1_w": lp["input_norm"]["weight"].reshape(1, hidden),
        "wq": jnp.transpose(att["q_proj"]["weight"]),
        "wk": kvw[:, :, 0, :].reshape(hidden, kvh_w * d),
        "wv": kvw[:, :, 1, :].reshape(hidden, kvh_w * d),
        "wo": jnp.transpose(att["o_proj"]["weight"]),
        "ln2_w": lp["post_attention_norm"]["weight"].reshape(1, hidden),
        "wg": jnp.transpose(lp["mlp"]["gate_proj"]["weight"]),
        "wu": jnp.transpose(lp["mlp"]["up_proj"]["weight"]),
        "wd": jnp.transpose(lp["mlp"]["down_proj"]["weight"]),
    }


def _llama_fused_tail(cfg, blk, x, part):
    x2 = x + jax.lax.psum(part, TENSOR_AXIS)
    h2 = rms_norm(x2, blk["ln2_w"].reshape(-1), eps=cfg.rms_eps)
    u = jax.nn.silu(jnp.matmul(h2, blk["wg"])) * jnp.matmul(h2, blk["wu"])
    return x2 + jax.lax.psum(jnp.matmul(u, blk["wd"]), TENSOR_AXIS)


# --------------------------------------------------------------------------
# Laguna (standalone_laguna's per-layer pieces; ISSUE 30): a head count
# per layer, window layers beside full ones, an expert FFN that drops no
# token
# --------------------------------------------------------------------------

#: what a step of a kind with an expert FFN reports beside its tokens, in
#: this order — the int32 tail of the token read
#: (``InferenceEngine.stats_tail`` long; the scheduler hands them to
#: ``ServeTelemetry.step_counters`` under these names).  A kind that HOLDS a share of its experts counts the
#: assignments that land on a held expert and the held experts hit.
EXPERT_STATS = ("moe_assignments", "moe_experts_hit",
                "moe_expert_load_max", "window_pages_live")


def _laguna_dims(cfg) -> dict:
    return {"layers": cfg.num_layers,
            "heads": tuple(cfg.heads_per_layer),
            "layer_types": tuple(cfg.layer_types),
            "ffn_types": tuple(cfg.mlp_types),
            "kv_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
            "pool_layers": len(cfg.full_layers),
            "window_layers": len(cfg.window_layers),
            "window": cfg.sliding_window, "latent": 0, "index": 0}


def _laguna_check(cfg) -> None:
    if not isinstance(cfg, laguna.LagunaConfig):
        raise TypeError(
            f"the 'laguna' kind takes a LagunaConfig, got "
            f"{type(cfg).__name__}")
    # its expert FFN IS built (dropless, ISSUE 30)


def _laguna_rope(cfg, dims, positions, n):
    """One table per layer type: YaRN over part of the head for the full
    layers, plain over the whole head for the sliding ones."""
    if positions is None:
        positions = jnp.arange(n, dtype=jnp.int32)
    return {t: laguna.rope_cos_sin(cfg, t, positions)
            for t in dict.fromkeys(cfg.layer_types)}


def _laguna_project(cfg, dims, i, lp, h, rope):
    return laguna.attn_project(cfg, i, lp, h, *rope)


def _laguna_attn_out(lp, ctx, gate, tp):
    return laguna.attn_output(lp, ctx, gate)


def _laguna_ffn(cfg, i, lp, h, valid, tp):
    """``valid`` marks the rows that carry a token: the others are routed
    to no expert (they would otherwise read experts nobody asked for)."""
    y, stats = laguna.ffn(cfg, i, lp, h.reshape(-1, h.shape[-1]),
                          valid=valid)
    return y.reshape(h.shape), stats


# --------------------------------------------------------------------------
# A.X-K1 (standalone_axk1's per-layer pieces; ISSUE 34): latent attention
# over a pool with no KV-head axis, a group-limited sigmoid router, an
# expert FFN that holds a share of its experts
# --------------------------------------------------------------------------

def _axk1_dims(cfg) -> dict:
    return {"layers": cfg.num_layers, "heads": cfg.num_heads,
            "kv_heads": 0, "head_dim": cfg.qk_head_dim,
            "layer_types": (FULL,) * cfg.num_layers,
            "pool_layers": cfg.num_layers, "window_layers": 0, "window": 0,
            "latent": cfg.latent_dim, "latent_values": cfg.kv_lora_rank,
            "index": 0}


def _axk1_check(cfg) -> None:
    if not isinstance(cfg, axk1.AXK1Config):
        raise TypeError(
            f"the 'axk1' kind takes an AXK1Config, got "
            f"{type(cfg).__name__}")


def _axk1_rope(cfg, dims, positions, n):
    if positions is None:
        positions = jnp.arange(n, dtype=jnp.int32)
    return {FULL: axk1.rope_cos_sin(cfg, positions)}


def _axk1_attn_out(lp, ctx, extra, tp):
    return axk1.attn_output(lp, ctx)


def _axk1_ffn(cfg, i, lp, h, valid, tp):
    y, stats = axk1.ffn(cfg, i, lp, h.reshape(-1, h.shape[-1]),
                        valid=valid)
    return y.reshape(h.shape), stats


def _not_built(kind: str, what: str, module: str) -> str:
    return (f"{what} is not built for the {kind!r} kind: {module} would "
            f"have to change")


# --------------------------------------------------------------------------
# Keye-VL-2.0's language model (standalone_keye's per-layer pieces;
# ISSUE 36): grouped-query attention over the positions a learned indexer
# picks, an index-key pool beside the K/V pool, every layer an expert FFN
# --------------------------------------------------------------------------

#: what a step of a kind that SELECTS reports behind the expert counters:
#: query rows x layers that went through the indexer, those of them whose
#: context exceeded the selection's size (so that it cut something), and
#: the positions attended, summed
SELECT_STATS = ("dsa_rows", "dsa_rows_sparse", "dsa_selected")
#: what a step of a kind whose layers REUSE earlier picks reports behind
#: those: the query rows x layers that attended a carried pick set
REUSE_STATS = ("dsa_rows_reused",)


def _keye_dims(cfg) -> dict:
    return {"layers": cfg.num_layers, "heads": cfg.num_heads,
            "kv_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
            "layer_types": (FULL,) * cfg.num_layers,
            "pool_layers": cfg.num_layers, "window_layers": 0, "window": 0,
            "latent": 0, "index": cfg.index_head_dim}


def _keye_check(cfg) -> None:
    if not isinstance(cfg, keye.KeyeConfig):
        raise TypeError(
            f"the 'keye' kind takes a KeyeConfig, got "
            f"{type(cfg).__name__}")


def _keye_rope(cfg, dims, positions, n):
    """The engine hands text positions (one index a token), which is plain
    RoPE; a triple of position arrays (temporal, height, width) takes the
    three-section form.  The indexer ropes by the temporal one."""
    if positions is None:
        positions = jnp.arange(n, dtype=jnp.int32)
    axes = positions if isinstance(positions, tuple) else (positions,) * 3
    return {FULL: keye.mrope_cos_sin(cfg, *axes),
            INDEX: keye.index_rope_cos_sin(cfg, axes[0])}


def _keye_project(cfg, dims, i, lp, h, rope):
    return (*keye.attn_project(cfg, lp, h, *rope), None)


def _keye_attn_out(lp, ctx, extra, tp):
    return keye.attn_output(lp, ctx)


def _keye_ffn(cfg, i, lp, h, valid, tp):
    y, stats = keye.ffn(cfg, lp, h.reshape(-1, h.shape[-1]), valid=valid)
    return y.reshape(h.shape), stats


# --------------------------------------------------------------------------
# Hy4-preview (standalone_hy4's per-layer pieces): latent
# attention over the positions an indexer picks, the picks of a full layer
# reused by the shared layers after it, a sink a head and an elementwise
# gate, hyper-connected residual streams, held experts
# --------------------------------------------------------------------------

def _hy4_dims(cfg) -> dict:
    return dict(_axk1_dims(cfg), index=cfg.index_head_dim,
                index_layers=len(cfg.index_layers))


def _hy4_check(cfg) -> None:
    if not isinstance(cfg, hy4.HY4Config):
        raise TypeError(
            f"the 'hy4' kind takes an HY4Config, got "
            f"{type(cfg).__name__}")


def _hy4_rope(cfg, dims, positions, n):
    if positions is None:
        positions = jnp.arange(n, dtype=jnp.int32)
    return {FULL: hy4.rope_cos_sin(cfg, positions),
            INDEX: hy4.index_rope_cos_sin(cfg, positions)}


def _hy4_attn_out(lp, ctx, gate, tp):
    """The gated output projection (elementwise gate, or dots3's
    headwise one broadcast over each head's values)."""
    return hy4.attn_output(lp, ctx, gate)


def _hy4_ffn(cfg, i, lp, h, valid, tp):
    y, stats = hy4.ffn(cfg, i, lp, h.reshape(-1, h.shape[-1]), valid=valid)
    return y.reshape(h.shape), stats


# --------------------------------------------------------------------------
# dots3-note (standalone_dots3's per-layer pieces): latent attention of two
# geometries — the full layers' under a learned indexer, the window layers'
# in per-slot rings — a headwise gate on both, a selection-biased router
# over held experts
# --------------------------------------------------------------------------

#: what a step of a kind whose window layers attend in latent form reports
#: behind the selection's counters: the ring positions its window layers
#: attended, summed over query rows and window layers
WINDOW_STATS = ("swa_attended",)


def _dots3_dims(cfg) -> dict:
    full, window = (dots3.geometry(cfg, t) for t in (FULL, laguna.SLIDING))
    return {"layers": cfg.num_layers,
            "heads": tuple(dots3.geometry(cfg, t).num_heads
                           for t in cfg.layer_types),
            "kv_heads": 0, "head_dim": full.qk_head_dim,
            "layer_types": tuple(cfg.layer_types),
            "pool_layers": len(cfg.full_layers),
            "window_layers": len(cfg.window_layers),
            "window": cfg.sliding_window,
            "latent": full.latent_dim, "latent_values": full.kv_lora_rank,
            "window_latent": window.latent_dim,
            "window_latent_values": window.kv_lora_rank,
            "index": cfg.index_head_dim,
            "index_layers": len(cfg.full_layers)}


def _dots3_check(cfg) -> None:
    if not isinstance(cfg, dots3.Dots3Config):
        raise TypeError(
            f"the 'dots3' kind takes a Dots3Config, got "
            f"{type(cfg).__name__}")


def _dots3_rope(cfg, dims, positions, n):
    """One table per layer type (each its own base), and the indexer's."""
    if positions is None:
        positions = jnp.arange(n, dtype=jnp.int32)
    return {**{t: dots3.rope_cos_sin(cfg, t, positions)
               for t in dict.fromkeys(cfg.layer_types)},
            INDEX: dots3.index_rope_cos_sin(cfg, positions)}


def _dots3_ffn(cfg, i, lp, h, valid, tp):
    y, stats = dots3.ffn(cfg, i, lp, h.reshape(-1, h.shape[-1]),
                         valid=valid)
    return y.reshape(h.shape), stats


# --------------------------------------------------------------------------
# the record
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Fused:
    """A kind's half of the fused-block decode path (ISSUE 15)."""
    #: ``(cfg, dims, lp) -> blk``: one layer's weights, matmul-ready
    layout: Callable
    #: ``(cfg, blk, x, part) -> h``: finish the block OUTSIDE the kernel
    #: under tp — psum the rank-partial attention output at the row
    #: boundary, add the out-proj bias once, then norm2 + the column /
    #: row-parallel MLP with its own row-boundary psum (the same two
    #: psums a layer the unfused sharded path pays)
    tail: Callable
    #: ``cfg -> float``: the norms' epsilon
    eps: Callable


@dataclasses.dataclass(frozen=True)
class Latent:
    """How a layer of a kind with latent attention attends (ISSUE 34):
    the cache holds one ``row`` a position, ``dims["latent"]`` wide, whose
    leading ``dims["latent_values"]`` columns are the values."""
    #: ``(cfg, lp, h, cos, sin) -> q, k [..., heads, d], v [..., heads,
    #: dv], row [..., width]``: the EXPANDED form, for prefill
    expand: Callable
    #: ``(cfg, lp, h, cos, sin) -> q [..., heads, width], row [...,
    #: width]``: the ABSORBED query against the cached rows, for decode
    absorb: Callable
    #: ``(cfg, lp, u [..., heads, latent_values]) -> ctx [..., heads,
    #: dv]``: the value up-projection, behind the softmax
    value_up: Callable
    #: ``cfg -> float``: the softmax scale (no head size gives it)
    scale: Callable
    #: ``(cfg, lp, h) -> g [..., heads, dv]`` (elementwise) or ``[...,
    #: heads, 1]`` (headwise) or None: a gate on each head's output, read
    #: from the sublayer's normed input and handed to ``attn_out`` as its
    #: ``extra``
    gate: Optional[Callable] = None
    #: ``lp -> [heads]`` float32 or None: each head's sink logit, one more
    #: term of the softmax's denominator that carries no value
    sink: Optional[Callable] = None
    #: ``(cfg, layer type) -> g`` or None: what the forms, the gate and the
    #: scale are handed in place of ``cfg`` for a layer of that type — a
    #: geometry a layer type, whose window layers' rows (``dims
    #: ["window_latent"]`` wide, the leading ``dims["window_latent_values"]``
    #: the values) live in per-slot rings; None: ``cfg`` for every layer
    geometry: Optional[Callable] = None


@dataclasses.dataclass(frozen=True)
class Select:
    """How a layer of a kind with a learned indexer picks the positions it
    attends (ISSUE 36): the cache holds one index key a position a layer,
    ``dims["index"]`` wide, beside the layer's k and v."""
    #: ``(cfg, lp, h, cos, sin) -> qi [..., index_heads, width], wi [...,
    #: index_heads] float32, ki [..., width]``: the index queries, their
    #: weights and the ONE index key the cache keeps; ``cos``/``sin`` the
    #: ``INDEX`` table of the kind's ``rope``
    index: Callable
    #: ``cfg -> int``: positions a query attends at most
    topk: Callable
    #: ``cfg -> int``: query rows a prefill scores and selects at a time
    block: Callable
    #: ``cfg -> tuple`` or None: per layer, the layer whose picks it
    #: attends — itself where it scores its own index keys, an earlier one
    #: where it REUSES that layer's picks and holds no indexer;
    #: None: every pool layer picks for itself (a window layer picks
    #: nothing: it attends its window)
    sources: Optional[Callable] = None


@dataclasses.dataclass(frozen=True)
class Residual:
    """A residual carried in several streams: ``x [..., n,
    hidden]`` where the other kinds carry ``h [..., hidden]``."""
    #: ``(cfg, h) -> x``: the embedding into the streams
    expand: Callable
    #: ``(cfg, lp, which, x) -> (u [..., hidden], mix)``: a sublayer's
    #: input (``which`` is ``"attention"`` or ``"ffn"``) and what its
    #: output is written back with
    pre: Callable
    #: ``(cfg, mix, x, y) -> x``: the streams after the sublayer's output
    #: ``y``
    post: Callable
    #: ``(cfg, p, x) -> h [..., hidden]``: one row before the final norm
    collapse: Callable


@dataclasses.dataclass(frozen=True)
class Kind:
    """What the three layer loops and the engine ask of a model kind:
    pure functions of ``(cfg, p | lp, ...)`` and static facts.  ``p`` is
    the param tree, ``lp`` one layer's subtree, ``dims`` what
    :func:`tp_dims` returns, ``tp`` the static tensor-parallel width."""
    #: ``cfg -> dict``: layers / heads / kv_heads / head_dim, the layers'
    #: types (``FULL`` = paged pool, else window ring), how many layers
    #: the pool and the rings hold, the window in positions
    dims: Callable
    #: ``cfg -> None``: raise for a config the forwards do not serve
    check: Callable
    #: ``(p, tokens, positions, tp) -> h [*tokens.shape, hidden]``;
    #: ``positions`` is None for a cold prefill (rows 0 .. s)
    embed: Callable
    #: ``(cfg, dims, positions, n) -> {layer type: (cos, sin)}`` of shape
    #: ``[*positions.shape, rot]`` (``[n, rot]``, the first ``n``
    #: positions, where ``positions`` is None); ``{}`` without RoPE
    rope: Callable
    #: ``(cfg, p | lp, which, x) -> x``; ``which`` is ``"input"``,
    #: ``"post_attention"`` or ``"final"``
    norm: Callable
    #: ``(cfg, dims, i, lp, h, rope) -> q [..., heads, d], k, v [...,
    #: kv_heads, d], extra`` — roped where the kind ropes; ``extra`` is
    #: whatever ``attn_out`` wants back (laguna's gate).  None for a
    #: kind with ``latent`` attention, which has no per-head k/v to cache
    project: Optional[Callable]
    #: ``(lp, ctx [..., heads, d], extra, tp) -> [..., hidden]``
    attn_out: Callable
    #: ``(cfg, i, lp, h, valid, tp) -> (y, stats | None)``
    ffn: Callable
    #: ``(p, h, tp) -> logits`` over this rank's vocab shard
    head: Callable
    #: the fused-block layout, or None where the kernel is not built
    fused: Optional[Fused] = None
    #: latent attention's two forms, or None: q/k/v through ``project``
    latent: Optional[Latent] = None
    #: the learned selection of the positions a layer attends, or None:
    #: every causal (or window) position
    select: Optional[Select] = None
    #: the residual streams, or None: ``h + sublayer``
    residual: Optional[Residual] = None
    #: names of the int32 counters a step appends to its tokens
    stats: Tuple[str, ...] = ()
    #: feature -> why it is not built for the kind; the features are
    #: ``dense`` (the slot cache), ``tp``, ``verify``, ``host_tier``,
    #: ``fused`` — refused at engine construction — and
    #: ``prefix_sharing`` (a prefill that resumes mid-prompt)
    refuses: Mapping[str, str] = dataclasses.field(default_factory=dict)


KINDS = {
    "gpt": Kind(
        dims=_gpt_dims, check=_check_dense, embed=_gpt_embed,
        rope=lambda cfg, dims, positions, n: {}, norm=_gpt_norm,
        project=_gpt_project, attn_out=_gpt_attn_out, ffn=_gpt_ffn,
        head=_gpt_head,
        fused=Fused(_gpt_fused_layout, _gpt_fused_tail, lambda cfg: 1e-5)),
    "llama": Kind(
        dims=_llama_dims, check=_check_dense, embed=_token_embed,
        rope=_llama_rope, norm=_rms_norm, project=_llama_project,
        attn_out=_llama_attn_out, ffn=_llama_ffn, head=_untied_head,
        fused=Fused(_llama_fused_layout, _llama_fused_tail,
                    lambda cfg: cfg.rms_eps)),
    "laguna": Kind(
        dims=_laguna_dims, check=_laguna_check, embed=_token_embed,
        rope=_laguna_rope, norm=_rms_norm, project=_laguna_project,
        attn_out=_laguna_attn_out, ffn=_laguna_ffn, head=_untied_head,
        stats=EXPERT_STATS,
        refuses={
            "dense": "the 'laguna' kind serves from the paged cache only "
                     "(its full layers page, its window layers ring): "
                     "pass page_size=/num_pages=",
            "tp": "tp > 1 is not built for the 'laguna' kind: its expert "
                  "stacks, per-layer head counts and window rings have no "
                  "partition specs yet (serve it on one chip)",
            "verify": "speculative verify is not built for the 'laguna' "
                      "kind (a rejected slab would have to roll its "
                      "window rings back)",
            "host_tier": "the host KV tier is not built for the 'laguna' "
                         "kind (it swaps prefix pages, and a prefix over "
                         "window rings cannot be shared)",
            "fused": "fused_block_decode is not built for the 'laguna' "
                     "kind (the kernel has one head count, a dense FFN "
                     "and no window)",
            "prefix_sharing": "the 'laguna' kind prefills a prompt whole "
                              "(the positions its window rings would "
                              "need are not in the pages a prefix "
                              "shares)",
        }),
    "axk1": Kind(
        dims=_axk1_dims, check=_axk1_check, embed=_token_embed,
        rope=_axk1_rope, norm=_rms_norm, project=None,
        attn_out=_axk1_attn_out, ffn=_axk1_ffn, head=_untied_head,
        latent=Latent(expand=axk1.attn_expand, absorb=axk1.attn_absorb,
                      value_up=axk1.attn_value_up,
                      scale=axk1.softmax_scale),
        stats=EXPERT_STATS,
        refuses={
            "dense": "the 'axk1' kind serves from the paged cache only "
                     "(a latent pool, one row a position: "
                     "ops/attention.py's decode_attention scores per-head "
                     "k/v): pass page_size=/num_pages=",
            "tp": _not_built(
                "axk1", "tp > 1", "models.param_partition_specs and "
                "kv_cache.paged_cache_partition_specs (a replicated "
                "latent, head-sharded up-projections, the all-to-all "
                "round the held experts)"),
            "verify": _not_built(
                "axk1", "speculative verify", "ops/paged_attention.py's "
                "paged_slab_attention (it gathers per-head k/v windows) "
                "and kv_cache.append_slab"),
            "host_tier": _not_built(
                "axk1", "the host KV tier", "kv_cache.HostPageStore and "
                "engine.swap_in_pages (they move a k and a v slab)"),
            "fused": _not_built(
                "axk1", "fused_block_decode", "ops/paged_attention.py's "
                "_fused_block_kernel (per-head k/v, a dense FFN)"),
            "prefix_sharing": _not_built(
                "axk1", "prefix sharing, and with it chunked prefill,",
                "models._suffix_attend (the cached prefix would have to "
                "be up-projected a chunk at a time)"),
        }),
    "keye": Kind(
        dims=_keye_dims, check=_keye_check, embed=_token_embed,
        rope=_keye_rope, norm=_rms_norm, project=_keye_project,
        attn_out=_keye_attn_out, ffn=_keye_ffn, head=_untied_head,
        select=Select(index=keye.index_project,
                      topk=lambda cfg: cfg.index_topk,
                      block=lambda cfg: cfg.index_q_chunk),
        stats=EXPERT_STATS + SELECT_STATS,
        refuses={
            "dense": "the 'keye' kind serves from the paged cache only "
                     "(its index keys live in a pool beside the K/V pool; "
                     "kv_cache.KVCache has no such array and "
                     "ops/attention.py's decode_attention attends every "
                     "live position): pass page_size=/num_pages=",
            "tp": _not_built(
                "keye", "tp > 1", "models.param_partition_specs and "
                "kv_cache.paged_cache_partition_specs (index keys "
                "replicated beside head-sharded K/V, the selection made "
                "once for all ranks, the expert stacks' all-to-all)"),
            "verify": _not_built(
                "keye", "speculative verify", "ops/paged_attention.py's "
                "paged_slab_attention (a slab row would score the cached "
                "index keys and pick its own set) and "
                "kv_cache.append_slab (no index keys)"),
            "host_tier": _not_built(
                "keye", "the host KV tier", "kv_cache.HostPageStore and "
                "engine.swap_in_pages (they move a k and a v slab, not "
                "the page's index keys)"),
            "fused": _not_built(
                "keye", "fused_block_decode", "ops/paged_attention.py's "
                "_fused_block_kernel (it attends every live page, with a "
                "dense FFN)"),
            "prefix_sharing": _not_built(
                "keye", "prefix sharing, and with it chunked prefill,",
                "models._suffix_attend (a resumed prefill would have to "
                "score the CACHED index keys and pick among cached rows)"),
        }),
    "hy4": Kind(
        dims=_hy4_dims, check=_hy4_check, embed=_token_embed,
        rope=_hy4_rope, norm=_rms_norm, project=None,
        attn_out=_hy4_attn_out, ffn=_hy4_ffn,
        head=lambda p, h, tp: hy4.head(p, h),
        latent=Latent(expand=hy4.attn_expand, absorb=hy4.attn_absorb,
                      value_up=hy4.attn_value_up, scale=hy4.softmax_scale,
                      gate=hy4.attn_gate, sink=hy4.attn_sink),
        select=Select(index=hy4.index_project,
                      topk=lambda cfg: cfg.index_topk,
                      block=lambda cfg: cfg.index_q_chunk,
                      sources=lambda cfg: cfg.index_sources),
        residual=Residual(expand=hy4.hc_expand, pre=hy4.hc_pre,
                          post=hy4.hc_post, collapse=hy4.hc_collapse),
        stats=EXPERT_STATS + SELECT_STATS + REUSE_STATS,
        refuses={
            "dense": "the 'hy4' kind serves from the paged cache only (a "
                     "latent pool and an index-key pool: "
                     "kv_cache.KVCache has neither): pass "
                     "page_size=/num_pages=",
            "tp": _not_built(
                "hy4", "tp > 1", "models.param_partition_specs and "
                "kv_cache.paged_cache_partition_specs (the residual "
                "streams' mixes read every hidden channel, so a "
                "head-sharded layer would gather the streams twice a "
                "sublayer; the held experts' all-to-all)"),
            "verify": _not_built(
                "hy4", "speculative verify", "ops/paged_attention.py's "
                "paged_slab_attention (per-head k/v windows, no picks) and "
                "kv_cache.append_slab (no latent rows, no index keys)"),
            "host_tier": _not_built(
                "hy4", "the host KV tier", "kv_cache.HostPageStore and "
                "engine.swap_in_pages (they move a k and a v slab)"),
            "fused": _not_built(
                "hy4", "fused_block_decode", "ops/paged_attention.py's "
                "_fused_block_kernel (per-head k/v, one residual stream, "
                "a dense FFN)"),
            "prefix_sharing": _not_built(
                "hy4", "prefix sharing, and with it chunked prefill,",
                "models._suffix_attend (a resumed prefill would have to "
                "up-project the cached latent a chunk at a time and pick "
                "among the cached rows)"),
        }),
    "dots3": Kind(
        dims=_dots3_dims, check=_dots3_check, embed=_token_embed,
        rope=_dots3_rope, norm=_rms_norm, project=None,
        attn_out=_hy4_attn_out, ffn=_dots3_ffn, head=_untied_head,
        latent=Latent(expand=dots3.attn_expand, absorb=dots3.attn_absorb,
                      value_up=dots3.attn_value_up,
                      scale=dots3.softmax_scale, gate=dots3.attn_gate,
                      geometry=dots3.geometry),
        select=Select(index=dots3.index_project,
                      topk=lambda cfg: cfg.index_topk,
                      block=lambda cfg: cfg.index_q_chunk),
        stats=EXPERT_STATS + SELECT_STATS + WINDOW_STATS,
        refuses={
            "dense": "the 'dots3' kind serves from the paged cache only (a "
                     "latent pool, an index-key pool and rings of latent "
                     "rows: kv_cache.KVCache has none of them): pass "
                     "page_size=/num_pages=",
            "tp": _not_built(
                "dots3", "tp > 1", "models.param_partition_specs and "
                "kv_cache.paged_cache_partition_specs (two latent "
                "geometries, each with head-sharded up-projections over a "
                "replicated latent, the rings and the index keys "
                "replicated, the held experts' all-to-all)"),
            "verify": _not_built(
                "dots3", "speculative verify", "ops/paged_attention.py's "
                "paged_slab_attention (per-head k/v windows, no picks) and "
                "kv_cache.append_slab (no latent rows, no index keys; a "
                "rejected slab would have to roll the rings back)"),
            "host_tier": _not_built(
                "dots3", "the host KV tier", "kv_cache.HostPageStore and "
                "engine.swap_in_pages (they move a k and a v slab; a "
                "prefix over window rings cannot be shared)"),
            "fused": _not_built(
                "dots3", "fused_block_decode", "ops/paged_attention.py's "
                "_fused_block_kernel (per-head k/v, no window, a dense "
                "FFN)"),
            "prefix_sharing": _not_built(
                "dots3", "prefix sharing, and with it chunked prefill,",
                "models._suffix_attend (the rings hold no positions a "
                "shared prefix's pages could hand back, and a resumed "
                "prefill would have to pick among the cached rows)"),
        }),
}


def _kind(kind: str) -> Kind:
    if kind not in KINDS:
        raise ValueError(f"unknown generative model kind {kind!r} "
                         f"(expected one of {', '.join(map(repr, KINDS))})")
    return KINDS[kind]


def model_dims(kind: str, cfg) -> dict:
    """Static cache geometry for a model config: layers / kv_heads /
    head_dim (+ query heads), ``layer_types`` per layer, and how many
    layers the paged pool (``pool_layers``) and the window rings
    (``window_layers``, ``window`` positions each) hold (``kv_cache``
    module docstring).  ``laguna`` (ISSUE 30) has no ONE head count:
    its ``heads`` is a per-layer tuple."""
    return _kind(kind).dims(cfg)


def cache_row_values(dims: dict, kv_heads: int) -> int:
    """Values ONE cached position holds in ONE layer, over every buffer:
    a key and a value per KV head — or the latent row, which is both —
    and the index key of a kind that selects."""
    return (dims["latent"] or 2 * kv_heads * dims["head_dim"]) \
        + dims.get("index", 0)


def ring_row_values(dims: dict, kv_heads: int) -> int:
    """Values ONE position holds in ONE window layer's ring: a key and a
    value per KV head, or the window layers' latent row."""
    return dims.get("window_latent") or 2 * kv_heads * dims["head_dim"]


def cache_position_values(dims: dict, kv_heads: int) -> int:
    """Values ONE cached position holds over every pool layer: each
    layer's rows, and an index key for each layer the index-key pool
    keeps (every pool layer, or only those that pick)."""
    layers = dims["pool_layers"]
    return layers * cache_row_values(dict(dims, index=0), kv_heads) \
        + dims.get("index_layers", layers) * dims.get("index", 0)


def tp_dims(kind: str, cfg, tp: int) -> dict:
    """Per-rank geometry under tensor-parallel serving, validated.

    ``heads_local`` / ``kv_heads_local`` are what each rank's forwards
    compute with; ``kv_heads_pool`` is the GLOBAL kv-head count of the
    sharded paged pool (``kvh * rep`` — GQA/MQA heads replicate below
    tp, each kv head repeated ``rep = tp/kvh`` times head-major so the
    plain shard over the pool's kv-head dim hands every rank the kv
    head its query group reads)."""
    d = model_dims(kind, cfg)
    heads, kvh = d["heads"], d["kv_heads"]
    if tp <= 1:
        return dict(d, heads_local=heads, kv_heads_local=kvh,
                    kv_heads_pool=kvh, rep=1)
    refuses = KINDS[kind].refuses
    if "tp" in refuses:
        raise ValueError(refuses["tp"])
    if heads % tp:
        raise ValueError(
            f"tp={tp} does not divide num_attention_heads={heads}")
    if kvh % tp == 0:
        rep = 1
    elif tp % kvh == 0:
        rep = tp // kvh
    else:
        raise ValueError(
            f"tp={tp} vs kv_heads={kvh}: need tp | kv_heads (shard) or "
            f"kv_heads | tp (replicate below tp)")
    return dict(d, heads_local=heads // tp,
                kv_heads_local=max(kvh // tp, 1),
                kv_heads_pool=kvh * rep, rep=rep)


def check_supported(kind: str, cfg) -> None:
    _kind(kind).check(cfg)


def fused_layer_params(kind: str, cfg, params):
    """The per-layer weights re-laid-out for the fused-block decode
    kernel (ISSUE 15): matmul-ready ``[in, out]`` arrays with q/k/v
    split into head-major planes (``Fused.layout``), built ONCE at
    engine construction so no transpose/gather ever runs inside the
    decode step.

    The layout is a one-time device-side copy of the layer weights —
    the engine then holds BOTH layouts (prefill keeps the original
    tree), a deliberate HBM-for-latency trade the README documents next
    to the knob."""
    rec = _kind(kind)
    if rec.fused is None:
        raise ValueError(rec.refuses["fused"])
    p, dims = _params_subtree(params), rec.dims(cfg)
    return [rec.fused.layout(cfg, dims, p[f"layer_{i}"])
            for i in range(cfg.num_layers)]


# --------------------------------------------------------------------------
# tensor-parallel param mirrors (ISSUE 17)
# --------------------------------------------------------------------------

#: parent module names whose ``weight`` is column-partitioned ([out, in]
#: layout, out dim sharded — heads/ffn/vocab-major, so whole heads land
#: per rank) and whose ``bias`` shards with the out dim
_COL_PARENTS = frozenset({
    "query_key_value", "dense_h_to_4h",            # gpt
    "q_proj", "kv_proj", "gate_proj", "up_proj",   # llama
    "lm_head", "word_embeddings", "embed_tokens",  # vocab-sharded
})

#: parent module names whose ``weight`` is row-partitioned (in dim
#: sharded); their bias stays replicated — added once post-psum
_ROW_PARENTS = frozenset({
    "dense", "dense_4h_to_h",                      # gpt
    "o_proj", "down_proj",                         # llama
})


def expand_kv_for_tp(kind: str, cfg, params, tp: int):
    """Replicate GQA/MQA kv heads below tp (``rep = tp/kvh > 1``): each
    kv head's packed ``[2*head_dim]`` output columns of ``kv_proj``
    repeat ``rep`` times head-major, so the plain column shard over the
    expanded out dim hands every rank exactly the kv head its query
    group reads — the training layers' "replicate below tp" for
    serving mirrors.  Identity when ``rep == 1`` (tp=1, MHA, or
    tp-divisible GQA)."""
    td = tp_dims(kind, cfg, tp)
    rep, kvh, d = td["rep"], td["kv_heads"], td["head_dim"]
    if rep == 1:
        return params
    sub = _params_subtree(params)
    fixed = dict(sub)
    for name, lp in sub.items():
        if not name.startswith("layer_"):
            continue
        kvp = dict(lp["attention"]["kv_proj"])
        w = kvp["weight"]                          # [kvh*2d, hidden]
        kvp["weight"] = jnp.repeat(
            w.reshape(kvh, 2 * d, w.shape[1]), rep, axis=0
        ).reshape(kvh * rep * 2 * d, w.shape[1])
        if "bias" in kvp:
            kvp["bias"] = jnp.repeat(
                kvp["bias"].reshape(kvh, 2 * d), rep, axis=0).reshape(-1)
        att = dict(lp["attention"])
        att["kv_proj"] = kvp
        fixed[name] = dict(lp)
        fixed[name]["attention"] = att
    if sub is not params:
        return {**params, "params": fixed}
    return fixed


def param_partition_specs(kind: str, cfg, params, tp: int):
    """``PartitionSpec`` tree for the (kv-expanded) param tree: qkv /
    gate / up column-sharded over heads/ffn, out-proj / down
    row-sharded, embed + LM head vocab-sharded, norms / position table
    replicated.  Validates divisibility leaf by leaf so a bad geometry
    names the offending module."""
    from jax.sharding import PartitionSpec as P

    def spec(path, leaf):
        if tp <= 1:
            return P()
        keys = [getattr(k, "key", getattr(k, "name", str(k)))
                for k in path]
        name = keys[-1] if keys else ""
        parent = keys[-2] if len(keys) >= 2 else ""
        if parent in _COL_PARENTS:
            if leaf.shape[0] % tp:
                raise ValueError(
                    f"tp={tp} does not divide {parent}.{name} out dim "
                    f"{leaf.shape[0]}")
            return (P(TENSOR_AXIS, None) if name == "weight"
                    else P(TENSOR_AXIS))
        if parent in _ROW_PARENTS:
            if name == "weight":
                if leaf.shape[1] % tp:
                    raise ValueError(
                        f"tp={tp} does not divide {parent}.weight in "
                        f"dim {leaf.shape[1]}")
                return P(None, TENSOR_AXIS)
            return P()                  # row bias: replicated, post-psum
        return P()
    return jax.tree_util.tree_map_with_path(spec, params)


def fused_partition_specs(fused_layers, tp: int):
    """``PartitionSpec`` list matching :func:`fused_layer_params`'s
    ``[in, out]`` layout: q/k/v/gate/up planes column-sharded on the
    out dim, out-proj/down row-sharded on the in dim, norms and the
    post-psum biases (``bo``/``bd``) replicated."""
    from jax.sharding import PartitionSpec as P
    col = {"wq", "bq", "wk", "bk", "wv", "bv", "wg", "wu", "bu"}
    row = {"wo", "wd"}

    def one(blk):
        out = {}
        for k in blk:
            if tp > 1 and k in col:
                out[k] = P(None, TENSOR_AXIS)
            elif tp > 1 and k in row:
                out[k] = P(TENSOR_AXIS, None)
            else:
                out[k] = P()
        return out
    return [one(b) for b in fused_layers]


# --------------------------------------------------------------------------
# what the loops share
# --------------------------------------------------------------------------

def _cache_layers(layer_types):
    """Per layer ``(pooled, n)``: does the paged pool keep it (its type
    is ``FULL``) or a window ring, and which of the pool's — or the
    rings' — layers it is."""
    out, pool, rings = [], 0, 0
    for t in layer_types:
        if t == FULL:
            out.append((True, pool))
            pool += 1
        else:
            out.append((False, rings))
            rings += 1
    return out


def _expand_kv(t, heads: int):
    """``[b, kvh, s, d]`` -> ``[b, heads, s, d]`` (GQA: share kv across
    the group); the array itself where every head has its own."""
    return t if heads == t.shape[1] else laguna.expand_kv(t, heads)


def _last_row(h, length):
    """Hidden state at the last REAL position (``length - 1``) of a
    bucket-padded ``[s, b, hid]`` activation — sliced BEFORE the lm
    head, so the O(s·vocab·hidden) projection runs on one row instead
    of every dead padding position (~1/3 of prefill FLOPs at the
    flagship shape)."""
    return jax.lax.dynamic_index_in_dim(h, length - 1, axis=0,
                                        keepdims=False)       # [b, hid]


def _suffix_attend(cache, layer: int, row, q, k, v, start):
    """Prefill attention for a (possibly mid-prompt) token slab: cold
    (``start == 0``) it is EXACTLY the causal flash path the original
    prefill ran — bitwise, so cold prefills and the dense-parity tests
    are untouched; warm (``start > 0``, a prefix-cache hit or a later
    chunk of a chunked prefill) each row additionally attends to the
    already-cached prefix, gathered from the slot's KV pages through
    ``row`` (:func:`~apex_tpu.ops.attention.prefix_window_attention`).

    ``q``: ``[b, h, s, d]``; ``k``/``v``: pre-broadcast
    ``[b, kv_heads, s, d]``.  One ``lax.cond`` keeps both paths inside
    the ONE compiled prefill executable per bucket — the runtime
    executes only the taken branch, so cold prefills never pay the
    window gather."""
    h, (_, kvh, _, d) = q.shape[1], k.shape

    def cold(q, k, v, pk, pv):
        return flash_attention(q, _expand_kv(k, h), _expand_kv(v, h),
                               causal=True)

    def warm(q, k, v, pk, pv):
        # pk/pv: the WHOLE pool [pages, layers, kvh, ps, d] -> the
        # slot's virtual window [b, kvh, max_seq, d] of this layer in
        # row order; unowned ordinals gather the trash page — finite
        # garbage masked by start
        def window(p):
            w = p[row, layer]                     # [mpps, kvh, ps, d]
            return w.transpose(1, 0, 2, 3).reshape(
                1, kvh, -1, d).astype(q.dtype)
        return prefix_window_attention(q, k, v, window(pk), window(pv),
                                       start)

    # the pool goes into the cond whole and only the slot's own pages
    # are gathered inside: a per-layer slice as the operand is
    # materialized — one pool-sized temporary per layer, 9 GB of them
    # for a 24 GiB pool over tp=4 (PERF.md "Bring-up, PR 21")
    return jax.lax.cond(start > 0, warm, cold, q, k, v, cache.k, cache.v)


def _slab_attend(cache, layer: int, q, lengths):
    """Verify-slab attention against ONE layer of whichever cache
    layout the engine runs: the dense slot window scored directly
    (:func:`~apex_tpu.ops.attention.slab_decode_attention`) or the
    paged pool gathered through the slot page table
    (:func:`~apex_tpu.ops.paged_attention.paged_slab_attention`).
    ``lengths`` is the live count BEFORE the slab was appended (the
    causal offset)."""
    if isinstance(cache, kv_cache.PagedKVCache):
        return paged_slab_attention(q, cache.k[:, layer],
                                    cache.v[:, layer], cache.page_table,
                                    lengths)
    return slab_decode_attention(q, cache.k[:, layer], cache.v[:, layer],
                                 lengths)


def _cache_attend(cache, layer: int, q, live, work):
    """Single-token attention against ONE layer of whichever cache
    layout the engine runs: the dense slot window
    (:func:`~apex_tpu.ops.attention.decode_attention`) or the paged
    pool, handed to the kernel WHOLE and walked along ``work``, the
    step's list of live pages (None with the dense cache)
    (:func:`~apex_tpu.ops.paged_attention.paged_decode_attention`).
    Both score the pre-broadcast per-kv-head cache (GQA/MQA grouped)."""
    if isinstance(cache, kv_cache.PagedKVCache):
        return paged_decode_attention(q, cache.k, cache.v,
                                      cache.page_table, live, layer=layer,
                                      work=work)
    return decode_attention(q, cache.k[:, layer], cache.v[:, layer], live)


def _sub_in(rec: Kind, cfg, lp, which: str, h):
    """A sublayer's input and what its output is written back with: ``h``
    itself and nothing, or the residual streams' mixes."""
    if rec.residual is None:
        return h, None
    return rec.residual.pre(cfg, lp, which, h)


def _sub_out(rec: Kind, cfg, mix, h, y):
    """The residual after a sublayer whose output is ``y``."""
    if rec.residual is None:
        return h + y
    return rec.residual.post(cfg, mix, h, y)


def _final(rec: Kind, cfg, p, h):
    """The final norm, of the streams collapsed to one row where the kind
    carries several."""
    if rec.residual is not None:
        h = rec.residual.collapse(cfg, p, h)
    return rec.norm(cfg, p, "final", h)


def _gate(rec: Kind, cfg, lp, hn):
    """A latent kind's output gate from the sublayer's normed input (the
    ``extra`` its ``attn_out`` takes), or None."""
    return rec.latent.gate(cfg, lp, hn) if rec.latent.gate else None


def _geometry(rec: Kind, cfg, layer_type: str):
    """What a latent kind's forms read for a layer of ``layer_type``: its
    type's geometry, or the config itself."""
    return (rec.latent.geometry(cfg, layer_type) if rec.latent.geometry
            else cfg)


def _sink(rec: Kind, lp):
    """Each head's sink logit, or None for a kind without one."""
    return rec.latent.sink(lp) if rec.latent and rec.latent.sink else None


def _pick_sources(rec: Kind, cfg, layer_types):
    """Per layer ``(source, slot)`` for a kind that selects: the layer
    whose picks it attends, and — where that is itself — which of the
    index-key pool's layers holds its keys (None where it reuses).  Where
    every pool layer picks for itself, its keys are its pool layer's and a
    window layer picks nothing: ``(None, None)``."""
    if rec.select.sources is None:
        return [(i, n) if pooled else (None, None)
                for i, (pooled, n) in enumerate(_cache_layers(layer_types))]
    sources = rec.select.sources(cfg)
    own = [i for i in range(len(layer_types)) if sources[i] == i]
    return [(src, own.index(i) if src == i else None)
            for i, src in enumerate(sources)]


def _decode_select(cache, qi, wi, slot: int, work, live, topk: int):
    """Decode's first two stages: index scores of the live positions
    against the pool's index keys of ``slot``, then each slot's picked set
    ``[slots, max_seq]``."""
    with jax.named_scope("apex_dsa_index"):
        scores = paged_index_scores(qi, wi, cache.ik, work, layer=slot)
    with jax.named_scope("apex_dsa_select"):
        cols = jnp.arange(cache.max_seq, dtype=jnp.int32)
        return select_top_mask(scores, topk, cols[None] < live[:, None])


def stats_tail(names, acc, cache):
    """A step's counters as ``int32[len(names)]``, ``names`` the record's
    ``stats``: the expert counters the loop folded over its layers
    (``acc``, None where no expert layer ran), and the ring pages live in
    ``cache`` once the step has updated it (0 without rings)."""
    acc = acc or {}
    zero = jnp.int32(0)
    values = {"moe_assignments": acc.get("assignments", zero),
              "moe_experts_hit": acc.get("experts_hit", zero),
              "moe_expert_load_max": acc.get("load_max", zero),
              "window_pages_live": kv_cache.window_pages_live(cache),
              **{n: acc.get(n, zero)
                 for n in SELECT_STATS + REUSE_STATS + WINDOW_STATS}}
    return jnp.stack([values[n] for n in names]).astype(jnp.int32)


def _select_stats(acc, counted, context, picked, topk: int, reused=None):
    """Fold one selecting layer into a step's :data:`SELECT_STATS`: per
    query row its ``context`` (the positions it could attend) and
    ``picked`` (those it did); ``counted`` (bool) the rows that carry a
    token.  ``reused`` (a kind whose layers reuse picks, static): did this
    layer attend a carried set — :data:`REUSE_STATS`."""
    layer = {"dsa_rows": jnp.sum(counted, dtype=jnp.int32),
             "dsa_rows_sparse": jnp.sum(counted & (context > topk),
                                        dtype=jnp.int32),
             "dsa_selected": jnp.sum(jnp.where(counted, picked, 0),
                                     dtype=jnp.int32)}
    if reused is not None:
        layer["dsa_rows_reused"] = layer["dsa_rows"] if reused \
            else jnp.int32(0)
    return {n: layer[n] + (acc or {}).get(n, 0) for n in layer}


def _window_stats(acc, counted, attended):
    """Fold one window layer into a step's :data:`WINDOW_STATS`: per query
    row the positions it ``attended``, summed over the ``counted`` rows."""
    return {"swa_attended": jnp.sum(jnp.where(counted, attended, 0),
                                    dtype=jnp.int32)
            + (acc or {}).get("swa_attended", 0)}


# --------------------------------------------------------------------------
# the three layer loops
# --------------------------------------------------------------------------

def prefill_forward(kind: str, cfg, params, tokens, length=None, *,
                    cache=None, row=None, prefill_from=None, tp=1):
    """Full-prompt forward: ``tokens [1, s]`` -> ``(logits, ks, vs, wks,
    wvs, iks, stats)``.  ``ks`` / ``vs`` ``[pool_layers, kv_heads, s,
    head_dim]`` are the pool layers' k/v, ready for
    :func:`kv_cache.insert` / ``insert_tokens`` (a kind with latent
    attention: ``ks [pool_layers, s, width]`` the latent rows, ``vs``
    None — the expanded k/v are never cached); ``wks`` / ``wvs`` the
    window layers', for ``insert_window`` (None for a kind without
    them); ``iks [pool_layers, s, width]`` the index keys of a kind that
    selects (None without an indexer); ``stats`` the expert counters
    folded over the layers, with the selection's for a kind that selects
    (None without either).

    With ``length`` (the real prompt length inside a bucket-padded
    ``s``, traced OK) the lm head runs on ONLY the last real position —
    ``logits [1, v]`` — and the rows at or past it, bucket padding, are
    routed to no expert; without it every position is projected
    (``logits [s, 1, v]``, the full-forward shape parity tests pin).

    Suffix mode (ISSUE 12 — paged engines only): with ``cache`` (the
    :class:`~apex_tpu.inference.kv_cache.PagedKVCache`), ``row`` (the
    slot's full page-table row) and ``prefill_from`` (how many prompt
    tokens are already cached, traced OK), ``tokens`` is the
    bucket-padded UNCACHED TAIL: rows sit at absolute positions
    ``prefill_from + i``, attend to the cached prefix through the page
    window (:func:`_suffix_attend`) and causally to the slab itself,
    and ``length`` is the TOTAL live length (prefix + real suffix).
    ``prefill_from == 0`` reproduces the cold path bitwise — one
    compiled executable per bucket serves cold prefills, prefix-cache
    hits, and chunked-prefill continuation chunks alike.  Refused for a
    kind whose record refuses ``prefix_sharing`` (a window ring cannot
    be shared or resumed)."""
    if tokens.ndim != 2 or tokens.shape[0] != 1:
        raise ValueError(
            f"prefill takes one prompt [1, s], got {tuple(tokens.shape)}")
    rec, p = _kind(kind), _params_subtree(params)
    suffix = cache is not None          # static: suffix-prefill variant
    if suffix and "prefix_sharing" in rec.refuses:
        raise ValueError(rec.refuses["prefix_sharing"])
    if suffix and (row is None or prefill_from is None or length is None):
        raise ValueError(
            "suffix prefill needs cache, row, prefill_from AND length")
    dims = tp_dims(kind, cfg, tp)
    s = tokens.shape[1]
    positions = None
    if suffix:
        # rows sit at absolute positions prefill_from + i (clamped: dead
        # bucket-padding rows past the cache's window stay in range)
        positions = jnp.minimum(
            jnp.asarray(prefill_from, jnp.int32)
            + jnp.arange(s, dtype=jnp.int32),
            jnp.int32(cache.max_seq - 1))
    h = rec.embed(p, tokens, positions, tp).transpose(1, 0, 2)  # [s, b, h]
    if rec.residual:                                    # [s, b, n, h]
        h = rec.residual.expand(cfg, h)
    tables = rec.rope(cfg, dims, positions, cache.max_seq if suffix else s)
    rope = {t: tuple(c[:, None, None, :] for c in cs)       # [s, 1, 1, r]
            for t, cs in tables.items() if t != INDEX}
    # the indexer's table, against its one key a position: [s, 1, width]
    irope = tuple(c[:, None, :] for c in tables.get(INDEX, ()))
    # the rows that carry a token, for a kind whose FFN routes (it is the
    # one that reports stats): bucket padding goes to no expert
    valid = None if length is None or not rec.stats else (
        jnp.arange(s, dtype=jnp.int32) < length)

    ks, vs, wks, wvs, iks, stats, picks = [], [], [], [], [], None, None
    # a kind whose layers reuse picks: source layer -> (masks, picked)
    sources, carried, window = None, {}, None
    if rec.select:
        sources = _pick_sources(rec, cfg, dims["layer_types"])
    for i, (pooled, n) in enumerate(_cache_layers(dims["layer_types"])):
        lp = p[f"layer_{i}"]
        u, mix = _sub_in(rec, cfg, lp, "attention", h)
        hn = rec.norm(cfg, lp, "input", u)
        lrope = rope.get(dims["layer_types"][i])
        # a latent kind's softmax scale is its own (its layer type's);
        # None is the head size's
        scale = None
        if rec.latent:
            # expanded: k/v made from the latent, the latent row cached —
            # in the pool, or a window layer's in its ring
            lcfg = _geometry(rec, cfg, dims["layer_types"][i])
            scale = rec.latent.scale(lcfg)
            q, k, v, latent_row = rec.latent.expand(lcfg, lp, hn, *lrope)
            (ks if pooled else wks).append(latent_row[:, 0])  # [s, width]
            extra = _gate(rec, lcfg, lp, hn)
        else:
            q, k, v, extra = rec.project(cfg, dims, i, lp, hn,
                                         lrope)             # [s, b, n, d]
        q, k, v = (t.transpose(1, 2, 0, 3) for t in (q, k, v))
        if not rec.latent:
            # cache the PRE-broadcast kv (once per kv head)
            (ks if pooled else wks).append(k[0])            # [kv, s, d]
            (vs if pooled else wvs).append(v[0])
        if suffix:
            ctx = _suffix_attend(cache, n, row, q, k, v, prefill_from)
        elif rec.select and pooled:
            # each row attends the positions of largest index score among
            # its causal ones, a block of rows at a time; the index keys
            # are cached with k and v — a layer that reuses the picks of
            # an earlier one scores nothing and caches no index key
            src = sources[i][0]
            if src == i:
                qi, wi, ki = rec.select.index(cfg, lp, hn, *irope)
                iks.append(ki[:, 0])                        # [s, width]
            heads, topk = q.shape[1], rec.select.topk(cfg)
            block = rec.select.block(cfg)
            if rec.select.sources is None:
                ctx, picked = select_attention(
                    q, _expand_kv(k, heads), _expand_kv(v, heads), qi[:, 0],
                    wi[:, 0], ki[:, 0], topk=topk, block_q=block,
                    sm_scale=scale)
            else:
                if src == i:
                    carried[i] = select_picks(qi[:, 0], wi[:, 0], ki[:, 0],
                                              topk=topk, block_q=block)
                masks, picked = carried[src]
                ctx = select_attend(
                    q, _expand_kv(k, heads), _expand_kv(v, heads), masks,
                    topk=topk, block_q=block, sm_scale=scale,
                    sink=_sink(rec, lp))
            rows = jnp.arange(s, dtype=jnp.int32)
            picks = _select_stats(
                picks, rows >= 0 if length is None else rows < length,
                rows + 1, picked, topk,
                None if rec.select.sources is None else src != i)
        elif pooled:
            heads = q.shape[1]
            ctx = flash_attention(
                q, _expand_kv(k, heads), _expand_kv(v, heads), causal=True,
                sm_scale=scale)
        else:
            heads = q.shape[1]
            with jax.named_scope("apex_swa_attend"):
                ctx = flash_attention(
                    q, _expand_kv(k, heads), _expand_kv(v, heads),
                    causal=True, sm_scale=scale, window=dims["window"])
            if "swa_attended" in rec.stats:
                rows = jnp.arange(s, dtype=jnp.int32)
                window = _window_stats(
                    window, rows >= 0 if length is None else rows < length,
                    jnp.minimum(rows + 1, dims["window"]))
        x = _sub_out(rec, cfg, mix, h,
                     rec.attn_out(lp, ctx.transpose(2, 0, 1, 3), extra, tp))
        u, mix = _sub_in(rec, cfg, lp, "ffn", x)
        y, st = rec.ffn(cfg, i, lp, rec.norm(cfg, lp, "post_attention", u),
                        valid, tp)
        stats = fold_stats(stats, st)
        h = _sub_out(rec, cfg, mix, x, y)

    h = _final(rec, cfg, p, h)
    if length is not None:      # the slab's own index of the last real row
        h = _last_row(h, length - prefill_from if suffix else length)
    logits = _gather_logits(rec.head(p, h, tp), tp)
    if picks or window:
        stats = {**(stats or {}), **(picks or {}), **(window or {})}
    return (logits, *(jnp.stack(x) if x else None
                      for x in (ks, vs, wks, wvs, iks)), stats)


def decode_forward(kind: str, cfg, params, cache, tokens, fused=None,
                   tp=1, active=None):
    """One-token step for every slot: ``tokens [slots]`` ->
    ``(logits [slots, v], cache, stats)`` with the new k/v appended at
    each slot's position — a pool layer's through the page table, a
    window layer's at its ring row.  Lengths do not advance here (the
    engine advances active slots once per step).  ``stats`` are the
    expert counters folded over the layers (None without an expert
    FFN); ``active [slots]`` marks the slots that carry a request, the
    only ones such an FFN routes.

    ``fused`` (ISSUE 15) is the per-layer fused weight layout from
    :func:`fused_layer_params`: when present (paged engines under
    ``APEX_TPU_DECODE_FUSION``), every transformer block runs as ONE
    Pallas kernel (:func:`~apex_tpu.ops.paged_attention.
    fused_block_decode`: norm1 -> qkv -> paged attention incl. this
    token -> out proj -> norm2 -> MLP; only the pool append leaves it)
    instead of the per-op XLA sequence — same embed/head, same pool
    append, same signature, tolerance-level numerics (the in-kernel
    residual chain stays fp32 where the unfused path rounds to bf16 at
    each sublayer).  Under ``tp > 1`` (ISSUE 17) the kernel runs on the
    1/tp weight shard and emits the RANK-PARTIAL out-proj product (no
    residual, no bias); ``Fused.tail`` finishes the block outside."""
    rec, p = _kind(kind), _params_subtree(params)
    dims = tp_dims(kind, cfg, tp)
    positions = cache.lengths                               # [slots]
    h = rec.embed(p, tokens, positions, tp)                 # [slots, hid]
    if rec.residual:                                    # [slots, n, hid]
        h = rec.residual.expand(cfg, h)
    flat = rec.rope(cfg, dims, positions, cache.max_seq)    # [slots, r]
    rope = {t: tuple(c[:, None, :] for c in cs) for t, cs in flat.items()
            if t != INDEX}
    live = positions + 1                    # incl. the token written now
    # the live (slot, page) pairs every pool layer's kernel walks: the
    # table and the lengths are the step's, so the list is built ONCE
    work = None
    if fused is None and isinstance(cache, kv_cache.PagedKVCache):
        work = paged_work_list(cache.page_table, live,
                               page_size=cache.page_size)
    cos, sin = flat.get(FULL, (None, None))     # the kernel's: unshaped
    stats, picks, carried, window = None, None, {}, None
    sources = (_pick_sources(rec, cfg, dims["layer_types"])
               if rec.select else None)
    counted = live > 0 if active is None else active
    for i, (pooled, n) in enumerate(_cache_layers(dims["layer_types"])):
        if fused is not None:
            out, k_tok, v_tok = fused_block_decode(
                h, fused[i], cache.k[:, i], cache.v[:, i],
                cache.page_table, positions, kind=kind,
                eps=rec.fused.eps(cfg), cos=cos, sin=sin,
                **({"fuse_mlp": False, "partial_out": True}
                   if tp > 1 else {}))
            cache = kv_cache.append_layer(cache, i, k_tok, v_tok)
            h = rec.fused.tail(cfg, fused[i], h, out) if tp > 1 else out
            continue
        lp = p[f"layer_{i}"]
        u, mix = _sub_in(rec, cfg, lp, "attention", h)
        hn = rec.norm(cfg, lp, "input", u)
        lrope = rope.get(dims["layer_types"][i])
        if rec.latent:
            # absorbed: the query against the latent rows, one pool, one
            # DMA a page; the value up-projection behind the softmax
            lcfg = _geometry(rec, cfg, dims["layer_types"][i])
            q, latent_row = rec.latent.absorb(lcfg, lp, hn, *lrope)
            extra = _gate(rec, lcfg, lp, hn)
            if not pooled:
                # a window layer's row goes to its ring, whose last
                # `window` positions the query attends whole
                cache = kv_cache.append_window(cache, n, latent_row, None)
                with jax.named_scope("apex_swa_attend"):
                    u = ring_decode_attention_latent(
                        q, cache.wk[n], positions, window=dims["window"],
                        sm_scale=rec.latent.scale(lcfg),
                        values=dims["window_latent_values"])
                if "swa_attended" in rec.stats:
                    window = _window_stats(
                        window, counted, jnp.minimum(live, dims["window"]))
            elif rec.select:
                # a layer that picks caches its index key — with its row
                # where every pool layer picks, else in its own slot of a
                # pool that keeps the picking layers' keys — scores and
                # picks; one that reuses attends the carried set
                src, slot = sources[i]
                if rec.select.sources is None:
                    qi, wi, ki = rec.select.index(cfg, lp, hn, *flat[INDEX])
                    cache = kv_cache.append_layer(cache, n, latent_row, None,
                                                  ki)
                else:
                    cache = kv_cache.append_layer(cache, n, latent_row, None)
                    if src == i:
                        qi, wi, ki = rec.select.index(cfg, lp, hn,
                                                      *flat[INDEX])
                        cache = kv_cache.append_index(cache, slot, ki)
                if src == i:
                    carried[i] = _decode_select(
                        cache, qi, wi, slot, work, live,
                        rec.select.topk(cfg))
                picked = carried[src]
                with jax.named_scope("apex_dsa_attend"):
                    u = paged_select_attention_latent(
                        q, cache.k, picked, work, layer=n,
                        sm_scale=rec.latent.scale(lcfg),
                        values=dims["latent_values"], sink=_sink(rec, lp))
                picks = _select_stats(
                    picks, counted, live,
                    jnp.sum(picked, axis=1, dtype=jnp.int32),
                    rec.select.topk(cfg),
                    None if rec.select.sources is None else src != i)
            else:
                cache = kv_cache.append_layer(cache, n, latent_row, None)
                u = paged_decode_attention(
                    q, cache.k, None, cache.page_table, live, layer=n,
                    work=work, sm_scale=rec.latent.scale(lcfg),
                    values=dims["latent_values"])
            ctx = rec.latent.value_up(lcfg, lp, u)
        else:
            q, k_tok, v_tok, extra = rec.project(cfg, dims, i, lp, hn,
                                                 lrope)
            if rec.select:
                # the token's index key is cached with its k and v, so it
                # is a candidate like any other; then index scores of the
                # live positions, the picked set of each slot, attention
                # over the picked rows only
                qi, wi, ki = rec.select.index(cfg, lp, hn, *flat[INDEX])
                cache = kv_cache.append_layer(cache, n, k_tok, v_tok, ki)
                topk = rec.select.topk(cfg)
                picked = _decode_select(cache, qi, wi, n, work, live, topk)
                with jax.named_scope("apex_dsa_attend"):
                    ctx = paged_select_attention(q, cache.k, cache.v,
                                                 picked, work, layer=n)
                picks = _select_stats(
                    picks, counted, live,
                    jnp.sum(picked, axis=1, dtype=jnp.int32), topk)
            elif pooled:
                cache = kv_cache.append_layer(cache, n, k_tok, v_tok)
                # grouped-query scoring straight off the per-kv-head pool
                ctx = _cache_attend(cache, n, q, live, work)
            else:
                cache = kv_cache.append_window(cache, n, k_tok, v_tok)
                with jax.named_scope("apex_swa_attend"):
                    ctx = ring_decode_attention(
                        q, cache.wk[n], cache.wv[n], positions,
                        window=dims["window"])
        x = _sub_out(rec, cfg, mix, h, rec.attn_out(lp, ctx, extra, tp))
        u, mix = _sub_in(rec, cfg, lp, "ffn", x)
        y, st = rec.ffn(cfg, i, lp, rec.norm(cfg, lp, "post_attention", u),
                        active, tp)
        stats = fold_stats(stats, st)
        h = _sub_out(rec, cfg, mix, x, y)

    h = _final(rec, cfg, p, h)
    if picks or window:
        stats = {**(stats or {}), **(picks or {}), **(window or {})}
    return _gather_logits(rec.head(p, h, tp), tp), cache, stats


def verify_forward(kind: str, cfg, params, cache, tokens, tp=1):
    """Speculative-verify step (ISSUE 15): ``tokens [slots, S]`` (the
    last confirmed token followed by ``S - 1`` drafts, per slot) ->
    ``(logits [slots, S, v], cache)`` — logits at EVERY slab position,
    RoPE at each row's absolute position, the slab's k/v appended at
    positions ``[lengths, lengths + S)``.  Lengths do NOT advance — the
    verify fn advances by the accepted count
    (:func:`kv_cache.advance_by`), which IS the page-table/length
    rollback (rejected rows go dead-by-mask; pages were already
    reserved, so rejection releases nothing).  Pool layers only: a kind
    with window rings refuses ``verify``."""
    if tokens.ndim != 2:
        raise ValueError(
            f"verify takes a [slots, S] slab, got {tuple(tokens.shape)}")
    rec, p = _kind(kind), _params_subtree(params)
    if "verify" in rec.refuses:
        raise ValueError(rec.refuses["verify"])
    dims = tp_dims(kind, cfg, tp)
    s = tokens.shape[1]
    base = cache.lengths                                    # [slots]
    pos = jnp.minimum(base[:, None] + jnp.arange(s, dtype=jnp.int32)[None],
                      jnp.int32(cache.max_seq - 1))
    h = rec.embed(p, tokens, pos, tp)                       # [b, S, hid]
    rope = {t: tuple(c[:, :, None, :] for c in cs)          # [b, S, 1, r]
            for t, cs in rec.rope(cfg, dims, pos, cache.max_seq).items()}
    for i in range(cfg.num_layers):
        lp = p[f"layer_{i}"]
        q, k, v, extra = rec.project(
            cfg, dims, i, lp, rec.norm(cfg, lp, "input", h),
            rope.get(FULL))                                 # [b, S, n, d]
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        cache = kv_cache.append_slab(cache, i, k, v)
        ctx = _slab_attend(cache, i, q, base)               # [b, h, S, d]
        x = h + rec.attn_out(lp, ctx.transpose(0, 2, 1, 3), extra, tp)
        h = x + rec.ffn(cfg, i, lp, rec.norm(cfg, lp, "post_attention", x),
                        None, tp)[0]

    h = rec.norm(cfg, p, "final", h)
    return _gather_logits(rec.head(p, h, tp), tp), cache
