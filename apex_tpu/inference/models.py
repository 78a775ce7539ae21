"""Pure prefill/decode forwards over the standalone model param trees.

The training models (``transformer/testing/standalone_{gpt,llama}``) are
flax modules built for the training shapes; inference needs the same
math split into a *prefill* (full prompt, causal flash attention,
emitting every layer's k/v for the cache) and a *decode* (one token per
slot against the cache).  These functions consume the EXACT param pytree
``model.init`` produces — no re-keying, no conversion step — and mirror
the modules' op sequence call for call (same fused LayerNorm/RMSNorm
kernels, same flash attention, same RoPE convention, same qkv
reshape/split layout), so prefill logits reproduce ``model.apply``
bit-for-bit on the same weights and the parity tests in
``tests/L0/run_inference`` can pin decode against the full forward.

Single-chip serving (tp = 1): the TP layers all collapse to plain
matmuls at world size 1, which is what these forwards implement.
Unsupported training-only configs (scan_layers, the capacity-slot MoE
FFN of ``transformer/moe/MoELayer``, sequence/context parallelism) fail
loudly at engine construction.

The ``laguna`` kind (ISSUE 30) is built from the per-layer pieces of
``transformer/testing/standalone_laguna.py`` rather than mirrored by
hand: a head count per layer, window layers (``flash_attention(window=)``
in prefill, a per-slot ring in decode) beside full layers in the paged
pool, and an expert FFN that drops no token
(``transformer/moe/dropless.py``) inside both executables.  Its steps
return their expert counters (:data:`LAGUNA_STATS`) beside the logits.

Multi-chip serving (ISSUE 17): every forward takes a static ``tp`` and,
at ``tp > 1``, runs as the per-rank body of a ``shard_map`` over the
``parallel_state`` tensor axis — the same column/row partitioning the
training ``transformer/tensor_parallel`` layers implement.  qkv / gate /
up projections are column-sharded over heads/ffn (no comm), out-proj and
down-proj are row-sharded with ONE psum each at the row boundary
(:func:`_row_linear` — the ``RowParallelLinear`` reduce, bias added
once AFTER the reduction), and the embedding / LM head are
vocab-sharded: the lookup is the ``VocabParallelEmbedding``
mask-clip-take-zero-psum (the PR 9 vocab-parallel xent target-pick
algebra), the head a local vocab-shard matmul whose tiled ``all_gather``
reassembles the full logits rank-major — original vocab order — so
sampling stays replica-uniform off one folded key.  GQA/MQA kv heads
replicate below tp (:func:`expand_kv_for_tp`): each kv head's packed
columns repeat ``tp/kvh`` times head-major, so the plain column shard
hands every rank exactly the kv head its query group reads.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from apex_tpu.inference import kv_cache
from apex_tpu.ops import layer_norm, rms_norm
from apex_tpu.ops.attention import (
    decode_attention,
    flash_attention,
    prefix_window_attention,
    ring_decode_attention,
    slab_decode_attention,
)
from apex_tpu.ops.paged_attention import (
    fused_block_decode,
    paged_decode_attention,
    paged_slab_attention,
)
from apex_tpu.transformer.functional.fused_rope import (
    fused_apply_rotary_pos_emb_cached,
)
from apex_tpu.transformer.moe.dropless import fold_stats
from apex_tpu.transformer.parallel_state import TENSOR_AXIS
from apex_tpu.transformer.testing import standalone_laguna as laguna
from apex_tpu.transformer.testing.standalone_llama import _rope_cos_sin

__all__ = ["model_dims", "tp_dims", "check_supported", "prefill_forward",
           "LAGUNA_STATS", "laguna_stats_tail",
           "decode_forward", "verify_forward", "fused_layer_params",
           "expand_kv_for_tp", "param_partition_specs",
           "fused_partition_specs"]


def model_dims(kind: str, cfg) -> dict:
    """Static cache geometry for a model config: layers / kv_heads /
    head_dim (+ query heads).

    ``laguna`` (ISSUE 30) has no ONE head count: ``heads``,
    ``layer_types`` and ``ffn_types`` are per-layer tuples, and
    ``pool_layers`` / ``window_layers`` say how many layers the paged
    pool and the window rings hold (``kv_cache`` module docstring);
    ``window`` is the sliding window in positions."""
    if kind == "laguna":
        return {"layers": cfg.num_layers,
                "heads": tuple(cfg.heads_per_layer),
                "layer_types": tuple(cfg.layer_types),
                "ffn_types": tuple(cfg.mlp_types),
                "kv_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
                "pool_layers": len(cfg.full_layers),
                "window_layers": len(cfg.window_layers),
                "window": cfg.sliding_window}
    head_dim = cfg.hidden_size // cfg.num_attention_heads
    kv_heads = (cfg.kv_heads if kind == "llama"
                else cfg.num_attention_heads)
    return {"layers": cfg.num_layers, "heads": cfg.num_attention_heads,
            "kv_heads": kv_heads, "head_dim": head_dim,
            "pool_layers": cfg.num_layers, "window_layers": 0, "window": 0}


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
    if kind == "laguna":
        raise ValueError(
            "tp > 1 is not built for the 'laguna' kind: its expert "
            "stacks, per-layer head counts and window rings have no "
            "partition specs yet (serve it on one chip)")
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
    if kind not in ("gpt", "llama", "laguna"):
        raise ValueError(f"unknown generative model kind {kind!r} "
                         "(expected 'gpt', 'llama' or 'laguna')")
    if kind == "laguna":
        if not isinstance(cfg, laguna.LagunaConfig):
            raise TypeError(
                f"the 'laguna' kind takes a LagunaConfig, got "
                f"{type(cfg).__name__}")
        return          # its expert FFN IS built (dropless, ISSUE 30)
    for flag in ("sequence_parallel", "context_parallel", "scan_layers"):
        if getattr(cfg, flag, False):
            raise ValueError(
                f"inference forwards run tp=1 unrolled; cfg.{flag} is a "
                "training-topology knob — export the weights into a "
                "plain config instead")
    if getattr(cfg, "num_moe_experts", None):
        raise ValueError("MoE FFN decode is not implemented yet")


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
    b, h, s, d = q.shape
    kvh = k.shape[1]
    group = h // kvh

    def cold(q, k, v, pk, pv):
        if group > 1:                   # GQA: share kv across the group
            k, v = (jnp.broadcast_to(
                t[:, :, None], (b, kvh, group, s, d)
            ).reshape(b, h, s, d) for t in (k, v))
        return flash_attention(q, k, v, causal=True)

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


def _cache_attend(cache, layer: int, q, live):
    """Single-token attention against ONE layer of whichever cache
    layout the engine runs: the dense slot window
    (:func:`~apex_tpu.ops.attention.decode_attention`) or the paged
    pool, handed to the kernel WHOLE and threaded through the slot page
    table (:func:`~apex_tpu.ops.paged_attention.paged_decode_attention`).
    Both score the pre-broadcast per-kv-head cache (GQA/MQA grouped)."""
    if isinstance(cache, kv_cache.PagedKVCache):
        return paged_decode_attention(q, cache.k, cache.v,
                                      cache.page_table, live, layer=layer)
    return decode_attention(q, cache.k[:, layer], cache.v[:, layer], live)


def _fused_bias(p, width):
    """A linear's bias as the fused layout's ``[1, width]`` row (zeros
    when the layer was built bias-free)."""
    if "bias" in p:
        return p["bias"].reshape(1, width)
    return jnp.zeros((1, width), p["weight"].dtype)


def fused_layer_params(kind: str, cfg, params):
    """The per-layer weights re-laid-out for the fused-block decode
    kernel (ISSUE 15): matmul-ready ``[in, out]`` arrays with q/k/v
    split into head-major planes, built ONCE at engine construction so
    no transpose/gather ever runs inside the decode step.

    GPT's interleaved ``query_key_value`` columns (per head:
    ``[q(d), k(d), v(d)]``) deinterleave into ``wq``/``wk``/``wv``;
    LLaMA's packed ``kv_proj`` splits the same way.  The layout is a
    one-time device-side copy of the layer weights — the engine then
    holds BOTH layouts (prefill keeps the original tree), a deliberate
    HBM-for-latency trade the README documents next to the knob.
    """
    p = _params_subtree(params)
    dims = model_dims(kind, cfg)
    heads, kvh, d = dims["heads"], dims["kv_heads"], dims["head_dim"]
    hidden = cfg.hidden_size
    out = []
    for i in range(cfg.num_layers):
        lp = p[f"layer_{i}"]
        if kind == "gpt":
            att = lp["self_attention"]
            w = jnp.transpose(att["query_key_value"]["weight"])
            w = w.reshape(hidden, heads, 3, d)
            b = _fused_bias(att["query_key_value"],
                            3 * heads * d).reshape(heads, 3, d)
            blk = {
                "ln1_w": lp["input_layernorm"]["weight"].reshape(
                    1, hidden),
                "ln1_b": lp["input_layernorm"]["bias"].reshape(1, hidden),
                "wq": w[:, :, 0, :].reshape(hidden, heads * d),
                "bq": b[:, 0, :].reshape(1, heads * d),
                "wk": w[:, :, 1, :].reshape(hidden, heads * d),
                "bk": b[:, 1, :].reshape(1, heads * d),
                "wv": w[:, :, 2, :].reshape(hidden, heads * d),
                "bv": b[:, 2, :].reshape(1, heads * d),
                "wo": jnp.transpose(att["dense"]["weight"]),
                "bo": _fused_bias(att["dense"], hidden),
                "ln2_w": lp["post_attention_layernorm"][
                    "weight"].reshape(1, hidden),
                "ln2_b": lp["post_attention_layernorm"][
                    "bias"].reshape(1, hidden),
                "wu": jnp.transpose(lp["mlp"]["dense_h_to_4h"]["weight"]),
                "bu": _fused_bias(lp["mlp"]["dense_h_to_4h"], cfg.ffn),
                "wd": jnp.transpose(lp["mlp"]["dense_4h_to_h"]["weight"]),
                "bd": _fused_bias(lp["mlp"]["dense_4h_to_h"], hidden),
            }
        else:
            att = lp["attention"]
            kvw = jnp.transpose(att["kv_proj"]["weight"])
            # kv-head count from the WEIGHT, not the config: a
            # kv-expanded tree (expand_kv_for_tp) carries kvh*rep heads
            kvh_w = kvw.shape[1] // (2 * d)
            kvw = kvw.reshape(hidden, kvh_w, 2, d)
            blk = {
                "ln1_w": lp["input_norm"]["weight"].reshape(1, hidden),
                "wq": jnp.transpose(att["q_proj"]["weight"]),
                "wk": kvw[:, :, 0, :].reshape(hidden, kvh_w * d),
                "wv": kvw[:, :, 1, :].reshape(hidden, kvh_w * d),
                "wo": jnp.transpose(att["o_proj"]["weight"]),
                "ln2_w": lp["post_attention_norm"]["weight"].reshape(
                    1, hidden),
                "wg": jnp.transpose(lp["mlp"]["gate_proj"]["weight"]),
                "wu": jnp.transpose(lp["mlp"]["up_proj"]["weight"]),
                "wd": jnp.transpose(lp["mlp"]["down_proj"]["weight"]),
            }
        out.append(blk)
    return out


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


def _fused_block_tail_tp(kind: str, blk, x, part, eps):
    """Finish one fused block OUTSIDE the kernel under tp: psum the
    rank-partial attention output at the row boundary (the out-proj
    psum the ISSUE moves out of the kernel), add the out-proj bias
    once, then norm2 + the column/row-parallel MLP with its own
    row-boundary psum — the same two-psums-per-layer the unfused
    sharded path pays."""
    attn = jax.lax.psum(part, TENSOR_AXIS)
    if kind == "gpt":
        x2 = x + attn + blk["bo"]
        h2 = layer_norm(x2, blk["ln2_w"].reshape(-1),
                        blk["ln2_b"].reshape(-1))
        u = jax.nn.gelu(jnp.matmul(h2, blk["wu"]) + blk["bu"])
        y = jax.lax.psum(jnp.matmul(u, blk["wd"]), TENSOR_AXIS)
        y = y + blk["bd"]
    else:
        x2 = x + attn
        h2 = rms_norm(x2, blk["ln2_w"].reshape(-1), eps=eps)
        u = jax.nn.silu(jnp.matmul(h2, blk["wg"])) * jnp.matmul(
            h2, blk["wu"])
        y = jax.lax.psum(jnp.matmul(u, blk["wd"]), TENSOR_AXIS)
    return x2 + y


# --------------------------------------------------------------------------
# GPT (standalone_gpt mirror)
# --------------------------------------------------------------------------

def _gpt_attn_proj(lp, h, heads, head_dim):
    """qkv projection + the model's reshape/split layout: returns
    q/k/v with a trailing ``[..., heads, head_dim]``."""
    qkv = _linear(lp["self_attention"]["query_key_value"], h)
    qkv = qkv.reshape(*h.shape[:-1], heads, 3 * head_dim)
    return jnp.split(qkv, 3, axis=-1)


def _gpt_mlp(lp, h, tp=1):
    return _row_linear(lp["mlp"]["dense_4h_to_h"],
                       jax.nn.gelu(_linear(lp["mlp"]["dense_h_to_4h"],
                                           h)), tp)


def _last_row(h, length):
    """Hidden state at the last REAL position (``length - 1``) of a
    bucket-padded ``[s, b, hid]`` activation — sliced BEFORE the lm
    head, so the O(s·vocab·hidden) projection runs on one row instead
    of every dead padding position (~1/3 of prefill FLOPs at the
    flagship shape)."""
    return jax.lax.dynamic_index_in_dim(h, length - 1, axis=0,
                                        keepdims=False)       # [b, hid]


def _gpt_prefill(cfg, params, tokens, length=None, cache=None, row=None,
                 start=None, tp=1):
    p = _params_subtree(params)
    b, s = tokens.shape
    dims = model_dims("gpt", cfg)
    heads, head_dim = dims["heads"] // tp, dims["head_dim"]
    suffix = cache is not None          # static: suffix-prefill variant

    emb_w = p["embedding"]["word_embeddings"]["weight"]
    h = _vocab_embed(emb_w, tokens, tp)                     # [b, s, h]
    pos_tab = p["embedding"]["position_embeddings"]
    if suffix:
        # rows sit at absolute positions start + i (clamped: dead
        # bucket-padding rows past the table stay in range)
        positions = jnp.minimum(
            jnp.asarray(start, jnp.int32)
            + jnp.arange(s, dtype=jnp.int32),
            jnp.int32(pos_tab.shape[0] - 1))
        h = h + jnp.take(pos_tab, positions, axis=0)[None]
    else:
        h = h + pos_tab[None, :s, :]
    h = h.transpose(1, 0, 2)                                # [s, b, h]

    ks, vs = [], []
    for i in range(cfg.num_layers):
        lp = p[f"layer_{i}"]
        x = h
        h1 = layer_norm(x, lp["input_layernorm"]["weight"],
                        lp["input_layernorm"]["bias"])
        q, k, v = _gpt_attn_proj(lp, h1, heads, head_dim)   # [s, b, n, d]
        q, k, v = (t.transpose(1, 2, 0, 3) for t in (q, k, v))
        ks.append(k[0])                                     # [n, s, d]
        vs.append(v[0])
        if suffix:
            ctx = _suffix_attend(cache, i, row, q, k, v, start)
        else:
            ctx = flash_attention(q, k, v, causal=True)
        ctx = ctx.transpose(2, 0, 1, 3).reshape(s, b, -1)
        x = x + _row_linear(lp["self_attention"]["dense"], ctx, tp)
        h2 = layer_norm(x, lp["post_attention_layernorm"]["weight"],
                        lp["post_attention_layernorm"]["bias"])
        h = x + _gpt_mlp(lp, h2, tp)

    h = layer_norm(h, p["final_layernorm"]["weight"],
                   p["final_layernorm"]["bias"])
    if length is not None:
        last = length - start if suffix else length   # local slab index
        logits = jnp.einsum("bh,vh->bv", _last_row(h, last), emb_w)
    else:
        logits = jnp.einsum("sbh,vh->sbv", h, emb_w)        # tied head
    return _gather_logits(logits, tp), jnp.stack(ks), jnp.stack(vs)


def _gpt_decode(cfg, params, cache, tokens, fused=None, tp=1):
    p = _params_subtree(params)
    dims = model_dims("gpt", cfg)
    heads, head_dim = dims["heads"] // tp, dims["head_dim"]
    positions = cache.lengths                               # [slots]

    emb_w = p["embedding"]["word_embeddings"]["weight"]
    h = _vocab_embed(emb_w, tokens, tp)                     # [slots, h]
    h = h + jnp.take(p["embedding"]["position_embeddings"],
                     positions, axis=0)

    live = positions + 1                    # incl. the token written now
    for i in range(cfg.num_layers):
        if fused is not None:
            if tp > 1:
                # sharded fused block (ISSUE 17): the kernel runs on
                # the 1/tp weight shard and emits the RANK-PARTIAL
                # out-proj product (no residual, no bias) — the row
                # psum + bias + norm2 + col/row MLP finish outside
                part, k_tok, v_tok = fused_block_decode(
                    h, fused[i], cache.k[:, i], cache.v[:, i],
                    cache.page_table, positions, kind="gpt", eps=1e-5,
                    fuse_mlp=False, partial_out=True)
                cache = kv_cache.append_layer(cache, i, k_tok, v_tok)
                h = _fused_block_tail_tp("gpt", fused[i], h, part, 1e-5)
                continue
            # ISSUE 15: the whole block in ONE kernel (norm1 -> qkv ->
            # paged attention incl. this token -> out proj -> norm2 ->
            # MLP); only the pool append leaves the per-op path
            h, k_tok, v_tok = fused_block_decode(
                h, fused[i], cache.k[:, i], cache.v[:, i],
                cache.page_table, positions, kind="gpt", eps=1e-5)
            cache = kv_cache.append_layer(cache, i, k_tok, v_tok)
            continue
        lp = p[f"layer_{i}"]
        x = h
        h1 = layer_norm(x, lp["input_layernorm"]["weight"],
                        lp["input_layernorm"]["bias"])
        q, k_tok, v_tok = _gpt_attn_proj(lp, h1, heads, head_dim)
        cache = kv_cache.append_layer(cache, i, k_tok, v_tok)
        ctx = _cache_attend(cache, i, q, live)
        x = x + _row_linear(lp["self_attention"]["dense"],
                            ctx.reshape(ctx.shape[0], -1), tp)
        h2 = layer_norm(x, lp["post_attention_layernorm"]["weight"],
                        lp["post_attention_layernorm"]["bias"])
        h = x + _gpt_mlp(lp, h2, tp)

    h = layer_norm(h, p["final_layernorm"]["weight"],
                   p["final_layernorm"]["bias"])
    logits = jnp.einsum("bh,vh->bv", h, emb_w)
    return _gather_logits(logits, tp), cache


def _gpt_verify(cfg, params, cache, tokens, tp=1):
    """Speculative verify (ISSUE 15): score an ``S``-token drafted slab
    per slot in ONE batched step — logits at EVERY slab position, the
    slab's k/v appended at ``[lengths, lengths + S)``.  Lengths do not
    advance here; the verify step advances by the accepted count
    (:func:`kv_cache.advance_by`) so rejection is a pure length
    rollback."""
    p = _params_subtree(params)
    dims = model_dims("gpt", cfg)
    heads, head_dim = dims["heads"] // tp, dims["head_dim"]
    slots, s = tokens.shape
    base = cache.lengths                                    # [slots]
    pos = base[:, None] + jnp.arange(s, dtype=jnp.int32)[None]

    emb_w = p["embedding"]["word_embeddings"]["weight"]
    pos_tab = p["embedding"]["position_embeddings"]
    h = _vocab_embed(emb_w, tokens, tp)                     # [b, S, hid]
    h = h + jnp.take(pos_tab,
                     jnp.minimum(pos, jnp.int32(pos_tab.shape[0] - 1)),
                     axis=0)

    for i in range(cfg.num_layers):
        lp = p[f"layer_{i}"]
        x = h
        h1 = layer_norm(x, lp["input_layernorm"]["weight"],
                        lp["input_layernorm"]["bias"])
        q, k, v = _gpt_attn_proj(lp, h1, heads, head_dim)   # [b,S,n,d]
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        cache = kv_cache.append_slab(cache, i, k, v)
        ctx = _slab_attend(cache, i, q, base)               # [b,h,S,d]
        ctx = ctx.transpose(0, 2, 1, 3).reshape(slots, s, -1)
        x = x + _row_linear(lp["self_attention"]["dense"], ctx, tp)
        h2 = layer_norm(x, lp["post_attention_layernorm"]["weight"],
                        lp["post_attention_layernorm"]["bias"])
        h = x + _gpt_mlp(lp, h2, tp)

    h = layer_norm(h, p["final_layernorm"]["weight"],
                   p["final_layernorm"]["bias"])
    logits = jnp.einsum("bsh,vh->bsv", h, emb_w)
    return _gather_logits(logits, tp), cache


# --------------------------------------------------------------------------
# LLaMA (standalone_llama mirror; GQA/MQA cached once per kv head)
# --------------------------------------------------------------------------

def _llama_rope_table(cfg, head_dim, max_seq):
    """Flat ``[max_seq, head_dim]`` cos/sin tables (the model's
    ``_rope_cos_sin`` values, position-indexable for decode)."""
    cos, sin = _rope_cos_sin(max_seq, head_dim, cfg.rope_theta)
    return cos.reshape(max_seq, head_dim), sin.reshape(max_seq, head_dim)


def _llama_proj(lp, h, cfg, heads, kv_heads, head_dim):
    q = _linear(lp["attention"]["q_proj"], h)
    kv = _linear(lp["attention"]["kv_proj"], h)
    q = q.reshape(*h.shape[:-1], heads, head_dim)
    k, v = jnp.split(kv.reshape(*h.shape[:-1], kv_heads, 2 * head_dim),
                     2, axis=-1)
    return q, k, v


def _llama_mlp(lp, h, tp=1):
    gate = _linear(lp["mlp"]["gate_proj"], h)
    up = _linear(lp["mlp"]["up_proj"], h)
    return _row_linear(lp["mlp"]["down_proj"],
                       jax.nn.silu(gate) * up, tp)


def _llama_prefill(cfg, params, tokens, length=None, cache=None,
                   row=None, start=None, tp=1):
    p = _params_subtree(params)
    b, s = tokens.shape
    dims = tp_dims("llama", cfg, tp)
    heads, kv_heads = dims["heads_local"], dims["kv_heads_local"]
    head_dim, group = dims["head_dim"], (dims["heads_local"]
                                         // dims["kv_heads_local"])
    suffix = cache is not None          # static: suffix-prefill variant

    h = _vocab_embed(p["embed_tokens"]["weight"], tokens, tp)
    h = h.transpose(1, 0, 2)                                # [s, b, h]
    if suffix:
        # RoPE at the slab's absolute positions start + i (clamped for
        # dead bucket-padding rows), indexed from the full-window table
        cos_t, sin_t = _rope_cos_sin(cache.max_seq, head_dim,
                                     cfg.rope_theta)  # [max_seq, 1, 1, d]
        positions = jnp.minimum(
            jnp.asarray(start, jnp.int32)
            + jnp.arange(s, dtype=jnp.int32),
            jnp.int32(cache.max_seq - 1))
        cos = jnp.take(cos_t, positions, axis=0)            # [s, 1, 1, d]
        sin = jnp.take(sin_t, positions, axis=0)
    else:
        cos, sin = _rope_cos_sin(s, head_dim, cfg.rope_theta)

    ks, vs = [], []
    for i in range(cfg.num_layers):
        lp = p[f"layer_{i}"]
        x = h
        h1 = rms_norm(x, lp["input_norm"]["weight"], eps=cfg.rms_eps)
        q, k, v = _llama_proj(lp, h1, cfg, heads, kv_heads, head_dim)
        q = fused_apply_rotary_pos_emb_cached(q, cos, sin)
        k = fused_apply_rotary_pos_emb_cached(k, cos, sin)
        # cache the PRE-broadcast kv (once per kv head)
        ks.append(k.transpose(1, 2, 0, 3)[0])               # [kv, s, d]
        vs.append(v.transpose(1, 2, 0, 3)[0])
        if suffix:
            qb, kb, vb = (t.transpose(1, 2, 0, 3) for t in (q, k, v))
            ctx = _suffix_attend(cache, i, row, qb, kb, vb, start)
        else:
            if group > 1:               # GQA: share kv across the group
                k, v = (jnp.broadcast_to(
                    t[:, :, :, None, :],
                    (s, b, kv_heads, group, head_dim)
                ).reshape(s, b, heads, head_dim) for t in (k, v))
            q, k, v = (t.transpose(1, 2, 0, 3) for t in (q, k, v))
            ctx = flash_attention(q, k, v, causal=True)
        ctx = ctx.transpose(2, 0, 1, 3).reshape(s, b, -1)
        x = x + _row_linear(lp["attention"]["o_proj"], ctx, tp)
        h1 = rms_norm(x, lp["post_attention_norm"]["weight"],
                      eps=cfg.rms_eps)
        h = x + _llama_mlp(lp, h1, tp)

    h = rms_norm(h, p["final_norm"]["weight"], eps=cfg.rms_eps)
    if length is not None:
        last = length - start if suffix else length   # local slab index
        logits = _linear(p["lm_head"], _last_row(h, last))    # [b, v]
    else:
        logits = _linear(p["lm_head"], h)                     # [s, b, v]
    return _gather_logits(logits, tp), jnp.stack(ks), jnp.stack(vs)


def _llama_decode(cfg, params, cache, tokens, fused=None, tp=1):
    p = _params_subtree(params)
    dims = tp_dims("llama", cfg, tp)
    heads, kv_heads = dims["heads_local"], dims["kv_heads_local"]
    head_dim = dims["head_dim"]
    positions = cache.lengths

    h = _vocab_embed(p["embed_tokens"]["weight"], tokens, tp)
    cos_t, sin_t = _llama_rope_table(cfg, head_dim, cache.max_seq)
    cos2 = jnp.take(cos_t, positions, axis=0)               # [slots, d]
    sin2 = jnp.take(sin_t, positions, axis=0)
    cos, sin = cos2[:, None, :], sin2[:, None, :]           # [slots, 1, d]

    live = positions + 1
    for i in range(cfg.num_layers):
        if fused is not None:
            if tp > 1:
                part, k_tok, v_tok = fused_block_decode(
                    h, fused[i], cache.k[:, i], cache.v[:, i],
                    cache.page_table, positions, kind="llama",
                    eps=cfg.rms_eps, cos=cos2, sin=sin2,
                    fuse_mlp=False, partial_out=True)
                cache = kv_cache.append_layer(cache, i, k_tok, v_tok)
                h = _fused_block_tail_tp("llama", fused[i], h, part,
                                         cfg.rms_eps)
                continue
            h, k_tok, v_tok = fused_block_decode(
                h, fused[i], cache.k[:, i], cache.v[:, i],
                cache.page_table, positions, kind="llama",
                eps=cfg.rms_eps, cos=cos2, sin=sin2)
            cache = kv_cache.append_layer(cache, i, k_tok, v_tok)
            continue
        lp = p[f"layer_{i}"]
        x = h
        h1 = rms_norm(x, lp["input_norm"]["weight"], eps=cfg.rms_eps)
        q, k_tok, v_tok = _llama_proj(lp, h1, cfg, heads, kv_heads,
                                      head_dim)
        q = fused_apply_rotary_pos_emb_cached(q, cos, sin)
        k_tok = fused_apply_rotary_pos_emb_cached(k_tok, cos, sin)
        cache = kv_cache.append_layer(cache, i, k_tok, v_tok)
        # grouped-query scoring straight off the per-kv-head cache/pool
        ctx = _cache_attend(cache, i, q, live)
        x = x + _row_linear(lp["attention"]["o_proj"],
                            ctx.reshape(ctx.shape[0], -1), tp)
        h1 = rms_norm(x, lp["post_attention_norm"]["weight"],
                      eps=cfg.rms_eps)
        h = x + _llama_mlp(lp, h1, tp)

    h = rms_norm(h, p["final_norm"]["weight"], eps=cfg.rms_eps)
    logits = _linear(p["lm_head"], h)                       # [slots, v]
    return _gather_logits(logits, tp), cache


def _llama_verify(cfg, params, cache, tokens, tp=1):
    """LLaMA twin of :func:`_gpt_verify`: RoPE at each slab row's
    absolute position, GQA/MQA slab scoring straight off the
    per-kv-head cache/pool."""
    p = _params_subtree(params)
    dims = tp_dims("llama", cfg, tp)
    heads, kv_heads = dims["heads_local"], dims["kv_heads_local"]
    head_dim = dims["head_dim"]
    slots, s = tokens.shape
    base = cache.lengths
    pos = base[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
    pos = jnp.minimum(pos, jnp.int32(cache.max_seq - 1))

    h = _vocab_embed(p["embed_tokens"]["weight"], tokens, tp)
    cos_t, sin_t = _llama_rope_table(cfg, head_dim, cache.max_seq)
    cos = jnp.take(cos_t, pos, axis=0)[:, :, None, :]     # [b, S, 1, d]
    sin = jnp.take(sin_t, pos, axis=0)[:, :, None, :]

    for i in range(cfg.num_layers):
        lp = p[f"layer_{i}"]
        x = h
        h1 = rms_norm(x, lp["input_norm"]["weight"], eps=cfg.rms_eps)
        q, k, v = _llama_proj(lp, h1, cfg, heads, kv_heads, head_dim)
        q = fused_apply_rotary_pos_emb_cached(q, cos, sin)
        k = fused_apply_rotary_pos_emb_cached(k, cos, sin)
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        cache = kv_cache.append_slab(cache, i, k, v)
        ctx = _slab_attend(cache, i, q, base)               # [b,h,S,d]
        ctx = ctx.transpose(0, 2, 1, 3).reshape(slots, s, -1)
        x = x + _row_linear(lp["attention"]["o_proj"], ctx, tp)
        h1 = rms_norm(x, lp["post_attention_norm"]["weight"],
                      eps=cfg.rms_eps)
        h = x + _llama_mlp(lp, h1, tp)

    h = rms_norm(h, p["final_norm"]["weight"], eps=cfg.rms_eps)
    logits = _linear(p["lm_head"], h)                     # [b, S, v]
    return _gather_logits(logits, tp), cache


# --------------------------------------------------------------------------
# Laguna (standalone_laguna's per-layer pieces; ISSUE 30): a head count
# per layer, window layers in per-slot rings beside full layers in the
# paged pool, an expert FFN that drops no token
# --------------------------------------------------------------------------

#: what a laguna step reports beside its tokens, in this order — the
#: int32 tail of the token read (``InferenceEngine.stats_tail`` long)
LAGUNA_STATS = ("moe_assignments", "moe_experts_hit",
                "moe_expert_load_max", "window_pages_live")


def laguna_stats_tail(acc, cache):
    """The step's counters as ``int32[4]`` in ``LAGUNA_STATS`` order."""
    zero = jnp.int32(0)
    acc = acc or {"assignments": zero, "experts_hit": zero,
                  "load_max": zero}
    return jnp.stack([acc["assignments"], acc["experts_hit"],
                      acc["load_max"], kv_cache.window_pages_live(cache)
                      ]).astype(jnp.int32)


def _laguna_prefill(cfg, params, tokens, length=None):
    """``tokens [1, s]`` -> ``(logits, ks, vs, wks, wvs, stats)``: the
    full layers' k/v ``[full_layers, kvh, s, d]`` for the paged pool, the
    window layers' for the rings.  Rows at or past ``length`` are bucket
    padding: they are routed to no expert."""
    p = _params_subtree(params)
    valid = None if length is None else (
        jnp.arange(tokens.shape[1], dtype=jnp.int32) < length)
    x, kv, stats = laguna.forward_hidden(cfg, p, tokens, valid=valid)
    x = x.transpose(1, 0, 2)                                # [s, 1, h]
    logits = _linear(p["lm_head"],
                     x if length is None else _last_row(x, length))

    def stack(layers, which):
        return jnp.stack([kv[i][which][0] for i in layers]) \
            if layers else None

    return (logits, stack(cfg.full_layers, 0), stack(cfg.full_layers, 1),
            stack(cfg.window_layers, 0), stack(cfg.window_layers, 1), stats)


def _laguna_decode(cfg, params, cache, tokens, active=None):
    """One token per slot against the two pools -> ``(logits, cache,
    stats)``.  ``active [slots]`` marks the slots that carry a request:
    the others are routed to no expert (they would otherwise read
    experts nobody asked for)."""
    p = _params_subtree(params)
    positions = cache.lengths                               # [slots]
    live = positions + 1
    x = jnp.take(p["embed_tokens"]["weight"], tokens, axis=0)
    rope = {t: tuple(c[:, None, :]
                     for c in laguna.rope_cos_sin(cfg, t, positions))
            for t in set(cfg.layer_types)}
    full_of = {i: n for n, i in enumerate(cfg.full_layers)}
    ring_of = {i: n for n, i in enumerate(cfg.window_layers)}
    stats = None
    for i in range(cfg.num_layers):
        lp, kind_i = p[f"layer_{i}"], cfg.layer_types[i]
        h1 = rms_norm(x, lp["input_norm"]["weight"], eps=cfg.rms_eps)
        q, k_tok, v_tok, g = laguna.attn_project(cfg, i, lp, h1,
                                                 *rope[kind_i])
        if kind_i == laguna.FULL:
            n = full_of[i]
            cache = kv_cache.append_layer(cache, n, k_tok, v_tok)
            ctx = paged_decode_attention(q, cache.k, cache.v,
                                         cache.page_table, live, layer=n)
        else:
            n = ring_of[i]
            cache = kv_cache.append_window(cache, n, k_tok, v_tok)
            ctx = ring_decode_attention(
                q, cache.wk[n], cache.wv[n], positions,
                window=cfg.sliding_window)
        x = x + laguna.attn_output(lp, ctx, g)
        h2 = rms_norm(x, lp["post_attention_norm"]["weight"],
                      eps=cfg.rms_eps)
        y, st = laguna.ffn(cfg, i, lp, h2, valid=active)
        stats = fold_stats(stats, st)
        x = x + y
    x = rms_norm(x, p["final_norm"]["weight"], eps=cfg.rms_eps)
    return _linear(p["lm_head"], x), cache, stats


# --------------------------------------------------------------------------
# dispatch
# --------------------------------------------------------------------------

def prefill_forward(kind: str, cfg, params, tokens, length=None, *,
                    cache=None, row=None, prefill_from=None, tp=1):
    """Full-prompt forward: ``tokens [1, s]`` -> ``(logits, k_stack,
    v_stack)`` with k/v ``[layers, kv_heads, s, head_dim]`` ready for
    :func:`kv_cache.insert`.

    With ``length`` (the real prompt length inside a bucket-padded
    ``s``, traced OK) the lm head runs on ONLY the last real position —
    ``logits [1, v]``; without it every position is projected
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
    hits, and chunked-prefill continuation chunks alike."""
    if tokens.ndim != 2 or tokens.shape[0] != 1:
        raise ValueError(
            f"prefill takes one prompt [1, s], got {tuple(tokens.shape)}")
    if kind == "laguna":
        # -> (logits, ks, vs, wks, wvs, stats): the pool's layers, the
        # rings' layers, the expert counters.  No suffix mode: a window
        # ring cannot be shared or resumed (the engine refuses both)
        return _laguna_prefill(cfg, params, tokens, length)
    fn = _gpt_prefill if kind == "gpt" else _llama_prefill
    if cache is None:
        return fn(cfg, params, tokens, length, tp=tp)
    if row is None or prefill_from is None or length is None:
        raise ValueError(
            "suffix prefill needs cache, row, prefill_from AND length")
    return fn(cfg, params, tokens, length, cache=cache, row=row,
              start=prefill_from, tp=tp)


def decode_forward(kind: str, cfg, params, cache, tokens, fused=None,
                   tp=1, active=None):
    """One-token step for every slot: ``tokens [slots]`` ->
    ``(logits [slots, v], cache)`` with the new k/v appended at each
    slot's position.  Lengths do not advance here (the engine advances
    active slots once per step).

    ``fused`` (ISSUE 15) is the per-layer fused weight layout from
    :func:`fused_layer_params`: when present (paged engines under
    ``APEX_TPU_DECODE_FUSION``), every transformer block runs as ONE
    Pallas kernel (:func:`~apex_tpu.ops.paged_attention.
    fused_block_decode`) instead of the per-op XLA sequence — same
    embed/head, same pool append, same signature, tolerance-level
    numerics (the in-kernel residual chain stays fp32 where the
    unfused path rounds to bf16 at each sublayer).

    ``laguna`` returns a third value, the step's expert counters, and
    takes ``active`` (the slots that carry a request)."""
    if kind == "laguna":
        return _laguna_decode(cfg, params, cache, tokens, active=active)
    fn = _gpt_decode if kind == "gpt" else _llama_decode
    return fn(cfg, params, cache, tokens, fused=fused, tp=tp)


def verify_forward(kind: str, cfg, params, cache, tokens, tp=1):
    """Speculative-verify step (ISSUE 15): ``tokens [slots, S]`` (the
    last confirmed token followed by ``S - 1`` drafts, per slot) ->
    ``(logits [slots, S, v], cache)`` with the slab's k/v appended at
    positions ``[lengths, lengths + S)``.  Lengths do NOT advance —
    the verify fn advances by the accepted count, which IS the
    page-table/length rollback (rejected rows go dead-by-mask; pages
    were already reserved, so rejection releases nothing)."""
    if tokens.ndim != 2:
        raise ValueError(
            f"verify takes a [slots, S] slab, got {tuple(tokens.shape)}")
    if kind == "laguna":
        raise ValueError(
            "speculative verify is not built for the 'laguna' kind (a "
            "rejected slab would have to roll its window rings back)")
    fn = _gpt_verify if kind == "gpt" else _llama_verify
    return fn(cfg, params, cache, tokens, tp=tp)
