"""Plain reference of the ``keye-vl-2.0-30b-a3b-serve`` configuration: the
language model that Kwai-Keye/Keye-VL-2.0-30B-A3B's ``config.json``
(``model_type: KeyeVL2``) describes, written from the equations of ISSUE 36
section 1 — float32 ``jax.numpy``, every product at ``Precision.HIGHEST``, no
kernel, no cache, no batching.  One sequence, one full forward; it imports
nothing of the program.

``x`` is the residual stream, RMSNorm with a learned scale, no bias on any
projection.  With ``a = RMSNorm(x)`` and position ``t``:

* Projections: ``q_t = RoPE(RMSNorm_d(a W_Q))`` (``heads`` x ``d``), ``k_t =
  RoPE(RMSNorm_d(a W_K))`` and ``v_t = a W_V`` (``kv_heads`` x ``d``); query
  head ``j`` reads KV head ``j // (heads / kv_heads)``.  RoPE pairs channel
  ``i`` with ``i + d/2``; a position is a triple (temporal, height, width)
  and pair ``i`` turns by ``p_axis(i) * theta^(-i / (d/2))``, the axis given
  by ``mrope_section``; a text token's three are its index.
* Indexer: ``qI_t = RoPE_i(a W_QI)`` (``index_heads`` x ``di``), ``kI_t =
  RoPE_i(LayerNorm(a W_KI))`` (ONE ``di``-vector a position), ``w_t = (a W_W)
  index_heads^-1/2 di^-1/2``; ``RoPE_i`` plain over all ``di`` channels by the
  temporal position.  Index score ``I[t, s] = sum_j w_t[j] relu(qI_t[j] .
  kI_s)``.
* Selection: ``S_t`` = the ``min(topk, t + 1)`` positions ``s <= t`` of
  largest ``I[t, s]``, equal scores to the lower ``s`` (``jax.lax.top_k``'s
  order) — over the whole sequence in row blocks: index scores, ``top_k``, a
  mask.
* Attention: ``softmax`` of ``q . k / sqrt(d)`` over ``S_t`` ONLY (a
  position outside it gets no probability mass), times ``v``; ``x <- x +
  concat(heads) W_O``.
* Expert FFN, every layer: ``p = softmax(h W_r)`` in float32, the ``top_k``
  largest, ``w_e = p_e / sum_top p``; ``x <- x + sum_top w_e E_e(h)``, ``E`` a
  SwiGLU; no shared expert, no scaling factor.  Computed the plain way: EVERY
  expert over EVERY token, weighted by ``w`` (zero where the token did not
  choose it) — the whole of it, not the cheaper form ISSUE 36 allows: the
  binding hands over a sequence cut to its judged rows (causal: later rows
  change nothing), which keeps it inside a run's tail.
* Head: final RMSNorm, untied ``W_head``.

What the ``config.json`` leaves open is marked ``ASSUMED (a)``..``(e)`` on the
one line that decides it, as in the program
(``apex_tpu/transformer/testing/standalone_keye.py``) and under ``assumed`` in
the configuration file.

``select`` is a CONTROL, never the reference, beside ``quant`` (``"fp8"``:
both operands of every matrix product rounded to float8_e4m3): ``"all"``
attends every causal position (a program that skipped the indexer),
``"recent"`` the most recent ``topk`` (a program that took a window for the
selection).  The reference is ``select="learned"``, ``quant=None``.

Weights (``reference_weights`` of ``bindings/dsa_keye.py``) stay in the type they
are served in; each layer is up-cast as it is used, the routed experts one at
a time.
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
    heads: int
    kv_heads: int
    head_dim: int
    sections: Tuple[int, int, int]      # rotary pairs per position axis
    theta: float
    index_heads: int
    index_dim: int
    topk: int                           # positions a query attends
    top_k: int                          # experts a token
    eps: float


def spec_from_config(cfg: dict) -> Spec:
    """From the published keys of a ``model_type: KeyeVL2`` configuration."""
    sa, rope = cfg["sa_config"], cfg["rope_scaling"]
    assert rope["rope_type"] == "default" and sa["indexer_num_kv_heads"] == 1
    assert not cfg["attention_bias"] and not cfg["tie_word_embeddings"]
    assert cfg["norm_topk_prob"] and cfg["decoder_sparse_step"] == 1 \
        and not cfg["mlp_only_layers"] and not cfg["use_sliding_window"]
    assert cfg["num_local_experts"] == cfg["num_experts"]
    assert 2 * sum(rope["mrope_section"]) == cfg["head_dim"]
    return Spec(
        heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        sections=tuple(rope["mrope_section"]),
        theta=float(cfg["rope_theta"]),
        index_heads=sa["indexer_num_heads"],
        index_dim=sa["indexer_head_dim"], topk=sa["topk"],
        top_k=cfg["num_experts_per_tok"], eps=float(cfg["rms_norm_eps"]))


def _rotate(x, ang):
    """``x [seq, ..., d]``, ``ang [seq, d/2]``: channel ``i`` with ``i +
    d/2``."""
    half = x.shape[-1] // 2
    ang = ang.reshape(ang.shape[0], *(1,) * (x.ndim - 2), half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    # ASSUMED (e): RoPE pairs channel i with i + half
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def rope(x, spec: Spec, positions):
    """``x [seq, heads, d]``, ``positions [3, seq]`` (temporal, height,
    width): pair ``i`` turns by its section's axis."""
    pairs = spec.head_dim // 2
    axis = sum(([a] * n for a, n in enumerate(spec.sections)), [])
    inv = jnp.asarray([spec.theta ** (-i / pairs) for i in range(pairs)],
                      jnp.float32)
    pos = positions.astype(jnp.float32)[jnp.asarray(axis)]   # [pairs, seq]
    return _rotate(x, pos.T * inv[None])


def index_rope(x, spec: Spec, positions):
    """``x [seq, (heads,) di]``: all channels, the temporal position."""
    # ASSUMED (c): 32 pairs cannot carry the three sections; same theta
    pairs = spec.index_dim // 2
    inv = jnp.asarray([spec.theta ** (-i / pairs) for i in range(pairs)],
                      jnp.float32)
    return _rotate(x, positions[0].astype(jnp.float32)[:, None] * inv[None])


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * gain


def layer_norm(x, gain, bias, eps):
    xc = x - jnp.mean(x, axis=-1, keepdims=True)
    return xc * jax.lax.rsqrt(jnp.mean(jnp.square(xc), axis=-1,
                                       keepdims=True) + eps) * gain + bias


def f32(x):
    return jnp.asarray(x, jnp.float32)


def index_parts(h, lw, spec: Spec, positions, quant):
    """``qI [seq, heads_i, di]``, ``w [seq, heads_i]``, ``kI [seq, di]``."""
    s, hi, di = h.shape[0], spec.index_heads, spec.index_dim
    # ASSUMED (b): the lightning indexer's form — ReLU, head weights from
    # the hidden state scaled heads^-1/2 di^-1/2, a LayerNorm on the key
    qi = index_rope(matmul(h, f32(lw["wqi"]), quant).reshape(s, hi, di),
                    spec, positions)
    ki = index_rope(layer_norm(matmul(h, f32(lw["wki"]), quant),
                               f32(lw["ki_gain"]), f32(lw["ki_bias"]),
                               spec.eps), spec, positions)
    w = matmul(h, f32(lw["ww"]), quant) * (hi ** -0.5 * di ** -0.5)
    return qi, w, ki


def picked_rows(qi, w, ki, start, spec: Spec, quant, select: str):
    """The bool mask ``[ROW_BLOCK, seq]`` of the positions rows ``start ..``
    attend."""
    s = ki.shape[0]
    i = start + jnp.arange(ROW_BLOCK)[:, None]
    cols = jnp.arange(s)[None, :]
    causal = cols <= i
    if select == "all":
        return causal
    if select == "recent":
        return causal & (cols > i - spec.topk)
    qb = jax.lax.dynamic_slice_in_dim(qi, start, ROW_BLOCK, axis=0)
    wb = jax.lax.dynamic_slice_in_dim(w, start, ROW_BLOCK, axis=0)
    dots = jnp.einsum("thd,sd->ths", _round(qb, quant), _round(ki, quant),
                      precision=HI)
    score = jnp.sum(wb[..., None] * jnp.maximum(dots, 0.0), axis=1)
    score = jnp.where(causal, score, -jnp.inf)
    # ASSUMED (d): per token, not per block; ties to the lower position
    _, idx = jax.lax.top_k(score, min(spec.topk, s))
    chosen = jnp.zeros((ROW_BLOCK, s), bool).at[
        jnp.arange(ROW_BLOCK)[:, None], idx].set(True)
    return chosen & causal


def attention(x, lw, spec: Spec, positions, quant, select):
    s, d, kvh, heads = x.shape[0], spec.head_dim, spec.kv_heads, spec.heads
    h = rms_norm(x, f32(lw["ln1"]), spec.eps)
    q = matmul(h, f32(lw["wq"]), quant).reshape(s, heads, d)
    k = matmul(h, f32(lw["wk"]), quant).reshape(s, kvh, d)
    v = matmul(h, f32(lw["wv"]), quant).reshape(s, kvh, d)
    # ASSUMED (a): per-head RMSNorm on q and k before RoPE
    q = rope(rms_norm(q, f32(lw["q_gain"]), spec.eps), spec, positions)
    k = rope(rms_norm(k, f32(lw["k_gain"]), spec.eps), spec, positions)
    qi, w, ki = index_parts(h, lw, spec, positions, quant)
    group = heads // kvh          # query head a reads KV head a // group
    q = q.reshape(s, kvh, group, d)
    kq, vq = _round(k, quant), _round(v, quant)

    def rows(start):
        see = picked_rows(qi, w, ki, start, spec, quant, select)
        qb = jax.lax.dynamic_slice_in_dim(q, start, ROW_BLOCK, axis=0)
        sc = jnp.einsum("qngd,knd->ngqk", _round(qb, quant), kq,
                        precision=HI) / math.sqrt(d)
        pr = jax.nn.softmax(jnp.where(see[None, None], sc, -jnp.inf),
                            axis=-1)
        return jnp.einsum("ngqk,knd->qngd", _round(pr, quant), vq,
                          precision=HI)

    assert s % ROW_BLOCK == 0, (s, ROW_BLOCK)
    ctx = jax.lax.map(rows, jnp.arange(0, s, ROW_BLOCK)).reshape(
        s, heads * d)
    return matmul(ctx, f32(lw["wo"]), quant)


def expert_ffn(h, lw, spec: Spec, quant):
    p = jax.nn.softmax(matmul(h, f32(lw["router"]), quant), axis=-1)
    top_p, top_e = jax.lax.top_k(p, spec.top_k)
    w = top_p / jnp.sum(top_p, axis=-1, keepdims=True)   # norm_topk_prob
    rows = jnp.arange(h.shape[0])[:, None]
    dense_w = jnp.zeros_like(p).at[rows, top_e].set(w)         # [s, E]

    def one(acc, e):
        wg, wu, wd, col = e           # [in, out] slices of the stacks
        y = matmul(jax.nn.silu(matmul(h, f32(wg).T, quant))
                   * matmul(h, f32(wu).T, quant), f32(wd).T, quant)
        return acc + col[:, None] * y, None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        lw["e_gate"], lw["e_up"], lw["e_down"], dense_w.T))
    return routed


@functools.partial(jax.jit, static_argnames=("spec", "quant", "select"))
def layer(x, lw, positions, *, spec: Spec, quant=None, select="learned"):
    """One decoder layer over ``x [seq, hidden]`` float32."""
    x = x + attention(x, lw, spec, positions, quant, select)
    return x + expert_ffn(rms_norm(x, f32(lw["ln2"]), spec.eps), lw, spec,
                          quant)


@functools.partial(jax.jit, static_argnames=("rows", "eps", "quant"))
def _head(x, first, gain, w_head, *, rows: int, eps: float, quant=None):
    at = jnp.clip(first + jnp.arange(rows), 0, x.shape[0] - 1)
    return matmul(rms_norm(x[at], f32(gain), eps), f32(w_head), quant)


def hidden(weights, tokens, spec: Spec, quant=None, select="learned",
           positions=None):
    """The residual stream ``[seq, hidden]`` after the last layer;
    ``positions [3, seq]`` default to a text's (its indices, three times)."""
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(tokens.shape[0]),
                                     (3, tokens.shape[0]))
    x = f32(weights["embed"][tokens])
    for lw in weights["layers"]:
        x = layer(x, lw, positions, spec=spec, quant=quant, select=select)
    return x


def logits(weights, tokens, first, rows: int, *, spec: Spec, quant=None,
           select="learned"):
    """Float32 logits ``[rows, vocab]`` of the ``rows`` positions from
    ``first`` on (held to the last one) of the one sequence ``tokens``
    ``[seq]``, ``seq`` a multiple of ``ROW_BLOCK``."""
    x = hidden(weights, tokens, spec, quant, select)
    return _head(x, first, weights["final_norm"], weights["head"],
                 rows=rows, eps=spec.eps, quant=quant)
