"""Plain reference of the ``hy4-preview-serve`` configuration: the decoder
that tencent/Hy4-preview's ``config.json`` (``model_type: hy_v4``)
describes, written from the layer equations stated for it — float32
``jax.numpy``, every product at ``Precision.HIGHEST``, no kernel, no cache,
no batching.  One sequence, one full forward; it imports nothing of the
program.

The residual is ``n = hc_mult`` streams ``X [seq, n, hidden]``, RMSNorm with
a learned scale, no bias but the index key's LayerNorm.  Per layer, for each
sublayer ``F`` (attention, then the FFN), with ``x~ = RMSNorm_hc_eps(vec X)``
(no gain):

* Mixes: ``H_pre = sigmoid(a_pre x~ phi_pre + b_pre)``, ``H_post = hc_magnitude
  sigmoid(a_post x~ phi_post + b_post)``, ``H_res = Sinkhorn(exp(a_res
  mat(x~ phi_res) + b_res))`` (``SINKHORN`` rounds of row then column
  normalisation, each denominator ``+ hc_eps``); ``u = sum_i H_pre[i] X_i``,
  ``X <- H_res X + H_post (x) F(u)``.  After the last layer ``h = sum_i
  H_head[i] X_i``, ``H_head = sigmoid(a_head x~ phi_head + b_head)``.
* Attention, ``a = RMSNorm(u)``: ``c_q = RMSNorm(a W_DQ)``, ``[q_nope | q_rope]
  = c_q W_UQ`` (``heads`` x (``nope`` + ``rope``)); ``c = RMSNorm(a W_DKV)``
  (``kv_lora``), ``k_rope`` the row's last ``rope`` channels; RoPE (theta,
  channel ``i`` with ``i + rope/2``) on ``q_rope`` and ``k_rope``; ``k_nope =
  W_UK c``, ``v = W_UV c``.  Scores ``z[t, s] = (q_nope . k_nope + q_rope .
  k_rope) / sqrt(nope + rope)`` over the picked ``s`` only; ``p = exp(z) /
  (exp(sink_h) + sum exp(z))``; ``o = W_O (sigmoid(W_G a) * concat_h sum_s p
  v)``.  Computed ABSORBED — ``q_nope . W_UK c_s = (W_UK^T q_nope) . c_s``
  and ``sum_s p W_UV c_s = W_UV sum_s p c_s`` — the same sums in another
  order, so that no ``[seq, heads, 256]`` key or value array is made: the
  chip's memory holds the weights beside a 16k sequence this way.
* Indexer, on a ``full`` layer: ``qI = RoPE_i(c_q W_qI)`` (``index_heads`` x
  ``di``), ``kI = RoPE_i(LayerNorm(a W_kI))``, ``w = (a W_w) index_heads^-1/2
  di^-1/2``; ``RoPE_i`` turns the leading ``rope`` channels.  ``I[t, s] = sum_j
  w[t, j] relu(qI[t, j] . kI[s])``; the picked set of row ``t`` is the
  ``min(topk, t + 1)`` causal positions of largest ``I``, equal scores to the
  lower position (``jax.lax.top_k``'s order).  A ``shared`` layer has no
  indexer and attends the set of the nearest full layer before it (here the
  set is made again from that layer's index queries and keys: the same set).
* FFN: ``swiglu(x) = W_down(silu(min(W_gate x, L)) * clip(W_up x, -L, L))``,
  ``L = swiglu_limit``.  The dense layers are one SwiGLU; the others a
  sigmoid router over ALL ``n_routed_experts`` (one group: the top
  ``num_experts_per_tok`` of ``sigmoid(x W_r)``, renormalised, times
  ``routed_scaling_factor``), of which the HELD experts add their part —
  EVERY held expert over EVERY token, weighted by its routing weight (zero
  where the token did not choose it) — plus the shared expert.
* Head: final RMSNorm of ``h``, float32 ``W_head`` over this chip's slice.

What the ``config.json`` leaves open is marked ``ASSUMED`` on the one line
that decides it, as in the program
(``apex_tpu/transformer/testing/standalone_hy4.py``) and under ``assumed`` in
the configuration file.

The CONTROLS, never the reference: ``quant="fp8"`` (both operands of every
matrix product rounded to float8_e4m3), and in full precision ``select=``
``"all"`` (every causal position: a program that skipped the indexer),
``"recent"`` (the most recent ``topk``), ``"self"`` (every layer picks for
itself: a shared layer scores with the indexer of the full layer it would
have reused, over its own input), and ``static_hc=True`` (the mixes' input
terms dropped: ``H = f(b)``).  ``drop`` leaves mechanisms out one at a time
(``"sink"``, ``"gate"``, ``"sinkhorn"``, ``"clamp"``), for the tests that show
each one matters.

Weights (``reference_weights`` of ``bindings/mla_dsa_hy4.py``) stay in the
type they are served in; each layer is up-cast as it is used, the routed
experts one at a time.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from .keye_lm import layer_norm, rms_norm
from .transformer import HI, _round, matmul

ROW_BLOCK = 128       # query rows scored at a time: [heads, 128, seq] floats
CHUNK = 1024          # rows whose streams and FFN are computed at a time
#: ASSUMED (a): mHC's t_max, Sinkhorn rounds a mix
SINKHORN = 20


class Spec(NamedTuple):
    """The numbers of a configuration file the equations need."""
    heads: int
    nope: int
    rope: int
    v: int
    kv_lora: int
    theta: float
    index_heads: int
    index_dim: int
    topk: int
    indexer_types: Tuple[str, ...]
    dense: Tuple[bool, ...]             # per layer: a plain SwiGLU
    top_k: int                          # experts a token
    scale: float                        # routed_scaling_factor
    held_first: int
    limit: float                        # swiglu_limit
    streams: int
    magnitude: float
    hc_eps: float
    eps: float


def spec_from_config(cfg: dict) -> Spec:
    """From the published keys of a ``model_type: hy_v4`` configuration."""
    layers = cfg["num_hidden_layers"]
    assert cfg["n_group"] == cfg["topk_group"] == 1 and cfg["norm_topk_prob"]
    assert cfg["use_mla"] and cfg["use_dsa"] and cfg["gated_mla"] \
        and cfg["gating_type"] == "elementwise" and cfg["learnable_sink"]
    assert cfg["enable_ihc"] and cfg["enable_lm_head_fp32"]
    assert cfg["rope_parameters"]["rope_type"] == "default"
    assert not cfg["attention_bias"] and not cfg["tie_word_embeddings"]
    assert cfg["n_shared_experts"] == 1
    assert cfg["qk_head_dim"] == cfg["qk_nope_head_dim"] \
        + cfg["qk_rope_head_dim"]
    assert len(cfg["indexer_types"]) == len(cfg["mlp_layer_types"]) == layers
    return Spec(
        heads=cfg["num_attention_heads"], nope=cfg["qk_nope_head_dim"],
        rope=cfg["qk_rope_head_dim"], v=cfg["v_head_dim"],
        kv_lora=cfg["kv_lora_rank"],
        theta=float(cfg["rope_parameters"]["rope_theta"]),
        index_heads=cfg["index_n_heads"], index_dim=cfg["index_head_dim"],
        topk=cfg["index_topk"], indexer_types=tuple(cfg["indexer_types"]),
        dense=tuple(t == "dense" for t in cfg["mlp_layer_types"]),
        top_k=cfg["num_experts_per_tok"],
        scale=float(cfg["routed_scaling_factor"]),
        held_first=cfg["held_experts_first"],
        limit=float(cfg["swiglu_limit"]), streams=cfg["hc_mult"],
        magnitude=float(cfg["hc_magnitude"]), hc_eps=float(cfg["hc_eps"]),
        eps=float(cfg["rms_norm_eps"]))


def f32(x):
    return jnp.asarray(x, jnp.float32)


def rope(x, spec: Spec, positions):
    """The leading ``spec.rope`` channels of ``x [seq, ..., d]`` turned by
    their position, channel ``i`` with ``i + rope/2``; the rest as they
    are."""
    half = spec.rope // 2
    inv = spec.theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv[None]
    ang = ang.reshape(ang.shape[0], *(1,) * (x.ndim - 2), half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b, rest = x[..., :half], x[..., half:spec.rope], x[..., spec.rope:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest],
                           axis=-1)


# --------------------------------------------------------------------------
# the streams
# --------------------------------------------------------------------------

def sinkhorn(m, eps: float):
    for _ in range(SINKHORN):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


def mixes(x, hp, sizes, spec: Spec, quant, static: bool):
    """``a x~ phi + b`` of each mix, ``[seq, sum(sizes)]``."""
    flat = x.reshape(x.shape[0], -1)
    xt = flat * jax.lax.rsqrt(jnp.mean(jnp.square(flat), axis=-1,
                                       keepdims=True) + spec.hc_eps)
    z = matmul(xt, f32(hp["phi"]), quant)
    alpha = jnp.concatenate([jnp.full((k,), a) for k, a in zip(
        sizes, f32(hp["alpha"]))])
    if static:
        alpha = jnp.zeros_like(alpha)
    return z * alpha + f32(hp["bias"])


def read_streams(x, hp, spec: Spec, quant, static, drop):
    """A sublayer's input ``sum_i H_pre[i] X_i`` and its write mixes
    ``(H_post, H_res)``."""
    n = spec.streams
    z = mixes(x, hp, (n, n, n * n), spec, quant, static)
    pre = jax.nn.sigmoid(z[:, :n])
    post = spec.magnitude * jax.nn.sigmoid(z[:, n:2 * n])
    res = jnp.exp(z[:, 2 * n:].reshape(-1, n, n))
    if "sinkhorn" not in drop:
        res = sinkhorn(res, spec.hc_eps)
    return jnp.einsum("si,sih->sh", pre, x, precision=HI), post, res


def write_streams(x, post, res, y):
    """``X <- H_res X + H_post (x) y``."""
    return jnp.einsum("sij,sjh->sih", res, x, precision=HI) \
        + post[..., None] * y[:, None, :]


def sublayer(x, hp, fn, spec: Spec, quant, static, drop):
    """``X <- H_res X + H_post (x) fn(sum_i H_pre[i] X_i)``."""
    u, post, res = read_streams(x, hp, spec, quant, static, drop)
    return write_streams(x, post, res, fn(u))


def _chunk(s: int) -> int:
    return math.gcd(CHUNK, s)


def by_chunks(fn, x, *rest):
    """``fn`` over row chunks of ``x`` and ``rest`` (every row alone), ``x``
    rewritten in place a chunk at a time: no second ``x`` is made."""
    c = _chunk(x.shape[0])

    def body(i, x):
        rows = [jax.lax.dynamic_slice_in_dim(a, i * c, c)
                for a in (x, *rest)]
        return jax.lax.dynamic_update_slice_in_dim(x, fn(*rows), i * c, 0)

    return jax.lax.fori_loop(0, x.shape[0] // c, body, x)


# --------------------------------------------------------------------------
# attention over the picked positions
# --------------------------------------------------------------------------

def index_parts(a, c_q, iw, spec: Spec, positions, quant):
    """``qI [seq, heads_i, di]``, ``w [seq, heads_i]``, ``kI [seq, di]``."""
    s, hi, di = a.shape[0], spec.index_heads, spec.index_dim
    qi = rope(matmul(c_q, f32(iw["wqi"]), quant).reshape(s, hi, di), spec,
              positions)
    ki = rope(layer_norm(matmul(a, f32(iw["wki"]), quant),
                         f32(iw["ki_gain"]), f32(iw["ki_bias"]), spec.eps),
              spec, positions)
    w = matmul(a, f32(iw["ww"]), quant) * (hi ** -0.5 * di ** -0.5)
    return qi, w, ki


def picked_rows(parts, start, spec: Spec, quant, select: str):
    """The bool mask ``[ROW_BLOCK, seq]`` of the positions rows ``start ..``
    attend."""
    qi, w, ki = parts
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
    _, idx = jax.lax.top_k(score, min(spec.topk, s))
    chosen = jnp.zeros((ROW_BLOCK, s), bool).at[
        jnp.arange(ROW_BLOCK)[:, None], idx].set(True)
    return chosen & causal


def attention(u, lw, iw, carried, spec: Spec, positions, quant, select,
              drop):
    """The attention sublayer over its input ``u [seq, hidden]`` -> ``(o,
    parts)``: the picks are made from ``index_parts`` of this sublayer's
    input under the indexer ``iw``, or, with ``iw`` None, are the
    ``carried`` parts of the layer it reuses."""
    s, heads, nope = u.shape[0], spec.heads, spec.nope
    a = rms_norm(u, f32(lw["ln1"]), spec.eps)
    c_q = rms_norm(matmul(a, f32(lw["wdq"]), quant), f32(lw["q_gain"]),
                   spec.eps)
    row = matmul(a, f32(lw["wdkv"]), quant)
    c = rms_norm(row[:, :spec.kv_lora], f32(lw["kv_gain"]), spec.eps)
    k_rope = rope(row[:, spec.kv_lora:], spec, positions)
    w_ukv = f32(lw["wukv"]).reshape(heads, nope + spec.v, spec.kv_lora)
    w_uk, w_uv = w_ukv[:, :nope], w_ukv[:, nope:]
    parts = carried if iw is None else index_parts(a, c_q, iw, spec,
                                                   positions, quant)
    cq, kq = _round(c, quant), _round(k_rope, quant)
    sink = f32(lw["sink"])
    scale = (nope + spec.rope) ** -0.5

    def rows(start):
        """The sublayer's output for rows ``start ..``: their queries, the
        picked positions' softmax, the gate and ``W_O``."""
        see = picked_rows(parts, start, spec, quant, select)
        ab, cb, pb = (jax.lax.dynamic_slice_in_dim(t, start, ROW_BLOCK)
                      for t in (a, c_q, positions))
        q = matmul(cb, f32(lw["wuq"]), quant).reshape(ROW_BLOCK, heads,
                                                      nope + spec.rope)
        qn, qr = q[..., :nope], rope(q[..., nope:], spec, pb)
        q_lat = jnp.einsum("thn,hnc->thc", _round(qn, quant),
                           _round(w_uk, quant), precision=HI)
        sc = (jnp.einsum("thc,sc->hts", _round(q_lat, quant), cq,
                         precision=HI)
              + jnp.einsum("thr,sr->hts", _round(qr, quant), kq,
                           precision=HI)) * scale
        sc = jnp.where(see[None], sc, -jnp.inf)
        if "sink" not in drop:
            sc = jnp.concatenate([sc, jnp.broadcast_to(
                sink[:, None, None], (heads, ROW_BLOCK, 1))], axis=-1)
        pr = jax.nn.softmax(sc, axis=-1)[..., :s]
        ctx = jnp.einsum("hts,sc->thc", _round(pr, quant), cq, precision=HI)
        ctx = jnp.einsum("thc,hvc->thv", _round(ctx, quant),
                         _round(w_uv, quant), precision=HI).reshape(
                             ROW_BLOCK, heads * spec.v)
        if "gate" not in drop:
            # ASSUMED (c): elementwise = one gate a value channel, from a
            ctx = ctx * jax.nn.sigmoid(matmul(ab, f32(lw["wg"]), quant))
        return matmul(ctx, f32(lw["wo"]), quant)

    assert s % ROW_BLOCK == 0, (s, ROW_BLOCK)
    return jax.lax.map(rows, jnp.arange(0, s, ROW_BLOCK)).reshape(
        s, -1), parts


# --------------------------------------------------------------------------
# the FFN
# --------------------------------------------------------------------------

def swiglu(h, wg, wu, wd, spec: Spec, quant, drop):
    """``[out, in]`` weights; ASSUMED (d): the gate clamped from above, the
    up projection both ways, at ``swiglu_limit``."""
    g, u = matmul(h, wg, quant), matmul(h, wu, quant)
    if "clamp" not in drop:
        g, u = jnp.minimum(g, spec.limit), jnp.clip(u, -spec.limit,
                                                    spec.limit)
    return matmul(jax.nn.silu(g) * u, wd, quant)


def ffn(h, lw, spec: Spec, dense: bool, quant, drop):
    if dense:
        return swiglu(h, f32(lw["gate"]), f32(lw["up"]), f32(lw["down"]),
                      spec, quant, drop)
    sig = jax.nn.sigmoid(matmul(h, f32(lw["router"]), quant))   # [s, E]
    top_s, top_e = jax.lax.top_k(sig, spec.top_k)
    w = spec.scale * top_s / (jnp.sum(top_s, axis=-1, keepdims=True)
                              + 1e-20)
    rows = jnp.arange(h.shape[0])[:, None]
    dense_w = jnp.zeros_like(sig).at[rows, top_e].set(w)         # [s, E]
    count = lw["e_gate"].shape[0]
    held_w = jax.lax.dynamic_slice_in_dim(dense_w, spec.held_first, count,
                                          axis=1)

    def one(acc, e):
        wg, wu, wd, col = e           # [in, out] slices of the stacks
        y = swiglu(h, f32(wg).T, f32(wu).T, f32(wd).T, spec, quant, drop)
        return acc + col[:, None] * y, None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        lw["e_gate"], lw["e_up"], lw["e_down"], held_w.T))
    return routed + swiglu(h, f32(lw["s_gate"]), f32(lw["s_up"]),
                           f32(lw["s_down"]), spec, quant, drop)


@functools.partial(jax.jit, static_argnames=(
    "spec", "dense", "quant", "select", "static", "drop"),
    donate_argnums=(0,))
def layer(x, lw, iw, carried, positions, *, spec: Spec, dense: bool,
          quant=None, select="learned", static=False, drop=()):
    """One decoder layer over the streams ``x [seq, n, hidden]`` float32 ->
    ``(x, parts)``, the index parts its picks were made from (``attention``
    says which)."""
    # attention: the streams read a chunk of rows at a time, attention over
    # every row, the streams written back in place a chunk at a time
    c = _chunk(x.shape[0])
    u, post, res = (t.reshape(-1, *t.shape[2:]) for t in jax.lax.map(
        lambda xc: read_streams(xc, lw["hc_attn"], spec, quant, static,
                                drop), x.reshape(-1, c, *x.shape[1:])))
    y, parts = attention(u, lw, iw, carried, spec, positions, quant,
                         select, drop)
    x = by_chunks(write_streams, x, post, res, y)
    # the FFN: every row alone
    return by_chunks(lambda xc: sublayer(xc, lw["hc_ffn"], lambda u: ffn(
        rms_norm(u, f32(lw["ln2"]), spec.eps), lw, spec, dense, quant,
        drop), spec, quant, static, drop), x), parts


@functools.partial(jax.jit, static_argnames=("rows", "spec", "quant",
                                             "static"))
def _head(x, first, hc, gain, w_head, *, rows: int, spec: Spec, quant=None,
          static=False):
    at = jnp.clip(first + jnp.arange(rows), 0, x.shape[0] - 1)
    xs = x[at]
    z = mixes(xs, hc, (spec.streams,), spec, quant, static)
    h = jnp.einsum("si,sih->sh", jax.nn.sigmoid(z), xs, precision=HI)
    return matmul(rms_norm(h, f32(gain), spec.eps), f32(w_head), quant)


def logits(weights, tokens, first, rows: int, *, spec: Spec, quant=None,
           select="learned", static=False, drop=()):
    """Float32 logits ``[rows, vocab]`` of the ``rows`` positions from
    ``first`` on (held to the last one) of the one sequence ``tokens``
    ``[seq]``, ``seq`` a multiple of ``ROW_BLOCK``."""
    positions = jnp.arange(tokens.shape[0])
    e = f32(weights["embed"][tokens])
    x = jnp.broadcast_to(e[:, None, :], (e.shape[0], spec.streams,
                                          e.shape[1]))
    drop = tuple(sorted(drop))
    last_full, parts = None, None
    for i, lw in enumerate(weights["layers"]):
        full = spec.indexer_types[i] == "full"
        if full:
            last_full = lw["indexer"]
        # a shared layer attends the nearest full layer's picks, made from
        # THAT layer's index queries and keys (carried); the control "self"
        # makes them from its own input under the indexer it would reuse
        iw = last_full if full or select == "self" else None
        x, parts = layer(x, lw, iw, parts, positions, spec=spec,
                         dense=spec.dense[i], quant=quant, select=select,
                         static=static, drop=drop)
    return _head(x, first, weights["hc_head"], weights["final_norm"],
                 weights["head"], rows=rows, spec=spec, quant=quant,
                 static=static)
