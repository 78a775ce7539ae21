"""Plain reference of the ``dots3-note-prev-serve`` configuration: the decoder
that dots-studio/dots3-note-prev's ``config.json`` (``model_type:
dots3_note``) describes, written from the layer equations stated for it —
float32 ``jax.numpy``, every product at ``Precision.HIGHEST``, no kernel, no
cache, no ring, no batching.  One sequence, one full forward; it imports
nothing of the program.

Pre-norm residual ``x <- x + Attn(RMSNorm(x)); x <- x + FFN(RMSNorm(x))``,
RMSNorm with a learned scale (``rms_norm_eps``), no bias but the index
key's LayerNorm and the router's selection bias.  A layer's attention is
latent attention of its TYPE's geometry — ``full_attention``: ``heads``,
``nope`` + ``rope``, values ``v``, latent ``kv_lora``, base ``theta``;
``sliding_attention``: the ``swa_*`` sizes and base.  With ``a`` the
sublayer's normed input:

* ``c_q = RMSNorm(a W_DQ)``, ``[q_nope | q_rope] = c_q W_UQ`` (``heads`` x
  (``nope`` + ``rope``)); ``[c | k_rope] = a W_DKV``, ``c <- RMSNorm(c)``;
  RoPE (the type's base, channel ``i`` with ``i + rope/2``) on ``q_rope``
  and the head-shared ``k_rope``; ``k_nope = W_UK c``, ``v = W_UV c``.
  Scores ``z[t, s] = (q_nope . k_nope + q_rope . k_rope) / sqrt(nope +
  rope)``, softmax over the positions ``s`` the row attends, ``ctx_h =
  sum_s p v_s``; the headwise gate ``g = sigmoid(a W_G)`` (one a head) and
  ``o = W_O concat_h(g_h ctx_h)``.  Computed ABSORBED — ``q_nope . W_UK c_s
  = (W_UK^T q_nope) . c_s`` and ``sum_s p W_UV c_s = W_UV sum_s p c_s`` —
  the same sums in another order, so that no ``[seq, heads, width]`` key or
  value array is made: the chip's memory holds the weights beside a 16k
  sequence this way.
* A ``full_attention`` layer attends the positions its own indexer picks:
  ``qI = RoPE(c_q W_qI)`` (``index_heads`` x ``di``), ``kI = RoPE(LayerNorm(a
  W_kI))``, ``w = (a W_w) index_heads^-1/2 di^-1/2``, RoPE turning the
  leading ``rope`` channels at the full layers' base; ``I[t, s] = sum_j w[t,
  j] relu(qI[t, j] . kI[s])``; row ``t`` attends the ``min(topk, t + 1)``
  causal positions of largest ``I``, equal scores to the lower position
  (``jax.lax.top_k``'s order).
* A ``sliding_attention`` layer attends ``t - window < s <= t``: the
  window as a MASK over the whole causal sequence, not as a ring.
* FFN: layer ``i < first_k_dense_replace`` is ``W_down(silu(W_gate x) *
  W_up x)``; every later layer a sigmoid router over ALL
  ``n_routed_experts`` — the ``num_experts_per_tok`` experts of largest
  ``sigmoid(x W_r) + e_score_correction_bias`` are CHOSEN, weighted
  ``routed_scaling_factor sigma_e / sum of the chosen sigma`` — of which
  the HELD experts add their part (EVERY held expert over EVERY token,
  weighted by its routing weight, zero where the token did not choose it),
  plus the ungated shared expert.
* Head: final RMSNorm, ``W_head`` over this chip's slice.

What the ``config.json`` leaves open is marked ``ASSUMED`` on the one line
that decides it, as in the program
(``apex_tpu/transformer/testing/standalone_dots3.py``) and under ``assumed``
in the configuration file.

The CONTROLS, never the reference: ``quant="fp8"`` (both operands of every
matrix product rounded to float8_e4m3), and in full precision ``select=``
``"all"`` (every full layer attends every causal position: a program that
skipped the indexer) or ``"recent"`` (the most recent ``topk``), ``swa=
"all"`` (every window layer attends its whole causal context: a program
that lost the window), and ``drop``: ``"bias"`` (the router chooses by
``sigma`` alone), ``"gate"`` (no gate), ``"geometry"`` (the window layers
computed with the full layers' RoPE base and softmax scale — what of the
full geometry their weights' shapes leave free).

Weights (``reference_weights`` of ``bindings/mla_swa_dots3.py``) stay in the
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

ROW_BLOCK = 64        # query rows scored at a time: [heads, 64, seq] floats
CHUNK = 1024          # rows whose FFN is computed at a time
FULL, SLIDING = "full_attention", "sliding_attention"


class Geometry(NamedTuple):
    """One latent-attention geometry of the configuration."""
    heads: int
    nope: int
    rope: int
    v: int
    kv_lora: int
    theta: float


class Spec(NamedTuple):
    """The numbers of a configuration file the equations need."""
    full: Geometry
    swa: Geometry
    window: int
    index_heads: int
    index_dim: int
    topk: int
    layer_types: Tuple[str, ...]
    dense: Tuple[bool, ...]             # per layer: a plain SwiGLU
    top_k: int                          # experts a token
    scale: float                        # routed_scaling_factor
    held_first: int
    eps: float


def spec_from_config(cfg: dict) -> Spec:
    """From the published keys of a ``model_type: dots3_note``
    configuration."""
    layers = cfg["num_hidden_layers"]
    assert cfg["scoring_func"] == "sigmoid" and cfg["norm_topk_prob"]
    assert cfg["topk_method"] == "noaux_tc" and cfg["hidden_act"] == "silu"
    assert cfg["attention_gate_type"] == "headwise" \
        and cfg["swa_attention_gate_type"] == "headwise"
    assert cfg["rope_scaling"] is None and not cfg["attention_bias"] \
        and not cfg["tie_word_embeddings"]
    assert cfg["n_shared_experts"] == 1 and cfg["moe_layer_freq"] == 1
    assert len(cfg["layer_types"]) == layers
    assert set(cfg["layer_types"]) <= {FULL, SLIDING}
    return Spec(
        full=Geometry(cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                      cfg["qk_rope_head_dim"], cfg["v_head_dim"],
                      cfg["kv_lora_rank"], float(cfg["rope_theta"])),
        swa=Geometry(cfg["swa_num_attention_heads"],
                     cfg["swa_qk_nope_head_dim"],
                     cfg["swa_qk_rope_head_dim"], cfg["swa_v_head_dim"],
                     cfg["swa_kv_lora_rank"], float(cfg["swa_rope_theta"])),
        window=cfg["sliding_window_size"],
        index_heads=cfg["index_n_heads"], index_dim=cfg["index_head_dim"],
        topk=cfg["index_topk"], layer_types=tuple(cfg["layer_types"]),
        dense=tuple(i < cfg["first_k_dense_replace"] for i in range(layers)),
        top_k=cfg["num_experts_per_tok"],
        scale=float(cfg["routed_scaling_factor"]),
        held_first=cfg["held_experts_first"], eps=float(cfg["rms_norm_eps"]))


def f32(x):
    return jnp.asarray(x, jnp.float32)


def rope(x, rot: int, theta: float, positions):
    """The leading ``rot`` channels of ``x [seq, ..., d]`` turned by their
    position, channel ``i`` with ``i + rot/2``; the rest as they are."""
    half = rot // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv[None]
    ang = ang.reshape(ang.shape[0], *(1,) * (x.ndim - 2), half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest],
                           axis=-1)


def _chunk(s: int) -> int:
    return math.gcd(CHUNK, s)


def by_chunks(fn, x):
    """``fn`` over row chunks of ``x`` (every row alone), ``x`` rewritten
    in place a chunk at a time: no second ``x`` is made."""
    c = _chunk(x.shape[0])

    def body(i, x):
        rows = jax.lax.dynamic_slice_in_dim(x, i * c, c)
        return jax.lax.dynamic_update_slice_in_dim(x, fn(rows), i * c, 0)

    return jax.lax.fori_loop(0, x.shape[0] // c, body, x)


# --------------------------------------------------------------------------
# attention
# --------------------------------------------------------------------------

def index_parts(a, c_q, iw, spec: Spec, positions, quant):
    """``qI [seq, heads_i, di]``, ``w [seq, heads_i]``, ``kI [seq, di]``."""
    s, hi, di = a.shape[0], spec.index_heads, spec.index_dim
    rot, theta = spec.full.rope, spec.full.theta
    # ASSUMED (c): DeepSeek-V3.2's lightning indexer: qI from the query
    # latent, one LayerNormed key a position, RoPE on the leading 64
    # channels at the full layers' base, no Hadamard rotation, no fp8
    qi = rope(matmul(c_q, f32(iw["wqi"]), quant).reshape(s, hi, di), rot,
              theta, positions)
    ki = rope(layer_norm(matmul(a, f32(iw["wki"]), quant),
                         f32(iw["ki_gain"]), f32(iw["ki_bias"]), spec.eps),
              rot, theta, positions)
    w = matmul(a, f32(iw["ww"]), quant) * (hi ** -0.5 * di ** -0.5)
    return qi, w, ki


def _mask(parts, seq: int, start, spec: Spec, quant, how: str):
    """The bool mask ``[ROW_BLOCK, seq]`` of the positions rows ``start ..``
    attend: ``"window"`` (the last ``window`` up to their own), ``"all"``
    (every causal one), ``"recent"`` (the last ``topk``), ``"learned"``
    (the ``topk`` of largest index score under ``parts``)."""
    i = start + jnp.arange(ROW_BLOCK)[:, None]
    cols = jnp.arange(seq)[None, :]
    causal = cols <= i
    if how == "all":
        return causal
    if how == "window":
        # ASSUMED (b): the window holds the query's own position and the
        # window - 1 before it
        return causal & (cols > i - spec.window)
    if how == "recent":
        return causal & (cols > i - spec.topk)
    qi, w, ki = parts
    qb = jax.lax.dynamic_slice_in_dim(qi, start, ROW_BLOCK, axis=0)
    wb = jax.lax.dynamic_slice_in_dim(w, start, ROW_BLOCK, axis=0)
    dots = jnp.einsum("thd,sd->ths", _round(qb, quant), _round(ki, quant),
                      precision=HI)
    score = jnp.sum(wb[..., None] * jnp.maximum(dots, 0.0), axis=1)
    score = jnp.where(causal, score, -jnp.inf)
    _, idx = jax.lax.top_k(score, min(spec.topk, seq))
    chosen = jnp.zeros((ROW_BLOCK, seq), bool).at[
        jnp.arange(ROW_BLOCK)[:, None], idx].set(True)
    return chosen & causal


def attention(a, lw, spec: Spec, g: Geometry, positions, quant, how: str,
              drop):
    """The attention sublayer over its normed input ``a [seq, hidden]``
    in the geometry ``g``, each row attending what ``how`` says
    (:func:`_mask`)."""
    s, heads, nope = a.shape[0], g.heads, g.nope
    theta, scale = g.theta, (nope + g.rope) ** -0.5
    if "geometry" in drop and g != spec.full:
        theta, scale = spec.full.theta, (spec.full.nope
                                         + spec.full.rope) ** -0.5
    c_q = rms_norm(matmul(a, f32(lw["wdq"]), quant), f32(lw["q_gain"]),
                   spec.eps)
    row = matmul(a, f32(lw["wdkv"]), quant)
    # ASSUMED (a): no rescale of the q and kv latents
    # (apply_mla_qkv_lora_rescale names no factor)
    c = rms_norm(row[:, :g.kv_lora], f32(lw["kv_gain"]), spec.eps)
    k_rope = rope(row[:, g.kv_lora:], g.rope, theta, positions)
    w_ukv = f32(lw["wukv"]).reshape(heads, nope + g.v, g.kv_lora)
    w_uk, w_uv = w_ukv[:, :nope], w_ukv[:, nope:]
    parts = (index_parts(a, c_q, lw["indexer"], spec, positions, quant)
             if how == "learned" else None)
    cq, kq = _round(c, quant), _round(k_rope, quant)

    def rows(start):
        """The sublayer's output for rows ``start ..``: their queries, the
        softmax over what they attend, the gate and ``W_O``."""
        see = _mask(parts, s, start, spec, quant, how)
        ab, cb, pb = (jax.lax.dynamic_slice_in_dim(t, start, ROW_BLOCK)
                      for t in (a, c_q, positions))
        q = matmul(cb, f32(lw["wuq"]), quant).reshape(ROW_BLOCK, heads,
                                                      nope + g.rope)
        qn, qr = q[..., :nope], rope(q[..., nope:], g.rope, theta, pb)
        q_lat = jnp.einsum("thn,hnc->thc", _round(qn, quant),
                           _round(w_uk, quant), precision=HI)
        sc = (jnp.einsum("thc,sc->hts", _round(q_lat, quant), cq,
                         precision=HI)
              + jnp.einsum("thr,sr->hts", _round(qr, quant), kq,
                           precision=HI)) * scale
        # ASSUMED (e): the softmax scale is 1/sqrt(nope + rope), no YaRN
        sc = jnp.where(see[None], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        ctx = jnp.einsum("hts,sc->thc", _round(pr, quant), cq, precision=HI)
        ctx = jnp.einsum("thc,hvc->thv", _round(ctx, quant),
                         _round(w_uv, quant), precision=HI)
        if "gate" not in drop:
            # ASSUMED (d): headwise = one sigmoid a head, from a
            ctx = ctx * jax.nn.sigmoid(matmul(ab, f32(lw["wg"]), quant))[
                ..., None]
        return matmul(ctx.reshape(ROW_BLOCK, heads * g.v), f32(lw["wo"]),
                      quant)

    assert s % ROW_BLOCK == 0, (s, ROW_BLOCK)
    return jax.lax.map(rows, jnp.arange(0, s, ROW_BLOCK)).reshape(s, -1)


# --------------------------------------------------------------------------
# the FFN
# --------------------------------------------------------------------------

def swiglu(h, wg, wu, wd, quant):
    """``[out, in]`` weights."""
    return matmul(jax.nn.silu(matmul(h, wg, quant)) * matmul(h, wu, quant),
                  wd, quant)


def route(h, lw, spec: Spec, quant, drop):
    """``[seq, E]`` routing weights: the chosen experts' renormalised
    sigmoid, zero elsewhere."""
    sig = jax.nn.sigmoid(matmul(h, f32(lw["router"]), quant))    # [s, E]
    choose = sig if "bias" in drop else sig + f32(lw["router_bias"])
    _, top_e = jax.lax.top_k(choose, spec.top_k)
    top_s = jnp.take_along_axis(sig, top_e, axis=-1)
    w = spec.scale * top_s / (jnp.sum(top_s, axis=-1, keepdims=True)
                              + 1e-20)
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(sig).at[rows, top_e].set(w)


def ffn(h, lw, spec: Spec, dense: bool, quant, drop):
    if dense:
        return swiglu(h, f32(lw["gate"]), f32(lw["up"]), f32(lw["down"]),
                      quant)
    dense_w = route(h, lw, spec, quant, drop)
    count = lw["e_gate"].shape[0]
    held_w = jax.lax.dynamic_slice_in_dim(dense_w, spec.held_first, count,
                                          axis=1)

    def one(acc, e):
        wg, wu, wd, col = e           # [in, out] slices of the stacks
        y = swiglu(h, f32(wg).T, f32(wu).T, f32(wd).T, quant)
        return acc + col[:, None] * y, None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        lw["e_gate"], lw["e_up"], lw["e_down"], held_w.T))
    return routed + swiglu(h, f32(lw["s_gate"]), f32(lw["s_up"]),
                           f32(lw["s_down"]), quant)


@functools.partial(jax.jit, static_argnames=(
    "spec", "kind", "dense", "quant", "how", "drop"), donate_argnums=(0,))
def layer(x, lw, positions, *, spec: Spec, kind: str, dense: bool,
          quant=None, how="learned", drop=()):
    """One decoder layer over ``x [seq, hidden]`` float32: its attention
    in its type's geometry, then its FFN a chunk of rows at a time."""
    g = spec.full if kind == FULL else spec.swa
    a = rms_norm(x, f32(lw["ln1"]), spec.eps)
    x = x + attention(a, lw, spec, g, positions, quant, how, drop)
    return by_chunks(lambda xc: xc + ffn(
        rms_norm(xc, f32(lw["ln2"]), spec.eps), lw, spec, dense, quant,
        drop), x)


@functools.partial(jax.jit, static_argnames=("rows", "spec", "quant"))
def _head(x, first, gain, w_head, *, rows: int, spec: Spec, quant=None):
    at = jnp.clip(first + jnp.arange(rows), 0, x.shape[0] - 1)
    return matmul(rms_norm(x[at], f32(gain), spec.eps), f32(w_head), quant)


def logits(weights, tokens, first, rows: int, *, spec: Spec, quant=None,
           select="learned", swa="window", drop=()):
    """Float32 logits ``[rows, vocab]`` of the ``rows`` positions from
    ``first`` on (held to the last one) of the one sequence ``tokens``
    ``[seq]``, ``seq`` a multiple of ``ROW_BLOCK``.  ``select`` is what a
    full layer attends (``"learned"``, ``"all"``, ``"recent"``), ``swa``
    what a window layer attends (``"window"``, ``"all"``)."""
    positions = jnp.arange(tokens.shape[0])
    x = f32(weights["embed"][tokens])
    drop = tuple(sorted(drop))
    for i, lw in enumerate(weights["layers"]):
        kind = spec.layer_types[i]
        x = layer(x, lw, positions, spec=spec, kind=kind,
                  dense=spec.dense[i], quant=quant,
                  how=select if kind == FULL else swa, drop=drop)
    return _head(x, first, weights["final_norm"], weights["head"],
                 rows=rows, spec=spec, quant=quant)
