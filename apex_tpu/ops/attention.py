"""Fused (flash) attention — the TPU-native equivalent of the reference's
fused-attention extensions.

Reference surface being rebuilt (see SURVEY.md §2.3):

* ``apex/contrib/csrc/fmha/`` (``fmhalib``): fused MHA fwd+bwd, fp16,
  head_dim 64, seqlen ≤ 512 (FasterTransformer-derived fixed-shape kernels).
* ``apex/contrib/csrc/multihead_attn/`` (``fast_multihead_attn``): fused
  QKV GEMM → scaled masked softmax(+dropout) → AV → out-proj chains.
* ``csrc/megatron/scaled_upper_triang_masked_softmax*``: the causal
  softmax those attention stacks lean on.

On TPU one blockwise-streaming kernel family covers all of them with no
shape table: an online-softmax ("flash") attention in Pallas.  Scores for a
(q-block, k-block) tile live in VMEM, softmax statistics (running max m and
normalizer l) are carried across k-blocks in VMEM scratch, and the O(s²)
score matrix never touches HBM — which is exactly the memory-traffic
property the CUDA kernels buy, achieved compiler-portably.  Unlike
``fmhalib`` there is no 512-token ceiling: block streaming scales to the
16k+ sequences the reference's softmax kernels cap out at.

The backward follows the standard flash decomposition: save only
(out, logsumexp); recompute score tiles blockwise.  The default is a
FUSED one-pass backward (dq/dk/dv from a single k-major sweep with a
full-sequence dq accumulator in VMEM scratch — one exp+mask recompute
instead of two); shapes whose dq accumulator would not fit the scoped
VMEM budget fall back to the split q-major dq / k-major dkv kernels.

Attention-probability dropout runs IN-KERNEL, like the reference's
softmax+dropout fusion (``apex/contrib/csrc/multihead_attn/philox.h``:
the CUDA kernels drop softmax *probabilities* with a counter-based
philox stream so forward and backward regenerate identical masks from a
seed).  The TPU equivalent here is a keyed counter hash (murmur3
finalizer over the global ``(batch·head, row, col)`` coordinates): pure
int32 VPU ops, so the SAME bits come out of CPU interpret mode and
compiled TPU — the mask generation the tests cover is the mask
generation the chip runs, with no O(s²) mask array ever touching HBM.
Dropout applies to the normalized probabilities (softmax THEN dropout,
the reference's order): the l/lse statistics accumulate clean p, only
the p·V contraction sees the dropped+rescaled p̃.

Oracle: :func:`mha_reference` (pure jnp, materializes the score matrix);
tests assert kernel ≡ oracle, the reference's fused-vs-eager pattern.
Tolerance note: on-chip, fp32 operands still contract at JAX's default
matmul precision (bf16 on the MXU) in kernel and oracle alike, so
fp32 comparisons on real hardware see ~1e-3 blockwise noise; interpret
mode is exact and the fused-vs-split tests hold at 1e-5.
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.utils import cdiv, interpret_mode

__all__ = ["flash_attention", "mha_reference", "decode_attention",
           "ring_decode_attention", "ring_decode_attention_latent",
           "prefix_window_attention",
           "slab_decode_attention", "index_scores",
           "index_scores_reference", "select_top_mask", "select_attention",
           "select_picks", "select_attend", "with_sink"]

#: pallas_audit registration (analysis hook only, no behavior change):
#: every attention kernel carries online-softmax (m/l/acc) or wgrad
#: accumulators whose scratch must be fp32 (APX302).
PALLAS_AUDIT = {
    "_fwd_kernel": {"reduction": True},
    "_dq_kernel": {"reduction": True},
    "_dkv_kernel": {"reduction": True},
    "_bwd_fused_kernel": {"reduction": True},
    "_index_kernel": {},
}

_NEG_INF = -1e30          # finite "masked" score: keeps exp()/where() NaN-free
# The kernels work in BASE-2 log domain: the dot's scalar scale absorbs
# log2(e), and every softmax exp is jnp.exp2.  The VPU lowers exp(x) as
# exp2(x * log2e) anyway, so folding the constant into the (free) score
# scale deletes one full [bq, bk] vector multiply per exp site — fwd p,
# rescale alpha, and the backward recompute — pure VPU savings exactly
# where PERF.md locates the d=64 attention floor.  lse is produced and
# consumed in base 2 strictly inside the kernels; the public API and the
# oracle stay in natural log.
_LOG2E = 1.4426950408889634
_LN2 = 0.6931471805599453
# a row whose max score is below this is FULLY masked (causal sq > sk,
# fully-masked varlen rows): it must emit 0 output and 0 grads.  One
# definition shared by the oracle, the forward kernel, and the backward
# recompute so the three can never disagree on which rows qualify.
_MASKED_ROW_THRESH = _NEG_INF * 0.5
_LANES = 128              # TPU lane width; m/l scratch is lane-replicated
# murmur3 fmix32 constants as signed int32 literals (int32 arithmetic
# wraps two's-complement in XLA, bit-identical to uint32 mod-2^32)
_H1 = 0x9E3779B9 - (1 << 32)
_H2 = 0x85EBCA6B - (1 << 32)
_H3 = 0xC2B2AE35 - (1 << 32)
# seed-fold multiplier for fold_rank_seed — murmur3's c1, deliberately
# distinct from the coordinate multipliers above so a rank fold can't
# alias a row/col shift in the pre-finalizer state
_HF = 0xCC9E2D51 - (1 << 32)
# lane width for the per-row softmax stats (lse, delta) at the kernel
# HBM boundary.  Full 128-lane replication cost real bandwidth: at
# [8,16,1024,64] the two broadcast stats were 134 MB of HBM traffic per
# backward — ~25% of its runtime — carrying 1 useful lane in 128.  Eight
# lanes keeps the arrays 2-D-tileable while cutting that 16x; kernels
# only ever read [:, :1].
_STAT_LANES = 8


def _rows_can_be_fully_masked(causal, off, masked, valid) -> bool:
    """Statically decide whether ANY query row could end up fully
    masked — only then do the kernels pay the [bq, bk] zero-forcing
    ``where`` on p (fwd) / the recompute (bwd).  Possible sources: an
    explicit mask, a validity window (padded rows), or causal with
    sq > sk (queries before the first key).  The flagship causal
    sq == sk unpadded path — the VPU-bound case PERF.md profiles —
    skips the select entirely."""
    return masked or (valid is not None) or (causal and off < 0)


def _keep_mask(seed, bi, qi, ki, bq, bk, rate, row_off=0, col_off=0):
    """Counter-based keep mask for one (qi, ki) block of batch·head bi.

    The philox-equivalent: bits are a pure function of
    ``(seed, bi, global row, global col)``, so the forward kernel and
    every backward recompute regenerate the identical mask regardless
    of grid order.  murmur3's 32-bit finalizer over the coordinates
    gives well-mixed bits in ~10 int32 VPU ops per element; the top 24
    bits form the uniform variate (2^-24 rate resolution).

    ``row_off``/``col_off`` translate LOCAL kernel coordinates to the
    GLOBAL sequence position — ring attention sets them per shard pair
    so a context-sharded run draws the exact mask the unsharded run
    would (the coordinates, not the blocking, define the stream)."""
    bi = jnp.asarray(bi, jnp.int32)   # python ints would overflow in *_H1
    rows = (row_off + qi * bq
            + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0))
    cols = (col_off + ki * bk
            + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1))
    h = seed ^ (bi * _H1) ^ (rows * _H2) ^ (cols * _H3)
    h = h ^ jax.lax.shift_right_logical(h, 16)
    h = h * _H2
    h = h ^ jax.lax.shift_right_logical(h, 13)
    h = h * _H3
    h = h ^ jax.lax.shift_right_logical(h, 16)
    u24 = jax.lax.shift_right_logical(h, 8)          # uniform in [0, 2^24)
    return u24 >= int(round(rate * (1 << 24)))


def _dropout_reference(p, *, rate, seed):
    """Oracle twin of the kernels' dropout on a full ``[b, h, sq, sk]``
    probability array.  Because the keep mask is a pure function of the
    GLOBAL (bh, row, col) coordinates, it is independent of the kernel's
    block decomposition — one full-matrix draw per bh predicts every
    flash_attention blocking (and the backward's recompute) bit-for-bit."""
    b, hh, sq, sk = p.shape
    seed = jnp.asarray(seed, jnp.int32)
    keep = jnp.stack([
        _keep_mask(seed, bi, 0, 0, sq, sk, rate)
        for bi in range(b * hh)]).reshape(b, hh, sq, sk)
    return jnp.where(keep, p, 0.0) * (1.0 / (1.0 - rate))


def mha_reference(q, k, v, *, causal: bool = False, mask=None,
                  sm_scale: Optional[float] = None,
                  dropout_rate: float = 0.0, dropout_seed=None):
    """Pure-jnp oracle: softmax(scale·QKᵀ + mask)·V, fp32 accumulation.

    ``mask`` is boolean, True = masked out (the reference's convention in
    ``scaled_masked_softmax``), broadcastable to ``[b, h, sq, sk]``.
    """
    *_, sq, d = q.shape
    sk = k.shape[-2]
    scale = (d ** -0.5) if sm_scale is None else sm_scale
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        cm = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(cm, s, _NEG_INF)
    if mask is not None:
        s = jnp.where(mask, _NEG_INF, s)
    p = jax.nn.softmax(s, axis=-1)
    # fully-masked rows (e.g. causal sq > sk: queries before the first
    # key) emit 0, not softmax-of-constant's uniform artifact — the
    # FlashAttention convention the kernel implements
    p = jnp.where(jnp.max(s, axis=-1, keepdims=True) <= _MASKED_ROW_THRESH,
                  0.0, p)
    if dropout_rate:
        # softmax THEN dropout, drawing the kernel's exact
        # (block-independent) mask
        p = _dropout_reference(p, rate=dropout_rate, seed=dropout_seed)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)
                      ).astype(q.dtype)


# --------------------------------------------------------------------------
# forward kernel: grid (bh, nq, nk), k innermost ("arbitrary"), online softmax
# --------------------------------------------------------------------------

def _valid_mask(s, valid, qi, ki, bq, bk):
    """Mask scores outside the (q_len, k_len) valid region to _NEG_INF —
    used when the sequence was padded up to a lane multiple."""
    if valid is None:
        return s
    rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return jnp.where((rows < valid[0]) & (cols < valid[1]), s, _NEG_INF)


def _fwd_kernel(causal, off, scale, bq, bk, nk, masked, valid, rate,
                *refs, window=None):
    q_ref, k_ref, v_ref = refs[:3]
    i = 3
    mask_ref = refs[i] if masked else None
    i += 1 if masked else 0
    seed_ref = refs[i] if rate else None
    i += 1 if rate else 0
    o_ref, lse_ref, m_scr, l_scr, acc_scr = refs[i:i + 5]
    bi = pl.program_id(0)
    ki = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal: whole block above the diagonal contributes nothing — skip
    run = True if not causal else (ki * bk <= qi * bq + bq - 1 + off)
    if window is not None:
        # ... and a block wholly BEHIND the window of its every row
        # (its last column at or before the first row's first key)
        run = run & (ki * bk + bk - 1 > qi * bq + off - window)

    @pl.when(run)
    def _body():
        # dots run on the INPUT dtype (bf16 in, fp32 MXU accumulate):
        # pre-casting operands to fp32 would force the MXU into its
        # several-times-slower fp32 mode.  The scale moves to the fp32
        # product (linear, identical math).
        q = q_ref[0]
        kb = k_ref[0]
        # base-2 log domain: log2e folded into the scalar scale (see
        # _LOG2E note at the top of the module)
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * (
                                    scale * _LOG2E)
        if causal:
            rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            keep = rows + off >= cols
            if window is not None:    # row i sees keys (i - window, i]
                keep = keep & (rows + off - cols < window)
            s = jnp.where(keep, s, _NEG_INF)
        if masked:
            s = jnp.where(mask_ref[0], _NEG_INF, s)
        s = _valid_mask(s, valid, qi, ki, bq, bk)
        m_prev = m_scr[...]                              # [bq, LANES]
        m_cur = jnp.max(s, axis=1, keepdims=True)        # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)               # lane-replicated
        alpha = jnp.exp2(m_prev[:, :1] - m_new[:, :1])   # [bq, 1]
        # _NEG_INF is finite, so a fully-masked row would get
        # exp2(s - m) = exp2(0) = 1 everywhere and emit mean(v) instead
        # of 0 (hit by causal sq > sk: queries before the first key);
        # force p = 0 there so l stays 0 and _finish emits 0.  Shapes
        # that can't produce such rows skip the [bq, bk] select.
        p = jnp.exp2(s - m_new[:, :1])                   # [bq, bk]
        if _rows_can_be_fully_masked(causal, off, masked, valid):
            p = jnp.where(m_new[:, :1] <= _MASKED_ROW_THRESH, 0.0, p)
        l_scr[...] = l_scr[...] * alpha + \
            jnp.sum(p, axis=1, keepdims=True)
        # prob dropout: the l/lse normalizer above accumulates CLEAN p
        # (softmax first); only the p·V feed sees the dropped+rescaled
        # probabilities — dividing by l in _finish then yields
        # dropout(softmax(s)) @ V exactly
        pv = p
        if rate:
            keep = _keep_mask(seed_ref[0], bi, qi, ki, bq, bk, rate,
                              seed_ref[1], seed_ref[2])
            pv = jnp.where(keep, p, 0.0) * (1.0 / (1.0 - rate))
        # p rounds to the input dtype for the MXU pass (the standard
        # flash-on-TPU precision: probabilities in [0,1] lose ~3 decimal
        # digits in bf16, accumulation stays fp32 in scratch)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
            pv.astype(v_ref.dtype), v_ref[0],
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_scr[:, :1]
        # fully-masked rows (l == 0) emit 0, not NaN — matches the oracle's
        # softmax-of-all--inf convention closely enough for padding rows
        o_ref[0] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)
                    ).astype(o_ref.dtype)
        # lse in BASE 2 (m is a base-2 log max): consumed only by
        # _recompute_p, which is in the same domain
        lse_ref[0] = (m_scr[...] + jnp.log2(jnp.where(l == 0.0, 1.0, l))
                      )[:, :_STAT_LANES]


def _fwd(q3, k3, v3, mask3, causal, scale, bq, bk, out_dtype=None,
         causal_off=None, valid=None, rate=0.0, seed3=None, window=None):
    bh, sq, d = q3.shape
    dv = v3.shape[2]        # the values' own width (latent attention)
    out_dtype = out_dtype or q3.dtype
    sk = k3.shape[1]
    off = (sk - sq) if causal_off is None else causal_off
    nq, nk = cdiv(sq, bq), cdiv(sk, bk)
    masked = mask3 is not None

    def kv_index(b, i, j):
        if window is None:
            return (b, j, 0)
        # hold the block index inside the band of q block i: a skipped
        # block then repeats its neighbour's index and is not fetched
        lo = jnp.maximum(i * bq + off - window + 1, 0) // bk
        hi = jnp.minimum((i * bq + bq - 1 + off) // bk, nk - 1)
        return (b, jnp.clip(j, lo, jnp.maximum(hi, lo)), 0)

    in_specs = [
        pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, bk, d), kv_index),
        pl.BlockSpec((1, bk, dv), kv_index),
    ]
    operands = [q3, k3, v3]
    if masked:
        nmask = mask3.shape[0]
        h_per = bh // nmask
        in_specs.append(pl.BlockSpec(
            (1, bq, bk), lambda b, i, j: (b // h_per, i, j)))
        operands.append(mask3)
    if rate:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.append(seed3)
    kernel = functools.partial(_fwd_kernel, causal, off, scale, bq, bk, nk,
                               masked, valid, rate, window=window)
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, _STAT_LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, dv), out_dtype),
            jax.ShapeDtypeStruct((bh, sq, _STAT_LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret_mode(),
        name="apex_flash_fwd",
    )(*operands)
    return out, lse[:, :, 0]


# --------------------------------------------------------------------------
# backward kernels (flash decomposition): recompute p blockwise from lse
# --------------------------------------------------------------------------

def _parse_bwd_refs(refs, masked, rate):
    """Common backward operand layout: [q, k, v, do, lse, delta]
    (+mask)(+seed), then the kernel-specific outs/scratch as the tail."""
    fixed = list(refs[:6])
    i = 6
    mask_ref = refs[i] if masked else None
    i += 1 if masked else 0
    seed_ref = refs[i] if rate else None
    i += 1 if rate else 0
    return fixed, mask_ref, seed_ref, refs[i:]


def _dropped_dp(rate, seed_ref, bi, qi, ki, bq, bk, p, dp):
    """(p̃ for the dv contraction, dL/dp for ds) under prob dropout.

    With out = (M ⊙ p / keep) @ V: dv sees the dropped p̃, and the
    softmax backward's upstream is dL/dp = M ⊙ dp / keep.  delta keeps
    its no-dropout definition (Σ do·out = Σ_j dL/dp_j · p_j still holds,
    so the saved-residual contract is unchanged)."""
    if not rate:
        return p, dp
    keep = _keep_mask(seed_ref[0], bi, qi, ki, bq, bk, rate,
                      seed_ref[1], seed_ref[2])
    inv = 1.0 / (1.0 - rate)
    return jnp.where(keep, p, 0.0) * inv, jnp.where(keep, dp * inv, 0.0)


def _dq_kernel(causal, off, scale, bq, bk, nk, masked, valid, rate,
               *refs):
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), mask_ref, \
        seed_ref, (dq_ref, dq_scr) = _parse_bwd_refs(refs, masked, rate)
    bi = pl.program_id(0)
    ki = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    run = True if not causal else (ki * bk <= qi * bq + bq - 1 + off)

    @pl.when(run)
    def _body():
        p = _recompute_p(causal, off, scale, bq, bk, masked, valid,
                         qi, ki, q_ref, k_ref, lse_ref, mask_ref)
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0],
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        _, g = _dropped_dp(rate, seed_ref, bi, qi, ki, bq, bk, p, dp)
        ds = p * (g - delta_ref[0][:, :1])
        dq_scr[...] += scale * jax.lax.dot(
            ds.astype(k_ref.dtype), k_ref[0],
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(causal, off, scale, bq, bk, nq, masked, valid, rate,
                *refs):
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), mask_ref, \
        seed_ref, (dk_ref, dv_ref, dk_scr, dv_scr) = \
        _parse_bwd_refs(refs, masked, rate)
    bi = pl.program_id(0)
    qi = pl.program_id(2)
    ki = pl.program_id(1)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    run = True if not causal else (ki * bk <= qi * bq + bq - 1 + off)

    @pl.when(run)
    def _body():
        p = _recompute_p(causal, off, scale, bq, bk, masked, valid,
                         qi, ki, q_ref, k_ref, lse_ref, mask_ref)
        do = do_ref[0]
        dp = jax.lax.dot_general(
            do, v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        pd, g = _dropped_dp(rate, seed_ref, bi, qi, ki, bq, bk, p, dp)
        dv_scr[...] += jax.lax.dot_general(
            pd.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # p̃ᵀ @ do
        ds = p * (g - delta_ref[0][:, :1])
        dk_scr[...] += scale * jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # dsᵀ @ q

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _recompute_p(causal, off, scale, bq, bk, masked, valid, qi, ki,
                 q_ref, k_ref, lse_ref, mask_ref):
    """Shared backward score recompute: p = exp2(s - lse) for one
    (qi, ki) block pair, with causal/mask/valid-window masking — base-2
    log domain throughout, matching the forward (lse is base 2).  One
    definition so the three backward kernels can never drift apart."""
    s = jax.lax.dot_general(
        q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * (scale * _LOG2E)
    if causal:
        rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(rows + off >= cols, s, _NEG_INF)
    if masked:
        s = jnp.where(mask_ref[0], _NEG_INF, s)
    s = _valid_mask(s, valid, qi, ki, bq, bk)
    # fully-masked rows carry lse = _NEG_INF (finite), so exp2(s - lse)
    # would be 1, not 0 — mirror the forward's guard (and its static
    # skip for shapes that can't produce such rows)
    p = jnp.exp2(s - lse_ref[0][:, :1])
    if _rows_can_be_fully_masked(causal, off, masked, valid):
        p = jnp.where(lse_ref[0][:, :1] <= _MASKED_ROW_THRESH, 0.0, p)
    return p


def _bwd_fused_kernel(causal, off, scale, bq, bk, nq, nk, masked, valid,
                      rate, *refs):
    """One-pass backward (FlashAttention-2 shape): dq, dk, dv from a
    single sweep over (ki, qi) blocks.

    The split dq/dkv kernels each recompute the scores and the exp — the
    dominant VPU cost at small head_dim — and each re-read q/k/v/do.
    Fusing them computes p/ds ONCE per block pair (5 MXU dots instead of
    7, 1 exp+mask pass instead of 2).  The price is a full-sequence
    ``[sq, d]`` fp32 dq accumulator in VMEM scratch (dq contributions
    arrive k-major, so no single output block is complete until the
    sweep ends) — affordable exactly when sq*d is moderate, which the
    caller gates on; and the ki grid dim turns sequential (the scratch
    carries across it), keeping only bh as the parallel dim.
    """
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), mask_ref, \
        seed_ref, (dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr) = \
        _parse_bwd_refs(refs, masked, rate)
    bi = pl.program_id(0)
    qi = pl.program_id(2)
    ki = pl.program_id(1)

    @pl.when((ki == 0) & (qi == 0))
    def _init_dq():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(qi == 0)
    def _init_dkv():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    run = True if not causal else (ki * bk <= qi * bq + bq - 1 + off)

    @pl.when(run)
    def _body():
        p = _recompute_p(causal, off, scale, bq, bk, masked, valid,
                         qi, ki, q_ref, k_ref, lse_ref, mask_ref)
        do = do_ref[0]
        dp = jax.lax.dot_general(
            do, v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        pd, g = _dropped_dp(rate, seed_ref, bi, qi, ki, bq, bk, p, dp)
        dv_scr[...] += jax.lax.dot_general(
            pd.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # p̃ᵀ @ do
        ds = p * (g - delta_ref[0][:, :1])
        dsl = ds.astype(q_ref.dtype)
        dk_scr[...] += scale * jax.lax.dot_general(
            dsl, q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # dsᵀ @ q
        dq_scr[pl.ds(qi * bq, bq), :] += scale * jax.lax.dot(
            dsl, k_ref[0], preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _fin_dkv():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)

    @pl.when((ki == nk - 1) & (qi == nq - 1))
    def _fin_dq():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


# fused-backward gate: the [sq, d] fp32 dq scratch (plus the same-sized
# output block) must stay a small slice of the ~16 MB scoped VMEM —
# 2 MB covers seq 8192 @ d 64 / seq 4096 @ d 128; beyond it the split
# two-kernel backward below takes over.  Module-level so tests can
# force either path.
_FUSED_BWD_MAX_BYTES = 2 * 1024 * 1024


def _bwd_impl(q3, k3, v3, mask3, o3, lse, do3, causal, scale, bq, bk,
              out_dtype=None, causal_off=None, valid=None, rate=0.0,
              seed3=None):
    bh, sq, d = q3.shape
    sk = k3.shape[1]
    off = (sk - sq) if causal_off is None else causal_off
    nq, nk = cdiv(sq, bq), cdiv(sk, bk)
    masked = mask3 is not None
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1)                               # [bh, sq]
    lse2 = jnp.broadcast_to(lse[..., None], (bh, sq, _STAT_LANES))
    delta2 = jnp.broadcast_to(delta[..., None], (bh, sq, _STAT_LANES))

    h_per = bh // mask3.shape[0] if masked else 1
    common = [q3, k3, v3, do3, lse2, delta2] + ([mask3] if masked else []) \
        + ([seed3] if rate else [])

    # k-major (grid (bh, ki, qi)) input specs — shared by the fused and
    # dkv kernels, which iterate the identical block layout
    kmajor_in_specs = [
        pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)),
        pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)),
        pl.BlockSpec((1, bq, _STAT_LANES), lambda b, j, i: (b, i, 0)),
        pl.BlockSpec((1, bq, _STAT_LANES), lambda b, j, i: (b, i, 0)),
    ]
    if masked:
        kmajor_in_specs.append(pl.BlockSpec(
            (1, bq, bk), lambda b, j, i: (b // h_per, i, j)))
    if rate:
        kmajor_in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))

    if sq * d * 4 <= _FUSED_BWD_MAX_BYTES:
        kernel = functools.partial(
            _bwd_fused_kernel, causal, off, scale, bq, bk, nq, nk,
            masked, valid, rate)
        dq, dk, dv = pl.pallas_call(
            kernel,
            grid=(bh, nk, nq),
            in_specs=kmajor_in_specs,
            out_specs=[
                pl.BlockSpec((1, sq, d), lambda b, j, i: (b, 0, 0)),
                pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
                pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, sq, d), out_dtype or q3.dtype),
                jax.ShapeDtypeStruct((bh, sk, d), out_dtype or k3.dtype),
                jax.ShapeDtypeStruct((bh, sk, d), out_dtype or v3.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((sq, d), jnp.float32),
                pltpu.VMEM((bk, d), jnp.float32),
                pltpu.VMEM((bk, d), jnp.float32),
            ],
            # ki is sequential: the dq scratch carries across it
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary")),
            interpret=interpret_mode(),
            name="apex_flash_bwd",
        )(*common)
        return dq, dk, dv

    dq_in_specs = [
        pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, bq, _STAT_LANES), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, bq, _STAT_LANES), lambda b, i, j: (b, i, 0)),
    ]
    if masked:
        dq_in_specs.append(pl.BlockSpec(
            (1, bq, bk), lambda b, i, j: (b // h_per, i, j)))
    if rate:
        dq_in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))

    dq_kernel = functools.partial(_dq_kernel, causal, off, scale, bq, bk,
                                  nk, masked, valid, rate)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(bh, nq, nk),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), out_dtype or q3.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret_mode(),
        name="apex_flash_bwd_dq",
    )(*common)

    dkv_kernel = functools.partial(
        _dkv_kernel, causal, off, scale, bq, bk, nq, masked, valid, rate)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(bh, nk, nq),
        in_specs=kmajor_in_specs,
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), out_dtype or k3.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), out_dtype or v3.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret_mode(),
        name="apex_flash_bwd_dkv",
    )(*common)
    return dq, dk, dv


# --------------------------------------------------------------------------
# public entry: custom VJP over the kernel pair, oracle fallback for odd shapes
# --------------------------------------------------------------------------

def fold_rank_seed(seed, axis_name):
    """Derive a per-rank dropout seed from a replicated one (Megatron's
    per-tensor-rank rng stream): distinct ranks get well-separated
    streams; rank 0 keeps ``seed`` unchanged.  Must run inside
    ``shard_map`` binding ``axis_name``.  Do NOT fold the context axis —
    ring attention's sharded-equals-dense dropout needs a CP-uniform
    seed."""
    return (jnp.asarray(seed, jnp.int32)
            ^ (jax.lax.axis_index(axis_name) * jnp.int32(_HF)))


def _zero_cotangent(x):
    """Cotangent for a non-differentiable custom_vjp argument: None for
    an absent (None) operand, float0 zeros for integer/bool primals,
    ordinary zeros for inexact dtypes (a 0/1 float mask is accepted by
    the forward's ``where``, so its grad path must not type-error)."""
    if x is None:
        return None
    if jnp.issubdtype(x.dtype, jnp.inexact):
        return jnp.zeros(x.shape, x.dtype)
    return np.zeros(np.shape(x), jax.dtypes.float0)


def _seed_operand(seed, row_off=0, col_off=0):
    """SMEM dropout operand: [seed, global row offset, global col
    offset].  Offsets are 0 for unsharded attention; ring attention sets
    them per shard pair (see _keep_mask)."""
    return jnp.stack([jnp.asarray(seed, jnp.int32),
                      jnp.asarray(row_off, jnp.int32),
                      jnp.asarray(col_off, jnp.int32)])


def _fit_block(s: int, preferred: int):
    """Largest block <= preferred that divides s and is a lane multiple
    (or s itself when s < 128); None -> needs padding."""
    if s <= preferred:
        return s
    for cand in range(preferred, _LANES - 1, -_LANES):
        if s % cand == 0:
            return cand
    return None


def _plan_block(s: int, preferred: int):
    """(block, padded_len) — pad s up to the next lane multiple when no
    lane-multiple block divides it (e.g. s=1000 -> 1024, block 512)."""
    b = _fit_block(s, preferred)
    if b is not None:
        return b, s
    s_pad = cdiv(s, _LANES) * _LANES
    return _fit_block(s_pad, preferred), s_pad


#: measured kernel/XLA crossover on v5e (bench_captures/
#: r5_attn_crossover.py, fwd+bwd, h=16 d=64): at s=128 the Pallas grid
#: degenerates to b*h tiny programs and Mosaic dispatch dominates —
#: 828 µs vs 119 µs for plain XLA einsum attention; at s=256 it is
#: 707 vs 379; from s=512 the kernel wins (777 vs 2033, and 4.3x at
#: s=2048).  Auto-dispatch sends padded-seq <= 256 to the XLA path.
#: The 256 boundary itself is interpolated from those four points, not
#: measured densely — override per-run with the environment variable
#: ``APEX_TPU_ATTN_XLA_MAX_SEQ`` or per-call with the
#: ``flash_attention(..., xla_max_seq=)`` kwarg (0 disables the XLA
#: path entirely); bench attn captures stamp the effective value.
_XLA_PATH_MAX_SEQ = 256

_XLA_MAX_SEQ_ENV = "APEX_TPU_ATTN_XLA_MAX_SEQ"


def xla_path_max_seq(override=None) -> int:
    """The effective auto-dispatch crossover: explicit kwarg override >
    ``APEX_TPU_ATTN_XLA_MAX_SEQ`` env var > the measured default."""
    if override is not None:
        return int(override)
    env = os.environ.get(_XLA_MAX_SEQ_ENV)
    if env:
        try:
            return int(env)
        except ValueError as e:
            raise ValueError(
                f"{_XLA_MAX_SEQ_ENV} must be an int, got {env!r}") from e
    return _XLA_PATH_MAX_SEQ


def _xla_attention(q, k, v, *, causal, scale, mask, rate, seed,
                   window=None):
    """Short-sequence attention as plain XLA ops — same semantics as the
    kernels (True-=-masked boolean mask, fully-masked rows emit zeros,
    the identical coordinate-hash probability dropout), but lowered to
    one batched einsum chain XLA fuses well at small ``s``.

    Numerics mirror the kernel: bf16 operands into the MXU with fp32
    accumulation (``preferred_element_type``), softmax in fp32, the
    probability matrix cast back to ``v.dtype`` for the PV dot."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    s = jax.lax.dot_general(
        q, k, (((3,), (3,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32) * scale
    if causal:
        rows = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        keep = cols <= rows + (sk - sq)
        if window is not None:
            keep = keep & (rows + (sk - sq) - cols < window)
        s = jnp.where(keep, s, _NEG_INF)
    if mask is not None:
        s = jnp.where(mask, _NEG_INF, s)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    if causal or mask is not None:
        p = jnp.where(m <= _MASKED_ROW_THRESH, 0.0, p)
    if rate:
        keep = _keep_mask(jnp.asarray(seed, jnp.int32),
                          jnp.arange(b * h, dtype=jnp.int32)[:, None, None],
                          0, 0, sq, sk, rate).reshape(b, h, sq, sk)
        p = jnp.where(keep, p, 0.0) * (1.0 / (1.0 - rate))
    out = jax.lax.dot_general(
        p.astype(v.dtype), v, (((3,), (2,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def flash_attention(q, k, v, *, causal: bool = False, mask=None,
                    sm_scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    dropout_rate: float = 0.0,
                    dropout_seed=None,
                    use_kernel: Optional[bool] = None,
                    xla_max_seq: Optional[int] = None,
                    window: Optional[int] = None,
                    return_lse: bool = False):
    """Fused blockwise attention, ``[b, h, s, d]`` layout.

    Drop-in fused path for the reference's ``fmhalib`` /
    ``fast_multihead_attn`` forward+backward.  ``mask`` is boolean with
    True = masked (broadcastable ``[b|1, 1, sq, sk]``).  Sequences that
    don't tile to the 128-lane grid are padded up to the next lane
    multiple and masked inside the kernel — the kernel path is taken for
    EVERY shape (the reference kernels instead refuse such shapes; the
    old behavior here was a silent O(s²) oracle fallback).

    ``use_kernel=None`` auto-dispatches: on the TPU, sequences at
    or under the crossover (``xla_max_seq`` kwarg >
    ``APEX_TPU_ATTN_XLA_MAX_SEQ`` env var > the measured default
    ``_XLA_PATH_MAX_SEQ`` — see its note; the guessed 256 boundary is
    tunable without a code edit) run as one fused XLA einsum chain
    instead of the Pallas kernels; identical semantics including the
    dropout mask stream.  Explicit ``block_q``/``block_k`` forces the
    kernel (the caller is tuning it), as does ``use_kernel=True``;
    the CPU platform always takes the kernel so interpret-mode tests
    exercise kernel code.

    ``dropout_rate`` > 0 drops attention *probabilities* in-kernel (the
    reference's philox softmax+dropout fusion; see the module
    docstring), rescaling survivors by ``1/(1-rate)``.  ``dropout_seed``
    (int32 scalar, traced OK — pass a fresh value per training step,
    e.g. drawn from the tensor-parallel RNG tracker) fully determines
    the mask; the backward regenerates it from the same seed, so
    activation-recompute training stays bit-identical.  ``rate`` itself
    is static: rate=0 compiles the exact pre-dropout kernels.

    ``window`` (static, needs ``causal``; ISSUE 30): sliding-window
    attention — query ``i`` sees key ``j`` iff ``i - window < j <= i``
    (the Hugging Face ``sliding_window`` convention).  Key blocks wholly
    behind the window are neither fetched nor computed, the partial one
    is masked.  ``None`` compiles exactly the kernel it always did.
    Forward only: the backward kernels know no window and refuse.

    ``v`` may have a width of its own, ``[b, h, sk, dv]`` (ISSUE 34:
    latent attention scores over 192 channels and sums values of 128):
    the output is ``[b, h, sq, dv]``.  Forward only, like the window;
    ``dv == d`` compiles exactly the kernel it always did.

    ``return_lse``: ``(out, lse)`` with ``lse [b, h, sq]`` float32 each
    row's natural log-sum-exp of its scaled scores, which the kernel keeps
    anyway — what a caller needs to add a term to the softmax's
    denominator afterwards (:func:`with_sink`).  Forward only, always the
    kernel.
    """
    b, h, sq, d = q.shape
    sk, v_width = k.shape[2], v.shape[3]
    scale = (d ** -0.5) if sm_scale is None else sm_scale
    if window is not None and (not causal or window < 1):
        raise ValueError(
            f"window={window!r} needs causal=True and window >= 1 (a "
            f"sliding window is a band below the causal diagonal)")
    if not 0.0 <= dropout_rate < 1.0:
        raise ValueError(f"dropout_rate must be in [0, 1), got "
                         f"{dropout_rate}")
    if dropout_rate and dropout_seed is None:
        raise ValueError(
            "dropout_rate > 0 requires dropout_seed (reusing an "
            "implicit constant seed would repeat the same mask "
            "every training step)")
    # validate the mask contract BEFORE the use_kernel dispatch so the
    # short-seq XLA path and the kernel path enforce the same shape —
    # a malformed mask must not silently broadcast on one side of the
    # auto-dispatch boundary and error on the other (ADVICE r5 #1)
    if mask is not None:
        shape_ok = (mask.ndim == 4
                    and mask.shape[0] in (1, b)
                    and mask.shape[1] in (1, h)
                    and mask.shape[2] in (1, sq)
                    and mask.shape[3] in (1, sk))
        if not shape_ok:
            raise ValueError(
                f"mask must be boolean [b|1, h|1, sq|1, sk|1] "
                f"(broadcastable to [{b}, {h}, {sq}, {sk}]); got "
                f"{tuple(mask.shape)}")
    if return_lse:
        use_kernel = True
    if use_kernel is None:
        use_kernel = (block_q is not None or block_k is not None
                      or max(sq, sk) > xla_path_max_seq(xla_max_seq)
                      or interpret_mode())
    if not use_kernel:
        return _xla_attention(q, k, v, causal=causal, scale=scale,
                              mask=mask, rate=dropout_rate,
                              seed=dropout_seed, window=window)
    seed3 = None
    if dropout_rate:
        seed3 = _seed_operand(dropout_seed)
    # default 1024x1024 blocks: measured ~21% faster fwd+bwd than
    # 512x512 at [*, 16, 1024-2048, 64] on v5e (fewer online-softmax
    # rescale rounds, larger MXU feeds).  Verified to fit scoped VMEM
    # through head_dim 128 UNMASKED; outside that envelope (d > 128, or
    # a mask operand adding a [bq, bk] block per grid step) fall back
    # to the conservative 512 so previously-compiling calls keep
    # compiling.  _plan_block shrinks further for short sequences.
    # Latent attention's expanded form (scores over 192 channels, values
    # of 128) is inside the envelope too: on the v5e 1024x1024 blocks ran
    # its forward in 4.85 ms against 7.36 at [1, 64, 4096] and 15.9
    # against 26.0 at 8192 (PERF.md section 6, PR 34).
    roomy = d <= 128 or (d <= 192 and v_width <= 128)
    default_block = 1024 if (roomy and mask is None) else 512
    if window is not None:
        # a band `window` wide under 1024-wide blocks is mostly masked
        # work: blocks no wider than the window (but lane-wide)
        default_block = min(default_block, max(
            _LANES, cdiv(window, _LANES) * _LANES))
    bq, sq_pad = _plan_block(sq, block_q or default_block)
    bk, sk_pad = _plan_block(sk, block_k or default_block)
    padded = (sq_pad != sq) or (sk_pad != sk)
    # real-length causal offset / validity window, pre-padding
    causal_off = sk - sq
    valid = (sq, sk) if padded else None

    q3 = q.reshape(b * h, sq, d)
    k3 = k.reshape(b * h, sk, d)
    v3 = v.reshape(b * h, sk, v_width)
    mask3 = None
    if mask is not None:
        # shape already validated ahead of the use_kernel dispatch
        mb, mh = mask.shape[0], mask.shape[1]
        if mh == 1:
            mask3 = jnp.broadcast_to(
                mask, (mb, 1, sq, sk)).reshape(mb, sq, sk)
        else:           # per-head mask: materialize the full [b*h, sq, sk]
            mask3 = jnp.broadcast_to(
                mask, (b, h, sq, sk)).reshape(b * h, sq, sk)
    if padded:
        q3 = jnp.pad(q3, ((0, 0), (0, sq_pad - sq), (0, 0)))
        k3 = jnp.pad(k3, ((0, 0), (0, sk_pad - sk), (0, 0)))
        v3 = jnp.pad(v3, ((0, 0), (0, sk_pad - sk), (0, 0)))
        if mask3 is not None:   # padding handled by the validity window
            mask3 = jnp.pad(
                mask3, ((0, 0), (0, sq_pad - sq), (0, sk_pad - sk)))

    if return_lse:
        out, lse = _fwd(q3, k3, v3, mask3, causal, scale, bq, bk,
                        causal_off=causal_off, valid=valid,
                        rate=dropout_rate, seed3=seed3, window=window)
        # the kernel's lse is base 2 (module note): ln x = log2 x * ln 2
        return (out[:, :sq, :].reshape(b, h, sq, v_width),
                (lse[:, :sq] * _LN2).reshape(b, h, sq))

    # mask3/seed3 are custom_vjp ARGUMENTS, not closure captures: a
    # traced value closed over by a custom_vjp function leaks its trace
    # under nn.scan/lax.scan + grad (UnexpectedTracerError — hit by
    # scan_layers models with dropout).  None passes through as an
    # empty pytree; arrays get float0 cotangents (bool/int primals).
    @jax.custom_vjp
    def run(q3, k3, v3, mask3, seed3):
        out, _ = _fwd(q3, k3, v3, mask3, causal, scale, bq, bk,
                      causal_off=causal_off, valid=valid,
                      rate=dropout_rate, seed3=seed3, window=window)
        return out

    def run_fwd(q3, k3, v3, mask3, seed3):
        out, lse = _fwd(q3, k3, v3, mask3, causal, scale, bq, bk,
                        causal_off=causal_off, valid=valid,
                        rate=dropout_rate, seed3=seed3, window=window)
        return out, (q3, k3, v3, mask3, seed3, out, lse)

    def run_bwd(res, do3):
        if window is not None:
            raise NotImplementedError(
                "flash_attention(window=) is forward-only: the backward "
                "kernels recompute scores without the window mask")
        if v_width != d:
            raise NotImplementedError(
                "flash_attention with a value width of its own is "
                "forward-only: the backward kernels hold one head size")
        q3, k3, v3, mask3, seed3, out, lse = res
        dq, dk, dv = _bwd_impl(q3, k3, v3, mask3, out, lse, do3,
                               causal, scale, bq, bk,
                               causal_off=causal_off, valid=valid,
                               rate=dropout_rate, seed3=seed3)
        return dq, dk, dv, _zero_cotangent(mask3), _zero_cotangent(seed3)

    run.defvjp(run_fwd, run_bwd)
    out = run(q3, k3, v3, mask3, seed3)
    if padded:
        out = out[:, :sq, :]
    return out.reshape(b, h, sq, v_width)


# --------------------------------------------------------------------------
# learned sparse selection (ISSUE 36): index scores, the picked set of a
# row, and prefill attention over the picked sets
# --------------------------------------------------------------------------

def index_scores_reference(qi, wi, ki):
    """Pure-jnp oracle of the index scores: ``qi [rows, heads, di]``,
    ``wi [rows, heads]``, ``ki [keys, di]`` -> ``I [rows, keys]`` float32,
    ``I[t, s] = sum_j wi[t, j] * relu(qi[t, j] . ki[s])``."""
    dots = jnp.einsum("thd,sd->ths", qi.astype(jnp.float32),
                      ki.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)
    return jnp.sum(wi.astype(jnp.float32)[..., None]
                   * jnp.maximum(dots, 0.0), axis=1)


def _index_kernel(heads, qi_ref, wi_ref, ki_ref, o_ref):
    # qi [heads, bq, di], wi [bq, heads] f32, ki [bk, di] -> o [bq, bk]:
    # one small product a head, rectified and weighted in float32
    ki = ki_ref[...]
    wi = wi_ref[...]
    acc = jnp.zeros(o_ref.shape, jnp.float32)
    for j in range(heads):
        dots = jax.lax.dot_general(qi_ref[j], ki, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        acc = acc + wi[:, j:j + 1] * jnp.maximum(dots, 0.0)
    o_ref[...] = acc


def index_scores(qi, wi, ki, *, block_k: int = 512):
    """The index scores of a block of query rows against the keys (module
    section note; :func:`index_scores_reference` is the oracle): ``qi
    [rows, heads, di]``, ``wi [rows, heads]`` float32, ``ki [keys, di]``
    -> ``[rows, keys]`` float32.  The Pallas kernel ``apex_dsa_index_fwd``
    (grid over key blocks): the per-head products never leave VMEM."""
    rows, heads, di = qi.shape
    keys = ki.shape[0]
    bk, keys_pad = _plan_block(keys, block_k)
    if keys_pad != keys:
        ki = jnp.pad(ki, ((0, keys_pad - keys), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_index_kernel, heads),
        grid=(keys_pad // bk,),
        in_specs=[
            pl.BlockSpec((heads, rows, di), lambda j: (0, 0, 0)),
            pl.BlockSpec((rows, heads), lambda j: (0, 0)),
            pl.BlockSpec((bk, di), lambda j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((rows, bk), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((rows, keys_pad), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret_mode(),
        name="apex_dsa_index_fwd",
    )(jnp.swapaxes(qi, 0, 1), wi.astype(jnp.float32), ki)
    return out[:, :keys] if keys_pad != keys else out


def select_top_mask(scores, k: int, live):
    """The picked set of every row: ``scores [rows, n]`` float32, ``live
    [rows, n]`` bool (the candidates) -> bool ``[rows, n]`` with, in each
    row, the ``min(k, candidates)`` candidates of largest score set; equal
    scores go to the lower position (``jax.lax.top_k``'s order).  EXACT:
    the k-th largest score is found bit by bit on an order-preserving
    integer image of the float (32 compare-and-count passes over the
    row), never by a sort of the row or a sampled threshold."""
    bits = jax.lax.bitcast_convert_type(scores.astype(jnp.float32),
                                        jnp.uint32)
    top = jnp.uint32(1 << 31)
    # larger float <-> larger unsigned image; no candidate's image is 0
    image = jnp.where(bits >= top, ~bits, bits | top)
    image = jnp.where(live, image, jnp.uint32(0))
    want = jnp.minimum(jnp.sum(live, axis=1, dtype=jnp.int32), k)

    def count(mask):
        return jnp.sum(mask, axis=1, dtype=jnp.int32)

    def bit(i, kth):
        cand = kth | (top >> i.astype(jnp.uint32))
        return jnp.where(count(image >= cand[:, None]) >= want, cand, kth)

    kth = jax.lax.fori_loop(0, 32, bit,
                            jnp.zeros((scores.shape[0],), jnp.uint32))
    above = image > kth[:, None]
    ties = (image == kth[:, None]) & live
    room = want - count(above)          # ties that still fit, per row

    def lower_ties(ties):
        return ties & (jnp.cumsum(ties, axis=1, dtype=jnp.int32)
                       <= room[:, None])

    # only a row with more ties than room has to rank them by position
    ties = jax.lax.cond(jnp.any(count(ties) > room), lower_ties,
                        lambda t: t, ties)
    return above | ties


def with_sink(out, lse, sink):
    """Attention ``out [b, h, sq, dv]`` whose softmax gains one more term
    in its denominator, ``exp(sink[h])``, that carries no value: ``out *
    sigmoid(lse - sink)``, exact given each row's natural log-sum-exp
    ``lse [b, h, sq]`` (``flash_attention(return_lse=True)``).  A row that
    attended nothing (``lse`` the kernel's masked floor) stays zero."""
    w = jax.nn.sigmoid(lse - sink.astype(jnp.float32)[None, :, None])
    return (out.astype(jnp.float32) * w[..., None]).astype(out.dtype)


def _attend(q, k, v, *, sm_scale, sink, **kw):
    """``flash_attention``, with each head's sink where there is one."""
    if sink is None:
        return flash_attention(q, k, v, sm_scale=sm_scale, **kw)
    return with_sink(*flash_attention(q, k, v, sm_scale=sm_scale,
                                      return_lse=True, **kw), sink)


def _select_groups(s: int, topk: int, block_q: int):
    """The row blocks of a sequence of ``s`` past its first ``topk`` rows:
    ``(bq, [(r0, r1), ...])``, at most eight groups of whole blocks, a
    group scoring and attending the keys up to its last row."""
    bq = int(np.gcd(block_q, s - topk))
    group = max(bq, cdiv(cdiv(s - topk, 8), bq) * bq)
    return bq, [(r0, min(r0 + group, s)) for r0 in range(topk, s, group)]


def _pick_block(qi, wi, kig, cols, t0, bq: int, topk: int):
    """The picked set ``[bq, r1]`` of rows ``t0 ..`` among the keys of
    ``kig [r1, di]`` (positions ``cols``): index scores, then the exact
    top ``topk`` of the causal ones."""
    rows = t0 + jnp.arange(bq, dtype=jnp.int32)
    with jax.named_scope("apex_dsa_index"):
        scores = index_scores(jax.lax.dynamic_slice_in_dim(qi, t0, bq),
                              jax.lax.dynamic_slice_in_dim(wi, t0, bq), kig)
    with jax.named_scope("apex_dsa_select"):
        return select_top_mask(scores, topk, cols[None, :] <= rows[:, None])


def _attend_block(q, kg, vg, picked, t0, bq: int, sm_scale, sink):
    """Rows ``t0 ..`` of ``q`` over the keys ``kg``/``vg`` they picked."""
    with jax.named_scope("apex_dsa_attend"):
        ctx = _attend(jax.lax.dynamic_slice_in_dim(q, t0, bq, axis=2), kg,
                      vg, mask=~picked[None, None], sm_scale=sm_scale,
                      sink=sink, use_kernel=True)
    return ctx[0]


def select_attention(q, k, v, qi, wi, ki, *, topk: int, block_q: int = 512,
                     sm_scale: Optional[float] = None):
    """Causal attention of ONE sequence in which query ``t`` attends the
    ``min(topk, t + 1)`` causal positions of largest index score and no
    other (the others get no probability mass — not a bias).

    ``q``/``k``: ``[1, h, s, d]``, ``v [1, h, s, dv]`` (k/v per query
    head); ``qi [s, heads, di]``, ``wi [s, heads]``, ``ki [s, di]`` the
    indexer's.  Returns ``(ctx [1, h, s, dv], picked [s] int32)``,
    ``picked`` the positions each row attended.

    Rows under ``topk`` are plain causal rows (one flash call over the
    first ``topk`` keys).  The others go ``block_q`` rows at a time: index
    scores against the causal keys (``apex_dsa_index_fwd``), the picked
    set (:func:`select_top_mask`), the flash kernel under that mask — so
    no ``[s, s]`` array outlives a block.  Blocks are grouped (at most
    eight groups) so that a block scores and attends the keys up to its
    GROUP's last row, not the sequence's.  A kind whose later layers
    attend the same picks makes them once (:func:`select_picks`) and
    attends under them in each layer (:func:`select_attend`)."""
    b, h, s, d = q.shape
    if b != 1:
        raise ValueError(f"select_attention takes one sequence, got "
                         f"batch {b}")
    with jax.named_scope("apex_dsa_attend"):
        head = flash_attention(q[:, :, :topk], k[:, :, :topk],
                               v[:, :, :topk], causal=True,
                               sm_scale=sm_scale)
    if s <= topk:
        return head, jnp.arange(1, s + 1, dtype=jnp.int32)
    bq, groups = _select_groups(s, topk, block_q)
    ctxs, counts = [head], [jnp.arange(1, topk + 1, dtype=jnp.int32)]
    for r0, r1 in groups:               # the group's rows; its keys [0, r1)
        kg, vg, kig = k[:, :, :r1], v[:, :, :r1], ki[:r1]
        cols = jnp.arange(r1, dtype=jnp.int32)

        def block(t0, kg=kg, vg=vg, kig=kig, cols=cols):
            picked = _pick_block(qi, wi, kig, cols, t0, bq, topk)
            ctx = _attend_block(q, kg, vg, picked, t0, bq, sm_scale, None)
            return ctx, jnp.sum(picked, axis=1, dtype=jnp.int32)

        ctx, n = jax.lax.map(block, jnp.arange(r0, r1, bq, dtype=jnp.int32))
        # [blocks, h, bq, dv] -> [1, h, rows, dv]
        ctxs.append(jnp.moveaxis(ctx, 0, 1).reshape(1, h, r1 - r0,
                                                    v.shape[3]))
        counts.append(n.reshape(-1))
    return jnp.concatenate(ctxs, axis=2), jnp.concatenate(counts)


def select_picks(qi, wi, ki, *, topk: int, block_q: int = 512):
    """The picked sets of ONE sequence, made once for every layer that
    attends them: ``qi [s, heads, di]``, ``wi [s, heads]``,
    ``ki [s, di]`` -> ``(masks, picked)``, ``masks`` one bool ``[blocks,
    bq, r1]`` a group of :func:`select_attention`'s row blocks (empty
    where ``s <= topk``: every row then attends its causal positions) and
    ``picked [s]`` int32 the positions each row attends."""
    s = ki.shape[0]
    if s <= topk:
        return (), jnp.arange(1, s + 1, dtype=jnp.int32)
    bq, groups = _select_groups(s, topk, block_q)
    masks, counts = [], [jnp.arange(1, topk + 1, dtype=jnp.int32)]
    for r0, r1 in groups:
        kig, cols = ki[:r1], jnp.arange(r1, dtype=jnp.int32)
        m = jax.lax.map(
            lambda t0, kig=kig, cols=cols: _pick_block(qi, wi, kig, cols, t0,
                                                       bq, topk),
            jnp.arange(r0, r1, bq, dtype=jnp.int32))
        masks.append(m)
        counts.append(jnp.sum(m, axis=2, dtype=jnp.int32).reshape(-1))
    return tuple(masks), jnp.concatenate(counts)


def select_attend(q, k, v, masks, *, topk: int, block_q: int = 512,
                  sm_scale: Optional[float] = None, sink=None):
    """:func:`select_attention`'s attention under picks made before
    (:func:`select_picks`' ``masks``, of the same ``s``, ``topk`` and
    ``block_q``): ``q``/``k`` ``[1, h, s, d]``, ``v [1, h, s, dv]`` ->
    ``[1, h, s, dv]``; ``sink [h]`` (float32, optional) each head's sink
    logit (:func:`with_sink`)."""
    b, h, s, _ = q.shape
    dv = v.shape[3]
    with jax.named_scope("apex_dsa_attend"):
        head = _attend(q[:, :, :topk], k[:, :, :topk], v[:, :, :topk],
                       causal=True, sm_scale=sm_scale, sink=sink)
    if s <= topk:
        return head
    bq, groups = _select_groups(s, topk, block_q)
    ctxs = [head]
    for (r0, r1), m in zip(groups, masks):
        kg, vg = k[:, :, :r1], v[:, :, :r1]

        def block(args, kg=kg, vg=vg):
            return _attend_block(q, kg, vg, args[1], args[0], bq, sm_scale,
                                 sink)

        ctx = jax.lax.map(block, (jnp.arange(r0, r1, bq, dtype=jnp.int32),
                                  m))
        ctxs.append(jnp.moveaxis(ctx, 0, 1).reshape(1, h, r1 - r0, dv))
    return jnp.concatenate(ctxs, axis=2)


# --------------------------------------------------------------------------
# single-token decode attention against a KV cache
# --------------------------------------------------------------------------

#: decode (q_len = 1) kernel/XLA crossover.  A single query row feeds the
#: Pallas kernel a q block padded up to the 128-lane grid — 128x wasted
#: MXU rows — while the whole op is one bandwidth-bound matvec over the
#: cache that XLA lowers to clean VPU code.  The XLA path therefore wins
#: everywhere the O(b·h·S) score tensor stays small; the kernel only
#: pays off once the materialized scores outgrow VMEM-friendly sizes at
#: very long contexts.  4096 is a PROVISIONAL boundary (same status the
#: attention crossover had before the r5 sweep); override per-run with
#: ``APEX_TPU_DECODE_XLA_MAX_SEQ`` or per-call with ``xla_max_seq=``
#: (0 forces the kernel path), and bench infer captures stamp the
#: effective value so on-chip sweeps can refine it without a code edit.
_DECODE_XLA_MAX_SEQ = 4096

_DECODE_XLA_MAX_SEQ_ENV = "APEX_TPU_DECODE_XLA_MAX_SEQ"


def decode_xla_max_seq(override=None) -> int:
    """Effective decode crossover: explicit kwarg override >
    ``APEX_TPU_DECODE_XLA_MAX_SEQ`` env var > the provisional default."""
    if override is not None:
        return int(override)
    env = os.environ.get(_DECODE_XLA_MAX_SEQ_ENV)
    if env:
        try:
            return int(env)
        except ValueError as e:
            raise ValueError(
                f"{_DECODE_XLA_MAX_SEQ_ENV} must be an int, got "
                f"{env!r}") from e
    return _DECODE_XLA_MAX_SEQ


def decode_attention(q, k, v, lengths, *, sm_scale: Optional[float] = None,
                     use_kernel: Optional[bool] = None,
                     xla_max_seq: Optional[int] = None):
    """Single-token attention against a per-slot KV cache.

    The inference engine's decode core: one query per sequence slot
    scores the slot's whole (statically shaped) cache, masked to the
    slot's live length.

    * ``q``: ``[b, h, 1, d]`` (or ``[b, h, d]``) — the current token's
      query heads per slot.
    * ``k``/``v``: ``[b, kv_heads, S, d]`` — the cache, ``kv_heads``
      dividing ``h`` (GQA/MQA: each kv head serves ``h // kv_heads``
      query heads, so LLaMA's replicated-kv layout is scored straight
      from its once-per-kv-head cache with no broadcast materialized on
      the XLA path).
    * ``lengths``: ``[b]`` int32 — valid entries per slot; positions at
      or past a slot's length are masked out.  A slot with length 0
      emits zeros (the kernels' fully-masked-row convention).

    ``use_kernel=None`` auto-dispatches on the cache length: at or under
    the crossover (``xla_max_seq`` kwarg > ``APEX_TPU_DECODE_XLA_MAX_SEQ``
    env var > the provisional default ``_DECODE_XLA_MAX_SEQ``) the op is
    a fused XLA einsum chain — the VPU-friendly shape for a bandwidth
    -bound matvec; above it the flash kernel streams the cache blockwise
    (k/v broadcast to the query heads, the length mask as the kernel's
    boolean mask operand).  Numerics mirror the kernels: input-dtype
    operands into the MXU with fp32 accumulation, fp32 softmax.
    """
    squeezed = q.ndim == 3
    if squeezed:
        q = q[:, :, None, :]
    b, h, q_len, d = q.shape
    if q_len != 1:
        raise ValueError(
            f"decode_attention is the q_len == 1 path, got q_len {q_len}; "
            "use flash_attention for prefill")
    if k.shape != v.shape or k.ndim != 4 or k.shape[0] != b \
            or k.shape[3] != d:
        raise ValueError(
            f"k/v must be [b, kv_heads, S, d] = [{b}, *, *, {d}] and "
            f"equal-shaped; got k {tuple(k.shape)} v {tuple(v.shape)}")
    kvh, s_cache = k.shape[1], k.shape[2]
    if kvh == 0 or h % kvh:
        raise ValueError(
            f"kv_heads ({kvh}) must divide query heads ({h})")
    if lengths.shape != (b,):
        raise ValueError(
            f"lengths must be [{b}], got {tuple(lengths.shape)}")
    scale = (d ** -0.5) if sm_scale is None else sm_scale
    lengths = lengths.astype(jnp.int32)

    if use_kernel is None:
        use_kernel = s_cache > decode_xla_max_seq(xla_max_seq)

    if use_kernel:
        group = h // kvh
        if group > 1:
            kb, vb = (jnp.broadcast_to(
                t[:, :, None], (b, kvh, group, s_cache, d)
            ).reshape(b, h, s_cache, d) for t in (k, v))
        else:
            kb, vb = k, v
        mask = (jnp.arange(s_cache, dtype=jnp.int32)[None, None, None, :]
                >= lengths[:, None, None, None])
        out = flash_attention(q, kb, vb, mask=mask, sm_scale=scale,
                              use_kernel=True)
        return out[:, :, 0] if squeezed else out

    # XLA path: grouped-query einsum chain, no kv broadcast materialized
    group = h // kvh
    qg = q.reshape(b, kvh, group, d)
    s = jax.lax.dot_general(
        qg, k, (((3,), (3,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32) * scale     # [b, kvh, group, S]
    live = (jnp.arange(s_cache, dtype=jnp.int32)[None, None, None, :]
            < lengths[:, None, None, None])
    s = jnp.where(live, s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    # length-0 slots: every score is _NEG_INF — emit 0, not uniform
    p = jnp.where(m <= _MASKED_ROW_THRESH, 0.0, p)
    out = jax.lax.dot_general(
        p.astype(v.dtype), v, (((3,), (2,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32)             # [b, kvh, group, d]
    out = out.reshape(b, h, 1, d).astype(q.dtype)
    return out[:, :, 0] if squeezed else out


def ring_decode_attention(q, k, v, positions, *, window: int,
                          sm_scale: Optional[float] = None):
    """Single-token attention of a SLIDING-WINDOW layer against per-slot
    ring buffers (ISSUE 30): position ``t`` of a slot lives at ring
    index ``t % ring``, so the ring holds the last ``ring`` positions
    whatever the context.

    * ``q``: ``[b, h, d]`` — the token at ``positions[b]``, whose own
      k/v row is already in the ring;
    * ``k``/``v``: ``[b, kv_heads, ring, d]`` with ``ring >= window``;
    * ``positions``: ``[b]`` int32.

    Ring index ``r`` holds position ``p - ((p - r) mod ring)`` — the
    newest one congruent to ``r`` that is not ahead of ``p``; it is
    attended iff that position exists (``>= 0``) and lies inside the
    window (``> p - window``).  Softmax does not care about the order of
    its keys, so the ring is never unrolled.  Plain XLA: a matvec over
    ``ring`` keys, the grouped-query einsum chain of
    :func:`decode_attention`."""
    b, h, d = q.shape
    kvh, ring = k.shape[1], k.shape[2]
    if k.shape != v.shape or k.shape[0] != b or k.shape[3] != d \
            or h % kvh or ring < window:
        raise ValueError(
            f"k/v must be [b={b}, kv_heads | {h}, ring >= {window}, "
            f"{d}] and equal-shaped; got k {tuple(k.shape)} v "
            f"{tuple(v.shape)}")
    scale = (d ** -0.5) if sm_scale is None else sm_scale
    live = _ring_live(positions, ring, window)[:, None, None, :]
    qg = q.reshape(b, kvh, h // kvh, d)
    s = jax.lax.dot_general(
        qg, k, (((3,), (3,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32) * scale   # [b, kvh, g, ring]
    s = jnp.where(live, s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    pr = jnp.exp(s - m)
    pr = pr / jnp.sum(pr, axis=-1, keepdims=True)
    out = jax.lax.dot_general(
        pr.astype(v.dtype), v, (((3,), (2,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32)           # [b, kvh, g, d]
    return out.reshape(b, h, d).astype(q.dtype)


def _ring_live(positions, ring: int, window: int):
    """``[b, ring]`` bool: does ring row ``r`` of each slot hold a position
    its token attends?  Row ``r`` holds ``p - ((p - r) mod ring)`` — the
    newest position congruent to ``r`` that is not ahead of ``p`` — and is
    attended iff it exists (``>= 0``) and lies inside the window (``> p -
    window``)."""
    p = positions.astype(jnp.int32)[:, None]                  # [b, 1]
    held = p - jnp.mod(p - jnp.arange(ring, dtype=jnp.int32)[None], ring)
    return (held >= 0) & (held > p - window)


def ring_decode_attention_latent(q, ring, positions, *, window: int,
                                 sm_scale: float, values: int):
    """Single-token LATENT attention of a sliding-window layer against
    per-slot rings of latent rows: :func:`ring_decode_attention`
    for a layer that caches one row a position and no per-head k/v.

    * ``q``: ``[b, h, width]`` — the ABSORBED queries (the key
      up-projection folded in, the roped channels beside it) of the token
      at ``positions[b]``, whose own row is already in the ring;
    * ``ring``: ``[b, ring, width]`` with ``ring >= window``, position
      ``t`` at row ``t % ring``;
    * returns ``[b, h, values]``: the softmax-weighted sum of the rows'
      leading ``values`` channels (the latent; the caller applies the
      value up-projection a head).

    Every head scores every row whole; the window rule is
    :func:`ring_decode_attention`'s.  Plain XLA: two products over
    ``ring`` rows a slot, float32 accumulation and softmax."""
    b, h, width = q.shape
    n = ring.shape[1]
    if ring.ndim != 3 or ring.shape[0] != b or ring.shape[2] != width \
            or n < window or not 0 < values <= width:
        raise ValueError(
            f"the ring must be [b={b}, ring >= {window}, {width}] and "
            f"values in (0, {width}]; got ring {tuple(ring.shape)}, "
            f"values {values}")
    live = _ring_live(positions, n, window)[:, None, :]        # [b, 1, n]
    s = jnp.einsum("bhw,bnw->bhn", q, ring,
                   preferred_element_type=jnp.float32) * sm_scale
    s = jnp.where(live, s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    pr = jnp.exp(s - m)
    pr = pr / jnp.sum(pr, axis=-1, keepdims=True)
    out = jnp.einsum("bhn,bnv->bhv", pr.astype(ring.dtype),
                     ring[..., :values],
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def slab_decode_attention(q, win_k, win_v, lengths,
                          *, sm_scale: Optional[float] = None):
    """Verify-step attention (ISSUE 15): a small slab of ``S`` drafted
    tokens per slot scores the slot's cache window, causally within the
    slab.

    The q_len = S generalization of :func:`decode_attention`'s XLA
    grouped-einsum chain, shaped for speculative decoding: the slab's
    own k/v have ALREADY been appended to the cache at positions
    ``[lengths, lengths + S)``, so query row ``r`` (absolute position
    ``lengths + r``) attends to window columns ``j <= lengths + r`` —
    the cached context plus the draft prefix up to and including
    itself.  S = 1 degenerates to exactly ``decode_attention``'s
    masking (``j < lengths + 1``).

    * ``q``: ``[slots, h, S, d]`` — the drafted tokens' query heads.
    * ``win_k``/``win_v``: ``[slots, kv_heads, W, d]`` — the slot's
      full cache window (dense cache directly; paged via the page
      gather in :func:`~apex_tpu.ops.paged_attention.
      paged_slab_attention`).
    * ``lengths``: ``[slots]`` int32 — live tokens BEFORE the slab was
      appended.

    Rows whose absolute position falls outside the window (a slot at
    the end of its virtual window — its slab rows were dropped by the
    append) are fully masked and emit zeros, mirroring the kernels'
    fully-masked-row convention; their emitted tokens are garbage the
    caller retires as truncated.  Numerics mirror
    :func:`decode_attention`: input-dtype MXU operands with fp32
    accumulation, fp32 softmax, no kv broadcast materialized.
    """
    slots, h, sq, d = q.shape
    if win_k.shape != win_v.shape or win_k.ndim != 4 \
            or win_k.shape[0] != slots or win_k.shape[3] != d:
        raise ValueError(
            f"window k/v must be [{slots}, kv_heads, W, {d}] and "
            f"equal-shaped; got win_k {tuple(win_k.shape)} win_v "
            f"{tuple(win_v.shape)}")
    kvh, w = win_k.shape[1], win_k.shape[2]
    if kvh == 0 or h % kvh:
        raise ValueError(
            f"kv_heads ({kvh}) must divide query heads ({h})")
    if lengths.shape != (slots,):
        raise ValueError(
            f"lengths must be [{slots}], got {tuple(lengths.shape)}")
    scale = (d ** -0.5) if sm_scale is None else sm_scale
    lengths = lengths.astype(jnp.int32)
    group = h // kvh
    qg = q.reshape(slots, kvh, group, sq, d)
    s = jax.lax.dot_general(
        qg, win_k, (((4,), (3,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32) * scale  # [b, kvh, g, S, W]
    col = jnp.arange(w, dtype=jnp.int32)[None, None, :]       # [1, 1, W]
    row = jnp.arange(sq, dtype=jnp.int32)[None, :, None]      # [1, S, 1]
    pos = lengths[:, None, None] + row            # absolute row position
    # rows past the virtual window (their append was dropped) mask
    # FULLY: without the pos < w term they would attend to the whole
    # window minus themselves and emit plausible-looking garbage
    live = (col <= pos) & (pos < jnp.int32(w))                # [b, S, W]
    s = jnp.where(live[:, None, None], s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    # rows past the virtual window (dropped appends) are fully masked —
    # emit zeros, not softmax-of-constant's uniform artifact
    p = jnp.where(m <= _MASKED_ROW_THRESH, 0.0, p)
    out = jax.lax.dot_general(
        p.astype(win_v.dtype), win_v, (((4,), (2,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32)          # [b, kvh, g, S, d]
    return out.reshape(slots, h, sq, d).astype(q.dtype)


def prefix_window_attention(q, k, v, win_k, win_v, start,
                            *, sm_scale: Optional[float] = None):
    """Suffix-prefill attention: each query row attends to a cached
    prefix WINDOW plus causally to the suffix itself (ISSUE 12 — the
    math behind prefix-cache hits and chunked prefill).

    * ``q``: ``[b, h, s, d]`` — the suffix tokens' query heads; row
      ``i`` sits at absolute position ``start + i``.
    * ``k``/``v``: ``[b, kv_heads, s, d]`` — the suffix's own
      (pre-broadcast, GQA/MQA) keys/values.
    * ``win_k``/``win_v``: ``[b, kv_heads, W, d]`` — the cached prefix
      window gathered from the slot's KV pages; only columns
      ``j < start`` are live (rows past the prefix hold other pages'
      garbage — finite by construction — and are masked, so their
      values can never leak into the context).
    * ``start``: ``[]`` int32 (traced OK) — the prefix length, i.e.
      how many window columns are valid.

    One fused XLA chain mirroring :func:`decode_attention`'s grouped
    einsum path: bf16 operands into the MXU with fp32 accumulation,
    fp32 softmax over the concatenated ``[W + s]`` key axis.  Every
    real query row has at least itself to attend to (causal self), so
    no fully-masked-row zeroing is needed.
    """
    b, h, sq, d = q.shape
    if k.shape != v.shape or k.ndim != 4 or k.shape[0] != b \
            or k.shape[2] != sq or k.shape[3] != d:
        raise ValueError(
            f"suffix k/v must be [b, kv_heads, {sq}, {d}], got "
            f"k {tuple(k.shape)} v {tuple(v.shape)}")
    if win_k.shape != win_v.shape or win_k.ndim != 4 \
            or win_k.shape[:2] != k.shape[:2] or win_k.shape[3] != d:
        raise ValueError(
            f"window k/v must be [b, kv_heads, W, {d}], got "
            f"win_k {tuple(win_k.shape)} win_v {tuple(win_v.shape)}")
    kvh, w = win_k.shape[1], win_k.shape[2]
    if kvh == 0 or h % kvh:
        raise ValueError(
            f"kv_heads ({kvh}) must divide query heads ({h})")
    scale = (d ** -0.5) if sm_scale is None else sm_scale
    start = jnp.asarray(start, jnp.int32)
    group = h // kvh
    qg = q.reshape(b, kvh, group, sq, d)
    kk = jnp.concatenate([win_k, k], axis=2)            # [b, kvh, W+s, d]
    vv = jnp.concatenate([win_v, v], axis=2)
    s = jax.lax.dot_general(
        qg, kk, (((4,), (3,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32) * scale  # [b,kvh,g,s,W+s]
    col = jnp.arange(w + sq, dtype=jnp.int32)[None, :]
    row = jnp.arange(sq, dtype=jnp.int32)[:, None]
    valid = jnp.where(col < w, col < start, (col - w) <= row)
    s = jnp.where(valid[None, None, None], s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    out = jax.lax.dot_general(
        p.astype(vv.dtype), vv, (((4,), (2,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32)          # [b, kvh, g, s, d]
    return out.reshape(b, h, sq, d).astype(q.dtype)
