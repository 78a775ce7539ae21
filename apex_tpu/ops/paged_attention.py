"""Ragged paged decode attention — single-token attention against a
paged KV pool (PAPERS.md "Ragged Paged Attention", TPU-native).

The paged twin of :func:`apex_tpu.ops.attention.decode_attention`: one
query per sequence slot scores the slot's live tokens, but the tokens
live in fixed-size PAGES of a shared pool rather than a contiguous
per-slot ``max_seq`` window —

    k_pool, v_pool : [pages, layers, kv_heads, page_size, head_dim]
    page_table     : [slots, max_pages_per_slot]  int32
    lengths        : [slots]                      int32

virtual position ``t`` of a slot resolves to physical page
``page_table[slot, t // page_size]``, row ``t % page_size``.

ONE implementation, for every kind and window size: the Pallas kernel
``apex_paged_decode`` on the WHOLE pool.  Its grid is ONE dimension of
DYNAMIC length: a flat work list of the live (slot, page) pairs, in slot
order and page order within a slot (:func:`paged_work_list`, built on
the device from ``page_table`` and ``lengths`` — once a decode step, and
handed to every layer's call).  The list, the lengths and the layer are
SCALAR-PREFETCH operands: the q and out BlockSpec index maps read the
item's slot, the k/v index map ``(item's physical page, layer)``, so
Pallas DMAs exactly the live pages of that layer from HBM, page by page,
with its standard double buffering, and walks nothing else — neither a
per-layer slice of the pool nor the gathered ``[slots, max_seq]`` window
ever materializes, and a dead table entry costs no grid step.  Online
softmax (fp32 running max/normalizer/accumulator in VMEM scratch, base-2
log domain like the flash kernels) carries across a slot's items: it is
initialised on the slot's first item and the output written on its
last.  Dead rows inside the last live page mask to ``_NEG_INF``; a slot
of length 0 has one item that initialises and finishes without a body,
and so emits zeros.

GQA/MQA: ``kv_heads`` divides the query heads; the kernel loops kv
heads (static, small) scoring each head's ``group`` query rows against
the once-per-kv-head page — no broadcast materialized anywhere.

A third form, for LATENT attention (ISSUE 34): ``apex_paged_decode_latent``
walks the SAME work list over a pool with no KV-head axis and no value
array —

    pool : [pages, layers, width, page_size]

— one row a position that every query head scores whole (the absorbed
query is ``width`` wide) and whose leading ``values`` channels ARE the
values: an item is ONE DMA of a ``[width, page_size]`` block, not two,
and the output is ``[slots, h, values]`` (the caller applies the value
up-projection per head).  A page holds its positions along the minor
axis (``kv_cache`` module docstring: a 576-wide minor axis is laid out
transposed by the chip's runtime and would be copied for the kernel);
the scores are then a plain product and the values contract the minor
axis of both operands, the form q k^T has in the other kernel.  Same scalar-prefetch operands, same online
softmax, its own ``pallas_call`` name so a trace tells the forms apart;
the per-head-K/V calls compile what they always did.

A kind whose layers SELECT the positions they attend by a learned index
(ISSUE 36) runs a decode layer in three stages over the same work list,
select-then-attend:

    ik_pool : [pages, layers, index_width, page_size]   (beside k and v)

*index* — :func:`paged_index_scores`, the kernel ``apex_dsa_index``: each
slot's few index queries against the index keys of its live pages (one
``[index_width, page_size]`` block an item: 1/16 of the page's K/V
bytes), rectified, weighted and summed over the index heads, one
``[page_size]`` stretch of float32 scores an item; *select* — the
``topk`` best positions of each slot, exactly, on the device
(:func:`apex_tpu.ops.attention.select_top_mask`: XLA, no kernel);
*attend* — :func:`paged_select_attention`, the kernel
``apex_dsa_attend``: ``apex_paged_decode``'s own body and walk with the
picked positions of each page as one more block (an operand the other
callers do not trace), so that an unpicked position gets no probability
mass and a slot that picked every live position gets
``apex_paged_decode``'s answer bit for bit.  Over a LATENT pool
the attend stage is :func:`paged_select_attention_latent`, the kernel
``apex_dsa_attend_latent``: ``apex_paged_decode_latent``'s body and walk
with the same block, and a sink a head (one more term of the softmax's
denominator, with no value) where the kind has one.  The mathematics is the
sparse one; the walk still reads every live page (gathering the picked
rows costs more than it saves at this pool's layout: PERF.md section 6,
PR 36).  No stage makes a pool-sized or ``[slots, max_seq, heads,
d]``-sized array.

The speculative verify slab (:func:`paged_slab_attention`) still
gathers the slot windows and scores them with the dense XLA chain.
"""
from __future__ import annotations

import functools
import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops.attention import (_LOG2E, _NEG_INF,
                                    slab_decode_attention)
from apex_tpu.utils import interpret_mode

__all__ = ["paged_decode_attention", "paged_work_list", "PagedWork",
           "paged_slab_attention", "paged_index_scores",
           "paged_select_attention", "paged_select_attention_latent",
           "fused_block_decode", "decode_fusion", "fusion_min_pages",
           "resolve_decode_fusion",
           "fused_block_vmem_bytes", "fused_block_refusal",
           "FUSED_BLOCK_VMEM_LIMIT"]

#: pallas_audit registration (analysis hook only, no behavior change):
#: both kernels run online-softmax in fp32 scratch (APX302) and mask
#: the rows beyond the length inside a slot's last live page in-kernel
#: (APX303 masked_tail).  ``_paged_kernel`` walks the live pages only
#: (one dynamic grid dimension, recorded as -1); ``_fused_block_kernel``
#: still covers the slot's max_pages and skips the dead ones.
PALLAS_AUDIT = {
    "_paged_kernel": {"reduction": True, "masked_tail": True},
    "_latent_kernel": {"reduction": True, "masked_tail": True},
    "_index_kernel": {"masked_tail": True},
    "_fused_block_kernel": {"reduction": True, "masked_tail": True},
}


# --------------------------------------------------------------------------
# the work list: one item a live (slot, page) pair
# --------------------------------------------------------------------------

class PagedWork(NamedTuple):
    """What ``apex_paged_decode`` walks (:func:`paged_work_list`).  The
    first ``start[-1]`` entries of ``slot`` / ``page`` are the items,
    slot-major and page-minor; the rest of their static capacity ``slots
    x max_pages_per_slot`` is never read."""
    slot: jax.Array         # [capacity] int32: the item's slot
    page: jax.Array         # [capacity] int32: the item's physical page
    start: jax.Array        # [slots + 1] int32: a slot's first item
    lengths: jax.Array      # [slots] int32: live tokens, as handed in


@functools.partial(jax.jit, static_argnames=("page_size",))
def paged_work_list(page_table, lengths, *, page_size: int) -> PagedWork:
    """The flat list of live (slot, page) pairs of ``page_table [slots,
    max_pages_per_slot]`` under ``lengths [slots]``: a slot contributes
    its ``ceil(length / page_size)`` leading table entries, in order — a
    dead entry never appears — and a slot of length 0 ONE item (page 0,
    never scored: the kernel still has to write the slot's zeros).
    Everything is computed on the device; a decode step builds it once
    and every pool layer's :func:`paged_decode_attention` takes it."""
    slots, mpps = page_table.shape
    page_table = page_table.astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)
    live = jnp.minimum((lengths + page_size - 1) // page_size, mpps)
    start = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                             jnp.cumsum(jnp.maximum(live, 1),
                                        dtype=jnp.int32)])
    item = jnp.arange(slots * mpps, dtype=jnp.int32)
    # the slot whose stretch holds the item: how many stretches end at
    # or before it (items past the count land on the last slot)
    slot = jnp.minimum(jnp.sum(item[:, None] >= start[None, 1:], axis=1,
                               dtype=jnp.int32), slots - 1)
    at = jnp.minimum(item - start[slot], mpps - 1)
    page = jnp.where(at < live[slot], page_table[slot, at], 0)
    return PagedWork(slot, page, start, lengths)


# --------------------------------------------------------------------------
# Pallas kernel: grid (items,), the work list as scalar prefetch
# --------------------------------------------------------------------------

def _paged_kernel(scale, kvh, group, ps, picks,
                  slot_ref, page_ref, start_ref, len_ref, layer_ref,
                  q_ref, *refs):
    # ``picks`` (static): one more operand stands before k and v, which of
    # the page's positions the slot PICKED (ISSUE 36).  Without it nothing
    # of it is traced: the kernel is the one it always was
    pick_ref = refs[0] if picks else None
    k_ref, v_ref, o_ref, s_scr, m_scr, l_scr, acc_scr = refs[picks:]
    # blocks of the whole pool: [1, 1, kvh, ps, d]
    k_ref, v_ref = k_ref.at[0], v_ref.at[0]
    item = pl.program_id(0)
    sid = slot_ref[item]
    p = item - start_ref[sid]               # the page's place in the slot
    h = kvh * group

    @pl.when(p == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[sid]

    @pl.when(length > 0)                    # an empty slot's one item
    def _body():
        q = q_ref[0]                                     # [h, d]
        # per-kv-head scoring: each kv head's page block serves its
        # `group` query rows (GQA) — kvh is static and small, and the
        # disjoint row segments land in one [h, ps] score scratch
        for i in range(kvh):
            seg = slice(i * group, (i + 1) * group)
            s_scr[seg, :] = jax.lax.dot_general(
                q[seg], k_ref[0, i], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * (scale * _LOG2E)
        cols = p * ps + jax.lax.broadcasted_iota(jnp.int32, (h, ps), 1)
        keep = cols < length
        if picks:       # an unpicked position gets no probability mass
            keep = keep & (pick_ref[0, 0] > 0.0)
        s = jnp.where(keep, s_scr[...], _NEG_INF)
        # online softmax, base-2 log domain (scale absorbed log2e):
        # within a live page every row has >= 1 live column, so no
        # fully-masked-row guard is needed here (length-0 slots never
        # enter the body and finish at l == 0 -> zeros)
        m_prev = m_scr[...]                              # [h, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp2(m_prev - m_new)
        pmat = jnp.exp2(s - m_new)
        if picks:
            # a page may hold no picked position at all: _NEG_INF is
            # finite, so its columns would each weigh exp2(0) = 1
            pmat = jnp.where(keep, pmat, 0.0)
        l_scr[...] = l_scr[...] * alpha + \
            jnp.sum(pmat, axis=1, keepdims=True)
        for i in range(kvh):
            seg = slice(i * group, (i + 1) * group)
            acc_scr[seg, :] = acc_scr[seg, :] * alpha[seg] + jax.lax.dot(
                pmat[seg, :].astype(v_ref.dtype), v_ref[0, i],
                preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(item == start_ref[sid + 1] - 1)
    def _finish():
        l = l_scr[...]
        o_ref[0] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale",))
def _paged_kernel_call(q, k_pool, v_pool, work, layer, picked=None, *,
                       scale):
    # ``layer`` is a TRACED int32 [1], a scalar-prefetch operand like the
    # work list: every layer of a decode step is then the same jitted
    # call, traced and lowered to Mosaic ONCE (a static layer in the
    # index map makes each layer a kernel of its own: 24 lowerings,
    # seconds of every process's start, compile cache or not).
    # ``picked [slots, max_seq]`` (bool), where given, is one more block
    # an item — the page's picked positions — and the kernel's other name
    slots, h, d = q.shape
    kvh, ps = k_pool.shape[2], k_pool.shape[3]
    group = h // kvh

    def slot_index(i, slot, page, start, ln, ly):
        return (slot[i], 0, 0)

    def pick_index(i, slot, page, start, ln, ly):
        return (slot[i], i - start[slot[i]], 0, 0)

    def page_index(i, slot, page, start, ln, ly):
        return (page[i], ly[0], 0, 0, 0)

    page_block = (1, 1, kvh, ps, d)
    slot_block = pl.BlockSpec((1, h, d), slot_index)
    name = "apex_paged_decode" if picked is None else "apex_dsa_attend"
    picks = () if picked is None else (picked.astype(jnp.float32).reshape(
        slots, picked.shape[1] // ps, 1, ps),)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(work.start[-1],),             # traced: the live items only
        in_specs=[
            slot_block,
            *[pl.BlockSpec((1, 1, 1, ps), pick_index) for _ in picks],
            pl.BlockSpec(page_block, page_index),
            pl.BlockSpec(page_block, page_index),
        ],
        out_specs=slot_block,
        scratch_shapes=[
            pltpu.VMEM((h, ps), jnp.float32),     # score block
            pltpu.VMEM((h, 1), jnp.float32),      # running max (base 2)
            pltpu.VMEM((h, 1), jnp.float32),      # running normalizer
            pltpu.VMEM((h, d), jnp.float32),      # fp32 output accum
        ],
    )
    kernel = functools.partial(_paged_kernel, scale, kvh, group, ps,
                               len(picks))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((slots, h, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret_mode(),
        name=name,
    )(work.slot, work.page, work.start, work.lengths, layer,
      q, *picks, k_pool, v_pool)


# --------------------------------------------------------------------------
# the latent form: one pool, every head scores the whole row
# --------------------------------------------------------------------------

def _latent_kernel(scale, ps, dv, picks, sinks,
                   slot_ref, page_ref, start_ref, len_ref, layer_ref,
                   q_ref, *refs):
    # ``picks`` / ``sinks`` (static): one more block before the
    # pool, the page's PICKED positions, and one after it, the heads' sink
    # logits.  Without them nothing of either is traced: the kernel is the
    # one it always was
    pick_ref = refs[0] if picks else None
    c_ref = refs[picks].at[0, 0]            # the block [1, 1, width, ps]
    sink_ref = refs[picks + 1] if sinks else None
    o_ref, m_scr, l_scr, acc_scr = refs[picks + 1 + sinks:]
    item = pl.program_id(0)
    sid = slot_ref[item]
    p = item - start_ref[sid]               # the page's place in the slot

    @pl.when(p == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[sid]

    @pl.when(length > 0)                    # an empty slot's one item
    def _body():
        q = q_ref[0]                                     # [h, width]
        rows = c_ref[...]                                # [width, ps]
        s = jax.lax.dot(
            q, rows,
            preferred_element_type=jnp.float32) * (scale * _LOG2E)
        cols = p * ps + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        keep = cols < length
        if picks:       # an unpicked position gets no probability mass
            keep = keep & (pick_ref[0, 0] > 0.0)
        s = jnp.where(keep, s, _NEG_INF)
        m_prev = m_scr[...]                              # [h, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp2(m_prev - m_new)
        pmat = jnp.exp2(s - m_new)
        if picks:       # a page may hold no picked position at all
            pmat = jnp.where(keep, pmat, 0.0)
        l_scr[...] = l_scr[...] * alpha + \
            jnp.sum(pmat, axis=1, keepdims=True)
        # the values are the rows' leading channels: no second block
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            pmat.astype(rows.dtype), rows[:dv, :],
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(item == start_ref[sid + 1] - 1)
    def _finish():
        l = l_scr[...]
        if sinks:
            # a head's sink is one more term of the denominator, with no
            # value: exp(sink - max), the max in the base-2 domain
            l = l + jnp.exp2(sink_ref[...] * _LOG2E - m_scr[...])
        o_ref[0] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "values"))
def _latent_kernel_call(q, pool, work, layer, picked=None, sink=None, *,
                        scale, values):
    # as _paged_kernel_call: the layer a traced scalar-prefetch operand,
    # one trace and one Mosaic lowering for every layer of a step.
    # ``picked [slots, max_seq]`` (bool) and ``sink [h]`` (float32), where
    # given, are one more block each and the kernel's other name
    slots, h, width = q.shape
    ps = pool.shape[3]

    def slot_index(i, slot, page, start, ln, ly):
        return (slot[i], 0, 0)

    def pick_index(i, slot, page, start, ln, ly):
        return (slot[i], i - start[slot[i]], 0, 0)

    def page_index(i, slot, page, start, ln, ly):
        return (page[i], ly[0], 0, 0)

    picks = () if picked is None else (picked.astype(jnp.float32).reshape(
        slots, picked.shape[1] // ps, 1, ps),)
    sinks = () if sink is None else (
        sink.astype(jnp.float32).reshape(h, 1),)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(work.start[-1],),             # traced: the live items only
        in_specs=[
            pl.BlockSpec((1, h, width), slot_index),
            *[pl.BlockSpec((1, 1, 1, ps), pick_index) for _ in picks],
            pl.BlockSpec((1, 1, width, ps), page_index),
            *[pl.BlockSpec((h, 1), lambda i, *_: (0, 0)) for _ in sinks],
        ],
        out_specs=pl.BlockSpec((1, h, values), slot_index),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),      # running max (base 2)
            pltpu.VMEM((h, 1), jnp.float32),      # running normalizer
            pltpu.VMEM((h, values), jnp.float32),  # fp32 output accum
        ],
    )
    kernel = functools.partial(_latent_kernel, scale, ps, values,
                               len(picks), len(sinks))
    name = "apex_dsa_attend_latent" if picks else "apex_paged_decode_latent"
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((slots, h, values), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret_mode(),
        name=name,
    )(work.slot, work.page, work.start, work.lengths, layer, q, *picks, pool,
      *sinks)


# --------------------------------------------------------------------------
# learned sparse selection (ISSUE 36): index scores of the live positions,
# then attention over the PICKED positions only
# --------------------------------------------------------------------------

def _index_kernel(ps, slot_ref, page_ref, start_ref, len_ref, layer_ref,
                  qi_ref, wi_ref, ik_ref, o_ref):
    # one item: the slot's index queries [hi, di] against a page of index
    # keys [di, ps]; rectified, weighted and summed over the index heads
    item = pl.program_id(0)
    sid = slot_ref[item]
    p = item - start_ref[sid]
    dots = jax.lax.dot(qi_ref[0], ik_ref[0, 0],
                       preferred_element_type=jnp.float32)      # [hi, ps]
    score = jnp.sum(wi_ref[0] * jnp.maximum(dots, 0.0), axis=0,
                    keepdims=True)                              # [1, ps]
    cols = p * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
    o_ref[0, 0] = jnp.where(cols < len_ref[sid], score, _NEG_INF)


@jax.jit
def _index_kernel_call(qi, wi, ik_pool, work, layer):
    slots, hi, di = qi.shape
    ps = ik_pool.shape[3]
    mpps = work.slot.shape[0] // slots

    def slot_index(i, slot, page, start, ln, ly):
        return (slot[i], 0, 0)

    def page_index(i, slot, page, start, ln, ly):
        return (page[i], ly[0], 0, 0)

    def out_index(i, slot, page, start, ln, ly):
        return (slot[i], i - start[slot[i]], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(work.start[-1],),             # traced: the live items only
        in_specs=[
            pl.BlockSpec((1, hi, di), slot_index),
            pl.BlockSpec((1, hi, 1), slot_index),
            pl.BlockSpec((1, 1, di, ps), page_index),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, ps), out_index),
    )
    out = pl.pallas_call(
        functools.partial(_index_kernel, ps),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((slots, mpps, 1, ps), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret_mode(),
        name="apex_dsa_index",
    )(work.slot, work.page, work.start, work.lengths, layer,
      qi, wi.astype(jnp.float32)[..., None], ik_pool)
    # a page no item visited was never written: every position at or past
    # a slot's length reads "masked", whatever the buffer held
    out = out.reshape(slots, mpps * ps)
    dead = jnp.arange(mpps * ps, dtype=jnp.int32)[None] \
        >= work.lengths[:, None]
    return jnp.where(dead, _NEG_INF, out)


def paged_index_scores(qi, wi, ik_pool, work: PagedWork, *, layer: int):
    """Index scores of every slot's live positions (ISSUE 36, the first of
    the three stages of a decode step that SELECTS): ``qi [slots, heads,
    di]`` the index queries, ``wi [slots, heads]`` their weights, ``ik_pool
    [pages, layers, di, page_size]`` the WHOLE index-key pool (a page's
    positions on the minor axis), ``work`` the step's
    :func:`paged_work_list`.  Returns ``[slots, max_seq]`` float32:
    ``sum_j wi[j] * relu(qi[j] . ik[s])`` at a live position ``s``, a large
    negative number at every other.

    The Pallas kernel ``apex_dsa_index`` walks the work list as the
    attention kernels do — one ``[di, page_size]`` block an item, 1/16 of
    the K/V bytes of the page — and writes one ``[page_size]`` stretch of
    scores an item."""
    slots, hi, di = qi.shape
    if ik_pool.ndim != 4 or ik_pool.shape[2] != di or wi.shape != (slots,
                                                                   hi):
        raise ValueError(
            f"index queries [slots, heads, {di}] and weights [slots, "
            f"heads] score the pool [pages, layers, {di}, page_size]; got "
            f"qi {tuple(qi.shape)} wi {tuple(wi.shape)} pool "
            f"{tuple(ik_pool.shape)}")
    if not 0 <= layer < ik_pool.shape[1]:
        raise ValueError(f"layer {layer} is outside the pool's "
                         f"{ik_pool.shape[1]} layers")
    return _index_kernel_call(qi, wi, ik_pool, work,
                              jnp.full((1,), layer, jnp.int32))


def paged_select_attention(q, k_pool, v_pool, picked, work: PagedWork, *,
                           layer: int, sm_scale: Optional[float] = None):
    """Single-token attention over the PICKED positions of ONE layer of a
    paged K/V pool (ISSUE 36, the last of the three stages): ``softmax(q .
    k) . v`` over the rows ``picked [slots, max_seq]`` (bool) names, found
    through the page table at token granularity; a live position that was
    not picked gets no probability mass.  ``q [slots, h, d]``, the pools
    WHOLE as :func:`paged_decode_attention` takes them, ``work`` the
    step's :func:`paged_work_list`.

    The Pallas kernel ``apex_dsa_attend`` IS ``apex_paged_decode``'s (one
    body, ``_paged_kernel``, one call): its walk of the live pages with the
    picked positions of each page as one more block, so a slot that picked
    all its live positions (every slot whose context is at most the
    selection's size) gets ``apex_paged_decode``'s answer.  Measured against gathering the picked rows into ``[slots,
    picked, kv_heads, d]`` (PERF.md section 6, PR 36): a row of this pool
    is ``kv_heads`` stretches of 256 B, and XLA's gather of them costs
    more than the walk reads."""
    slots, h, d = q.shape
    if k_pool.shape != v_pool.shape or k_pool.ndim != 5 \
            or k_pool.shape[4] != d or h % k_pool.shape[2]:
        raise ValueError(
            f"k/v must be the whole pool [pages, layers, kv_heads, "
            f"page_size, {d}], equal-shaped, kv_heads dividing {h}; got k "
            f"{tuple(k_pool.shape)} v {tuple(v_pool.shape)}")
    ps = k_pool.shape[3]
    if picked.ndim != 2 or picked.shape[0] != slots or picked.shape[1] % ps:
        raise ValueError(
            f"picked must be [{slots}, max_seq] with max_seq whole pages "
            f"of {ps}, got {tuple(picked.shape)}")
    if not 0 <= layer < k_pool.shape[1]:
        raise ValueError(f"layer {layer} is outside the pool's "
                         f"{k_pool.shape[1]} layers")
    scale = (d ** -0.5) if sm_scale is None else sm_scale
    return _paged_kernel_call(q, k_pool, v_pool, work,
                              jnp.full((1,), layer, jnp.int32), picked,
                              scale=float(scale))


def paged_select_attention_latent(q, pool, picked, work: PagedWork, *,
                                  layer: int, sm_scale: float, values: int,
                                  sink=None):
    """Single-token LATENT attention over the PICKED positions of ONE layer
    of a latent pool: ``q [slots, h, width]`` the absorbed
    queries, ``pool [pages, layers, width, page_size]`` WHOLE, ``picked
    [slots, max_seq]`` (bool) the positions each slot attends, ``work`` the
    step's :func:`paged_work_list`; ``sink [h]`` (float32, optional) each
    head's sink logit, one more term of the softmax's denominator that
    carries no value.  Returns ``[slots, h, values]``.

    The Pallas kernel ``apex_dsa_attend_latent`` IS
    ``apex_paged_decode_latent``'s (one body, ``_latent_kernel``): its walk
    of the live pages with the picked positions of each page as one more
    block — so a slot that picked every live position and has no sink gets
    that kernel's answer — and the sinks, where given, added at the end."""
    slots, h, width = q.shape
    if pool.ndim != 4 or pool.shape[2] != width or not 0 < values <= width:
        raise ValueError(
            f"the latent form takes the pool [pages, layers, {width}, "
            f"page_size] and values in (0, {width}]; got pool "
            f"{tuple(pool.shape)}, values {values}")
    ps = pool.shape[3]
    if picked.ndim != 2 or picked.shape[0] != slots or picked.shape[1] % ps:
        raise ValueError(
            f"picked must be [{slots}, max_seq] with max_seq whole pages "
            f"of {ps}, got {tuple(picked.shape)}")
    if sink is not None and sink.shape != (h,):
        raise ValueError(f"sink must be [{h}], got {tuple(sink.shape)}")
    if not 0 <= layer < pool.shape[1]:
        raise ValueError(f"layer {layer} is outside the pool's "
                         f"{pool.shape[1]} layers")
    return _latent_kernel_call(q, pool, work,
                               jnp.full((1,), layer, jnp.int32), picked,
                               sink, scale=float(sm_scale),
                               values=int(values))


# --------------------------------------------------------------------------
# public entry
# --------------------------------------------------------------------------

def paged_decode_attention(q, k_pool, v_pool, page_table, lengths, *,
                           layer: int, sm_scale: Optional[float] = None,
                           work: Optional[PagedWork] = None,
                           values: Optional[int] = None):
    """Single-token attention against ONE layer of a paged KV pool.

    * ``q``: ``[slots, h, 1, d]`` (or ``[slots, h, d]``) — the current
      token's query heads per slot.
    * ``k_pool``/``v_pool``: the WHOLE pool ``[pages, layers, kv_heads,
      page_size, d]``, ``kv_heads`` dividing ``h``.  ``layer`` (a
      python int) picks the layer INSIDE the kernel's block specs: a
      layer's slice handed to a custom call would first be copied out
      of the pool, once a step.  It reaches the kernel as a scalar-
      prefetch operand, so all layers of a step share one trace.
    * ``page_table``: ``[slots, max_pages_per_slot]`` int32 — physical
      page backing each ``page_size`` stretch of the slot's virtual
      window; dead entries may hold any valid page index (they are
      masked by ``lengths``, and the pool's trash page is the
      conventional filler).
    * ``lengths``: ``[slots]`` int32 — live tokens per slot; a slot
      with length 0 emits zeros.
    * ``work``: :func:`paged_work_list` of THIS ``page_table`` and
      ``lengths`` at the pool's page size.  A step whose layers attend
      through one table builds it once and hands it to each; left out,
      it is built here.

    The LATENT form (module docstring): ``v_pool=None``, ``k_pool`` the
    pool ``[pages, layers, d, page_size]`` of rows ``d`` wide like the
    (absorbed) queries, ``values`` how many of a row's leading channels
    are its values; the output is ``[slots, h, (1,) values]`` and
    ``sm_scale`` is the caller's (``d`` is no head size).

    Always the Pallas kernel (interpret mode off-TPU), whatever the
    window: it walks the live pages and nothing else, with no
    materialized gather, scoring bf16 operands with fp32 accumulation
    and an fp32 online softmax.
    """
    squeezed = q.ndim == 3
    if squeezed:
        q = q[:, :, None, :]
    slots, h, q_len, d = q.shape
    if q_len != 1:
        raise ValueError(
            f"paged_decode_attention is the q_len == 1 path, got q_len "
            f"{q_len}; use flash_attention for prefill")
    latent = v_pool is None
    if latent:
        if k_pool.ndim != 4 or k_pool.shape[2] != d or sm_scale is None \
                or values is None or not 0 < values <= d:
            raise ValueError(
                f"the latent form takes the pool [pages, layers, {d}, "
                f"page_size], values in (0, {d}] and sm_scale; got "
                f"pool {tuple(k_pool.shape)}, values {values}, sm_scale "
                f"{sm_scale}")
    elif k_pool.shape != v_pool.shape or k_pool.ndim != 5 \
            or k_pool.shape[4] != d:
        raise ValueError(
            f"k/v must be the whole pool [pages, layers, kv_heads, "
            f"page_size, {d}] and equal-shaped; got k "
            f"{tuple(k_pool.shape)} v {tuple(v_pool.shape)}")
    layers, kvh = k_pool.shape[1], 1 if latent else k_pool.shape[2]
    if not 0 <= layer < layers:
        raise ValueError(
            f"layer {layer} is outside the pool's {layers} layers")
    if kvh == 0 or h % kvh:
        raise ValueError(
            f"kv_heads ({kvh}) must divide query heads ({h})")
    if page_table.ndim != 2 or page_table.shape[0] != slots:
        raise ValueError(
            f"page_table must be [{slots}, max_pages_per_slot], got "
            f"{tuple(page_table.shape)}")
    if lengths.shape != (slots,):
        raise ValueError(
            f"lengths must be [{slots}], got {tuple(lengths.shape)}")
    scale = (d ** -0.5) if sm_scale is None else sm_scale
    if work is None:
        work = paged_work_list(page_table, lengths,
                               page_size=k_pool.shape[3])
    if latent:
        out = _latent_kernel_call(
            q[:, :, 0, :], k_pool, work, jnp.full((1,), layer, jnp.int32),
            scale=float(scale), values=int(values))
    else:
        out = _paged_kernel_call(
            q[:, :, 0, :], k_pool, v_pool, work,
            jnp.full((1,), layer, jnp.int32), scale=float(scale))
    return out if squeezed else out[:, :, None, :]


# --------------------------------------------------------------------------
# verify-slab attention (ISSUE 15): q_len = S against the paged pool
# --------------------------------------------------------------------------

def paged_slab_attention(q, k_pages, v_pages, page_table, lengths, *,
                         sm_scale: Optional[float] = None):
    """Speculative-verify attention against the paged pool: ``S``
    drafted tokens per slot (already appended to the slot's pages at
    positions ``[lengths, lengths + S)``) score the slot's virtual
    window, causally within the slab.

    The q_len = S sibling of :func:`paged_decode_attention`, as an XLA
    gather: the slot's pages (ONE layer's ``[pages, kv_heads,
    page_size, d]`` slice of the pool) gather into the dense
    ``[slots, kv_heads, max_seq, d]`` window (position for position the
    dense cache's view) and
    :func:`~apex_tpu.ops.attention.slab_decode_attention` scores it —
    numerically IDENTICAL to the dense cache's verify path, which is
    what keeps the speculative parity suite bitwise across cache
    layouts.  ``S`` is the engine's static ``spec_k + 1``, so one
    compiled verify step serves every wave.

    Scope note: unlike the q_len = 1 decode, the verify step has ONLY
    this gather lowering today — at very long virtual windows (where
    decode crosses to the Pallas streaming kernel) every verify round
    materializes the full window per layer, which erodes the
    speculation win.  The q_len = S streaming-kernel extension (the
    ``_paged_kernel`` grid with an S-row score block and causal
    masking on the final pages) is the PERF.md round-15 follow-up
    alongside the fused block's weight-tile streaming.
    """
    slots, h, sq, d = q.shape
    if k_pages.shape != v_pages.shape or k_pages.ndim != 4 \
            or k_pages.shape[3] != d:
        raise ValueError(
            f"k/v pages must be [pages, kv_heads, page_size, {d}] and "
            f"equal-shaped; got k {tuple(k_pages.shape)} v "
            f"{tuple(v_pages.shape)}")
    kvh = k_pages.shape[1]
    if kvh == 0 or h % kvh:
        raise ValueError(
            f"kv_heads ({kvh}) must divide query heads ({h})")
    if page_table.ndim != 2 or page_table.shape[0] != slots:
        raise ValueError(
            f"page_table must be [{slots}, max_pages_per_slot], got "
            f"{tuple(page_table.shape)}")
    mpps, ps = page_table.shape[1], k_pages.shape[2]
    page_table = page_table.astype(jnp.int32)

    def window(pages):
        g = jnp.take(pages, page_table, axis=0)
        return jnp.moveaxis(g, 2, 1).reshape(slots, kvh, mpps * ps, d)

    return slab_decode_attention(q, window(k_pages), window(v_pages),
                                 lengths, sm_scale=sm_scale)


# --------------------------------------------------------------------------
# fused transformer-block decode (ISSUE 15 tentpole)
# --------------------------------------------------------------------------
#
# One Pallas kernel per layer covering the decode hot path end to end:
#
#     norm1 -> qkv projection (+RoPE) -> paged attention over the
#     slot's live pages INCLUDING the current token -> output
#     projection -> residual -> [norm2 -> MLP -> residual]
#
# Grid (slots, pages), page table + lengths as scalar prefetch (the
# attention-only kernel above walks a flat list of the live pages; this
# one keeps the fixed grid and skips the dead ones: no cell runs it,
# ROADMAP D6).  The layer's weights ride in
# whole-array VMEM blocks with CONSTANT index maps, so Pallas DMAs each
# weight from HBM once and keeps it resident for every slot and page
# of the grid — the q_len = 1 activations (x, q, the fresh k/v, the
# online-softmax state) never leave VMEM between sublayers.  The
# unfused path round-trips five intermediates per layer through HBM
# (norm1 out, qkv, attention context, attn-out residual, norm2 out);
# here only the block output and the one token's k/v (for the pool
# append that follows) cross the HBM boundary.
#
# The current token's k/v are folded into the online softmax as one
# extra column FROM SCRATCH (the unfused path appends to the pool
# first and reads the row back); the caller appends them after the
# kernel, so the pool write stays the existing one-scatter-per-layer
# program and the kernel needs no aliased outputs.
#
# Numerics: fp32 norm statistics, bf16 operands into the MXU with fp32
# accumulation, fp32 online softmax in the base-2 log domain — the
# same discipline as the attention kernels.  The residual chain stays
# fp32 inside the kernel (the unfused path rounds to bf16 at each
# sublayer boundary), so fused vs unfused parity is tolerance, not
# bitwise; bitwise belongs to the XLA fallback (fusion off == the
# original per-op path, untouched).

_DECODE_FUSION_ENV = "APEX_TPU_DECODE_FUSION"

#: fused-block/unfused crossover in PAGES per slot, used when
#: ``APEX_TPU_DECODE_FUSION=auto``: short virtual windows are dominated
#: by the projections (XLA's fused matvecs are already good there);
#: long windows are where streaming pages through one kernel with the
#: weights resident wins.  PROVISIONAL like every crossover at
#: introduction — override with ``APEX_TPU_FUSION_MIN_PAGES``; bench
#: infer captures stamp the effective value.
_FUSION_MIN_PAGES = 8

_FUSION_MIN_PAGES_ENV = "APEX_TPU_FUSION_MIN_PAGES"


def decode_fusion(override=None) -> str:
    """Effective fused-block decode mode: explicit override >
    ``APEX_TPU_DECODE_FUSION`` env var > ``"0"`` (unfused default).
    ``"0"`` = the per-op XLA path, ``"1"`` = the fused-block kernel,
    ``"auto"`` = fuse when the engine's window is at least
    :func:`fusion_min_pages` pages."""
    val = override if override is not None \
        else (os.environ.get(_DECODE_FUSION_ENV) or "0")
    val = str(val).strip().lower() or "0"
    if val in ("0", "false", "off"):
        return "0"
    if val in ("1", "true", "on"):
        return "1"
    if val == "auto":
        return "auto"
    raise ValueError(
        f"{_DECODE_FUSION_ENV} must be 0, 1 or auto, got {val!r}")


def fusion_min_pages(override=None) -> int:
    """Effective auto-fusion crossover: explicit kwarg override >
    ``APEX_TPU_FUSION_MIN_PAGES`` env var > the provisional default."""
    if override is not None:
        return int(override)
    env = os.environ.get(_FUSION_MIN_PAGES_ENV)
    if env:
        try:
            return int(env)
        except ValueError as e:
            raise ValueError(
                f"{_FUSION_MIN_PAGES_ENV} must be an int, got "
                f"{env!r}") from e
    return _FUSION_MIN_PAGES


#: VMEM one kernel may claim through ``vmem_limit_bytes``.  OBSERVED in the
#: PR 21 bring-up on "TPU v5 lite" (jax 0.9.0, libtpu 0.0.34): Mosaic
#: reports 134217728 B (128 MiB) of VMEM per core, and the fused block
#: at hidden 2048 compiled and matched its twin with a 106.4 MiB limit
#: — with its weights single-buffered; double-buffered they need 2x and
#: cannot fit.  8 MiB under the physical size is left to the compiler.
#: PERF.md, "Bring-up, PR 21".
FUSED_BLOCK_VMEM_LIMIT = 120 * 1024 * 1024

#: room left for Mosaic's own scratch and the kernel's live values (the
#: [1, ffn] fp32 activations, the per-head score rows) on top of the
#: buffers the BlockSpecs name
_VMEM_HEADROOM = 8 * 1024 * 1024


def fused_block_vmem_bytes(kind: str, *, hidden: int, ffn: int, heads: int,
                           kv_heads: int, head_dim: int, page_size: int,
                           itemsize: int, cache_itemsize: int = 2,
                           fuse_mlp: bool = True,
                           partial_out: bool = False) -> int:
    """VMEM the fused block kernel's buffers occupy, from its BlockSpecs:
    the layer's weights ONCE (constant index maps, single-buffered), the
    per-slot activation rows and the k/v page blocks TWICE (pipelined),
    and the fp32 scratch.  ``heads``/``kv_heads``/``ffn`` are the
    per-rank values under tensor parallelism."""
    gpt = kind == "gpt"
    hd, kvd = heads * head_dim, kv_heads * head_dim
    # a [1, n] row block still occupies a full sublane tile
    row = lambda n, size: n * 32 if size < 4 else n * 8 * size  # noqa: E731
    w = hidden * hd + 2 * hidden * kvd + hd * hidden
    rows = [hidden] + ([hidden, hd, kvd, kvd] if gpt else [])
    if gpt and not partial_out:
        rows.append(hidden)                                   # bo
    if fuse_mlp:
        w += (2 if gpt else 3) * hidden * ffn
        rows += [hidden] + ([hidden, ffn, hidden] if gpt else [])
    resident = w * itemsize + sum(row(n, itemsize) for n in rows)
    streamed = (2 * row(hidden, itemsize) + 2 * row(kvd, itemsize)
                + (0 if gpt else 2 * row(head_dim, itemsize))
                + 2 * kv_heads * page_size * head_dim * cache_itemsize)
    lanes = lambda n: -(-n // 128) * 128                      # noqa: E731
    scratch = 4 * (2 * heads * head_dim + 2 * max(kv_heads, 8) * head_dim
                   + heads * lanes(page_size) + 2 * heads * 128)
    return resident + 2 * streamed + scratch


def fused_block_refusal(kind: str, **dims) -> Optional[str]:
    """Why the fused block kernel cannot run at these dims — its buffers
    plus headroom exceed the VMEM the compiler grants — or ``None`` when
    it fits.  The string names the observed limit."""
    need = fused_block_vmem_bytes(kind, **dims) + _VMEM_HEADROOM
    if need <= FUSED_BLOCK_VMEM_LIMIT:
        return None
    return (f"fused-block decode at hidden {dims['hidden']} (ffn "
            f"{dims['ffn']}, {dims['heads']} heads x {dims['head_dim']}) "
            f"needs {need / 2**20:.1f} MiB of VMEM for one layer's "
            f"resident weights, pages and scratch; the compiler grants a "
            f"kernel at most {FUSED_BLOCK_VMEM_LIMIT / 2**20:.0f} MiB")


def resolve_decode_fusion(mode=None, *, paged: bool,
                          max_pages: Optional[int] = None,
                          min_pages: Optional[int] = None,
                          dims: Optional[dict] = None) -> bool:
    """Engine-side dispatch: does THIS engine run the fused-block
    decode kernel?  The fused kernel streams the slot's pages via the
    page table, so it rides the paged cache only — ``mode="1"`` on a
    dense engine is a configuration error, while ``"auto"`` quietly
    resolves to the (only available) unfused path.

    ``dims`` (``kind`` plus the :func:`fused_block_vmem_bytes` keywords)
    lets the width be checked against the VMEM the compiler grants when
    the engine is BUILT: ``"1"`` at a width that does not fit raises
    with the limit in the message instead of failing inside Mosaic on
    the first decode; ``"auto"`` resolves to the unfused path there."""
    mode = decode_fusion(mode)
    if mode == "0":
        return False
    if not paged:
        if mode == "1":
            raise ValueError(
                "fused-block decode streams the slot's KV pages via "
                "the page table (APEX_TPU_DECODE_FUSION=1 needs a "
                "paged engine); this engine runs the dense slot cache")
        return False
    refusal = fused_block_refusal(**dims) if dims else None
    if mode == "1":
        if refusal:
            raise ValueError(
                f"{refusal} — serve this width with "
                f"{_DECODE_FUSION_ENV}=0 (the per-op decode) or shard "
                f"the layer over more tensor-parallel ranks")
        return True
    return (not refusal
            and int(max_pages or 0) >= fusion_min_pages(min_pages))


def _fused_block_kernel(kind, scale, kvh, group, ps, mpps, hidden, d,
                        eps, fuse_mlp, partial_out, *refs):
    gpt = kind == "gpt"
    h = kvh * group
    f32 = jnp.float32
    it = iter(refs)
    pt_ref, len_ref = next(it), next(it)
    x_ref = next(it)
    cos_ref = sin_ref = None
    if not gpt:
        cos_ref, sin_ref = next(it), next(it)
    ln1_w = next(it)
    ln1_b = next(it) if gpt else None
    wq = next(it)
    bq = next(it) if gpt else None
    wk = next(it)
    bk = next(it) if gpt else None
    wv = next(it)
    bv = next(it) if gpt else None
    k_ref, v_ref = next(it), next(it)
    wo = next(it)
    bo = next(it) if gpt and not partial_out else None
    ln2_w = ln2_b = wg = wu = bu = wd = bd = None
    if fuse_mlp:
        ln2_w = next(it)
        ln2_b = next(it) if gpt else None
        if not gpt:
            wg = next(it)
        wu = next(it)
        bu = next(it) if gpt else None
        wd = next(it)
        bd = next(it) if gpt else None
    o_ref, kt_ref, vt_ref = next(it), next(it), next(it)
    q_scr, kn_scr, vn_scr, s_scr, m_scr, l_scr, acc_scr = it

    sid = pl.program_id(0)
    p = pl.program_id(1)

    def norm(x, w_ref, b_ref):
        w = w_ref[...].astype(f32)
        if gpt:
            mu = jnp.mean(x, axis=1, keepdims=True)
            var = jnp.mean((x - mu) ** 2, axis=1, keepdims=True)
            return (x - mu) * jax.lax.rsqrt(var + eps) * w \
                + b_ref[...].astype(f32)
        ms = jnp.mean(x * x, axis=1, keepdims=True)
        return x * jax.lax.rsqrt(ms + eps) * w

    def matmul(x2d, w_ref, b_ref):
        y = jax.lax.dot(x2d.astype(w_ref.dtype), w_ref[...],
                        preferred_element_type=f32)
        if b_ref is not None:
            y = y + b_ref[...].astype(f32)
        return y

    @pl.when(p == 0)
    def _project():
        # norm1 + the three projections run ONCE per slot; everything
        # they produce stays in VMEM scratch across the page loop
        xv = x_ref[0].astype(f32)                        # [1, hidden]
        h1 = norm(xv, ln1_w, ln1_b)
        qh = matmul(h1, wq, bq).reshape(h, d)
        kh = matmul(h1, wk, bk).reshape(kvh, d)
        vh = matmul(h1, wv, bv).reshape(kvh, d)
        if not gpt:
            cos = cos_ref[0].astype(f32)                 # [1, d]
            sin = sin_ref[0].astype(f32)

            def rot(t):
                t1, t2 = jnp.split(t, 2, axis=-1)
                return jnp.concatenate((-t2, t1), axis=-1)

            qh = qh * cos + rot(qh) * sin
            kh = kh * cos + rot(kh) * sin
        q_scr[...] = qh
        kn_scr[...] = kh
        vn_scr[...] = vh
        kt_ref[0] = kh.reshape(1, kvh * d).astype(kt_ref.dtype)
        vt_ref[0] = vh.reshape(1, kvh * d).astype(vt_ref.dtype)
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[sid]
    live_pages = (length + ps - 1) // ps

    @pl.when(p < live_pages)
    def _pages():
        # the attention-only paged kernel's page loop, with q from the
        # in-VMEM projection instead of an HBM operand
        for i in range(kvh):
            seg = slice(i * group, (i + 1) * group)
            s_scr[seg, :] = jax.lax.dot_general(
                q_scr[seg, :].astype(k_ref.dtype), k_ref[0, i],
                (((1,), (1,)), ((), ())),
                preferred_element_type=f32) * (scale * _LOG2E)
        cols = p * ps + jax.lax.broadcasted_iota(jnp.int32, (h, ps), 1)
        s = jnp.where(cols < length, s_scr[...], _NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp2(m_prev - m_new)
        pmat = jnp.exp2(s - m_new)
        l_scr[...] = l_scr[...] * alpha + \
            jnp.sum(pmat, axis=1, keepdims=True)
        for i in range(kvh):
            seg = slice(i * group, (i + 1) * group)
            acc_scr[seg, :] = acc_scr[seg, :] * alpha[seg] + jax.lax.dot(
                pmat[seg, :].astype(v_ref.dtype), v_ref[0, i],
                preferred_element_type=f32)
        m_scr[...] = m_new

    @pl.when(p == mpps - 1)
    def _finish():
        # fold the CURRENT token as one extra online-softmax column
        # (the unfused path appends it to the pool first and reads the
        # row back; live = length + 1 either way), then run the whole
        # back half of the block on the VMEM-resident context
        q_ = q_scr[...]
        kn = kn_scr[...]
        s_new = jnp.sum(q_.reshape(kvh, group, d) * kn[:, None, :],
                        axis=-1).reshape(h, 1) * (scale * _LOG2E)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s_new)
        alpha = jnp.exp2(m_prev - m_new)
        p_new = jnp.exp2(s_new - m_new)                  # [h, 1]
        l = l_scr[...] * alpha + p_new
        vb = jnp.broadcast_to(vn_scr[...][:, None, :],
                              (kvh, group, d)).reshape(h, d)
        acc = acc_scr[...] * alpha + p_new * vb
        ctx = acc / l            # the current token is always live: l > 0
        attn = matmul(ctx.reshape(1, h * d), wo, bo)
        if partial_out:
            # tensor-parallel shard (ISSUE 17): emit the RANK-PARTIAL
            # out-proj row product — no residual, no bias.  The caller
            # psums at the row boundary, adds ``bo`` once, and runs
            # norm2 + the col/row MLP outside the kernel.
            o_ref[0] = attn.astype(o_ref.dtype)
            return
        x2 = x_ref[0].astype(f32) + attn                 # [1, hidden]
        if fuse_mlp:
            h2 = norm(x2, ln2_w, ln2_b)
            if gpt:
                u = jax.nn.gelu(matmul(h2, wu, bu))
                y = x2 + matmul(u, wd, bd)
            else:
                g = matmul(h2, wg, None)
                u = matmul(h2, wu, None)
                y = x2 + matmul(jax.nn.silu(g) * u, wd, None)
        else:
            y = x2
        o_ref[0] = y.astype(o_ref.dtype)


def fused_block_decode(x, blk, k_pages, v_pages, page_table, lengths, *,
                       kind: str, eps: float, cos=None, sin=None,
                       sm_scale: Optional[float] = None,
                       fuse_mlp: bool = True,
                       partial_out: bool = False):
    """One fused transformer-block decode step against the paged pool.

    * ``x``: ``[slots, hidden]`` — the block's input activations (the
      residual stream), one token per slot.
    * ``blk``: the layer's weights in the FUSED layout
      (:func:`apex_tpu.inference.models.fused_layer_params` builds it
      once at engine construction): matmul-ready ``[in, out]`` arrays
      ``wq [hidden, h*d]`` / ``wk``/``wv [hidden, kv_heads*d]`` /
      ``wo [h*d, hidden]`` (+ GPT biases ``bq/bk/bv/bo`` as ``[1, n]``
      rows and LayerNorm ``ln1_w/ln1_b``; LLaMA carries RMSNorm
      ``ln1_w`` only), plus — under ``fuse_mlp`` — the MLP half
      (``ln2_*``, GPT ``wu/bu/wd/bd``, LLaMA ``wg/wu/wd``).
    * ``k_pages``/``v_pages``: ONE layer's ``[pages, kv_heads,
      page_size, d]`` slice of the pool; ``page_table``/``lengths`` as
      in :func:`paged_decode_attention`.
    * ``cos``/``sin``: ``[slots, d]`` RoPE rows at each slot's current
      position (LLaMA only).

    Returns ``(y [slots, hidden], k_tok [slots, kv_heads, d], v_tok)``
    — the block output plus the current token's k/v for the caller's
    one-scatter-per-layer pool append (``kv_cache.append_layer``).
    Always the Pallas kernel (interpret mode off-TPU); the engine-level
    XLA fallback is the original unfused per-op path, selected by
    ``APEX_TPU_DECODE_FUSION`` / the ``auto`` crossover
    (:func:`resolve_decode_fusion`).

    ``partial_out`` (ISSUE 17, tensor-parallel serving): ``blk`` is a
    rank's 1/tp shard (heads/kvh column-split, ``wo`` row-split exactly
    as ``pallas_audit --mesh`` prices it) and ``y`` is the RANK-PARTIAL
    out-proj product — no residual, no out-proj bias.  The out-proj
    psum moves OUTSIDE the kernel: the caller reduces over the tensor
    axis, adds ``bo`` once, and finishes norm2 + the MLP with its own
    row-boundary psum.  Requires ``fuse_mlp=False`` (the MLP cannot
    fuse across the row reduction).
    """
    if kind not in ("gpt", "llama"):
        raise ValueError(f"unknown block kind {kind!r}")
    if partial_out and fuse_mlp:
        raise ValueError(
            "partial_out emits the pre-psum attention shard; the MLP "
            "runs after the row-boundary reduction (fuse_mlp=False)")
    gpt = kind == "gpt"
    slots, hidden = x.shape
    if k_pages.shape != v_pages.shape or k_pages.ndim != 4:
        raise ValueError(
            f"k/v pages must be [pages, kv_heads, page_size, d] and "
            f"equal-shaped; got k {tuple(k_pages.shape)} v "
            f"{tuple(v_pages.shape)}")
    _, kvh, ps, d = k_pages.shape
    hd = blk["wq"].shape[1]
    if hd % d:
        raise ValueError(
            f"wq width {hd} must be a multiple of head_dim {d}")
    h = hd // d
    if h % kvh:
        raise ValueError(
            f"kv_heads ({kvh}) must divide query heads ({h})")
    group = h // kvh
    mpps = page_table.shape[1]
    if page_table.shape[0] != slots or lengths.shape != (slots,):
        raise ValueError(
            f"page_table/lengths must cover {slots} slots; got "
            f"{tuple(page_table.shape)} / {tuple(lengths.shape)}")
    if (not gpt) and (cos is None or sin is None):
        raise ValueError("llama fused block needs cos/sin RoPE rows")
    scale = (d ** -0.5) if sm_scale is None else sm_scale
    page_table = page_table.astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)

    const = lambda s, p, pt, ln: (0, 0)                  # noqa: E731
    # per-slot rows travel as [slots, 1, n] so the block's last two dims
    # are the array's own: Mosaic rejects a (1, n) block of a
    # [slots, n] array (1 is neither 8-divisible nor the full dim)
    slot = lambda s, p, pt, ln: (s, 0, 0)                # noqa: E731

    def page_index(s, p, pt, ln):
        last = jnp.maximum((ln[s] + ps - 1) // ps - 1, 0)
        return (pt[s, jnp.minimum(p, last)], 0, 0, 0)

    def wspec(a):
        # constant index map: fetched once, so a second pipeline buffer
        # would only double the layer's VMEM footprint
        return pl.BlockSpec(a.shape, const, pipeline_mode=pl.Buffered(1))

    operands = [x[:, None, :]]
    in_specs = [pl.BlockSpec((1, 1, hidden), slot)]

    def add_w(*names):
        for n in names:
            operands.append(blk[n])
            in_specs.append(wspec(blk[n]))

    if not gpt:
        operands.extend([cos[:, None, :], sin[:, None, :]])
        in_specs.extend([pl.BlockSpec((1, 1, d), slot)] * 2)
        add_w("ln1_w", "wq", "wk", "wv")
    else:
        add_w("ln1_w", "ln1_b", "wq", "bq", "wk", "bk", "wv", "bv")
    operands.extend([k_pages, v_pages])
    in_specs.extend([pl.BlockSpec((1, kvh, ps, d), page_index)] * 2)
    add_w(*(("wo", "bo") if gpt and not partial_out else ("wo",)))
    if fuse_mlp:
        if gpt:
            add_w("ln2_w", "ln2_b", "wu", "bu", "wd", "bd")
        else:
            add_w("ln2_w", "wg", "wu", "wd")

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(slots, mpps),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, hidden), slot),
            pl.BlockSpec((1, 1, kvh * d), slot),
            pl.BlockSpec((1, 1, kvh * d), slot),
        ],
        scratch_shapes=[
            pltpu.VMEM((h, d), jnp.float32),      # q (RoPE'd, unscaled)
            pltpu.VMEM((kvh, d), jnp.float32),    # fresh k
            pltpu.VMEM((kvh, d), jnp.float32),    # fresh v
            pltpu.VMEM((h, ps), jnp.float32),     # score block
            pltpu.VMEM((h, 1), jnp.float32),      # running max (base 2)
            pltpu.VMEM((h, 1), jnp.float32),      # running normalizer
            pltpu.VMEM((h, d), jnp.float32),      # fp32 output accum
        ],
    )
    kernel = functools.partial(_fused_block_kernel, kind, scale, kvh,
                               group, ps, mpps, hidden, d, eps, fuse_mlp,
                               partial_out)
    # price the kernel against what it will actually be granted: without
    # a limit Mosaic scopes it to 16 MiB, far under one layer's weights
    vmem_limit = min(FUSED_BLOCK_VMEM_LIMIT, _VMEM_HEADROOM +
                     fused_block_vmem_bytes(
                         kind, hidden=hidden,
                         ffn=blk["wu"].shape[1] if fuse_mlp else 0,
                         heads=h, kv_heads=kvh, head_dim=d, page_size=ps,
                         itemsize=blk["wq"].dtype.itemsize,
                         cache_itemsize=k_pages.dtype.itemsize,
                         fuse_mlp=fuse_mlp, partial_out=partial_out))
    y, kt, vt = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((slots, 1, hidden), x.dtype),
            jax.ShapeDtypeStruct((slots, 1, kvh * d), x.dtype),
            jax.ShapeDtypeStruct((slots, 1, kvh * d), x.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit),
        interpret=interpret_mode(),
        name="apex_fused_block_decode",
    )(page_table, lengths, *operands)
    return (y[:, 0], kt.reshape(slots, kvh, d),
            vt.reshape(slots, kvh, d))
