"""KV caches: statically shaped, donated pure updates.

Two cache layouts share one mutation API (``insert*`` / ``append_layer``
/ ``advance`` / ``evict``), both the serving-side analog of the flat
optimizer master (ISSUE 2/3) — allocated once at engine construction,
carried through the jitted prefill/decode executables, donated every
step:

* :class:`KVCache` — the dense slot cache (ISSUE 4)::

      k, v : [slots, layers, kv_heads, max_seq, head_dim]

  One contiguous ``max_seq`` window per slot: simple, but a single
  128K-context straggler pins ``max_seq`` worth of HBM for EVERY slot.

* :class:`PagedKVCache` — the ragged paged pool (ISSUE 6, after
  PAPERS.md "Ragged Paged Attention")::

      k, v       : [pages, layers, kv_heads, page_size, head_dim]
      page_table : [slots, max_pages_per_slot]  int32
      lengths    : [slots]  int32   live tokens per slot
      capacity   : [slots]  int32   page_size * pages owned by the slot
      last_tokens: [slots]  int32   the next decode step's input tokens

  A slot's tokens live in whichever fixed-size pages the host-side
  :class:`PageAllocator` handed it; the page table (a small int32
  array, a *traced operand* like the lengths) maps virtual position
  ``t`` to physical page ``page_table[slot, t // page_size]``.  HBM is
  bounded by the POOL, not by ``slots * max_seq`` — concurrency scales
  with the mean sequence, not the straggler.

Shared design positions:

* **Slots, not sequences.**  A slot is a fixed request lane; the
  host-side scheduler maps live requests onto slots (and, paged, onto
  pages) between device steps, so admitting/retiring requests never
  changes a device shape — the decode executable compiles once.
* **GQA/MQA-aware.**  Both caches store ``kv_heads`` (not query
  heads): k/v are cached pre-broadcast, the group broadcast happens
  inside the grouped attention ops.
* **Pure donated updates.**  Every mutation is a
  ``lax.dynamic_update_slice`` returning ``cache.replace(...)`` —
  donation-safe and scan-carryable exactly like ``FlatState``.  Page
  indices come from the traced page table, so one compiled
  insert/append serves every page assignment.
* **Eviction is metadata.**  Retiring a request zeroes the slot's
  length (and, paged, its capacity); the stale k/v rows are dead
  weight masked out by the length.  No data movement on the retire
  path — the host allocator reclaims the page IDs.
* **Two kinds of state in one manager (ISSUE 30).**  A model that mixes
  full-attention layers with sliding-window layers keeps them in TWO
  pools under the one :class:`PagedKVCache`: ``k``/``v`` hold the FULL
  layers only (pages from the :class:`PageAllocator`, growing with the
  context), ``wk``/``wv`` the WINDOW layers — per slot a fixed ring of
  ``ceil(window / page_size) + 1`` pages in which position ``t`` lives
  at ring row ``t % ring``: no allocator traffic, no growth with the
  context, nothing to free at eviction (a stale row is masked by its
  position).  Lengths, capacity and the page table are shared.  Models
  without window layers leave ``wk``/``wv`` ``None`` and keep the one
  pool, op for op.  A model whose window layers attend in LATENT form
  keeps one row a position there too, of the window layers' own width
  (``init_paged_cache(..., window_latent=width)``)::

      wk : [window_layers, slots, ring, window_latent]      wv : None

  beside a latent pool of another width: the rows of two latent
  geometries under one manager.
* **A latent pool (ISSUE 34).**  A model with latent attention caches ONE
  row a position a layer — the normed latent beside the roped key
  channels every head shares — and no per-head keys or values.  Its pool
  under the same :class:`PagedKVCache` is ONE array with no KV-head
  axis::

      k : [pages, layers, latent_width, page_size]      v : None

  (``init_paged_cache(..., latent=width)``); the values ARE the row's
  leading channels, so there is no second array — not a zero-sized one,
  not a copy.  A page holds its positions along the MINOR axis: a row
  576 wide is 4.5 lane tiles, and the v5e's runtime lays a ``[...,
  page_size, 576]`` array out transposed, so that the kernel, which
  needs it row-major, was handed a pool-sized copy every layer
  (PERF.md section 6, PR 34); ``[..., 576, page_size]`` is row-major as
  it stands, and both of the kernel's products take it as it is.  Page
  table, lengths, capacity, allocator and every mutator are the paged
  pool's own: a prefill inserts ``[layers, s, width]`` rows, a decode
  step appends ``[slots, width]`` a layer.
* **An index-key pool beside the K/V pool (ISSUE 36).**  A model whose
  layers SELECT the positions they attend by a learned index caches a
  third kind of per-position state: one small index key a position a
  layer, in a pool array of its own beside ``k`` and ``v`` ::

      ik : [pages, layers, index_width, page_size]

  (``init_paged_cache(..., index=width)``; a page's positions on the
  minor axis, as the latent pool's are: the index kernel's product takes
  the block as it stands).  It lives under the SAME page table, lengths,
  capacity and :class:`PageAllocator` — one reservation a request covers
  all three arrays — and every mutator treats a page as all its arrays:
  a prefill inserts the prompt's index keys with its k/v, a decode step
  appends one a slot a layer, :func:`cow_page`, :func:`extract_pages` and
  :func:`restore_pages` move the page's index keys with the rest.  A model
  without an indexer holds no such array (``ik`` is ``None``, not a
  zero-sized array).  A model whose later layers REUSE the picks of an
  earlier one keeps keys for its picking layers only: ``ik``
  then has fewer layers than ``k`` (``init_paged_cache(...,
  index_layers=n)``), a prefill inserts ``[index_layers, s, width]`` keys,
  and a decode step appends a layer's rows with :func:`append_layer` and
  its key, where it has one, with :func:`append_index`.
* **The trash page.**  The paged pool carries ONE sacrificial page at
  index ``pages - 1`` that the allocator never hands out; page-table
  entries beyond a slot's reservation point there, so the statically
  shaped prefill/append writes that overrun a reservation land
  harmlessly instead of corrupting another slot's pages.
"""
from __future__ import annotations

import os
from typing import List, Optional, Sequence

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.transformer.parallel_state import TENSOR_AXIS

__all__ = ["KVCache", "init_cache", "PagedKVCache", "init_paged_cache",
           "insert_window", "append_window", "window_pages_live",
           "ring_rows",
           "PageAllocator", "HostPageStore", "default_page_size",
           "default_swap_batch_pages", "insert_tokens", "cow_page",
           "extract_pages", "restore_pages", "append_slab",
           "advance_by", "set_lengths", "feed_back",
           "paged_cache_partition_specs"]

_PAGE_SIZE_ENV = "APEX_TPU_PAGE_SIZE"
_DEFAULT_PAGE_SIZE = 64
_SWAP_BATCH_ENV = "APEX_TPU_SWAP_BATCH_PAGES"
_DEFAULT_SWAP_BATCH = 8


def default_page_size() -> int:
    """Engine-default KV page size: ``APEX_TPU_PAGE_SIZE`` env var >
    the built-in 64 (a power of two <= the smallest prefill bucket, so
    buckets always tile exactly into pages)."""
    env = os.environ.get(_PAGE_SIZE_ENV)
    if env:
        try:
            val = int(env)
        except ValueError as e:
            raise ValueError(
                f"{_PAGE_SIZE_ENV} must be an int, got {env!r}") from e
        if val < 1 or (val & (val - 1)):
            raise ValueError(
                f"{_PAGE_SIZE_ENV} must be a positive power of two, "
                f"got {val}")
        return val
    return _DEFAULT_PAGE_SIZE


def default_swap_batch_pages() -> int:
    """Pages moved per host-tier swap dispatch (ISSUE 18):
    ``APEX_TPU_SWAP_BATCH_PAGES`` env var > the built-in 8.  The batch
    width is a STATIC operand dimension of the two swap copy programs
    (:func:`extract_pages` / :func:`restore_pages`): page-ID vectors
    are padded host-side to this width, so one compiled program per
    direction serves every page count — the zero-recompile guarantee
    every other serving-path program already gives."""
    env = os.environ.get(_SWAP_BATCH_ENV)
    if env:
        try:
            val = int(env)
        except ValueError as e:
            raise ValueError(
                f"{_SWAP_BATCH_ENV} must be an int, got {env!r}") from e
        if val < 1:
            raise ValueError(
                f"{_SWAP_BATCH_ENV} must be >= 1, got {val}")
        return val
    return _DEFAULT_SWAP_BATCH


@flax.struct.dataclass
class KVCache:
    """Static-shape slot cache (see the module docstring for layout)."""
    k: jax.Array          # [slots, layers, kv_heads, max_seq, head_dim]
    v: jax.Array          # same shape/dtype as k
    lengths: jax.Array    # [slots] int32: live tokens per slot
    # [slots] int32: the token each slot's NEXT decode step takes as its
    # input (ISSUE 37) — written by the step that sampled it, never by
    # the host (:func:`feed_back`)
    last_tokens: jax.Array

    @property
    def slots(self) -> int:
        return self.k.shape[0]

    @property
    def layers(self) -> int:
        return self.k.shape[1]

    @property
    def kv_heads(self) -> int:
        return self.k.shape[2]

    @property
    def max_seq(self) -> int:
        return self.k.shape[3]

    @property
    def head_dim(self) -> int:
        return self.k.shape[4]


def init_cache(slots: int, layers: int, kv_heads: int, max_seq: int,
               head_dim: int, dtype=jnp.bfloat16) -> KVCache:
    """Allocate an empty cache (every slot free, length 0)."""
    shape = (slots, layers, kv_heads, max_seq, head_dim)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                   lengths=jnp.zeros((slots,), jnp.int32),
                   last_tokens=jnp.zeros((slots,), jnp.int32))


def insert(cache: KVCache, slot, k, v, length) -> KVCache:
    """Prefill write: park a prompt's k/v into one slot.

    ``k``/``v``: ``[layers, kv_heads, s, head_dim]`` with ``s`` the
    (possibly bucket-padded) prompt length, ``s <= max_seq``; ``length``
    is the number of REAL tokens (padding rows beyond it are stored but
    masked by the length everywhere they could be read).  ``slot`` and
    ``length`` may be traced — one compiled insert serves every slot.
    """
    s = k.shape[2]
    if k.shape != v.shape or k.shape[:2] != (cache.layers, cache.kv_heads) \
            or k.shape[3] != cache.head_dim:
        raise ValueError(
            f"prefill k/v must be [layers={cache.layers}, "
            f"kv_heads={cache.kv_heads}, s, head_dim={cache.head_dim}], "
            f"got k {tuple(k.shape)} v {tuple(v.shape)}")
    if s > cache.max_seq:
        raise ValueError(
            f"prompt length {s} exceeds cache max_seq {cache.max_seq}")
    slot = jnp.asarray(slot, jnp.int32)
    zero = jnp.int32(0)
    start = (slot, zero, zero, zero, zero)
    new_k = jax.lax.dynamic_update_slice(
        cache.k, k[None].astype(cache.k.dtype), start)
    new_v = jax.lax.dynamic_update_slice(
        cache.v, v[None].astype(cache.v.dtype), start)
    new_len = jax.lax.dynamic_update_slice(
        cache.lengths, jnp.asarray(length, jnp.int32)[None], (slot,))
    return cache.replace(k=new_k, v=new_v, lengths=new_len)


def append_layer(cache, layer: int, k_tok, v_tok, ik_tok=None):
    """Decode write for ONE layer: each slot's token row lands at that
    slot's current length.

    ``k_tok``/``v_tok``: ``[slots, kv_heads, head_dim]`` — the new
    token's k/v per slot.  ``layer`` is static (the decode forward is an
    unrolled python loop over layers).  Lengths do NOT advance here —
    call :func:`advance` once after the last layer so every layer of a
    decode step writes to the same position.  Dispatches on the cache
    layout: dense slot cache or paged pool.  ``ik_tok`` ``[slots,
    index_width]``: the token's index key a slot, for a paged cache with
    an index-key pool of every layer (and only for one); a pool that keeps
    some layers' keys takes them by :func:`append_index`.
    """
    paged = isinstance(cache, PagedKVCache)
    want = (cache.slots, *(cache.row_shape if paged else
                           (cache.kv_heads, cache.head_dim)))
    if k_tok.shape != want:
        raise ValueError(
            f"token k/v must be [slots={cache.slots}, "
            f"kv_heads={cache.kv_heads}, head_dim={cache.head_dim}] (a "
            f"latent pool: [slots, width]), got {tuple(k_tok.shape)}")
    if paged:
        if _some_layers_indexed(cache):
            if ik_tok is not None:
                raise ValueError(
                    "this pool keeps the index keys of some layers only: "
                    "append a layer's key with append_index")
            return _append_layer_paged(cache.replace(ik=None), layer, k_tok,
                                       v_tok).replace(ik=cache.ik)
        if cache.ik is not None:
            _check_index(ik_tok, (cache.slots, cache.ik.shape[2]),
                         "token k/v")
        return _append_layer_paged(cache, layer, k_tok, v_tok, ik_tok)
    if ik_tok is not None:
        raise ValueError("the dense slot cache has no index-key pool")

    def write(buf, tok, pos):
        # buf [kv_heads, max_seq, d], tok [kv_heads, d]: one token row
        # at this slot's own position
        return jax.lax.dynamic_update_slice(
            buf, tok[:, None, :].astype(buf.dtype),
            (jnp.int32(0), pos, jnp.int32(0)))

    upd = jax.vmap(write)
    new_k = cache.k.at[:, layer].set(
        upd(cache.k[:, layer], k_tok, cache.lengths))
    new_v = cache.v.at[:, layer].set(
        upd(cache.v[:, layer], v_tok, cache.lengths))
    return cache.replace(k=new_k, v=new_v)


def _some_layers_indexed(cache) -> bool:
    """Does the paged pool keep index keys for fewer layers than k/v?"""
    return cache.ik is not None and cache.ik.shape[1] != cache.k.shape[1]


def append_index(cache, index_layer: int, ik_tok):
    """Decode write of ONE index key a slot, ``ik_tok [slots,
    index_width]``, into layer ``index_layer`` of a pool that keeps the
    keys of some layers only, at each slot's current length —
    :func:`append_layer`'s write on the index-key pool alone."""
    if not isinstance(cache, PagedKVCache) or cache.ik is None:
        raise ValueError("append_index needs a paged cache with an "
                         "index-key pool")
    _check_index(ik_tok, (cache.slots, cache.ik.shape[2]), "token index key")
    k_only = cache.replace(k=cache.ik, v=None, ik=None)
    return cache.replace(ik=_append_layer_paged(k_only, index_layer,
                                                ik_tok, None).k)


def append_slab(cache, layer: int, k_slab, v_slab):
    """Speculative-verify write for ONE layer (ISSUE 15): each slot's
    ``S`` drafted-token rows land at that slot's positions
    ``[lengths, lengths + S)``.

    ``k_slab``/``v_slab``: ``[slots, kv_heads, S, head_dim]`` — the
    whole verify slab's k/v per slot.  ``S = 1`` is exactly
    :func:`append_layer`'s write.  Lengths do NOT advance here — the
    verify step advances by the ACCEPTED count once after the last
    layer (:func:`advance_by`), which is what makes rejection a length
    rollback: rows past the accepted length are dead-by-mask and the
    next append overwrites them.  Rows past a slot's virtual window
    are DROPPED (paged: an out-of-bounds page sentinel; dense: an
    out-of-bounds position), never clamped onto live rows — the same
    bounded-damage discipline as :func:`insert_tokens`.
    """
    slots, kvh, s, d = k_slab.shape
    if k_slab.shape != v_slab.shape or slots != cache.slots \
            or kvh != cache.kv_heads or d != cache.head_dim:
        raise ValueError(
            f"slab k/v must be [slots={cache.slots}, "
            f"kv_heads={cache.kv_heads}, S, head_dim={cache.head_dim}] "
            f"and equal-shaped; got k {tuple(k_slab.shape)} v "
            f"{tuple(v_slab.shape)}")
    pos = cache.lengths[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
    # [slots, S, kv_heads, d]: advanced indices lead, interior follow
    rows_k = jnp.moveaxis(k_slab, 2, 1).astype(cache.k.dtype)
    rows_v = jnp.moveaxis(v_slab, 2, 1).astype(cache.v.dtype)
    if isinstance(cache, PagedKVCache):
        ps, mpps = cache.page_size, cache.max_pages_per_slot
        ordinal = jnp.minimum(pos // ps, jnp.int32(mpps - 1))
        pages = jnp.take_along_axis(cache.page_table, ordinal, axis=1)
        # past the virtual window: OOB page sentinel -> mode="drop"
        # discards the row (clamping would clobber the last live token)
        pages = jnp.where(pos < jnp.int32(mpps * ps), pages,
                          jnp.int32(cache.pages))
        offs = jnp.minimum(pos - ordinal * ps, jnp.int32(ps - 1))
        new_k = cache.k.at[pages, layer, :, offs, :].set(rows_k,
                                                         mode="drop")
        new_v = cache.v.at[pages, layer, :, offs, :].set(rows_v,
                                                         mode="drop")
        return cache.replace(k=new_k, v=new_v)
    sid = jnp.arange(slots, dtype=jnp.int32)[:, None]
    # past max_seq: OOB position -> dropped (dynamic_update_slice would
    # clamp the whole slab backwards over live rows instead)
    posd = jnp.where(pos < jnp.int32(cache.max_seq), pos,
                     jnp.int32(cache.max_seq))
    new_k = cache.k.at[sid, layer, :, posd, :].set(rows_k, mode="drop")
    new_v = cache.v.at[sid, layer, :, posd, :].set(rows_v, mode="drop")
    return cache.replace(k=new_k, v=new_v)


def advance_by(cache, active, delta):
    """Advance the active slots' lengths by a PER-SLOT count — the
    speculative verify step's accept/rollback in one move (ISSUE 15):
    ``delta[slot]`` is the number of tokens the slot confirmed
    (accepted drafts + the bonus token), so rows appended beyond
    ``lengths + delta`` — the rejected tail of the slab — fall back to
    dead-by-mask without any data movement.  Returns
    ``(cache, truncated)`` with the same clamp/flag semantics as
    :func:`advance` (``delta = 1`` everywhere is exactly ``advance``):
    lengths clamp at capacity and ``truncated`` flags active slots
    whose confirmed tokens could not all be appended."""
    act = jnp.asarray(active)
    delta = jnp.asarray(delta, jnp.int32)
    cap = (cache.capacity if isinstance(cache, PagedKVCache)
           else jnp.int32(cache.max_seq))
    want = cache.lengths + act.astype(jnp.int32) * delta
    truncated = act.astype(bool) & (want > cap) & (cap > 0)
    return cache.replace(lengths=jnp.minimum(want, cap)), truncated


def set_lengths(cache, new_lengths):
    """Directly set every slot's length (clamped to capacity) — the
    host-driven rollback primitive a DRAFT engine needs (ISSUE 15):
    after the target verifies, the drafter rolls its own cache back to
    the pre-draft lengths so only CONFIRMED tokens ever stay resident.
    Rows beyond the restored length are dead-by-mask, exactly like a
    retired slot's rows."""
    new_lengths = jnp.asarray(new_lengths, jnp.int32)
    cap = (cache.capacity if isinstance(cache, PagedKVCache)
           else jnp.int32(cache.max_seq))
    return cache.replace(lengths=jnp.clip(new_lengths, 0, cap))


def advance(cache, active):
    """Advance the active slots' lengths by the one token the decode
    step just appended; inactive slots stay put (their garbage write at
    position ``length`` stays dead).  Returns ``(cache, truncated)``.

    Lengths clamp at capacity (``max_seq`` dense, the slot's owned
    pages paged): a slot decoded past capacity stops growing instead of
    walking its length off the buffer.  ``truncated`` is a ``[slots]``
    bool vector — True where an active slot was ALREADY at capacity, so
    the token this step emitted for it could not be appended and its
    stream is no longer extendable.  The silent clamp was ISSUE 6's
    surfaced bug: callers (the scheduler) must retire truncated slots
    and record why instead of dropping tokens on the floor."""
    act = jnp.asarray(active)
    cap = (cache.capacity if isinstance(cache, PagedKVCache)
           else jnp.int32(cache.max_seq))
    # cap > 0 gates the flag: a never-admitted paged slot (capacity 0)
    # marked active is empty, not a truncated stream
    truncated = act.astype(bool) & (cache.lengths >= cap) & (cap > 0)
    new_len = jnp.minimum(cache.lengths + act.astype(jnp.int32), cap)
    return cache.replace(lengths=new_len), truncated


def feed_back(cache, tokens, where):
    """The tokens a step sampled become the next decode step's input, on
    the device (ISSUE 37): ``where`` is a decode step's ``active [slots]``
    mask beside its ``tokens [slots]``, or a prefill's traced ``slot``
    beside its one token.  The host never uploads a token it was sent,
    so the next step can be launched before this one's vector is read."""
    tokens = jnp.asarray(tokens, jnp.int32)
    if tokens.ndim:
        new = jnp.where(jnp.asarray(where, bool), tokens,
                        cache.last_tokens)
    else:
        new = jax.lax.dynamic_update_slice(cache.last_tokens,
                                           tokens[None], (where,))
    return cache.replace(last_tokens=new)


def evict(cache, slot):
    """Retire a slot: zero its length (and, paged, its capacity, with
    the page-table row re-parked on the trash page).  Metadata-only —
    the k/v rows/pages (and rings) pass through untouched; a paged
    slot's page IDs are reclaimed host-side by the
    :class:`PageAllocator`.  A pure function of a traced ``slot``: the
    engine serves every retirement with ONE donated jit of it
    (``InferenceEngine.evict_slot``, ISSUE 35), in which the pool is
    aliased to itself and only these three small arrays are written.

    Paged eviction MUST run before the slot's pages are reassigned:
    unlike the dense cache's slot-private rows, a stale page-table row
    would keep routing the slot's (masked, garbage) decode appends into
    pages that now belong to another request.  Resetting the row to the
    trash page makes the idle slot's writes land where the pool absorbs
    them by design."""
    zero = jnp.zeros((1,), jnp.int32)
    new_len = jax.lax.dynamic_update_slice(cache.lengths, zero, (slot,))
    if isinstance(cache, PagedKVCache):
        null_row = jnp.full((1, cache.max_pages_per_slot),
                            cache.null_page, jnp.int32)
        return cache.replace(
            lengths=new_len,
            capacity=jax.lax.dynamic_update_slice(
                cache.capacity, zero, (slot,)),
            page_table=jax.lax.dynamic_update_slice(
                cache.page_table, null_row, (slot, 0)))
    return cache.replace(lengths=new_len)


# --------------------------------------------------------------------------
# ragged paged pool (ISSUE 6)
# --------------------------------------------------------------------------

@flax.struct.dataclass
class PagedKVCache:
    """Fixed-size page pool + per-slot page table (module docstring).

    ``k``/``v`` hold ``pages`` physical pages of ``page_size`` token
    rows each; the LAST page (``null_page == pages - 1``) is the trash
    page the allocator never hands out.  ``page_table[slot, j]`` names
    the physical page backing virtual positions ``[j*page_size,
    (j+1)*page_size)`` of the slot; entries beyond the slot's
    reservation hold ``null_page``.  ``capacity[slot]`` is
    ``page_size *`` the slot's owned pages — the clamp bound
    :func:`advance` enforces (the dense cache's ``max_seq``, made
    per-slot).

    A decode step hands ``k``/``v`` WHOLE to
    :func:`~apex_tpu.ops.paged_attention.paged_decode_attention`, which
    reads the slots' live pages of one layer straight from the pool,
    along the list :func:`~apex_tpu.ops.paged_attention.paged_work_list`
    makes of the table and the lengths once a step.

    Tensor-parallel serving (ISSUE 17) shards ONLY the ``k``/``v``
    pool, over the kv-head dim (``kv_heads/tp`` heads per rank — see
    :func:`paged_cache_partition_specs`); the page table, lengths and
    capacity stay REPLICATED, so admission, prefix sharing, COW and
    eviction run unchanged on the host-side allocator.  Inside the
    engine's ``shard_map`` every mutator here sees the per-rank shard
    as an ordinary pool — the shape checks validate against the
    LOCAL ``kv_heads`` and all page/length arithmetic is rank-
    invariant.
    """
    k: jax.Array           # [pages, layers, kv_heads, page_size, head_dim]
    # same shape/dtype as k; None for a LATENT pool (ISSUE 34), whose k is
    # [pages, layers, latent_width, page_size]: one row a position, no
    # KV-head axis, the values the row's leading channels
    v: Optional[jax.Array]
    page_table: jax.Array  # [slots, max_pages_per_slot] int32
    lengths: jax.Array     # [slots] int32: live tokens per slot
    capacity: jax.Array    # [slots] int32: page_size * owned pages
    # [slots] int32: the token each slot's NEXT decode step takes as its
    # input (ISSUE 37), written by the step that sampled it
    # (:func:`feed_back`); replicated under tensor parallelism
    last_tokens: jax.Array
    # the window layers' rings (ISSUE 30), None without window layers:
    # [window_layers, slots, kv_heads, ring, head_dim] (layer-major: a
    # decode step rewrites one layer at a time), ring a whole number of
    # pages; position t of a slot sits at ring row t % ring.  Rings of
    # LATENT rows are [window_layers, slots, ring, width] with wv None
    wk: Optional[jax.Array] = None
    wv: Optional[jax.Array] = None
    # the index keys of a kind whose layers SELECT (ISSUE 36), None
    # without an indexer: [pages, layers, index_width, page_size], under
    # the same page table as k and v
    ik: Optional[jax.Array] = None

    @property
    def latent(self) -> bool:
        """One row a position and no KV-head axis (module docstring)?"""
        return self.k.ndim == 4

    @property
    def ring(self) -> int:
        """Positions one slot's window ring holds (0 without one)."""
        return 0 if self.wk is None else self.wk.shape[-2]

    @property
    def pages(self) -> int:
        """Total physical pages INCLUDING the trash page."""
        return self.k.shape[0]

    @property
    def null_page(self) -> int:
        return self.k.shape[0] - 1

    @property
    def alloc_pages(self) -> int:
        """Pages the allocator may hand out (pool minus the trash page)."""
        return self.k.shape[0] - 1

    @property
    def layers(self) -> int:
        return self.k.shape[1]

    @property
    def kv_heads(self) -> int:
        """0 for a latent pool: it has no such axis."""
        return 0 if self.latent else self.k.shape[2]

    @property
    def page_size(self) -> int:
        return self.k.shape[3]

    @property
    def head_dim(self) -> int:
        """A cached row's width: a head's size, or the latent row's."""
        return self.k.shape[2] if self.latent else self.k.shape[4]

    @property
    def row_shape(self) -> tuple:
        """One position's cached values in one layer, per buffer:
        ``(kv_heads, head_dim)``, or ``(latent_width,)``."""
        return (self.k.shape[2],) if self.latent else (
            self.k.shape[2], self.k.shape[4])

    @property
    def slots(self) -> int:
        return self.page_table.shape[0]

    @property
    def max_pages_per_slot(self) -> int:
        return self.page_table.shape[1]

    @property
    def max_seq(self) -> int:
        """The virtual per-slot window: ``max_pages_per_slot *
        page_size`` (what the dense cache calls ``max_seq``)."""
        return self.page_table.shape[1] * self.page_size


def init_paged_cache(pages: int, layers: int, kv_heads: int,
                     page_size: int, head_dim: int, *, slots: int,
                     max_pages_per_slot: int, dtype=jnp.bfloat16,
                     window_layers: int = 0, window: int = 0,
                     latent: int = 0, index: int = 0,
                     index_layers: Optional[int] = None,
                     window_latent: int = 0) -> PagedKVCache:
    """Allocate an empty pool: ``pages`` allocatable pages (+1 trash
    page appended), every page-table entry pointing at the trash page,
    every slot empty.  ``layers`` counts the layers the POOL holds;
    ``window_layers`` sliding-window layers of ``window`` positions get
    the second pool of per-slot rings instead (module docstring).
    ``latent`` (a row's width) makes the pool a LATENT one: one array
    ``[pages + 1, layers, latent, page_size]``, no value array;
    ``kv_heads`` / ``head_dim`` are then not read.  ``index`` (an index
    key's width) adds the index-key pool ``[pages + 1, index_layers,
    index, page_size]`` under the same table (``index_layers`` defaults to
    ``layers``); 0 adds no array.  ``window_latent`` (a window row's width)
    makes the rings LATENT ones, ``[window_layers, slots, ring,
    window_latent]`` with no value array — what a latent pool's window
    layers need."""
    if pages < 1 or page_size < 1 or max_pages_per_slot < 1:
        raise ValueError(
            f"pages ({pages}), page_size ({page_size}) and "
            f"max_pages_per_slot ({max_pages_per_slot}) must be >= 1")
    if window_layers and bool(latent) != bool(window_latent):
        raise ValueError(
            f"the window layers of a latent pool keep latent rows and "
            f"those of a per-head pool per-head k/v: latent={latent} with "
            f"window_latent={window_latent}")
    shape = ((pages + 1, layers, latent, page_size) if latent
             else (pages + 1, layers, kv_heads, page_size, head_dim))
    rings = {}
    if window_layers:
        if window < 1:
            raise ValueError(f"window layers need window >= 1, got "
                             f"{window}")
        ring = ring_rows(window, page_size)
        wshape = ((window_layers, slots, ring, window_latent)
                  if window_latent
                  else (window_layers, slots, kv_heads, ring, head_dim))
        rings = dict(wk=jnp.zeros(wshape, dtype),
                     wv=None if window_latent else jnp.zeros(wshape, dtype))
    return PagedKVCache(
        k=jnp.zeros(shape, dtype),
        v=None if latent else jnp.zeros(shape, dtype),
        page_table=jnp.full((slots, max_pages_per_slot), pages,
                            jnp.int32),
        lengths=jnp.zeros((slots,), jnp.int32),
        capacity=jnp.zeros((slots,), jnp.int32),
        last_tokens=jnp.zeros((slots,), jnp.int32), **rings,
        ik=jnp.zeros((pages + 1, index_layers or layers, index, page_size),
                     dtype) if index else None)


def ring_rows(window: int, page_size: int) -> int:
    """Positions one slot's window ring holds: ``ceil(window / page_size)
    + 1`` whole pages — the window, and the page a prefill or the newest
    token is still filling."""
    return (-(-window // page_size) + 1) * page_size


def paged_cache_partition_specs(axis: str = TENSOR_AXIS) -> PagedKVCache:
    """The pool's ``PartitionSpec`` tree for tensor-parallel serving:
    ``k``/``v`` ``[pages+1, layers, kv_heads/tp, page_size, head_dim]``
    sharded over the kv-head dim, page table / lengths / capacity
    replicated — each rank's pages are a contiguous slab (the ragged-
    paged-attention layout argument), and page IDs mean the same thing
    on every rank.  Doubles as the engine's ``shard_map`` in/out spec
    for the cache operand and as the ``NamedSharding`` source for the
    one-time ``device_put``."""
    from jax.sharding import PartitionSpec as P
    kv = P(None, None, axis, None, None)
    return PagedKVCache(k=kv, v=kv, page_table=P(), lengths=P(),
                        capacity=P(), last_tokens=P())


def _pools(cache: PagedKVCache, fn, k, v, ik=None) -> dict:
    """``fn(pool, rows)`` over the key pool and — where the cache has one
    — the value pool and the index-key pool: the ``k=``/``v=``/``ik=`` of
    a ``cache.replace``.  A page is all its arrays: index keys for a cache
    without that pool, or none for a cache with it, raise."""
    if cache.v is None and v is not None:
        raise ValueError("a latent pool holds one row a position and no "
                         "values beside it: pass v=None")
    if k is not None and (cache.ik is None) != (ik is None):
        raise ValueError(
            "a cache with an index-key pool takes the index keys with "
            "every k/v it is handed, and a cache without one takes none; "
            f"got ik {None if ik is None else tuple(ik.shape)} for a cache "
            f"with{'out' if cache.ik is None else ''} the pool")
    return {"k": fn(cache.k, k),
            "v": None if cache.v is None else fn(cache.v, v),
            "ik": None if cache.ik is None else fn(cache.ik, ik)}


def _rows_minor(pool) -> bool:
    """Does a page of ``pool`` hold its positions on the minor axis (the
    latent pool and the index-key pool: ``[pages, layers, width,
    page_size]``) and not one row a position a KV head?"""
    return pool.ndim == 4


def _check_rows(cache: PagedKVCache, k, v, what: str, lead: tuple,
                ik=None) -> None:
    """``k`` (and ``v``) must be ``[*lead, <kv_heads>, n, head_dim]`` —
    without the KV-head axis for a latent pool, whose ``v`` is None —
    and ``ik``, where given, ``[*lead, n, index_width]``."""
    heads = () if cache.latent else (cache.kv_heads,)
    ok = (k.ndim == len(lead) + len(heads) + 2
          and tuple(k.shape[:len(lead) + len(heads)]) == lead + heads
          and k.shape[-1] == cache.head_dim
          and (v is None if cache.latent else
               v is not None and v.shape == k.shape))
    if not ok:
        raise ValueError(
            f"{what} must be {list(lead + heads) + ['n', cache.head_dim]}"
            f"{' and v None' if cache.latent else ', k and v alike'}; got "
            f"k {tuple(k.shape)} v {None if v is None else tuple(v.shape)}")
    if cache.ik is not None:
        # a pool that keeps some layers' keys takes as many layers of them
        _check_index(ik, (cache.ik.shape[1], *lead[1:], k.shape[-2],
                          cache.ik.shape[2]), what)


def _check_index(ik, want: tuple, what: str) -> None:
    """Index keys handed to a cache with that pool must be ``want``-shaped
    (their ABSENCE is :func:`_pools`' to refuse)."""
    if ik is not None and tuple(ik.shape) != tuple(want):
        raise ValueError(f"{what}: the index keys must be {list(want)}, "
                         f"got {tuple(ik.shape)}")


def page_row(page_ids: Sequence[int], max_pages_per_slot: int,
             null_page: int) -> np.ndarray:
    """Host helper: pad an allocator's page-ID list to a full
    ``[max_pages_per_slot]`` int32 page-table row (dead entries point
    at the trash page)."""
    ids = list(page_ids)
    if len(ids) > max_pages_per_slot:
        raise ValueError(
            f"{len(ids)} pages exceed max_pages_per_slot "
            f"{max_pages_per_slot}")
    return np.asarray(ids + [null_page] * (max_pages_per_slot - len(ids)),
                      np.int32)


def _slot_pages(cache: PagedKVCache, row, first, n: int):
    """The physical pages behind the slot's page ordinals ``[first, first
    + n)`` (``first`` traced OK).  Ordinals past the virtual window get an
    OUT-OF-BOUNDS page index so a ``mode="drop"`` scatter discards them —
    clamping them onto the last owned page would clobber live rows
    whenever the slab fills the window; ordinals past the reservation hold
    the trash page by construction."""
    mpps = cache.max_pages_per_slot
    if isinstance(first, (int, np.integer)) and first + n <= mpps:
        return row[first:first + n]
    ords = first + jnp.arange(n, dtype=jnp.int32)
    return jnp.where(ords < jnp.int32(mpps),
                     jnp.take(row, jnp.minimum(ords, jnp.int32(mpps - 1))),
                     jnp.int32(cache.pages))


def _as_pages(pool, x, n: int):
    """A slab that starts on a page boundary as ``n`` whole pages of
    ``pool``'s layout: ``[layers, kvh, n * ps, d] -> [n, layers, kvh, ps,
    d]``, or ``[layers, n * ps, w] -> [n, layers, w, ps]`` for a pool whose
    pages hold their positions on the minor axis — a free reshape and one
    transpose; no page of the pool is read."""
    ps = x.shape[-2] // n
    if _rows_minor(pool):
        slab = jnp.moveaxis(jnp.swapaxes(x, -1, -2).reshape(
            *x.shape[:-2], x.shape[-1], n, ps), -2, 0)
    else:
        slab = jnp.moveaxis(
            x.reshape(*x.shape[:-2], n, ps, x.shape[-1]), -3, 0)
    return slab.astype(pool.dtype)


def _rows_in_pages(pool, x, ids, off):
    """A slab that starts at row ``off`` of the first of the pages ``ids``,
    with the rows of those pages around it: its rows are contiguous in the
    pages' row space, so gather the pages, drop the slab in with one
    ``dynamic_update_slice``, and hand the pages' rows back in the slab's
    own layout (``[..., n * ps, d]``) — the rows below ``off`` (the copied
    prefix of a boundary page) as they were."""
    n, ps = ids.shape[0], pool.shape[-1 if _rows_minor(pool) else -2]
    pages = jnp.take(pool, ids, axis=0, mode="clip")
    zero = jnp.int32(0)
    if _rows_minor(pool):
        # [n, layers, w, ps] -> [layers, w, n * ps]: the positions lie
        # along the minor axis, token t at off + t
        layers, _, w = x.shape
        flat = jnp.moveaxis(pages, 0, -2).reshape(layers, w, n * ps)
        flat = jax.lax.dynamic_update_slice(
            flat, jnp.swapaxes(x, -1, -2).astype(pool.dtype),
            (zero, zero, off))
        return jnp.swapaxes(flat, -1, -2)
    # [n, layers, kvh, ps, d] -> [layers, kvh, n * ps, d]: token t of the
    # slab sits at row off + t
    layers, kvh, _, d = x.shape
    flat = jnp.moveaxis(pages, 0, 2).reshape(layers, kvh, n * ps, d)
    return jax.lax.dynamic_update_slice(
        flat, x.astype(pool.dtype), (zero, zero, off, zero))


def _write_pages(pool, ids, pages):
    """THE write of every prefill: whole pages into the pool, ONE scatter
    on the page axis — never a row scatter, which indexed on (page,
    row-in-page), dims 0 and 3, makes XLA relayout the entire pool around
    it: one pool-sized temporary and two pool-sized copies per call
    (observed on the v5e: 2 GiB of temporaries for a 4 GiB pool; a 24 GiB
    pool over tp=4 could not prefill).  ``pages`` holds one entry
    per page id of ``ids``; an id past the pool is dropped, and the trash
    page appearing more than once just stacks garbage."""
    return pool.at[ids].set(pages, mode="drop")


def insert_pages(cache: PagedKVCache, slot, k, v, length,
                 row, ik=None) -> PagedKVCache:
    """Prefill write of a cold prompt: park its k/v into the slot's pages
    from position 0 — :func:`insert_tokens` at ``start = 0``, whose
    aligned write it is.

    ``k``/``v``: ``[layers, kv_heads, s, head_dim]`` with ``s`` the
    bucket-padded prompt length — ``s`` must tile into whole pages (the
    engine guarantees it: buckets and page sizes are both powers of
    two, ``page_size <= bucket``).  ``row`` is the slot's FULL page-
    table row (``[max_pages_per_slot]`` int32, traced OK — see
    :func:`page_row`); the first ``s // page_size`` entries receive the
    prompt's pages, later owned entries are decode headroom, trash-page
    entries absorb any static overhang harmlessly.  The slot's capacity
    is derived in-program from the row (owned pages x page_size), so
    one compiled insert serves every page assignment.  ``ik`` ``[layers,
    s, index_width]``: the prompt's index keys, for a cache with that
    pool.
    """
    ps, s = cache.page_size, k.shape[-2]
    if s % ps or s > cache.max_seq:
        raise ValueError(
            f"prompt slab length {s} must be a multiple of page_size "
            f"{ps} and <= max_seq {cache.max_seq}")
    return insert_tokens(cache, slot, k, v, length, row, 0, ik)


def insert_tokens(cache: PagedKVCache, slot, k, v, length, row,
                  start, ik=None) -> PagedKVCache:
    """Prefill write: put a bucket-padded slab of ``s`` token rows into
    the slot's pages at positions ``[start, start + s)``.

    ``k``/``v``: ``[layers, kv_heads, s, head_dim]``; ``start`` (traced
    OK) is the first virtual position the slab covers — ``0`` for a
    cold prefill, the shared-prefix coverage for a hit, a chunk
    boundary for chunked prefill.  ``length`` is the slot's TOTAL live
    length after this write (prefix + real suffix tokens).

    Every write ends in :func:`_write_pages`, one scatter of whole pages
    on the page axis; what it is handed depends on ``start``:

    * **Aligned** (``start`` a multiple of ``page_size`` and ``s`` whole
      pages — every cold prefill, every chunk, every hit on a page
      boundary): the slab itself as pages (:func:`_as_pages`, a reshape
      and a transpose); no page of the pool is read.
    * **Mid-page** (a prefix-cache hit resuming inside a page after its
      boundary COW): the ``ceil(s / page_size) + 1`` pages the slab
      touches, read and rewritten with the slab inside them
      (:func:`_rows_in_pages`), which keeps the copied prefix rows below
      ``start`` in the boundary page.  Rows mapping into SHARED prefix
      pages never occur by contract (the scheduler COWs the boundary page
      before admitting a mid-page suffix, so every touched page is
      private or trash).

    Both leave every live row alike.  A ``start`` known when the program
    is traced (a python int: a kind that never resumes passes 0) picks
    its write there, with no branch; a traced one picks it with one
    ``lax.cond`` a pool array, so one compiled insert still serves every
    ``start``.  Positions past the reservation land in the trash page;
    past the virtual window they are dropped.

    The page-table row, lengths, and capacity update alike on both
    (capacity derived in-program from the owned entries), so one compiled
    insert serves every page assignment.  ``ik``
    ``[layers, s, index_width]``: the slab's index keys, for a cache with
    that pool.
    """
    ps, s = cache.page_size, k.shape[-2]
    _check_rows(cache, k, v, "prefill k/v", (cache.layers,), ik)
    if s < 1 or s > cache.max_seq:
        raise ValueError(
            f"suffix slab length {s} must be in [1, max_seq "
            f"{cache.max_seq}]")
    row = jnp.asarray(row, jnp.int32)
    if row.shape != (cache.max_pages_per_slot,):
        raise ValueError(
            f"page row must be [{cache.max_pages_per_slot}], got "
            f"{tuple(row.shape)}")
    static = isinstance(start, (int, np.integer))
    if not static:
        start = jnp.asarray(start, jnp.int32)
    first, off, n = start // ps, start % ps, -(-s // ps)

    def aligned(pool, x):
        return _write_pages(pool, _slot_pages(cache, row, first, n),
                            _as_pages(pool, x, n))

    def mid_page(pool, x):
        ids = _slot_pages(cache, row, first, n + 1)
        return _write_pages(pool, ids, _as_pages(
            pool, _rows_in_pages(pool, x, ids, off), n + 1))

    def either(pool, x):
        # the cond picks the ROWS to write: the slab as it is, or the slab
        # with its pages' rows around it.  The pool is read in it and
        # written after it (a branch that wrote the pool would be handed
        # a pool-sized copy on the v5e): the first n pages, then the page
        # after them, which only a mid-page start fills (an out-of-bounds
        # id drops the aligned write's empty one)
        ids = _slot_pages(cache, row, first, n + 1)

        def around(pool, x):
            rows = _rows_in_pages(pool, x, ids, off)
            return rows[..., :n * ps, :], rows[..., n * ps:, :]

        head, tail = jax.lax.cond(
            off == 0, lambda pool, x: (x.astype(pool.dtype), jnp.zeros_like(
                x[..., :ps, :], pool.dtype)), around, pool, x)
        pool = _write_pages(pool, ids[:n], _as_pages(pool, head, n))
        return _write_pages(
            pool, jnp.where(off == 0, jnp.int32(cache.pages), ids[n:]),
            _as_pages(pool, tail, 1))

    write = (mid_page if s % ps or (static and off)
             else aligned if static else either)
    pools = _pools(cache, write, k, v, ik)
    slot = jnp.asarray(slot, jnp.int32)
    owned = jnp.sum((row != cache.null_page).astype(jnp.int32))
    return cache.replace(
        **pools,
        page_table=jax.lax.dynamic_update_slice(
            cache.page_table, row[None], (slot, jnp.int32(0))),
        lengths=jax.lax.dynamic_update_slice(
            cache.lengths, jnp.asarray(length, jnp.int32)[None], (slot,)),
        capacity=jax.lax.dynamic_update_slice(
            cache.capacity, (owned * ps)[None], (slot,)))


def _ring_row_shape(cache: PagedKVCache) -> tuple:
    """One position's values in one window layer's ring: ``(kv_heads,
    head_dim)``, or ``(width,)`` for a ring of latent rows."""
    return cache.wk.shape[2:-2] + cache.wk.shape[-1:]


def _ring_pair(cache: PagedKVCache, fn, k, v) -> dict:
    """``fn(rings, rows)`` over the key rings and — where the cache has
    them — the value rings: the ``wk=``/``wv=`` of a ``cache.replace``."""
    return {"wk": fn(cache.wk, k),
            "wv": None if cache.wv is None else fn(cache.wv, v)}


def insert_window(cache: PagedKVCache, slot, k, v,
                  length) -> PagedKVCache:
    """Prefill write of the WINDOW layers (ISSUE 30): of a prompt's k/v
    ``[window_layers, kv_heads, s, head_dim]`` — or its latent rows
    ``[window_layers, s, width]`` with ``v`` None, for latent rings — only
    the last ``ring`` positions below ``length`` are kept, each at ring
    row ``t % ring``.

    Ring row ``r`` receives position ``(length - 1) - ((length - 1 - r)
    mod ring)`` — the newest one congruent to ``r``; where that is
    negative (a prompt shorter than the ring) the row keeps a clamped
    gather that every reader masks by position.  One gather along the
    sequence and one whole-ring write: the slot's ring is rewritten, so
    nothing of its previous occupant survives an admission."""
    ring = cache.ring
    row = () if cache.wk is None else _ring_row_shape(cache)
    if not ring or k.ndim != len(row) + 2 \
            or (k.shape[0],) + k.shape[1:-2] + k.shape[-1:] \
            != (cache.wk.shape[0],) + row \
            or (v is None) != (cache.wv is None) \
            or (v is not None and v.shape != k.shape):
        raise ValueError(
            f"window k/v must be [window_layers, kv_heads, s, head_dim] "
            f"(latent rings: rows [window_layers, s, width] and v None) "
            f"of a cache with rings; got k {tuple(k.shape)} v "
            f"{None if v is None else tuple(v.shape)}, rings "
            f"{None if cache.wk is None else tuple(cache.wk.shape)}")
    seq = k.ndim - 2                    # the prompt's positions
    s = k.shape[seq]
    last = jnp.asarray(length, jnp.int32) - 1
    held = last - jnp.mod(last - jnp.arange(ring, dtype=jnp.int32), ring)
    src = jnp.clip(held, 0, s - 1)
    slot = jnp.asarray(slot, jnp.int32)
    zero = jnp.int32(0)

    def write(rings, x):
        rows = jnp.take(x, src, axis=seq).astype(rings.dtype)
        return jax.lax.dynamic_update_slice(
            rings, rows[:, None], (zero, slot) + (zero,) * (rings.ndim - 2))

    return cache.replace(**_ring_pair(cache, write, k, v))


def append_window(cache: PagedKVCache, layer: int, k_tok,
                  v_tok) -> PagedKVCache:
    """Decode write for ONE window layer (``layer`` counts window layers
    only): slot ``i``'s token row lands at ring row ``lengths[i] %
    ring`` — where the position ``ring`` back, by now outside every
    window, used to be.  The dense cache's one-row-per-slot update.
    Latent rings take ``k_tok [slots, width]`` and ``v_tok`` None."""
    want = (cache.slots,) + _ring_row_shape(cache)
    if k_tok.shape != want or (v_tok is None) != (cache.wv is None):
        raise ValueError(
            f"token k/v must be {list(want)} (latent rings: v None), got "
            f"k {tuple(k_tok.shape)} v "
            f"{None if v_tok is None else tuple(v_tok.shape)}")
    rows = jnp.mod(cache.lengths, cache.ring)

    def write(buf, tok, row):
        # buf [kv_heads, ring, d] and tok [kv_heads, d], or a latent
        # ring's [ring, w] and [w]: the row at the ring's second-to-last
        return jax.lax.dynamic_update_slice(
            buf, tok[..., None, :].astype(buf.dtype),
            (jnp.int32(0),) * (buf.ndim - 2) + (row, jnp.int32(0)))

    upd = jax.vmap(write)
    return cache.replace(**_ring_pair(
        cache, lambda rings, tok: rings.at[layer].set(
            upd(rings[layer], tok, rows)), k_tok, v_tok))


def window_pages_live(cache: PagedKVCache):
    """Ring pages that hold a position some live slot can still attend
    (int32 scalar, on the device): per admitted slot ``min(ceil(length
    / page_size), ring pages)``.  Never above ``slots * ring pages``."""
    if not cache.ring:
        return jnp.int32(0)
    ps = cache.page_size
    pages = jnp.minimum(-(-cache.lengths // ps), cache.ring // ps)
    return jnp.sum(jnp.where(cache.capacity > 0, pages, 0)).astype(
        jnp.int32)


def cow_page(cache: PagedKVCache, src, dst) -> PagedKVCache:
    """Copy-on-write page duplication: copy physical page ``src``'s k/v
    rows into page ``dst`` (both traced int32 — ONE compiled copy
    serves every page pair).

    The sharing contract's write barrier: a slot about to write into a
    page whose refcount is above one (a prefix-cache boundary page
    shared mid-fill, or any future fork) first duplicates it into a
    freshly acquired page and points its table row at the copy, so the
    other owners' reads stay bitwise untouched.  The table-row swap is
    NOT performed here — the suffix prefill that follows writes the
    slot's full row (with ``dst`` at the boundary ordinal) through
    :func:`insert_tokens`, so the copy plus the row write stay two
    dispatches of already-compiled programs.  Pure donated update like
    every other cache mutation.
    """
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    def copy(pool, _):
        rest = (jnp.int32(0),) * (pool.ndim - 1)
        page = jax.lax.dynamic_slice(pool, (src,) + rest,
                                     (1,) + pool.shape[1:])
        return jax.lax.dynamic_update_slice(pool, page, (dst,) + rest)

    # every array of the page: k, v and the index keys alike
    return cache.replace(**_pools(cache, copy, None, None))


def extract_pages(cache: PagedKVCache, page_ids):
    """Swap-out gather (ISSUE 18 host page tier): read physical pages
    ``page_ids``' k/v rows into contiguous slabs —
    ``[n, layers, kv_heads, page_size, head_dim]`` per buffer, the
    :func:`insert_pages` slab layout — that the engine then
    ``device_get``\\ s into the host store.

    ``page_ids`` is a ``[n]`` int32 vector with STATIC ``n`` (the swap
    batch width): the engine pads short batches with the trash page —
    an in-bounds gather whose garbage rows the host slices off — so one
    compiled extract serves every page set.  Pure read: the cache
    operand is NOT donated (the pool stays live; eviction returns the
    page IDs to the free list host-side, no device-side erase needed).
    Under tensor parallelism each rank gathers its own ``kv_heads/tp``
    shard of the requested pages; the host-side ``device_get``
    assembles the global slab.  Returns ``(k, v, ik)`` whatever the
    cache holds — :func:`restore_pages`' three slabs."""
    page_ids = jnp.asarray(page_ids, jnp.int32)
    if page_ids.ndim != 1:
        raise ValueError(
            f"page_ids must be a rank-1 int32 vector, got shape "
            f"{tuple(page_ids.shape)}")
    slabs = _pools(cache, lambda pool, _: jnp.take(
        pool, page_ids, axis=0, mode="clip"), None, None)
    # always the triple (k, v, ik), None for a pool the cache does not
    # hold, as the cache itself has it: a latent pool has no values, and
    # only a kind that selects has index keys
    return slabs["k"], slabs["v"], slabs["ik"]


def restore_pages(cache: PagedKVCache, page_ids, k_slab,
                  v_slab, ik_slab=None) -> PagedKVCache:
    """Swap-in scatter (ISSUE 18 host page tier): write host-tier page
    slabs back into freshly acquired physical pages ``page_ids`` — the
    :func:`insert_pages` slab scatter aimed by an explicit page-ID
    vector instead of a table row.

    ``page_ids`` is ``[n]`` int32 with STATIC ``n`` (the swap batch
    width); ``k_slab``/``v_slab`` are ``[n, layers, kv_heads,
    page_size, head_dim]``.  The engine pads short batches with an
    OUT-OF-BOUNDS page index (``cache.pages``) and zero slabs, so
    ``mode="drop"`` discards the padding rows — one compiled restore
    serves every page set.  Pure donated update like every other cache
    mutation.  Under tensor parallelism each rank scatters its own
    ``kv_heads/tp`` shard of the (globally sharded) slab operand.
    ``ik_slab``: the pages' index keys (:func:`extract_pages`' third
    slab), for a cache with that pool — a page is restored whole or not
    at all."""
    page_ids = jnp.asarray(page_ids, jnp.int32)
    if page_ids.ndim != 1:
        raise ValueError(
            f"page_ids must be a rank-1 int32 vector, got shape "
            f"{tuple(page_ids.shape)}")
    want = (page_ids.shape[0],) + tuple(cache.k.shape[1:])
    if tuple(k_slab.shape) != want or (
            v_slab is not None and tuple(v_slab.shape) != want):
        raise ValueError(
            f"swap-in slabs must be {want}, got k "
            f"{tuple(k_slab.shape)} v "
            f"{None if v_slab is None else tuple(v_slab.shape)}")

    def write(pool, slab):
        return pool.at[page_ids].set(slab.astype(pool.dtype), mode="drop")

    if cache.ik is not None:
        _check_index(ik_slab, (page_ids.shape[0], *cache.ik.shape[1:]),
                     "swap-in slabs")
    return cache.replace(**_pools(cache, write, k_slab, v_slab, ik_slab))


def _append_layer_paged(cache: PagedKVCache, layer: int, k_tok,
                        v_tok, ik_tok=None) -> PagedKVCache:
    """Paged decode write for ONE layer: slot ``i``'s token row lands in
    page ``page_table[i, lengths[i] // page_size]`` at row
    ``lengths[i] % page_size``.  One gather + one scatter of the
    slots' current pages per buffer (every slot's ``(page, row)``
    target derives from the traced lengths/page table up front) — the
    paged analog of the dense append's vmap, donation-safe like every
    ``.at[].set`` on a donated operand; written pages are private to
    their slot by the sharing contract, idle slots write the trash
    page.  At capacity the write clamps into the trash page / last
    row — the same bounded-damage semantics as the dense clamp, with
    the damage redirected off the live data entirely (slots at
    capacity may alias the trash page; they hold garbage by contract,
    so scatter order between them is irrelevant)."""
    ps, mpps = cache.page_size, cache.max_pages_per_slot
    pos = cache.lengths                                     # [slots]
    ordinal = jnp.minimum(pos // ps, jnp.int32(mpps - 1))
    pages = jnp.take_along_axis(cache.page_table, ordinal[:, None],
                                axis=1)[:, 0]               # [slots]
    offs = jnp.minimum(pos - ordinal * ps, jnp.int32(ps - 1))
    # read-modify-write of each slot's CURRENT page of this layer
    # ([slots, kv_heads, page_size, head_dim], ~1 MiB): a row scatter
    # indexed on (page, layer, row-in-page) makes XLA relayout the
    # whole pool around it on every step (see insert_tokens); indexing
    # the two leading dims only does not
    sid = jnp.arange(cache.slots, dtype=jnp.int32)

    def write(pool, tok):
        # [slots, kv_heads, ps, d] — or a latent page, [slots, w, ps]:
        # the slot's position is the third axis of either
        cur = pool[pages, layer]
        cur = cur.at[sid, :, offs].set(tok.astype(pool.dtype))
        return pool.at[pages, layer].set(cur, mode="drop")

    return cache.replace(**_pools(cache, write, k_tok, v_tok, ik_tok))


class PageAllocator:
    """Host-side reference-counted free-list allocator over the pool's
    allocatable pages (ISSUE 12: refcounts make shared-prefix page
    sharing and copy-on-write a bookkeeping operation).

    The scheduler's admission-control arm: a request is admitted only
    if :meth:`acquire` can hand it every PRIVATE page it may need
    (suffix + token budget, rounded up to whole pages) — out-of-pages
    is BACKPRESSURE (the request waits), never a mid-decode failure,
    because reservations are made in full before prefill.  A request
    extending a cached prefix does not copy the prefix's pages: it
    :meth:`share`\\ s them (refcount + 1 per co-owner), so N
    concurrent requests over a P-page prefix pin P physical pages,
    not N·P.  :meth:`release` is the ONLY way out: the page returns
    to the LIFO free list exactly when its LAST owner releases it.
    LIFO reuse keeps recently-touched pages hot.  Double-release and
    foreign-page releases raise — a leaked page is a capacity leak
    forever and a premature free corrupts another request's stream,
    so the bookkeeping is strict.

    Conservation invariant (the allocator sweep test walks it every
    step): ``free_pages + live_pages == num_pages`` with
    ``live_pages`` counting DISTINCT outstanding pages, while
    ``weighted_live()`` (the refcount-weighted view) equals the sum of
    every holder's page list — shared pages counted once per owner.
    """

    def __init__(self, num_pages: int, page_size: int,
                 max_pages_per_slot: int):
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.max_pages_per_slot = int(max_pages_per_slot)
        self._free: List[int] = list(range(self.num_pages))
        self._refs: dict = {}          # page id -> outstanding refcount

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def live_pages(self) -> int:
        """Distinct pages with at least one outstanding reference."""
        return len(self._refs)

    def weighted_live(self) -> int:
        """Sum of refcounts over live pages — what N sharers of one
        page would have paid WITHOUT sharing."""
        return sum(self._refs.values())

    def shared_pages(self) -> int:
        """Pages currently held by more than one owner."""
        return sum(1 for c in self._refs.values() if c > 1)

    def refcount(self, pid: int) -> int:
        return self._refs.get(int(pid), 0)

    def pages_needed(self, tokens: int) -> int:
        """Whole pages covering ``tokens``, clamped to the per-slot
        table size (a request past the virtual window truncates at
        capacity — the scheduler records why)."""
        need = -(-int(tokens) // self.page_size)
        return max(1, min(need, self.max_pages_per_slot))

    def acquire(self, n: int) -> Optional[List[int]]:
        """``n`` fresh page IDs at refcount 1 each, or None
        (backpressure) if the free list can't cover the reservation."""
        if n > len(self._free):
            return None
        ids = [self._free.pop() for _ in range(n)]
        for pid in ids:
            self._refs[pid] = 1
        return ids

    def share(self, ids: Sequence[int]) -> None:
        """Take one additional reference on each (already outstanding)
        page — the sharing half of copy-on-write.  Sharing a page with
        no live owner raises: a freed page may already back another
        request, so silent resurrection is the corruption this
        allocator exists to prevent."""
        ids = [int(p) for p in ids]
        for pid in ids:
            if pid not in self._refs:
                raise ValueError(
                    f"page {pid} is not outstanding (cannot share a "
                    f"freed page, or a page this allocator never "
                    f"issued)")
        for pid in ids:
            self._refs[pid] += 1

    def release(self, ids: Sequence[int]) -> None:
        """Drop one reference per page; a page whose LAST owner
        releases it returns to the LIFO free list.  Strict: releasing
        a page with no outstanding reference (double release, or a
        page this allocator never issued) raises."""
        for pid in ids:
            pid = int(pid)
            if pid not in self._refs:
                raise ValueError(
                    f"page {pid} is not outstanding (double release, "
                    f"or a page this allocator never issued)")
            self._refs[pid] -= 1
            if self._refs[pid] == 0:
                del self._refs[pid]
                self._free.append(pid)

    def snapshot(self) -> dict:
        """Read-only copy of the books, the SANCTIONED way to observe
        allocator internals from outside this package (the APX112 lint
        rule bans underscore-attribute mutation from anywhere else;
        the protocol auditor canonicalizes states through this).
        ``free`` preserves LIFO order — it determines which page the
        next acquire hands out, so two states whose free lists differ
        only in order are NOT equivalent."""
        return {"free": tuple(self._free),
                "refs": dict(self._refs)}


class _DeferredSlab:
    """Placeholder for one page whose device→host drain has been
    DISPATCHED but not yet fetched (ISSUE 19): ``pending.resolve()``
    returns the batch's stacked ``(k, v)`` slabs and ``index`` selects
    this page's row.  Bytes are booked the moment the placeholder is
    parked — the drain WILL land — so the budget stays as strict as an
    eager put."""
    __slots__ = ("pending", "index")

    def __init__(self, pending, index: int):
        self.pending = pending
        self.index = index

    def materialize(self):
        k, v = self.pending.resolve()
        return k[self.index].copy(), v[self.index].copy()


class HostPageStore:
    """Host-DRAM page tier under the HBM pool (ISSUE 18): a
    byte-budgeted dict of per-page k/v slabs, keyed by opaque integer
    handles the prefix cache's ``host``-state edges carry.

    The store is deliberately dumb: which entries exist and WHEN they
    are dropped is the prefix cache's per-tier LRU policy — this class
    only owns the byte ledger.  Entries are the GLOBAL page geometry
    (``[layers, kv_heads, page_size, head_dim]`` per buffer) even under
    tensor parallelism: the engine's swap-out assembles the full
    kv-head dim via ``device_get`` and the swap-in re-shards, so the
    host books stay replicated exactly like the page table.

    Conservation mirror (the churn sweep walks it every step):
    ``pages == `` the prefix cache's count of host-state edges, and
    ``bytes_used == pages * page_bytes <= capacity_bytes``.
    """

    def __init__(self, capacity_bytes: int, page_bytes: int):
        capacity_bytes = int(capacity_bytes)
        page_bytes = int(page_bytes)
        if capacity_bytes < 0 or page_bytes < 1:
            raise ValueError(
                f"capacity_bytes ({capacity_bytes}) must be >= 0 and "
                f"page_bytes ({page_bytes}) >= 1")
        self.capacity_bytes = capacity_bytes
        self.page_bytes = page_bytes
        self._slabs: dict = {}      # handle -> (k_np, v_np)
        self._next_handle = 0

    @property
    def pages(self) -> int:
        return len(self._slabs)

    @property
    def bytes_used(self) -> int:
        return len(self._slabs) * self.page_bytes

    def fits(self, n: int = 1) -> bool:
        """Would ``n`` more pages stay inside the byte budget?"""
        return self.bytes_used + int(n) * self.page_bytes \
            <= self.capacity_bytes

    def put(self, k_np, v_np) -> int:
        """Park one page's k/v slabs; returns the handle.  Strict on
        the budget: the caller (the prefix cache's offload path) makes
        room FIRST — an over-budget put is a bookkeeping bug."""
        if not self.fits(1):
            raise ValueError(
                f"host tier over budget: {self.bytes_used} + "
                f"{self.page_bytes} > {self.capacity_bytes}")
        handle = self._next_handle
        self._next_handle += 1
        self._slabs[handle] = (k_np, v_np)
        return handle

    def put_deferred(self, n: int, pending) -> list:
        """Park ``n`` pages whose device→host drain is in flight
        (ISSUE 19): ``pending.resolve()`` must return the batch's
        stacked ``(k, v)`` slabs ``[n, ...]``.  Same strict budget as
        :meth:`put` — bytes are booked eagerly for all ``n`` pages.
        Returns one handle per page.  A :meth:`get`/:meth:`pop` before
        the owner drains ``pending`` forces resolution (a prefix hit
        racing its own eviction is correct, just no longer deferred)."""
        n = int(n)
        if not self.fits(n):
            raise ValueError(
                f"host tier over budget: {self.bytes_used} + "
                f"{n * self.page_bytes} > {self.capacity_bytes}")
        handles = []
        for i in range(n):
            handle = self._next_handle
            self._next_handle += 1
            self._slabs[handle] = _DeferredSlab(pending, i)
            handles.append(handle)
        return handles

    def get(self, handle: int):
        """The ``(k, v)`` slabs behind ``handle`` (KeyError if the
        host-tier LRU already dropped it)."""
        handle = int(handle)
        entry = self._slabs[handle]
        if isinstance(entry, _DeferredSlab):
            entry = entry.materialize()
            self._slabs[handle] = entry
        return entry

    def pop(self, handle: int):
        """Drop an entry, returning its slabs (None if already gone —
        a swapped-in entry may race a host-tier eviction)."""
        entry = self._slabs.pop(int(handle), None)
        if isinstance(entry, _DeferredSlab):
            entry = entry.materialize()
        return entry

    def snapshot(self) -> dict:
        """Read-only view of the ledger, the sanctioned external
        observation surface (APX112): handle -> ``"resident"`` or
        ``"deferred"``.  Purely observational — an in-flight deferred
        entry is NOT materialized (that would force its pending drain
        and mutate the state being observed); a deferred entry whose
        pending already resolved counts as resident."""
        return {int(h): ("resident" if not isinstance(e, _DeferredSlab)
                         or getattr(e.pending, "done", False)
                         else "deferred")
                for h, e in self._slabs.items()}

    def peek_resident(self, handle: int):
        """The ``(k, v)`` slabs behind ``handle`` if resident (eager,
        or deferred with its drain already resolved), else None —
        unlike :meth:`get` this never forces an in-flight drain, so
        invariant checkers can inspect content without mutating the
        observable state."""
        entry = self._slabs.get(int(handle))
        if entry is None:
            return None
        if isinstance(entry, _DeferredSlab):
            if not getattr(entry.pending, "done", False):
                return None
            entry = entry.materialize()
            self._slabs[int(handle)] = entry
        return entry
