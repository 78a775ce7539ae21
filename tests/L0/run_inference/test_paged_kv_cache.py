"""Paged KV pool semantics (ISSUE 6): page-table-threaded donated
mutations, trash-page overflow containment, truncation surfacing, and
the host-side page allocator's no-leak bookkeeping."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.inference import kv_cache

LAYERS, KVH, PS, D, SLOTS, MPPS, PAGES = 2, 2, 4, 8, 3, 4, 6


def _cache(dtype=jnp.float32, **kw):
    return kv_cache.init_paged_cache(PAGES, LAYERS, KVH, PS, D,
                                     slots=SLOTS, max_pages_per_slot=MPPS,
                                     dtype=dtype, **kw)


def _rand(shape, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape),
                       jnp.float32)


def _row(ids):
    return kv_cache.page_row(ids, MPPS, PAGES)


def test_init_geometry_and_trash_page():
    c = _cache(jnp.bfloat16)
    # pool carries PAGES allocatable pages + 1 trash page
    assert c.k.shape == (PAGES + 1, LAYERS, KVH, PS, D)
    assert c.k.dtype == jnp.bfloat16 and c.v.dtype == jnp.bfloat16
    assert (c.pages, c.null_page, c.alloc_pages) == (PAGES + 1, PAGES,
                                                     PAGES)
    assert (c.slots, c.max_pages_per_slot, c.page_size) == (SLOTS, MPPS,
                                                            PS)
    assert c.max_seq == MPPS * PS
    # empty: every table entry parks on the trash page, nothing owned
    assert np.all(np.asarray(c.page_table) == PAGES)
    assert np.all(np.asarray(c.lengths) == 0)
    assert np.all(np.asarray(c.capacity) == 0)


def test_insert_pages_places_slabs_and_derives_capacity():
    c = _cache()
    k = _rand((LAYERS, KVH, 2 * PS, D), 1)
    v = _rand((LAYERS, KVH, 2 * PS, D), 2)
    ids = [4, 1]                      # deliberately non-contiguous
    c = kv_cache.insert_pages(c, 1, k, v, 5, _row(ids))
    # slab pages landed at the assigned physical pages, in order
    np.testing.assert_array_equal(np.asarray(c.k[4]),
                                  np.asarray(k[:, :, :PS]))
    np.testing.assert_array_equal(np.asarray(c.k[1]),
                                  np.asarray(k[:, :, PS:]))
    np.testing.assert_array_equal(np.asarray(c.v[4]),
                                  np.asarray(v[:, :, :PS]))
    # table row = assigned pages padded with the trash page
    assert np.asarray(c.page_table[1]).tolist() == [4, 1, PAGES, PAGES]
    # capacity derived in-program from the owned-page count
    assert np.asarray(c.lengths).tolist() == [0, 5, 0]
    assert np.asarray(c.capacity).tolist() == [0, 2 * PS, 0]
    # other slots' rows untouched
    assert np.all(np.asarray(c.page_table[0]) == PAGES)


def test_bucket_overhang_spills_into_trash_page():
    """A prefill bucket larger than the reservation writes its dead
    padding pages into the trash page, not into anyone's data."""
    c = _cache()
    victim = _rand((LAYERS, KVH, PS, D), 3)
    c = kv_cache.insert_pages(c, 0, victim, victim, PS, _row([2]))
    # slot 1 inserts a 3-page slab but owns only 1 page: pages 1-2 of
    # the slab overhang into the trash page
    k = _rand((LAYERS, KVH, 3 * PS, D), 4)
    c = kv_cache.insert_pages(c, 1, k, k, 3, _row([5]))
    np.testing.assert_array_equal(np.asarray(c.k[2]), np.asarray(victim))
    np.testing.assert_array_equal(np.asarray(c.k[5]),
                                  np.asarray(k[:, :, :PS]))
    assert np.asarray(c.capacity).tolist() == [PS, PS, 0]


def test_append_crosses_page_boundary():
    c = _cache()
    k = _rand((LAYERS, KVH, PS, D), 1)
    c = kv_cache.insert_pages(c, 0, k, k, PS - 1, _row([0, 3]))
    tok1 = _rand((SLOTS, KVH, D), 5)
    tok2 = _rand((SLOTS, KVH, D), 6)
    for layer in range(LAYERS):
        c = kv_cache.append_layer(c, layer, tok1, tok1)
    c, _ = kv_cache.advance(c, jnp.asarray([True, False, False]))
    for layer in range(LAYERS):
        c = kv_cache.append_layer(c, layer, tok2, tok2)
    c, _ = kv_cache.advance(c, jnp.asarray([True, False, False]))
    # token 1 filled the last row of page 0; token 2 opened page 3
    np.testing.assert_array_equal(
        np.asarray(c.k[0, :, :, PS - 1]),
        np.broadcast_to(np.asarray(tok1[0]), (LAYERS, KVH, D)))
    np.testing.assert_array_equal(
        np.asarray(c.k[3, :, :, 0]),
        np.broadcast_to(np.asarray(tok2[0]), (LAYERS, KVH, D)))
    assert np.asarray(c.lengths)[0] == PS + 1


def test_advance_truncates_at_capacity_and_protects_pages():
    c = _cache()
    k = _rand((LAYERS, KVH, PS, D), 1)
    c = kv_cache.insert_pages(c, 0, k, k, PS - 1, _row([2]))  # cap PS
    tok = _rand((SLOTS, KVH, D), 7)
    for layer in range(LAYERS):
        c = kv_cache.append_layer(c, layer, tok, tok)
    c, trunc = kv_cache.advance(c, jnp.asarray([True, False, False]))
    assert np.asarray(trunc).tolist() == [False, False, False]
    assert np.asarray(c.lengths)[0] == PS
    # at capacity: the append clamps into the trash page, advance
    # reports truncation, the owned page keeps its data
    page2 = np.asarray(c.k[2]).copy()
    for layer in range(LAYERS):
        c = kv_cache.append_layer(c, layer, tok * 9, tok * 9)
    c, trunc = kv_cache.advance(c, jnp.asarray([True, False, False]))
    assert np.asarray(trunc).tolist() == [True, False, False]
    assert np.asarray(c.lengths)[0] == PS            # clamped
    np.testing.assert_array_equal(np.asarray(c.k[2]), page2)


def test_evict_zeroes_metadata_and_reparks_page_row():
    c = _cache()
    k = _rand((LAYERS, KVH, PS, D), 1)
    c = kv_cache.insert_pages(c, 1, k, k, 3, _row([0]))
    c = kv_cache.evict(c, 1)
    assert np.asarray(c.lengths).tolist() == [0, 0, 0]
    assert np.asarray(c.capacity).tolist() == [0, 0, 0]
    # the row re-parks on the trash page so the idle slot's future
    # appends cannot chase the freed page into its next owner
    assert np.all(np.asarray(c.page_table[1]) == c.null_page)
    # data untouched (masked; the allocator reclaims page 0 host-side)
    np.testing.assert_array_equal(np.asarray(c.k[0]), np.asarray(k))


def test_retired_slot_append_cannot_corrupt_reassigned_page():
    """Regression (review finding): slot 0 is retired and its page is
    reassigned to slot 1; slot 0's still-running masked decode appends
    must land in the trash page, not in slot 1's new data."""
    c = _cache()
    a = _rand((LAYERS, KVH, PS, D), 1)
    c = kv_cache.insert_pages(c, 0, a, a, 2, _row([3]))
    c = kv_cache.evict(c, 0)                 # retire; page 3 reclaimed
    b = _rand((LAYERS, KVH, PS, D), 2)
    c = kv_cache.insert_pages(c, 1, b, b, 3, _row([3]))  # reassigned
    tok = jnp.full((SLOTS, KVH, D), 7.0)
    for layer in range(LAYERS):
        c = kv_cache.append_layer(c, layer, tok, tok)
    c, _ = kv_cache.advance(c, jnp.asarray([True, True, False]))
    got = np.asarray(c.k[3])
    want = np.asarray(b).copy()
    want[:, :, 3] = 7.0                      # slot 1's own append only
    np.testing.assert_array_equal(got, want)


def test_advance_does_not_flag_empty_active_slots_truncated():
    """Regression (review finding): an active-but-never-admitted paged
    slot (capacity 0) is empty, not a truncated stream."""
    c = _cache()
    k = _rand((LAYERS, KVH, PS, D), 1)
    c = kv_cache.insert_pages(c, 0, k, k, 1, _row([0]))
    c, trunc = kv_cache.advance(c, jnp.ones((SLOTS,), bool))
    assert np.asarray(trunc).tolist() == [False, False, False]
    assert np.asarray(c.lengths).tolist() == [2, 0, 0]


def test_insert_validates():
    c = _cache()
    good = _rand((LAYERS, KVH, PS, D))
    with pytest.raises(ValueError, match="prefill k/v"):
        kv_cache.insert_pages(c, 0, _rand((LAYERS, KVH + 1, PS, D)),
                              _rand((LAYERS, KVH + 1, PS, D)), 3,
                              _row([0]))
    with pytest.raises(ValueError, match="multiple of page_size"):
        kv_cache.insert_pages(c, 0, _rand((LAYERS, KVH, PS + 1, D)),
                              _rand((LAYERS, KVH, PS + 1, D)), 3,
                              _row([0]))
    with pytest.raises(ValueError, match="page row"):
        kv_cache.insert_pages(c, 0, good, good, 3,
                              np.zeros((MPPS + 1,), np.int32))
    with pytest.raises(ValueError, match="exceed max_pages_per_slot"):
        kv_cache.page_row(list(range(MPPS + 1)), MPPS, PAGES)


def test_updates_are_donation_safe():
    """insert+append+advance jit with the pool donated — one
    allocation for the engine's lifetime, like the dense cache."""

    def step(c, slab, tok, row):
        c = kv_cache.insert_pages(c, 0, slab, slab, 3, row)
        for layer in range(LAYERS):
            c = kv_cache.append_layer(c, layer, tok, tok)
        c, _ = kv_cache.advance(c, jnp.ones((SLOTS,), bool))
        return c

    c = _cache()
    kbuf, tbuf = c.k, c.page_table
    slab = _rand((LAYERS, KVH, PS, D), 1)
    tok = _rand((SLOTS, KVH, D), 2)
    c2 = jax.jit(step, donate_argnums=(0,))(c, slab, tok,
                                            jnp.asarray(_row([0, 1])))
    jax.block_until_ready(c2)
    assert kbuf.is_deleted() and tbuf.is_deleted()
    # slots 1/2 own no pages (capacity 0): advance holds them at 0 —
    # un-admitted slots can't drift, unlike the dense cache's clamp
    assert np.asarray(c2.lengths).tolist() == [4, 0, 0]


def test_pool_is_scan_carryable():
    def body(c, tok):
        for layer in range(LAYERS):
            c = kv_cache.append_layer(c, layer, tok, tok)
        c, _ = kv_cache.advance(c, jnp.ones((SLOTS,), bool))
        return c, c.lengths

    c = _cache()
    slab = _rand((LAYERS, KVH, PS, D), 1)
    c = kv_cache.insert_pages(c, 0, slab, slab, 0, _row([0, 1]))
    c = kv_cache.insert_pages(c, 1, slab, slab, 0, _row([2]))
    c = kv_cache.insert_pages(c, 2, slab, slab, 0, _row([3]))
    toks = _rand((4, SLOTS, KVH, D), 7)
    c, hist = jax.lax.scan(body, c, toks)
    assert np.asarray(c.lengths).tolist() == [4, 4, 4]
    assert hist.shape == (4, SLOTS)


# --------------------------------------------------------------------------
# token-granular suffix insert + copy-on-write (ISSUE 12)
# --------------------------------------------------------------------------

#: the three pool layouts a prefill writes: K/V, a latent pool with no
#: values, K/V beside index keys (widths of the latent row / index key)
_LAYOUTS = {"kv": {}, "latent": {"latent": 12}, "index": {"index": 6}}


def _filled(layout):
    """A cache of ``layout`` whose every pool row holds data: what a write
    must keep or replace is then visible row by row."""
    c = _cache(**_LAYOUTS[layout])
    return c.replace(**{name: _rand(getattr(c, name).shape, seed)
                        for seed, name in enumerate(("k", "v", "ik"), 20)
                        if getattr(c, name) is not None})


def _slabs(c, s, seed):
    """A prefill's ``(k, v, ik)`` of ``s`` positions for cache ``c``."""
    rows = (LAYERS, s, c.head_dim) if c.latent else (LAYERS, KVH, s, D)
    return (_rand(rows, seed), None if c.latent else _rand(rows, seed + 1),
            None if c.ik is None else _rand((LAYERS, s, c.ik.shape[2]),
                                            seed + 2))


def _rmw_pools(c, k, v, row, start, ik):
    """The pools after the read-modify-write of the pages a slab touches,
    whatever its start: the reference the aligned write is held to."""
    ps, s = c.page_size, k.shape[-2]
    ids = kv_cache._slot_pages(c, row, start // ps, -(-s // ps) + 1)
    return kv_cache._pools(c, lambda pool, x: kv_cache._write_pages(
        pool, ids, kv_cache._as_pages(pool, kv_cache._rows_in_pages(
            pool, x, ids, start % ps), ids.shape[0])), k, v, ik)


#: slab length and pages the slot owns, by case: a slab inside the
#: reservation with an owned page after it; one overhanging the
#: reservation into the trash page; one overhanging the virtual window,
#: whose rows past it are dropped, not clamped
_OVERHANG = {"within": (PS, MPPS), "reservation": (2 * PS, None),
             "window": (MPPS * PS, MPPS)}


@pytest.mark.parametrize("traced", [False, True], ids=["static", "traced"])
@pytest.mark.parametrize("case", sorted(_OVERHANG))
@pytest.mark.parametrize("start", [0, PS, 2 * PS, PS + 1])
@pytest.mark.parametrize("layout", sorted(_LAYOUTS))
def test_aligned_write_matches_the_read_modify_write(layout, start, case,
                                                     traced):
    """A slab that starts on a page boundary is written with one scatter of
    whole pages, and every row it leaves live is bitwise what the
    read-modify-write of the pages it touches leaves: every page but the
    trash page, the page table, lengths and capacity are identical.  A
    start mid-page still takes the read-modify-write (the prefix rows
    below it in its boundary page are kept).  ``static``: a python start,
    as a kind that never resumes passes; ``traced``: a jitted one, which
    the insert's ``cond`` decides on."""
    c = _filled(layout)
    s, owned = _OVERHANG[case]
    owned = owned or start // PS + 1
    row = _row([5, 0, 3, 1][:owned])
    k, v, ik = _slabs(c, s, 7)
    length = min(start + s - 1, owned * PS)
    insert = (jax.jit(kv_cache.insert_tokens) if traced
              else kv_cache.insert_tokens)
    got = insert(c, 1, k, v, length, row, start, ik)
    want = _rmw_pools(c, k, v, jnp.asarray(row), start, ik)
    for name in ("k", "v", "ik"):
        if getattr(c, name) is not None:
            np.testing.assert_array_equal(
                np.asarray(getattr(got, name))[:-1],
                np.asarray(want[name])[:-1], err_msg=name)
    assert np.asarray(got.page_table).tolist() == [
        [PAGES] * MPPS, row.tolist(), [PAGES] * MPPS]
    assert np.asarray(got.lengths).tolist() == [0, length, 0]
    assert np.asarray(got.capacity).tolist() == [0, owned * PS, 0]
    # the slab's first row landed at its start, in its first page, and a
    # mid-page start kept the rows below it there (row axis leading)
    page = row[start // PS]
    rows = lambda pool: np.moveaxis(  # noqa: E731
        np.asarray(pool)[page], -1 if c.latent else 2, 0)
    np.testing.assert_array_equal(rows(got.k)[start % PS],
                                  np.moveaxis(np.asarray(k), -2, 0)[0])
    np.testing.assert_array_equal(rows(got.k)[:start % PS],
                                  rows(c.k)[:start % PS])
    if start == 0 and not traced:
        # a cold prompt's insert is this write
        cold = kv_cache.insert_pages(c, 1, k, v, length, row, ik)
        np.testing.assert_array_equal(np.asarray(cold.k), np.asarray(got.k))


def test_insert_tokens_mid_page_preserves_earlier_rows():
    """An unaligned suffix insert (a prefix-cache hit resuming mid-page
    after its boundary COW) writes rows [start % PS, ...) of the
    boundary page and leaves the copied prefix rows below untouched."""
    c = _cache()
    base = _rand((LAYERS, KVH, PS, D), 3)
    c = kv_cache.insert_pages(c, 0, base, base, PS, _row([2]))
    before = np.asarray(c.k[2]).copy()
    # resume at start = PS - 2: the slab's first rows land at offsets
    # PS-2, PS-1 of page 2, then roll into page 5
    slab = _rand((LAYERS, KVH, PS, D), 4)
    start = PS - 2
    c = kv_cache.insert_tokens(c, 0, slab, slab, start + PS,
                               _row([2, 5]), start)
    got = np.asarray(c.k[2])
    np.testing.assert_array_equal(got[:, :, :PS - 2],
                                  before[:, :, :PS - 2])   # kept
    np.testing.assert_array_equal(got[:, :, PS - 2:],
                                  np.asarray(slab[:, :, :2]))
    np.testing.assert_array_equal(np.asarray(c.k[5])[:, :, :PS - 2],
                                  np.asarray(slab[:, :, 2:PS]))
    assert np.asarray(c.lengths)[0] == start + PS
    assert np.asarray(c.capacity)[0] == 2 * PS


def test_insert_tokens_overhang_spills_into_trash_page():
    """Bucket positions beyond the reservation clamp into the trash
    page, exactly like the slab insert's overhang."""
    c = _cache()
    victim = _rand((LAYERS, KVH, PS, D), 5)
    c = kv_cache.insert_pages(c, 0, victim, victim, PS, _row([2]))
    slab = _rand((LAYERS, KVH, 3 * PS, D), 6)
    c = kv_cache.insert_tokens(c, 1, slab, slab, 3, _row([5]), 0)
    np.testing.assert_array_equal(np.asarray(c.k[2]), np.asarray(victim))
    np.testing.assert_array_equal(np.asarray(c.k[5]),
                                  np.asarray(slab[:, :, :PS]))
    assert np.asarray(c.capacity).tolist() == [PS, PS, 0]


def test_insert_tokens_full_window_overhang_is_dropped_not_clamped():
    """Regression (review finding): when the slab overhangs past the
    END of the virtual window (a prompt filling the whole per-slot
    window, e.g. an exact-repeat hit at max_seq), the overhang rows are
    DROPPED — clamping them onto the last owned position would clobber
    the real last token's KV with padding garbage."""
    c = _cache()
    base = _rand((LAYERS, KVH, MPPS * PS, D), 9)
    full_row = _row([0, 1, 2, 3])
    c = kv_cache.insert_pages(c, 0, base, base, MPPS * PS, full_row)
    # re-insert the LAST position only, with a bucket overhanging the
    # window end: positions MPPS*PS .. beyond must vanish
    slab = _rand((LAYERS, KVH, PS, D), 10)
    c = kv_cache.insert_tokens(c, 0, slab, slab, MPPS * PS, full_row,
                               MPPS * PS - 1)
    got = np.asarray(c.k[3])
    np.testing.assert_array_equal(got[:, :, PS - 1],
                                  np.asarray(slab)[:, :, 0])  # real row
    np.testing.assert_array_equal(got[:, :, :PS - 1],
                                  np.asarray(base)[:, :, -PS:-1])
    # the other owned pages are untouched by the dropped overhang
    np.testing.assert_array_equal(np.asarray(c.k[0]),
                                  np.asarray(base)[:, :, :PS])


def test_cow_page_copies_rows_and_isolates_writers():
    """cow_page duplicates a physical page; the copy's owner can then
    be written without perturbing the original — the write barrier
    behind shared-boundary-page admission."""
    c = _cache()
    base = _rand((LAYERS, KVH, PS, D), 7)
    c = kv_cache.insert_pages(c, 0, base, base, PS - 1, _row([3]))
    c = kv_cache.cow_page(c, 3, 0)
    np.testing.assert_array_equal(np.asarray(c.k[0]), np.asarray(c.k[3]))
    np.testing.assert_array_equal(np.asarray(c.v[0]), np.asarray(c.v[3]))
    # slot 1 maps the COPY and overwrites its tail; page 3 is untouched
    slab = _rand((LAYERS, KVH, PS, D), 8)
    c = kv_cache.insert_tokens(c, 1, slab, slab, PS, _row([0]), PS - 1)
    np.testing.assert_array_equal(np.asarray(c.k[3]), np.asarray(base))
    got = np.asarray(c.k[0])
    np.testing.assert_array_equal(got[:, :, :PS - 1],
                                  np.asarray(base)[:, :, :PS - 1])
    np.testing.assert_array_equal(got[:, :, PS - 1],
                                  np.asarray(slab)[:, :, 0])


def test_cow_page_is_donation_safe():
    def step(c):
        return kv_cache.cow_page(c, jnp.int32(1), jnp.int32(0))

    c = _cache()
    kbuf = c.k
    c2 = jax.jit(step, donate_argnums=(0,))(c)
    jax.block_until_ready(c2)
    assert kbuf.is_deleted()


# --------------------------------------------------------------------------
# host-side page allocator
# --------------------------------------------------------------------------

def test_allocator_acquire_release_reuse():
    al = kv_cache.PageAllocator(4, PS, MPPS)
    a = al.acquire(2)
    b = al.acquire(2)
    assert sorted(a + b) == [0, 1, 2, 3]
    assert al.acquire(1) is None          # exhausted -> backpressure
    al.release(a)
    c = al.acquire(2)
    assert sorted(c) == sorted(a)         # released pages come back
    assert al.free_pages == 0


def test_allocator_share_refcounts_and_last_owner_frees():
    """The ISSUE 12 sharing contract: share() adds one owner per call,
    release() drops one, and the page reaches the free list exactly
    when its LAST owner lets go — N sharers of one page pin ONE page."""
    al = kv_cache.PageAllocator(4, PS, MPPS)
    [pid] = al.acquire(1)
    al.share([pid])                       # second owner
    al.share([pid])                       # third owner
    assert al.refcount(pid) == 3
    assert (al.live_pages, al.free_pages) == (1, 3)   # ONE page pinned
    assert al.weighted_live() == 3        # ...by three owners
    assert al.shared_pages() == 1
    al.release([pid])
    al.release([pid])
    assert al.refcount(pid) == 1          # survivors keep it alive
    assert al.free_pages == 3
    al.release([pid])                     # last owner
    assert al.refcount(pid) == 0
    assert al.free_pages == 4
    with pytest.raises(ValueError, match="not outstanding"):
        al.share([pid])                   # sharing a freed page raises


def test_allocator_interleaved_retire_admit_leaks_nothing():
    """200-step fragmentation sweep WITH prefix sharing and COW
    (ISSUE 12 satellite): interleaved acquire/share/release of uneven
    requests — where a 'hit' takes extra references on a random live
    holder's leading pages and a 'COW' acquires a private copy page —
    returns the pool to fully-free.  At every step: no page is issued
    twice concurrently, distinct live + free == total (conservation),
    and the refcount-weighted live count equals the sum of every
    holder's page list."""
    total = 8
    al = kv_cache.PageAllocator(total, PS, MPPS)
    held = {}                              # uid -> list of page refs
    rng = np.random.RandomState(0)
    uid = 0
    for _ in range(200):
        r = rng.rand()
        if held and (r < 0.4 or al.free_pages == 0):
            k = list(held)[rng.randint(len(held))]
            al.release(held.pop(k))        # retire: release EVERY ref
        elif held and r < 0.6:
            # prefix hit: share a random holder's leading pages, then
            # acquire a private tail (suffix + COW boundary copy)
            src = held[list(held)[rng.randint(len(held))]]
            n_share = int(rng.randint(1, len(src) + 1))
            shared = src[:n_share]
            priv = al.acquire(int(rng.randint(1, 3)))
            if priv is not None:
                al.share(shared)
                held[uid] = list(shared) + priv
                uid += 1
        else:
            got = al.acquire(int(rng.randint(1, 4)))
            if got is not None:
                held[uid] = got
                uid += 1
        for ids in held.values():          # no double issue WITHIN one
            assert len(ids) == len(set(ids))
        live = {p for ids in held.values() for p in ids}
        assert len(live) == al.live_pages
        assert al.live_pages + al.free_pages == total   # conservation
        weighted = sum(len(ids) for ids in held.values())
        assert al.weighted_live() == weighted
    for ids in held.values():
        al.release(ids)
    assert al.free_pages == total
    assert al.live_pages == 0 and al.weighted_live() == 0


def test_allocator_eviction_returns_all_pages_and_rejects_double_release():
    al = kv_cache.PageAllocator(6, PS, MPPS)
    ids = al.acquire(3)
    al.release(ids)                       # retire returns EVERY page
    assert al.free_pages == 6
    with pytest.raises(ValueError, match="not outstanding"):
        al.release(ids)                   # double release, loudly
    with pytest.raises(ValueError, match="not outstanding"):
        al.release([99])                  # foreign page likewise


def test_allocator_pages_needed_rounds_and_clamps():
    al = kv_cache.PageAllocator(8, 4, 3)
    assert al.pages_needed(1) == 1
    assert al.pages_needed(4) == 1
    assert al.pages_needed(5) == 2
    assert al.pages_needed(400) == 3      # clamped to the table width
