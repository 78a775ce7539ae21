"""Tiered KV memory (ISSUE 18): the host-DRAM page tier under the
paged pool — copy-program semantics, the byte-budgeted host store, the
prefix cache's two-state edges (offload / resurrection / host-LRU),
and the scheduler's swap-in-before-prefill path, at tp=1 and tp=2.

The conservation laws walked here every step:

* allocator: ``distinct live + free == num_pages``
* ownership: ``weighted_live == sum(holder refs) + prefix pinned``
* tier mirror: ``prefix.host_pages == store.pages``
* disjoint tiers: no page id both HBM-pinned by the cache and
  host-resident
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.inference import InferenceEngine, SlotScheduler, kv_cache
from apex_tpu.inference.prefix_cache import PrefixCache
from apex_tpu.observability import MetricsRegistry, ServeTelemetry
from apex_tpu.transformer import parallel_state
from apex_tpu.transformer.testing import GPTConfig, gpt_model_provider

LAYERS, KVH, PS, D, SLOTS, MPPS, PAGES = 2, 2, 4, 8, 3, 4, 6


def _cache(dtype=jnp.float32):
    return kv_cache.init_paged_cache(PAGES, LAYERS, KVH, PS, D,
                                     slots=SLOTS,
                                     max_pages_per_slot=MPPS,
                                     dtype=dtype)


def _fill(c, seed=0):
    rng = np.random.RandomState(seed)
    shape = (PAGES + 1, LAYERS, KVH, PS, D)
    return c.replace(k=jnp.asarray(rng.randn(*shape), c.k.dtype),
                     v=jnp.asarray(rng.randn(*shape), c.v.dtype))


# --------------------------------------------------------------------------
# the two copy programs
# --------------------------------------------------------------------------

def test_extract_restore_roundtrip_moves_pages():
    c = _fill(_cache())
    k0, v0 = np.asarray(c.k), np.asarray(c.v)
    ks, vs, _ = kv_cache.extract_pages(c, jnp.asarray([4, 1], jnp.int32))
    np.testing.assert_array_equal(np.asarray(ks), k0[[4, 1]])
    np.testing.assert_array_equal(np.asarray(vs), v0[[4, 1]])
    # restore the slabs at DIFFERENT pages: content lands there bitwise
    c2 = kv_cache.restore_pages(c, jnp.asarray([0, 3], jnp.int32),
                                ks, vs)
    np.testing.assert_array_equal(np.asarray(c2.k[0]), k0[4])
    np.testing.assert_array_equal(np.asarray(c2.k[3]), k0[1])
    np.testing.assert_array_equal(np.asarray(c2.v[3]), v0[1])
    # untouched pages stay bitwise
    np.testing.assert_array_equal(np.asarray(c2.k[2]), k0[2])


def test_extract_pads_with_trash_restore_drops_oob():
    """The fixed-width batch contract: extract's padding lanes read the
    trash page (in-bounds garbage the host slices off), restore's
    padding lanes carry an out-of-bounds id and DROP — neither padding
    direction can touch live data."""
    c = _fill(_cache())
    k0 = np.asarray(c.k)
    ks, _, _ = kv_cache.extract_pages(
        c, jnp.asarray([2, PAGES, PAGES], jnp.int32))   # trash-padded
    np.testing.assert_array_equal(np.asarray(ks)[0], k0[2])
    # restore with OOB sentinel ids: whole cache stays bitwise
    slab = jnp.zeros((2, LAYERS, KVH, PS, D), c.k.dtype)
    oob = jnp.asarray([PAGES + 1, PAGES + 1], jnp.int32)
    c2 = kv_cache.restore_pages(c, oob, slab, slab)
    np.testing.assert_array_equal(np.asarray(c2.k), k0)


def test_restore_pages_is_donation_safe():
    def step(c, ids, ks, vs):
        return kv_cache.restore_pages(c, ids, ks, vs)

    c = _fill(_cache())
    kbuf = c.k
    slab = jnp.ones((1, LAYERS, KVH, PS, D), c.k.dtype)
    c2 = jax.jit(step, donate_argnums=(0,))(
        c, jnp.asarray([1], jnp.int32), slab, slab)
    jax.block_until_ready(c2)
    assert kbuf.is_deleted()


def test_copy_program_validation():
    c = _cache()
    with pytest.raises(ValueError, match="rank-1"):
        kv_cache.extract_pages(c, jnp.zeros((2, 2), jnp.int32))
    bad = jnp.zeros((2, LAYERS, KVH, PS + 1, D), c.k.dtype)
    with pytest.raises(ValueError, match="slab"):
        kv_cache.restore_pages(c, jnp.asarray([0, 1], jnp.int32),
                               bad, bad)


# --------------------------------------------------------------------------
# the host store's byte ledger
# --------------------------------------------------------------------------

def test_host_store_budget_and_handles():
    st = kv_cache.HostPageStore(3 * 128, 128)
    assert st.fits(3) and not st.fits(4)
    a = st.put(np.ones(2), np.ones(2))
    b = st.put(np.zeros(2), np.zeros(2))
    assert (st.pages, st.bytes_used) == (2, 256)
    st.put(None, None)
    with pytest.raises(ValueError, match="over budget"):
        st.put(None, None)                   # caller makes room FIRST
    k, _ = st.get(a)
    np.testing.assert_array_equal(k, np.ones(2))
    assert st.pop(b) is not None
    assert st.pop(b) is None                 # second pop: race-tolerant
    with pytest.raises(KeyError):
        st.get(b)
    assert st.pages == 2


def test_host_store_validation():
    with pytest.raises(ValueError):
        kv_cache.HostPageStore(-1, 128)
    with pytest.raises(ValueError):
        kv_cache.HostPageStore(0, 0)


def test_default_swap_batch_pages_env(monkeypatch):
    monkeypatch.delenv("APEX_TPU_SWAP_BATCH_PAGES", raising=False)
    assert kv_cache.default_swap_batch_pages() == 8
    monkeypatch.setenv("APEX_TPU_SWAP_BATCH_PAGES", "4")
    assert kv_cache.default_swap_batch_pages() == 4
    monkeypatch.setenv("APEX_TPU_SWAP_BATCH_PAGES", "0")
    with pytest.raises(ValueError):
        kv_cache.default_swap_batch_pages()


# --------------------------------------------------------------------------
# prefix-cache two-state edges (books only: fake offload)
# --------------------------------------------------------------------------

def _tiered(total=8, budget_pages=8):
    al = kv_cache.PageAllocator(total, PS, MPPS)
    st = kv_cache.HostPageStore(budget_pages * 128, 128)
    pc = PrefixCache(al, host_store=st,
                     offload=lambda ids: [st.put(i, i) for i in ids])
    return al, st, pc


def _books_ok(al, st, pc, holders=()):
    assert al.live_pages + al.free_pages == al.num_pages
    held = sum(len(ids) for ids in holders)
    assert al.weighted_live() == held + pc.pinned_pages
    assert pc.host_pages == st.pages
    # walk the tree: HBM pages distinct and counted; tiers disjoint
    hbm, host = [], []

    def walk(node):
        for e in node.partials.values():
            hbm.append(e.page)
        for e in node.children.values():
            (host if e.page is None else hbm).append(
                e.host if e.page is None else e.page)
            walk(e.child)

    walk(pc._root)
    assert len(hbm) == len(set(hbm)) == pc.pinned_pages
    assert len(host) == pc.host_pages


def test_evict_offloads_full_pages_and_discards_partials():
    al, st, pc = _tiered()
    toks = list(range(2 * PS + 2))               # 2 full pages + tail
    ids = al.acquire(3)
    pc.insert(toks, ids)
    al.release(ids)                              # request retires
    freed = pc.evict_lru(al.num_pages)
    assert freed == 3
    assert pc.host_pages == st.pages == 2        # partial discarded
    assert pc.swapped_out == 2 and pc.pinned_pages == 0
    _books_ok(al, st, pc)
    # match_tiered reports the host ordinals; match() truncates to 0
    c, pages, host = pc.match_tiered(toks)
    assert c == 2 * PS and pages == [-1, -1]
    assert [j for j, _ in host] == [0, 1]
    assert pc.match(toks) == (0, [])


def test_insert_resurrects_host_edges():
    al, st, pc = _tiered()
    toks = list(range(2 * PS))
    ids = al.acquire(2)
    pc.insert(toks, ids)
    al.release(ids)
    pc.evict_lru(al.num_pages)
    assert pc.host_pages == 2
    # a new request recomputed/swapped the same prefix into fresh pages
    fresh = al.acquire(2)
    new = pc.insert(toks, fresh)
    assert new == 2 and pc.host_pages == 0 and st.pages == 0
    c, pages, host = pc.match_tiered(toks)
    assert c == 2 * PS and pages == list(fresh) and host == []
    al.release(fresh)
    _books_ok(al, st, pc)


def test_host_budget_evicts_lru_leaves_then_trims():
    """A host budget of 2 pages holding a 3-page offload: the LRU host
    leaf drops to make room, and victims that still don't fit are
    discarded (oldest first) exactly as before the tier existed."""
    al, st, pc = _tiered(total=8, budget_pages=2)
    a = al.acquire(2)
    pc.insert(list(range(2 * PS)), a)
    al.release(a)
    pc.evict_lru(al.num_pages)                   # 2 pages parked
    assert st.pages == 2 and not st.fits(1)
    b = al.acquire(3)
    pc.insert([100 + t for t in range(3 * PS)], b)
    al.release(b)
    pc.evict_lru(al.num_pages)
    # room for 2 of the 3 new victims: host LRU dropped the old leaf
    # chain entirely (leaf-first), the oldest new victim was trimmed
    assert st.pages == 2 == pc.host_pages
    assert pc.host_evictions >= 1
    _books_ok(al, st, pc)


def test_tier_invariant_below_host_all_host():
    """Eviction drains a chain bottom-up (an interior edge is
    evictable only once its subtree holds no HBM pages), so a host
    edge never sits above an HBM edge and the host LRU always finds a
    true leaf to drop."""
    al, st, pc = _tiered()
    ids = al.acquire(3)
    pc.insert(list(range(3 * PS)), ids)
    al.release(ids)

    def check(node, above_host):
        for e in node.children.values():
            if above_host:
                assert e.page is None
            check(e.child, above_host or e.page is None)

    # one page at a time: the leaf goes host first, then its parent,
    # then the root edge — the invariant holds at every partial state
    for want_host in (1, 2, 3):
        assert pc.evict_lru(1) == 1
        assert pc.host_pages == want_host
        check(pc._root, False)
        _books_ok(al, st, pc)
    assert pc.pinned_pages == 0 and al.free_pages == al.num_pages


def test_clear_drops_both_tiers():
    al, st, pc = _tiered()
    ids = al.acquire(3)
    pc.insert(list(range(2 * PS + 1)), ids)
    al.release(ids)
    pc.evict_lru(1)
    pc.clear()
    assert (pc.pinned_pages, pc.host_pages, st.pages) == (0, 0, 0)
    assert al.free_pages == al.num_pages


def test_churn_sweep_conserves_across_tiers():
    """The ISSUE 12 200-step fragmentation sweep extended with
    eviction-to-host and swap-back (ISSUE 18 satellite): interleaved
    admissions (tiered matching, positional assembly, resurrection),
    retires, backpressure evictions, and a small host budget forcing
    host-LRU drops — every conservation law checked at EVERY step."""
    total = 8
    al, st, pc = _tiered(total=total, budget_pages=4)
    held = {}
    rng = np.random.RandomState(7)
    protos = [list(range(40, 40 + 3 * PS)),
              list(range(80, 80 + 2 * PS))]
    uid = 0
    for step in range(200):
        r = rng.rand()
        if held and (r < 0.35 or al.free_pages == 0):
            al.release(held.pop(list(held)[rng.randint(len(held))]))
        elif r < 0.75:
            toks = protos[rng.randint(2)][:int(rng.randint(PS, 3 * PS))]
            toks = toks + [int(t) for t in rng.randint(0, 30, 3)]
            covered, mpages, host = pc.match_tiered(toks)
            n_cov = -(-covered // PS)
            mpages, host = mpages[:n_cov], [h for h in host
                                            if h[0] < n_cov]
            host_map = dict(host)
            shared = [mpages[j] for j in range(covered // PS)
                      if j not in host_map]
            need = -(-len(toks) // PS)
            priv = al.acquire(need - len(shared))
            if priv is None:
                pc.evict_lru(need - len(shared))
                continue
            for _, h in host:
                st.get(h)                        # slabs still there
            al.share(shared)
            q, row = list(priv), []
            for j in range(need):
                if j < covered // PS and j not in host_map:
                    row.append(mpages[j])
                else:
                    row.append(q.pop(0))
            pc.insert(toks, row)
            held[uid] = row
            uid += 1
        else:
            pc.evict_lru(int(rng.randint(1, 3)))
        _books_ok(al, st, pc, holders=held.values())
    for ids in held.values():
        al.release(ids)
    pc.evict_lru(al.num_pages)
    _books_ok(al, st, pc)
    assert al.free_pages == total
    assert pc.swapped_out > 0 and pc.host_evictions > 0


# --------------------------------------------------------------------------
# engine wiring
# --------------------------------------------------------------------------

def _engine(tp=None, **kw):
    parallel_state.destroy_model_parallel()
    parallel_state.initialize_model_parallel(1)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                    num_attention_heads=2, max_seq_length=64,
                    hidden_dropout=0.0, attention_dropout=0.0)
    model = gpt_model_provider(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))
    return InferenceEngine("gpt", cfg, params, slots=2, max_seq=64,
                           page_size=8, num_pages=16,
                           cache_dtype=jnp.float32, tp=tp, **kw)


def _tel():
    return ServeTelemetry(MetricsRegistry())


PREFIX = list((np.arange(24) * 7 + 3) % 64)       # 3 full pages


def test_engine_rejects_tier_on_dense_and_bad_values():
    parallel_state.destroy_model_parallel()
    parallel_state.initialize_model_parallel(1)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                    num_attention_heads=2, max_seq_length=64,
                    hidden_dropout=0.0, attention_dropout=0.0)
    model = gpt_model_provider(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))
    with pytest.raises(ValueError, match="paged"):
        InferenceEngine("gpt", cfg, params, slots=2, max_seq=64,
                        host_tier_bytes=1 << 20)
    with pytest.raises(ValueError):
        _engine(host_tier_bytes=-1)
    with pytest.raises(ValueError):
        _engine(host_tier_bytes=1 << 20, swap_batch_pages=0)
    eng = _engine()                               # default: tier off
    assert eng.host_tier_bytes == 0
    tel = _tel()
    sched = SlotScheduler(eng, telemetry=tel)
    assert sched.host_store is None


def test_swap_batch_dispatch_counts_and_page_host_bytes():
    eng = _engine(host_tier_bytes=1 << 20, swap_batch_pages=2)
    # page_host_bytes is the GLOBAL page footprint: 2 buffers x layers
    # x kv_heads x page_size x head_dim x itemsize
    assert eng.page_host_bytes() == 2 * 1 * 2 * 8 * 16 * 4
    cache = eng.init_cache()
    ids = list(range(5))                          # 5 pages, batch 2
    # the dispatch counters live on the GLOBAL registry: measure the
    # deltas so earlier swap traffic in the process cannot skew them
    out0 = int(eng._swap_out_dispatches.total())
    in0 = int(eng._swap_in_dispatches.total())
    k, v = eng.swap_out_pages(cache, ids)
    assert k.shape == (5, 1, 2, 8, 16)
    reg = eng._swap_out_dispatches
    assert int(reg.total()) - out0 == 3           # ceil(5/2) batches
    cache = eng.swap_in_pages(cache, ids, k, v)
    assert int(eng._swap_in_dispatches.total()) - in0 == 3


@pytest.mark.parametrize("tp", [None, 2])
def test_hit_after_eviction_swaps_in_instead_of_recompute(tp):
    """The tentpole end-to-end at tp=1 and tp=2: outputs after
    evict->swap-out->hit->swap-in are bitwise the cold run's, the hit
    is served by uploads (swap counters move, prefix_host_hits fires),
    and every cross-tier book balances after each wave."""
    eng = _engine(tp=tp, host_tier_bytes=1 << 20)
    tel = _tel()
    sched = SlotScheduler(eng, telemetry=tel)

    def books():
        al = sched.alloc
        assert al.live_pages + al.free_pages == al.num_pages
        assert al.weighted_live() == sched.prefix.pinned_pages
        assert sched.prefix.host_pages == sched.host_store.pages

    u0 = sched.submit(PREFIX + [9], max_new_tokens=4)
    ref = sched.run()[u0]
    books()
    freed = sched.prefix.evict_lru(eng.num_pages)
    assert freed == 4 and sched.prefix.host_pages == 3
    assert int(tel.swap_out_pages.total()) == 3
    books()
    u1 = sched.submit(PREFIX + [9], max_new_tokens=4)
    out = sched.run()[u1]
    assert out == ref
    assert int(tel.swap_in_pages.total()) == 3
    assert int(tel.prefix_host_hits.total()) == 1
    assert sched.prefix.host_pages == 0 == sched.host_store.pages
    books()
    # dispatch counters moved under the fixed-width batch contract
    assert int(eng._swap_in_dispatches.total()) >= 1
    assert int(eng._swap_out_dispatches.total()) >= 1


def test_boundary_subpage_match_on_host_edge():
    """A hit whose boundary falls INSIDE a host-resident page: the
    swapped-in copy is request-private (no COW needed), the columns
    past the boundary are masked by prefill_from — outputs match a
    cold scheduler bitwise."""
    eng = _engine(host_tier_bytes=1 << 20)
    long = list((np.arange(32) * 5 + 1) % 64)     # 4 full pages
    probe = long[:28] + [7]                       # boundary at 28

    cold = SlotScheduler(eng, telemetry=_tel(), prefix_cache=False)
    uc = cold.submit(probe, max_new_tokens=4)
    ref = cold.run()[uc]

    tel = _tel()
    sched = SlotScheduler(eng, telemetry=tel)
    sched.submit(long, max_new_tokens=2)
    sched.run()
    sched.prefix.evict_lru(eng.num_pages)
    assert sched.prefix.host_pages == 4
    u = sched.submit(probe, max_new_tokens=4)
    out = sched.run()[u]
    assert out == ref
    assert int(tel.swap_in_pages.total()) == 4    # 3 full + boundary
    assert int(tel.prefix_host_hits.total()) == 1


@pytest.mark.parametrize("tp", [None, 2])
def test_scheduler_churn_waves_conserve(tp):
    """Multi-wave churn through the real engine at both widths:
    admissions, eviction-to-host between waves, swap-back hits, host
    books replicated under tp — conservation after every wave."""
    eng = _engine(tp=tp, host_tier_bytes=1 << 20)
    tel = _tel()
    sched = SlotScheduler(eng, telemetry=tel)
    rng = np.random.RandomState(3)
    outs = {}
    for wave in range(4):
        for j in range(3):
            tail = [int(t) for t in rng.randint(0, 64, 2)]
            sched.submit(PREFIX + tail, max_new_tokens=2)
        outs.update(sched.run())
        al = sched.alloc
        assert al.live_pages + al.free_pages == al.num_pages
        assert al.weighted_live() == sched.prefix.pinned_pages
        assert sched.prefix.host_pages == sched.host_store.pages
        if wave % 2 == 0:
            sched.prefix.evict_lru(eng.num_pages)
            assert sched.prefix.host_pages == sched.host_store.pages
    assert len(outs) == 12
    assert int(tel.swap_in_pages.total()) > 0
    assert int(tel.swap_out_pages.total()) > 0
