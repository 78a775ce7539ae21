"""Shared-prefix serving + SLO-aware scheduling (ISSUE 12 acceptance):

1. N concurrent requests extending one cached prefix hold ONE physical
   copy of the prefix's pages (+ per-request suffix pages) —
   conservation-checked in the allocator mid-flight;
2. sharing changes pages, never tokens: hit streams equal cold streams;
3. an exact-repeat prompt (full-cover hit) COWs its boundary page and
   reproduces the original stream bitwise;
4. retiring one of two prefix-sharing requests leaves the survivor's
   decode output bitwise unchanged (release, never free);
5. chunked prefill interleaves decode steps between chunks (bounded
   consecutive prefill chunks) with goodput conservation intact;
6. priority admission + per-tenant fairness order the queue.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.inference import InferenceEngine, SlotScheduler
from apex_tpu.observability import MetricsRegistry, ServeTelemetry
from apex_tpu.transformer import parallel_state
from apex_tpu.transformer.testing import GPTConfig, gpt_model_provider


@pytest.fixture(autouse=True)
def _single_rank():
    parallel_state.destroy_model_parallel()
    parallel_state.initialize_model_parallel(1)
    yield


@pytest.fixture(scope="module")
def engine():
    parallel_state.destroy_model_parallel()
    parallel_state.initialize_model_parallel(1)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                    num_attention_heads=2, max_seq_length=64,
                    hidden_dropout=0.0, attention_dropout=0.0)
    model = gpt_model_provider(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))
    # f32 cache: the bitwise assertions compare cached-prefix reads
    # against in-program recomputation
    return InferenceEngine("gpt", cfg, params, slots=3, max_seq=64,
                           page_size=8, num_pages=21,
                           cache_dtype=jnp.float32)


def _tel():
    return ServeTelemetry(MetricsRegistry())


PREFIX = list((np.arange(24) * 7 + 3) % 64)          # 3 full pages


def test_sharing_holds_one_prefix_copy_conservation(engine):
    """The capacity multiplier, machine-checked: with 3 concurrent
    requests over a 3-page prefix, the allocator holds the prefix ONCE
    (distinct live pages) while the refcount-weighted view counts every
    owner — and the books balance at every observation point."""
    tel = _tel()
    sched = SlotScheduler(engine, telemetry=tel)
    seed = sched.submit(PREFIX + [1], max_new_tokens=2)
    sched.run()

    c0, ppages = sched.prefix.match(PREFIX)
    assert c0 == 24 and len(ppages) == 3     # the cached prefix pages
    snaps = []
    orig = engine.decode

    def spy(*a, **kw):
        al = sched.alloc
        snaps.append((al.live_pages, al.weighted_live(),
                      al.shared_pages(), al.free_pages,
                      tuple(al.refcount(p) for p in ppages)))
        return orig(*a, **kw)

    engine.decode = spy
    try:
        uids = [sched.submit(PREFIX + [10 + i], max_new_tokens=2)
                for i in range(3)]
        out = sched.run()
    finally:
        engine.decode = orig
    assert sorted(out) == sorted(uids)
    assert int(tel.prefix_hits.total()) == 3
    # every snapshot balances: distinct live + free == pool
    for live, weighted, shared, free_p, _ in snaps:
        assert live + free_p == engine.num_pages
    # at the first decode all 3 hits are in flight: each prefix page is
    # held ONCE physically but by four owners (cache + 3 requests) —
    # cold, 3 requests would have pinned 3 distinct copies
    live, weighted, shared, _, rcs = snaps[0]
    assert rcs == (4, 4, 4)
    assert shared >= 3                       # the prefix's pages
    assert weighted - live >= 3 * 3          # >= 3 extra owners x 3 pages
    assert int(tel.prefix_hit_tokens.total()) == 3 * 24


def test_hit_streams_equal_cold_streams(engine):
    """Sharing is a memory-model change, not a math change."""
    prompts = [PREFIX + [10 + i] for i in range(3)]
    shared = SlotScheduler(engine, telemetry=_tel())
    shared.submit(PREFIX + [1], max_new_tokens=2)
    shared.run()                             # seed the cache
    us = [shared.submit(p, max_new_tokens=4) for p in prompts]
    out_s = shared.run()
    cold = SlotScheduler(engine, telemetry=_tel(), prefix_cache=False)
    uc = [cold.submit(p, max_new_tokens=4) for p in prompts]
    out_c = cold.run()
    assert [out_s[u] for u in us] == [out_c[u] for u in uc]


def test_exact_repeat_cow_reproduces_stream_bitwise(engine):
    """A fully-cached prompt shares every page, COWs the boundary page
    (its decode appends would otherwise write a page other owners still
    map), re-prefills ONLY the last token — and emits the exact stream
    the cold run emitted."""
    tel = _tel()
    sched = SlotScheduler(engine, telemetry=tel)
    u0 = sched.submit(PREFIX + [1, 2], max_new_tokens=4)
    out0 = sched.run()
    cows0 = int(tel.cow_copies.total())
    u1 = sched.submit(PREFIX + [1, 2], max_new_tokens=4)
    out1 = sched.run()
    assert out1[u1] == out0[u0]
    assert int(tel.cow_copies.total()) == cows0 + 1
    # the hit prefilled only the uncached tail: 26-token prompt,
    # 25 tokens covered
    assert int(tel.prefix_hit_tokens.total()) >= 25


def test_retire_releases_survivor_decode_bitwise_unchanged(engine):
    """ISSUE 12 satellite: retiring one of two prefix-sharing requests
    must only RELEASE its references.  A third request admitted into
    the freed pages afterwards must not perturb the survivor — its
    remaining decode output is bitwise identical to an undisturbed
    run."""
    def run(with_churn):
        sched = SlotScheduler(engine, telemetry=_tel())
        sched.submit(PREFIX + [1], max_new_tokens=2)
        sched.run()                          # seed
        survivor = sched.submit(PREFIX + [2], max_new_tokens=10)
        if with_churn:
            # sharer retires after 2 tokens; its release must not free
            # the shared prefix pages under the survivor
            sched.submit(PREFIX + [3], max_new_tokens=2)
            # filler (distinct prompt) reuses whatever pages actually
            # freed — if a shared page leaked into the free list, the
            # filler's prefill overwrites the survivor's prefix
            sched.submit(list((np.arange(20) * 5 + 1) % 64),
                         max_new_tokens=4)
        out = sched.run()
        return out[survivor]

    assert run(with_churn=True) == run(with_churn=False)


def test_aligned_prefills_are_counted(engine):
    """``serve_prefill_aligned_total`` counts the prefills whose slab
    starts on a page boundary (their K/V go in as whole pages): a cold
    prefill and a chunk at a page boundary, and not the suffix of a hit
    that resumes mid-page.  The engine counts in the process-wide
    registry, which a ``ServeTelemetry`` built on it reads as its own."""
    from apex_tpu import observability as obs
    reg = obs.global_registry()
    aligned = ServeTelemetry(reg).prefill_aligned
    assert aligned is reg.declared("serve_prefill_aligned_total")
    dispatched = reg.declared("infer_prefill_dispatch_total")
    a0, d0 = aligned.total(), dispatched.total()
    alloc, cache = engine.new_allocator(), engine.init_cache()
    prompt = PREFIX + [1, 2, 3]                      # 27 tokens, ps 8
    for slot, start in ((0, 0), (1, 16), (2, 25)):   # cold, chunk, hit
        cache = engine.prefill(cache, prompt, slot, pages=alloc.acquire(4),
                               prefill_from=start)[0]
        assert aligned.total() - a0 == (1 if slot == 0 else 2)
    assert dispatched.total() - d0 == 3


def test_chunked_prefill_interleaves_decode_steps(engine):
    """SLO path (ISSUE 12 satellite): a long prompt admitted behind a
    decoding stream prefills in chunks with decode steps interleaved —
    max consecutive prefill dispatches stays at max_chunks_per_pass —
    and the lifecycle conservation law survives chunked admission."""
    tel = _tel()
    sched = SlotScheduler(engine, telemetry=tel, prefix_cache=False,
                          prefill_chunk=16, max_chunks_per_pass=1)
    trace = []
    orig_p, orig_d = engine.prefill, engine.decode

    def spy_p(*a, **kw):
        trace.append("P")
        return orig_p(*a, **kw)

    def spy_d(*a, **kw):
        trace.append("D")
        return orig_d(*a, **kw)

    engine.prefill, engine.decode = spy_p, spy_d
    try:
        u_short = sched.submit([5, 6, 7], max_new_tokens=8)
        u_long = sched.submit(list((np.arange(40) + 2) % 64),
                              max_new_tokens=2)
        out = sched.run()
    finally:
        engine.prefill, engine.decode = orig_p, orig_d
    # every request completed, reasons recorded, books balanced
    assert len(out[u_short]) == 8 and len(out[u_long]) == 2
    assert sched.finish_reasons[u_short] == "length"
    assert sched.finish_reasons[u_long] == "length"
    c = tel.conservation()
    assert c["submitted"] == c["finished"] + c["active"] + c["rejected"]
    assert c == {"submitted": 2, "finished": 2, "rejected": 0,
                 "active": 0}
    # the 40-token prompt split into ceil(40/16) = 3 chunks
    assert int(tel.prefill_chunks.total()) == 3
    # bounded interleaving: once decoding starts, never two prefill
    # dispatches back to back
    first_d = trace.index("D")
    run_len, worst = 0, 0
    for ev in trace[first_d:]:
        run_len = run_len + 1 if ev == "P" else 0
        worst = max(worst, run_len)
    assert worst <= 1, trace


def test_chunked_prefill_streams_match_monolithic(engine):
    prompts = [list((np.arange(n) + 3) % 64) for n in (40, 25, 7)]
    mono = SlotScheduler(engine, telemetry=_tel(), prefix_cache=False)
    um = [mono.submit(p, max_new_tokens=4) for p in prompts]
    out_m = mono.run()
    chunked = SlotScheduler(engine, telemetry=_tel(),
                            prefix_cache=False, prefill_chunk=16)
    uc = [chunked.submit(p, max_new_tokens=4) for p in prompts]
    out_c = chunked.run()
    assert [out_m[u] for u in um] == [out_c[u] for u in uc]


def test_priority_admission_and_tenant_fairness(engine):
    """Highest effective priority first; ties round-robin across
    tenants by least-recent admission; FIFO last.  finish order on a
    1-slot drain IS admission order (serialized)."""
    cfg = engine.cfg
    model_params = engine.params
    one = InferenceEngine("gpt", cfg, model_params, slots=1, max_seq=64,
                          page_size=8, num_pages=8)
    tel = _tel()
    sched = SlotScheduler(one, telemetry=tel, prefix_cache=False,
                          tenant_priority={"vip": 10})
    ua1 = sched.submit([1, 2], max_new_tokens=1, tenant="a")
    ua2 = sched.submit([2, 3], max_new_tokens=1, tenant="a")
    ub1 = sched.submit([3, 4], max_new_tokens=1, tenant="b")
    uv = sched.submit([4, 5], max_new_tokens=1, tenant="vip")
    out = sched.run()
    order = list(out)                        # insertion = finish order
    # vip's override wins outright; then a (FIFO), then b (fairness:
    # a was just admitted), then a again
    assert order == [uv, ua1, ub1, ua2]
    assert tel.tenant_admitted.value(tenant="vip") == 1
    assert tel.tenant_admitted.value(tenant="a") == 2
    # rejected submissions are tenant-attributed too
    with pytest.raises(ValueError):
        sched.submit([], tenant="a")
    assert tel.tenant_rejected.value(tenant="a") == 1


def test_llama_gqa_hit_streams_equal_cold_streams():
    """The grouped-query path: suffix rows score the pre-broadcast
    per-kv-head window exactly as the cold flash path scores its
    broadcast — streams match across the memory models."""
    from apex_tpu.transformer.testing import (LlamaConfig,
                                              llama_model_provider)
    cfg = LlamaConfig(vocab_size=64, hidden_size=32, num_layers=2,
                      num_attention_heads=4, num_kv_heads=2,
                      max_seq_length=64)
    model = llama_model_provider(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))
    eng = InferenceEngine("llama", cfg, params, slots=3, max_seq=64,
                          page_size=8, num_pages=21,
                          cache_dtype=jnp.float32)
    prefix = list((np.arange(24) * 11 + 5) % 64)
    prompts = [prefix + [10 + i] for i in range(3)]
    tel = _tel()
    shared = SlotScheduler(eng, telemetry=tel)
    shared.submit(prefix + [1], max_new_tokens=2)
    shared.run()
    us = [shared.submit(p, max_new_tokens=5) for p in prompts]
    out_s = shared.run()
    cold = SlotScheduler(eng, telemetry=_tel(), prefix_cache=False)
    uc = [cold.submit(p, max_new_tokens=5) for p in prompts]
    out_c = cold.run()
    assert [out_s[u] for u in us] == [out_c[u] for u in uc]
    assert int(tel.prefix_hits.total()) == 3


def test_prefix_cache_eviction_under_backpressure(engine):
    """A pool mostly pinned by the prefix cache still admits new cold
    requests: LRU leaves are evicted to free pages instead of
    deadlocking on backpressure."""
    cfg = engine.cfg
    small = InferenceEngine("gpt", cfg, engine.params, slots=2,
                            max_seq=64, page_size=8, num_pages=6)
    tel = _tel()
    sched = SlotScheduler(small, telemetry=tel)
    sched.submit(list((np.arange(24) + 9) % 64), max_new_tokens=2)
    sched.run()                              # cache pins ~4 pages
    assert sched.prefix.pinned_pages >= 3
    # a distinct prompt needing most of the pool: must evict, not hang
    u = sched.submit(list((np.arange(30) * 3 + 1) % 64),
                     max_new_tokens=4)
    out = sched.run()
    assert len(out[u]) == 4
    assert int(tel.prefix_evictions.total()) >= 1
    al = sched.alloc
    assert al.live_pages + al.free_pages == small.num_pages
