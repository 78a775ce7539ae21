"""Paged engine acceptance (ISSUE 6): the paged cache serves the SAME
tokens as the dense slot cache and the full-sequence forward, decode
stays ONE executable across admits/retires, the scheduler admits by
free pages (more concurrent short requests than the equal-HBM slot
cache can hold), and capacity truncation is surfaced with a reason
code instead of silently clamped."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.inference import InferenceEngine, SlotScheduler
from apex_tpu.transformer import parallel_state
from apex_tpu.transformer.testing import (
    GPTConfig,
    LlamaConfig,
    gpt_model_provider,
    llama_model_provider,
)


@pytest.fixture(autouse=True)
def _single_rank():
    parallel_state.destroy_model_parallel()
    parallel_state.initialize_model_parallel(1)
    yield


def _tiny_gpt(max_seq=64, layers=1):
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=layers,
                    num_attention_heads=2, max_seq_length=max_seq,
                    hidden_dropout=0.0, attention_dropout=0.0)
    model = gpt_model_provider(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))
    return cfg, model, params


def _reference_greedy(model, params, prompt, n_new):
    total = len(prompt) + n_new
    toks = list(prompt)
    apply = jax.jit(model.apply)
    for _ in range(n_new):
        padded = np.zeros((1, total), np.int32)
        padded[0, :len(toks)] = toks
        logits = apply(params, jnp.asarray(padded))
        toks.append(int(jnp.argmax(logits[len(toks) - 1, 0]
                                   .astype(jnp.float32))))
    return toks[len(prompt):]


def test_llama_gqa_one_layer_paged_greedy_fast():
    """Fast-lane paged parity sentinel: smallest config walking the
    full paged GQA decode path (page-table gather, RoPE at position,
    grouped pool) — the paged twin of the dense sentinel."""
    cfg = LlamaConfig(vocab_size=32, hidden_size=16, num_layers=1,
                      num_attention_heads=4, num_kv_heads=2,
                      max_seq_length=16)
    model = llama_model_provider(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))
    engine = InferenceEngine("llama", cfg, params, slots=1, max_seq=16,
                             page_size=4)
    prompt = [3, 1, 4, 1]
    ref = _reference_greedy(model, params, prompt, 3)
    got = engine.generate([prompt], max_new_tokens=3)[0]
    assert got == ref


def test_paged_generate_equals_dense_generate():
    """The paged memory model changes storage, not tokens: identical
    streams from both caches, with the paged pool backpressured below
    dense-equivalent capacity so page reuse is actually exercised."""
    cfg, model, params = _tiny_gpt(max_seq=64, layers=2)
    rng = np.random.RandomState(3)
    prompts = [list(rng.randint(0, 64, size=n)) for n in (4, 9, 3, 7, 5)]
    dense = InferenceEngine("gpt", cfg, params, slots=2, max_seq=64)
    paged = InferenceEngine("gpt", cfg, params, slots=2, max_seq=64,
                            page_size=16, num_pages=4)
    out_d = dense.generate(prompts, max_new_tokens=5)
    out_p = paged.generate(prompts, max_new_tokens=5)
    assert out_d == out_p


def test_paged_kernel_path_engine_matches_dense():
    """Every paged decode executable holds the Pallas kernel (one page
    a slot here, the shortest window there is); greedy streams still
    match the dense engine."""
    cfg, model, params = _tiny_gpt(max_seq=16)
    rng = np.random.RandomState(5)
    prompts = [list(rng.randint(0, 64, size=n)) for n in (6, 3)]
    dense = InferenceEngine("gpt", cfg, params, slots=2, max_seq=16)
    kern = InferenceEngine("gpt", cfg, params, slots=2, max_seq=16,
                           page_size=16)
    assert kern.max_pages_per_slot == 1
    assert dense.generate(prompts, max_new_tokens=5) == \
        kern.generate(prompts, max_new_tokens=5)


def test_admission_by_pages_beats_equal_hbm_slot_cache():
    """ISSUE 6 acceptance: with page_size * num_pages < slots *
    max_seq, the paged scheduler admits MORE concurrent short requests
    than the slot cache could hold at the same KV HBM."""
    cfg, model, params = _tiny_gpt(max_seq=64)
    rng = np.random.RandomState(7)
    prompts = [list(rng.randint(0, 64, size=4)) for _ in range(6)]

    def peak(engine):
        sched = SlotScheduler(engine)
        for p in prompts:
            sched.submit(p, max_new_tokens=3)
        sched.run()
        return sched.peak_active, engine.cache_hbm_bytes()

    # HBM budget: a 2-slot dense cache
    dense = InferenceEngine("gpt", cfg, params, slots=2, max_seq=64)
    # same budget as a pool: 2 * 64 / 8 - 1 trash-equivalent pages,
    # slots are now cheap metadata
    paged = InferenceEngine("gpt", cfg, params, slots=len(prompts),
                            max_seq=64, page_size=8, num_pages=15)
    d_peak, d_bytes = peak(dense)
    p_peak, p_bytes = peak(paged)
    assert p_bytes <= d_bytes                  # no extra HBM spent
    assert paged.page_size * paged.num_pages < paged.slots * paged.max_seq
    assert d_peak <= dense.slots
    assert p_peak > d_peak, (p_peak, d_peak)   # the whole point


def test_out_of_pages_is_backpressure_not_failure():
    """A pool too small for the whole wave still completes every
    request — admission waits for reclaimed pages (FIFO), it never
    fails mid-decode or drops a request."""
    cfg, model, params = _tiny_gpt(max_seq=64)
    rng = np.random.RandomState(9)
    # prompt + 4 new tokens lands in (16, 32]: 2 pages per request
    prompts = [list(rng.randint(0, 64, size=n)) for n in (13, 20, 14, 17)]
    # 2 pages of 16: at most ONE request's reservation at a time
    paged = InferenceEngine("gpt", cfg, params, slots=4, max_seq=64,
                            page_size=16, num_pages=2)
    dense = InferenceEngine("gpt", cfg, params, slots=4, max_seq=64)
    sched = SlotScheduler(paged)
    uids = [sched.submit(p, max_new_tokens=4) for p in prompts]
    out = sched.run()
    assert sorted(out) == sorted(uids)
    assert sched.peak_active == 1              # serialized by the pool
    assert [out[u] for u in uids] == \
        dense.generate(prompts, max_new_tokens=4)


def test_prefill_rejects_undersized_reservation():
    """Regression (review finding): a reservation that can't hold the
    prompt must fail loudly, not park the prompt tail in the trash
    page."""
    cfg, model, params = _tiny_gpt(max_seq=64)
    eng = InferenceEngine("gpt", cfg, params, slots=2, max_seq=64,
                          page_size=16)
    alloc = eng.new_allocator()
    cache = eng.init_cache()
    with pytest.raises(ValueError, match="trash page"):
        eng.prefill(cache, list(range(2, 20)), 0, pages=alloc.acquire(1))


def test_request_larger_than_pool_fails_fast_at_submit():
    """A request no empty pool could cover is rejected at submit(),
    before any earlier request's work could be done and discarded."""
    cfg, model, params = _tiny_gpt(max_seq=64)
    paged = InferenceEngine("gpt", cfg, params, slots=2, max_seq=64,
                            page_size=16, num_pages=1)
    sched = SlotScheduler(paged)
    with pytest.raises(ValueError, match="grow num_pages"):
        sched.submit(list(range(2, 20)), max_new_tokens=4)  # 2 pages
    # BERT never has a cache — paged kwargs are rejected up front
    from apex_tpu.transformer.testing import BertConfig
    bcfg = BertConfig(vocab_size=32, hidden_size=32, num_layers=1,
                      num_attention_heads=2, max_seq_length=16,
                      hidden_dropout=0.0, attention_dropout=0.0)
    with pytest.raises(ValueError, match="encode-only"):
        InferenceEngine("bert", bcfg, {}, page_size=16)


def test_truncation_reason_codes():
    """A request whose prompt + budget overruns its capacity retires
    with reason "truncated" (tokens stop, loudly); budget and EOS cuts
    record their own codes."""
    cfg, model, params = _tiny_gpt(max_seq=32)
    paged = InferenceEngine("gpt", cfg, params, slots=2, max_seq=32,
                            page_size=8)
    sched = SlotScheduler(paged)
    rng = np.random.RandomState(11)
    u_trunc = sched.submit(list(rng.randint(0, 64, size=28)),
                           max_new_tokens=50)   # 28 + 50 >> max_seq 32
    u_len = sched.submit(list(rng.randint(0, 64, size=4)),
                         max_new_tokens=3)
    out = sched.run()
    assert sched.finish_reasons[u_trunc] == "truncated"
    # capacity = max_seq = 32: 28 prompt + 5 generated - 1 hits the cap
    assert len(out[u_trunc]) == 5
    assert sched.finish_reasons[u_len] == "length"
    assert len(out[u_len]) == 3
    # EOS cut records "eos"
    sched2 = SlotScheduler(paged)
    u = sched2.submit([1, 2, 3], max_new_tokens=4)
    first = sched2.run()[u][0]
    sched3 = SlotScheduler(paged)
    u2 = sched3.submit([1, 2, 3], max_new_tokens=4, eos_id=int(first))
    assert sched3.run()[u2] == [first]
    assert sched3.finish_reasons[u2] == "eos"


def test_paged_decode_is_one_executable_across_admits_and_retires():
    """ISSUE 6 acceptance: decode compile count stays 1 across N steps
    WITH admits/retires (page-table churn) in between — the page table
    is a traced operand, so reassigning pages never recompiles."""
    cfg, model, params = _tiny_gpt(max_seq=64)
    eng = InferenceEngine("gpt", cfg, params, slots=2, max_seq=64,
                          page_size=16)
    alloc = eng.new_allocator()

    events = []
    from jax._src import monitoring as _mon
    saved = {attr: list(getattr(_mon, attr))
             for attr in dir(_mon)
             if attr.endswith("_listeners")
             and isinstance(getattr(_mon, attr), list)}
    jax.monitoring.register_event_listener(
        lambda name, **kw: events.append(name))
    try:
        cache = eng.init_cache()
        pages0 = alloc.acquire(2)
        cache, _, _ = eng.prefill(cache, [1, 2, 3], 0, pages=pages0)
        last = np.zeros((2,), np.int32)
        active = np.array([True, False])
        cache, toks, _, _ = eng.decode(cache, last, active)   # warm up
        jax.block_until_ready(cache)
        jax.clear_caches()
        events.clear()
        # interleave: decode / retire+admit into the other slot (fresh
        # pages, same bucket) / decode / admit again / decode
        cache, toks, _, _ = eng.decode(cache, last, active)
        alloc.release(pages0)
        pages1 = alloc.acquire(2)
        cache, _, _ = eng.prefill(cache, [4, 5], 1, pages=pages1)
        active = np.array([False, True])
        cache, toks, _, _ = eng.decode(cache, last, active)
        pages2 = alloc.acquire(2)
        cache, _, _ = eng.prefill(cache, [6, 7, 8], 0, pages=pages2)
        active = np.array([True, True])
        for _ in range(3):
            cache, toks, _, _ = eng.decode(cache, last, active)
        jax.block_until_ready(cache)
        decode_compiles = sum(1 for e in events
                              if "compile_requests" in e)
        # one decode recompile (cleared cache) + one prefill bucket;
        # the admits/retires between steps must add NOTHING
        assert decode_compiles <= 2, decode_compiles
    finally:
        for attr, listeners in saved.items():
            getattr(_mon, attr)[:] = listeners


# -- one way to hand pages to the kernel (ISSUE 31) --------------------------

def _eqns(jaxpr):
    """Every equation reachable from ``jaxpr``, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


PAGED_LAYERS = 2


def _tiny_kind(kind):
    """``(cfg, params)`` of a tiny model of ``kind`` with
    ``PAGED_LAYERS`` layers that attend through the paged pool."""
    if kind == "gpt":
        cfg, _, params = _tiny_gpt(max_seq=64, layers=PAGED_LAYERS)
        return cfg, params
    if kind == "llama":
        cfg = LlamaConfig(vocab_size=32, hidden_size=16, num_layers=2,
                          num_attention_heads=4, num_kv_heads=2,
                          max_seq_length=64)
        model = llama_model_provider(cfg)
    else:
        from apex_tpu.transformer.testing import (LagunaConfig,
                                                  laguna_model_provider)
        cfg = LagunaConfig(vocab_size=64, hidden_size=32, head_dim=8,
                           heads_per_layer=(2, 4, 2),
                           layer_types=("full", "sliding", "full"),
                           mlp_types=("dense", "sparse", "sparse"),
                           ffn_hidden_size=32, moe_ffn_hidden_size=16,
                           shared_ffn_hidden_size=16, num_experts=4,
                           max_seq_length=64)
        model = laguna_model_provider(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    return cfg, params


@pytest.mark.parametrize("kind", ["gpt", "llama", "laguna"])
def test_decode_attends_through_the_kernel_on_the_whole_pool(kind):
    """The decode step of every kind: exactly one ``apex_paged_decode``
    call a paged (full) layer, each handed the WHOLE pool, and no gather
    of a slot's window out of it — the only gather from the pool is the
    append's read of each slot's one current page."""
    from apex_tpu.inference.engine import make_decode_fn
    from apex_tpu.inference.sampling import SamplingConfig

    cfg, params = _tiny_kind(kind)
    slots = 3
    eng = InferenceEngine(kind, cfg, params, slots=slots, max_seq=64,
                          page_size=8, sampling=SamplingConfig())
    assert eng.max_pages_per_slot == 8
    cache = jax.eval_shape(eng.init_cache)
    pool = cache.k.shape
    assert pool[1] == PAGED_LAYERS
    fn = make_decode_fn(kind, cfg, SamplingConfig())
    jaxpr = jax.make_jaxpr(fn)(
        cache, eng.params, jnp.zeros((slots,), jnp.int32),
        jnp.ones((slots,), bool), jax.random.PRNGKey(0), jnp.int32(0))
    eqns = list(_eqns(jaxpr.jaxpr))
    kernels = [e for e in eqns if e.primitive.name == "pallas_call"
               and e.params["name"] == "apex_paged_decode"]
    assert len(kernels) == PAGED_LAYERS
    for e in kernels:             # page table, lengths, layer, q, k, v
        assert [v.aval.shape for v in e.invars[-2:]] == [pool, pool]
    one_page_a_slot = slots * int(np.prod(pool[2:]))
    from_pool = [e for e in eqns if e.primitive.name == "gather"
                 and e.invars[0].aval.shape == pool]
    assert len(from_pool) == 2 * PAGED_LAYERS          # k and v appends
    assert all(e.outvars[0].aval.size == one_page_a_slot for e in from_pool)
    # and no layer's slice of the pool is taken anywhere
    assert not [e for e in eqns
                if e.primitive.name in ("slice", "dynamic_slice", "squeeze")
                and e.invars[0].aval.shape == pool]


@pytest.mark.parametrize("cache_dtype,tol", [(jnp.float32, 1e-4),
                                             (jnp.bfloat16, 2e-2)],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("kind", ["gpt", "llama"])
def test_paged_decode_logits_match_dense_within_the_kernels_tolerance(
        kind, cache_dtype, tol):
    """Same prompt, same step: the paged engine's decode logits against
    the dense engine's.  Not bit-identical (the kernel's online softmax
    accumulates page by page, and feeds the cache's own dtype to the
    products where the dense XLA chain up-casts to fp32), but within the
    kernel's tolerance for the cache's dtype — and the greedy token is
    the same."""
    cfg, params = _tiny_kind(kind)
    dense = InferenceEngine(kind, cfg, params, slots=2, max_seq=64,
                            cache_dtype=cache_dtype)
    paged = InferenceEngine(kind, cfg, params, slots=2, max_seq=64,
                            page_size=8, cache_dtype=cache_dtype)
    prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5]          # 2 pages of 8
    dc, d_tok, _ = dense.prefill(dense.init_cache(), prompt, 0)
    alloc = paged.new_allocator()
    pc, p_tok, _ = paged.prefill(paged.init_cache(), prompt, 0,
                                 pages=alloc.acquire(3))
    assert int(d_tok) == int(p_tok)
    last = np.array([int(d_tok), 0], np.int32)
    active = np.array([True, False])
    for _ in range(2):
        dc, d_toks, d_logits, _ = dense.decode(dc, last, active)
        pc, p_toks, p_logits, _ = paged.decode(pc, last, active)
        np.testing.assert_allclose(np.asarray(p_logits[0]),
                                   np.asarray(d_logits[0]),
                                   rtol=tol, atol=tol)
        assert int(d_toks[0]) == int(p_toks[0])
        last = np.array([int(d_toks[0]), 0], np.int32)
