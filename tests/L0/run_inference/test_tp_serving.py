"""Tensor-parallel serving acceptance (ISSUE 17): the tp-sharded
engine (param mirrors column/row-partitioned, paged pool sharded over
kv heads, page table replicated host-side) serves the SAME per-slot
tokens as the single-chip engine across GPT and LLaMA GQA/MQA, the
fused-block and speculative paths shard the same way, per-rank HBM is
1/tp (the capacity case for a model that cannot fit one chip), and the
host-side allocator/prefix-cache machinery is INVARIANT under tp —
conservation law unchanged, hit/COW churn adds zero compiles.

All meshes are forced host devices (tests/conftest.py pins 8)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.inference import InferenceEngine, SlotScheduler
from apex_tpu.inference.sampling import SamplingConfig
from apex_tpu.inference.step_vector import peel_step
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


def _gpt(hidden=64, heads=4, layers=2, vocab=128, max_seq=128):
    cfg = GPTConfig(vocab_size=vocab, hidden_size=hidden,
                    num_layers=layers, num_attention_heads=heads,
                    max_seq_length=max_seq, hidden_dropout=0.0,
                    attention_dropout=0.0)
    model = gpt_model_provider(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))
    return cfg, params


def _llama(kvh, heads=4):
    cfg = LlamaConfig(vocab_size=128, hidden_size=64, num_layers=2,
                      num_attention_heads=heads, num_kv_heads=kvh,
                      max_seq_length=128)
    model = llama_model_provider(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))
    return cfg, params


def _serve(kind, cfg, params, tp, fusion="0", spec_k=0):
    """Prefill slot 0, decode 4 steps with a half-active batch, and
    (spec_k) verify one slab — the per-slot outputs a tp-sharded
    engine must reproduce bit-for-tokens vs single-chip."""
    eng = InferenceEngine(kind, cfg, params, slots=2, paged=True,
                          page_size=16, num_pages=12,
                          sampling=SamplingConfig(), spec_k=spec_k,
                          decode_fusion=fusion, tp=tp)
    cache = eng.init_cache()
    alloc = eng.new_allocator()
    pages = alloc.acquire(4)
    cache, tok, logits = eng.prefill(cache, list(range(1, 11)), 0,
                                     pages=pages)
    toks = [int(tok)]
    last = np.array([int(tok), 0], np.int32)
    active = np.array([True, False])
    for _ in range(4):
        cache, nt, _, _ = eng.decode(cache, last, active)
        toks.append(int(np.asarray(nt)[0]))
        last = peel_step(np.asarray(nt), 2)[0]
    spec = None
    if spec_k:
        slab = np.zeros((2, spec_k + 1), np.int32)
        slab[0, 0] = toks[-1]
        cache, vt, n_emit, _ = eng.verify(cache, slab, active)
        vt = peel_step(np.asarray(vt), 2 * (spec_k + 1))[0]
        spec = (vt.reshape(2, spec_k + 1)[0].tolist(),
                int(np.asarray(n_emit)[0]))
    return toks, np.asarray(logits), spec, eng


def _assert_parity(base, got, tol=1e-4):
    assert base[0] == got[0], (base[0], got[0])
    assert base[2] == got[2], (base[2], got[2])
    assert float(np.max(np.abs(base[1] - got[1]))) < tol


# -- parity: sharded vs single-chip ------------------------------------------

def test_gpt_tp2_parity_and_per_rank_hbm_fast():
    """Fast-lane sentinel: GPT paged tp=2 serves the same tokens (and
    prefill logits) as single-chip, AND the HBM acceptance arithmetic
    holds — per-rank pool bytes are 1/tp, the sharded param mirrors
    hold 1/tp of every partitioned leaf, so a model+cache footprint
    that exceeds one chip's budget fits each rank of a tp=2 mesh."""
    cfg, params = _gpt(hidden=32, heads=2, layers=1, vocab=64,
                       max_seq=64)
    base = _serve("gpt", cfg, params, 1)
    got = _serve("gpt", cfg, params, 2)
    _assert_parity(base, got)

    eng1, eng2 = base[3], got[3]
    # the paged pool: cache_hbm_bytes reports PER-RANK bytes (the
    # number serving capacity prices against under sharding)
    assert eng2.cache_hbm_bytes() * 2 == eng1.cache_hbm_bytes()
    # the pool leaves really are kv-head-sharded on device: each
    # rank's addressable shard holds kv_heads_pool/tp heads
    kvh_pool = eng2.tp_dims["kv_heads_pool"]
    cache2 = eng2.init_cache()
    shard = cache2.k.addressable_shards[0].data
    assert shard.shape[2] == kvh_pool // 2
    assert cache2.k.shape[2] == kvh_pool

    def rank0_bytes(tree):
        return sum(x.addressable_shards[0].data.nbytes
                   for x in jax.tree_util.tree_leaves(tree))

    def total_bytes(tree):
        return sum(x.nbytes for x in jax.tree_util.tree_leaves(tree))

    full = total_bytes(eng1.params) + eng1.cache_hbm_bytes()
    rank = rank0_bytes(eng2.params) + eng2.cache_hbm_bytes()
    # the acceptance shape: pick any per-chip budget between the
    # per-rank and the unsharded footprint — single-chip cannot hold
    # it, each tp=2 rank can (embed/lm-head/qkv/mlp all sharded; only
    # norms/biases replicate, so the split is well under 3/4)
    assert rank < 0.75 * full, (rank, full)


def test_init_cache_builds_the_pool_sharded_never_whole(monkeypatch):
    """ISSUE 21: a pool sized for tp chips must never exist on one.
    ``init_cache`` at tp=2 returns k/v sharded over the kv-head dim, no
    array of the full pool shape is committed to a single device, and
    nothing of that shape is ever handed to ``device_put`` (the old
    build-whole-then-reshard path)."""
    from jax.sharding import PartitionSpec as P

    cfg, params = _gpt(hidden=32, heads=2, layers=1, vocab=64,
                       max_seq=64)
    eng = InferenceEngine("gpt", cfg, params, slots=2, paged=True,
                          page_size=16, num_pages=12,
                          sampling=SamplingConfig(), tp=2)
    full_shape = (12 + 1, 1, 2, 16, 16)
    put_shapes = []
    real_put = jax.device_put
    monkeypatch.setattr(
        jax, "device_put",
        lambda x, *a, **k: (put_shapes.extend(
            getattr(leaf, "shape", None)
            for leaf in jax.tree_util.tree_leaves(x)),
            real_put(x, *a, **k))[1])
    before = {id(a) for a in jax.live_arrays()}
    cache = eng.init_cache()
    assert cache.k.shape == cache.v.shape == full_shape
    for pool in (cache.k, cache.v):
        assert pool.sharding.spec == P(None, None, "tensor", None, None)
        assert len(pool.sharding.device_set) == 2
        assert {s.data.shape for s in pool.addressable_shards} == \
            {(13, 1, 1, 16, 16)}
    for table in (cache.page_table, cache.lengths, cache.capacity):
        assert table.sharding.is_fully_replicated
    assert full_shape not in put_shapes
    whole = [a for a in jax.live_arrays()
             if id(a) not in before and a.shape == full_shape
             and len(a.sharding.device_set) == 1]
    assert not whole, "an unsharded pool was committed to one device"


@pytest.mark.parametrize("tp", [2, 4])
@pytest.mark.parametrize("fusion", ["0", "1"])
def test_gpt_tp_matrix(tp, fusion):
    """GPT paged parity over tp in {2,4} x per-op/fused decode (the
    fused path takes the 1/tp weight shard with the out-proj psum
    OUTSIDE the kernel)."""
    cfg, params = _gpt()
    _assert_parity(_serve("gpt", cfg, params, 1, fusion),
                   _serve("gpt", cfg, params, tp, fusion))


@pytest.mark.parametrize("tp", [2, 4])
@pytest.mark.parametrize("kvh", [4, 2, 1])
def test_llama_kv_replication_tp_matrix(kvh, tp):
    """LLaMA MHA/GQA/MQA parity under tp: kv heads shard when tp
    divides them and REPLICATE below tp (tp=4 over kvh=2 carries each
    kv head twice; MQA replicates its one head tp ways) — the
    kv-expansion scheme the pool's [kv_heads_pool] dimension encodes."""
    cfg, params = _llama(kvh)
    _assert_parity(_serve("llama", cfg, params, 1),
                   _serve("llama", cfg, params, tp))


@pytest.mark.parametrize("kind", ["gpt", "llama"])
def test_spec_verify_tp2_parity(kind):
    """The spec-decode verify slab scores identically on the sharded
    engine (same tokens emitted, same n_emit)."""
    cfg, params = _gpt() if kind == "gpt" else _llama(2)
    _assert_parity(_serve(kind, cfg, params, 1, spec_k=2),
                   _serve(kind, cfg, params, 2, spec_k=2))


# -- engine contract ---------------------------------------------------------

def test_tp_requires_paged_generative():
    cfg, params = _gpt()
    with pytest.raises(ValueError, match="PAGED"):
        InferenceEngine("gpt", cfg, params, slots=2, max_seq=64, tp=2)
    with pytest.raises(ValueError):
        InferenceEngine("gpt", cfg, params, slots=2, paged=True,
                        page_size=16, num_pages=8, tp=0)
    # tp must divide heads (4 heads / tp=3 has no whole-head shard)
    with pytest.raises(ValueError):
        InferenceEngine("gpt", cfg, params, slots=2, paged=True,
                        page_size=16, num_pages=8, tp=3)


def test_serve_tp_env_knob(monkeypatch):
    """APEX_TPU_SERVE_TP semantics: unset/0 -> 1, explicit engine tp
    wins over the env, garbage raises."""
    from apex_tpu.inference.engine import serve_tp
    monkeypatch.delenv("APEX_TPU_SERVE_TP", raising=False)
    assert serve_tp() == 1
    monkeypatch.setenv("APEX_TPU_SERVE_TP", "0")
    assert serve_tp() == 1
    monkeypatch.setenv("APEX_TPU_SERVE_TP", "2")
    assert serve_tp() == 2
    cfg, params = _gpt(hidden=32, heads=2, layers=1, vocab=64,
                       max_seq=32)
    # explicit tp=1 beats the env's 2 (no mesh is built at all)
    eng = InferenceEngine("gpt", cfg, params, slots=1, paged=True,
                          page_size=16, num_pages=4, tp=1)
    assert eng.tp == 1 and eng.mesh is None
    monkeypatch.setenv("APEX_TPU_SERVE_TP", "banana")
    with pytest.raises(ValueError, match="APEX_TPU_SERVE_TP"):
        serve_tp()
    monkeypatch.setenv("APEX_TPU_SERVE_TP", "-2")
    with pytest.raises(ValueError):
        serve_tp()


# -- host-side machinery invariance under tp ---------------------------------

def test_allocator_prefix_churn_invariant_and_zero_compiles_under_tp():
    """The page table/allocator stay host-side and REPLICATED under
    sharding, so admission, prefix sharing and COW are the SAME
    machinery: a shared-prefix burst on a tp=2 engine reproduces the
    single-chip engine's hit/COW/sharing counters, the allocator's
    conservation law balances after the waves, and the churn adds ZERO
    compiles to the warm sharded executables."""
    from apex_tpu.observability import MetricsRegistry, ServeTelemetry

    prefix = list(range(1, 33))                       # two full pages
    burst = [prefix + [40 + i, 50 + i] for i in range(2)]

    def churn(tp):
        cfg, params = _gpt()
        eng = InferenceEngine("gpt", cfg, params, slots=2, paged=True,
                              page_size=16, num_pages=12,
                              sampling=SamplingConfig(), tp=tp)
        # warm every executable the churn touches on ONE scheduler
        # (the prefix cache is per-scheduler): wave 1 the cold
        # full-prompt bucket, wave 2 the hit path's suffix bucket +
        # the COW copy program, wave 3 the dual-concurrent admission
        w = SlotScheduler(eng,
                          telemetry=ServeTelemetry(MetricsRegistry()))
        w.submit(list(burst[0]), max_new_tokens=2)
        w.run()
        w.submit(list(burst[0]), max_new_tokens=2)
        w.run()
        for p in burst:
            w.submit(list(p), max_new_tokens=2)
        w.run()
        tel = ServeTelemetry(MetricsRegistry())
        sched = SlotScheduler(eng, telemetry=tel)
        events = []
        from jax._src import monitoring as _mon
        saved = {attr: list(getattr(_mon, attr))
                 for attr in dir(_mon)
                 if attr.endswith("_listeners")
                 and isinstance(getattr(_mon, attr), list)}
        jax.monitoring.register_event_listener(
            lambda name, **kw: events.append(name))
        try:
            sched.submit(list(burst[0]), max_new_tokens=2)  # seed
            sched.run()
            for p in burst:                                 # hit wave
                sched.submit(list(p), max_new_tokens=2)
            sched.run()
        finally:
            for attr, listeners in saved.items():
                getattr(_mon, attr)[:] = listeners
        compiles = sum(1 for e in events if "compile_requests" in e)
        s = tel.summary()
        alloc = sched.alloc
        assert alloc.free_pages + alloc.live_pages == eng.num_pages
        return (compiles, s.get("prefix_hit_tokens", 0),
                int(tel.prefix_hits.total()), s.get("cow_copies", 0),
                alloc.free_pages)

    base, sharded = churn(1), churn(2)
    assert sharded[0] == 0, f"tp churn compiled {sharded[0]} programs"
    # identical to the single-chip run: zero compiles AND the same
    # hit/COW/free-page books (the machinery is the same host code)
    assert sharded == base, (base, sharded)
