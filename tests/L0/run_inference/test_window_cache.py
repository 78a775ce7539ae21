"""Two kinds of state in one cache manager (ISSUE 30): full layers in the
paged pool under the allocator, window layers in per-slot rings — and the
kinds without window layers keeping the one pool they had."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.inference import (InferenceEngine, SamplingConfig,
                                SlotScheduler, kv_cache)
from apex_tpu.observability import MetricsRegistry, ServeTelemetry
from apex_tpu.transformer.testing import (GPTConfig, LagunaConfig,
                                          LlamaConfig, gpt_model_provider,
                                          laguna_model_provider,
                                          llama_model_provider)


def ring_cache(slots=3, window=8, ps=4):
    return kv_cache.init_paged_cache(
        20, 1, 2, ps, 8, slots=slots, max_pages_per_slot=16,
        dtype=jnp.float32, window_layers=2, window=window)


def test_ring_is_window_pages_plus_one_and_fixed():
    c = ring_cache()
    assert c.ring == (8 // 4 + 1) * 4 == 12
    assert c.wk.shape == (2, 3, 2, 12, 8) and c.k.shape == (21, 1, 2, 4, 8)
    # the published sizes: ceil(512 / 64) + 1 = 9 pages a slot
    big = jax.eval_shape(lambda: kv_cache.init_paged_cache(
        8, 2, 8, 64, 128, slots=2, max_pages_per_slot=4,
        window_layers=3, window=512))
    assert big.wk.shape == (3, 2, 8, 9 * 64, 128)


@pytest.mark.parametrize("length", [5, 12, 13, 40])
def test_prefill_keeps_the_last_ring_positions_at_t_mod_ring(length):
    c = ring_cache()
    s = 64
    k = jnp.broadcast_to(jnp.arange(s, dtype=jnp.float32)[None, None, :,
                                                         None], (2, 2, s, 8))
    c = kv_cache.insert_window(c, 1, k, -k, length)
    got = np.asarray(c.wk[0, 1, 0, :, 0])
    for t in range(max(0, length - 12), length):
        assert got[t % 12] == t
    assert np.asarray(c.wv[1, 1, 1, (length - 1) % 12, 3]) == -(length - 1)
    assert not np.asarray(c.wk[:, 0]).any() and not np.asarray(
        c.wk[:, 2]).any()                       # other slots untouched


def test_decode_append_lands_at_length_mod_ring():
    c = ring_cache().replace(lengths=jnp.asarray([0, 13, 30], jnp.int32))
    tok = jnp.ones((3, 2, 8), jnp.float32) * jnp.asarray(
        [1.0, 2.0, 3.0])[:, None, None]
    c = kv_cache.append_window(c, 1, tok, tok)
    wk = np.asarray(c.wk[1, :, 0, :, 0])
    assert wk[0, 0] == 1 and wk[1, 13 % 12] == 2 and wk[2, 30 % 12] == 3
    assert wk.sum() == 6 and not np.asarray(c.wk[0]).any()


def test_window_pages_live_is_capped_by_the_ring():
    c = ring_cache().replace(
        lengths=jnp.asarray([3, 12, 500], jnp.int32),
        capacity=jnp.asarray([8, 16, 0], jnp.int32))
    # ceil(3/4) = 1, min(3, 3) = 3; the third slot is not admitted
    assert int(kv_cache.window_pages_live(c)) == 4
    c = c.replace(capacity=jnp.asarray([8, 16, 512], jnp.int32))
    assert int(kv_cache.window_pages_live(c)) == 7 <= 3 * 3
    plain = kv_cache.init_paged_cache(4, 1, 1, 4, 8, slots=1,
                                      max_pages_per_slot=2)
    assert int(kv_cache.window_pages_live(plain)) == 0


@pytest.fixture(scope="module")
def served():
    cfg = LagunaConfig(vocab_size=64, hidden_size=32, head_dim=8,
                       heads_per_layer=(2, 4), layer_types=("full",
                                                            "sliding"),
                       mlp_types=("dense", "sparse"), ffn_hidden_size=32,
                       moe_ffn_hidden_size=16, shared_ffn_hidden_size=16,
                       num_experts=4, max_seq_length=64)
    params = laguna_model_provider(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    eng = InferenceEngine("laguna", cfg, params, slots=3, max_seq=64,
                          page_size=4, num_pages=30,
                          sampling=SamplingConfig())
    # a registry of its own: the global one is shared by the worker's tests
    sched = SlotScheduler(eng, telemetry=ServeTelemetry(MetricsRegistry()))
    rng = np.random.RandomState(0)
    for n in (30, 9, 17, 22, 6):
        sched.submit(rng.randint(0, 64, size=n), max_new_tokens=7)
    peaks = []
    sched.begin_run()
    while sched.run_pending():
        sched.run_pass()
        peaks.append((sched.alloc.live_pages,
                      sched.telemetry.window_pages_live.value()))
    return eng, sched, sched.finish_run(), peaks


def test_scheduler_serves_the_kind_and_frees_every_full_pool_page(served):
    eng, sched, out, peaks = served
    assert sorted(len(v) for v in out.values()) == [7] * 5
    assert set(sched.finish_reasons.values()) == {"length"}
    # sched.alloc is the FULL pool's allocator: pages were live while
    # requests ran, and evicting the last slot freed them all
    assert max(p for p, _ in peaks) >= -(-37 // 4)
    assert sched.alloc.live_pages == 0
    assert sched.alloc.free_pages == eng.num_pages
    assert not np.asarray(sched.cache.capacity).any()


def test_window_pages_live_never_passes_slots_times_ring_pages(served):
    eng, sched, _, peaks = served
    ring_pages = sched.cache.ring // eng.page_size
    assert ring_pages == 8 // 4 + 1
    live = [w for _, w in peaks if w is not None]
    assert live and max(live) <= eng.slots * ring_pages
    tel = sched.telemetry
    assert tel.window_pages_live_peak.value() == max(live)
    # every decode step and every prefill reported its expert counters
    assert tel.moe_passes.value(phase="prefill") == 5
    assert tel.moe_passes.value(phase="decode") == tel.decode_steps.total()
    assert tel.moe_assignments.value(phase="prefill") == \
        (30 + 9 + 17 + 22 + 6) * 2
    assert tel.moe_experts_hit.value(phase="decode") <= \
        4 * tel.decode_steps.total()
    # cache_hbm_bytes counts both pools
    per_layer_tok = 2 * 2 * 8 * 2
    assert eng.cache_hbm_bytes() == (31 * 4 * per_layer_tok
                                     + 3 * 12 * per_layer_tok)


@pytest.mark.parametrize("kind", ["gpt", "llama"])
def test_kinds_without_window_layers_keep_their_one_pool(kind):
    from apex_tpu.transformer import parallel_state
    parallel_state.destroy_model_parallel()
    parallel_state.initialize_model_parallel(1)
    if kind == "gpt":
        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                        num_attention_heads=4, max_seq_length=32,
                        hidden_dropout=0.0, attention_dropout=0.0)
        model = gpt_model_provider(cfg)
    else:
        cfg = LlamaConfig(vocab_size=64, hidden_size=32, num_layers=2,
                          num_attention_heads=4, num_kv_heads=2,
                          max_seq_length=32)
        model = llama_model_provider(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    eng = InferenceEngine(kind, cfg, params, slots=2, page_size=4,
                          num_pages=8)
    cache = eng.init_cache()
    assert cache.wk is None and cache.wv is None and cache.ring == 0
    # k, v, page table, lengths, capacity, last_tokens
    assert len(jax.tree_util.tree_leaves(cache)) == 6
    assert cache.k.shape[1] == 2            # every layer in the one pool
    assert eng.stats_tail == 0 and eng.supports_prefix_sharing
    tok = np.asarray(eng.prefill(cache, [1, 2, 3], 0, pages=[0, 1])[1])
    assert tok.shape == ()                  # a bare token, no counters
