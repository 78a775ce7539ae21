"""Speculative decoding (ISSUE 15): the verify step emits EXACTLY the
target's greedy stream regardless of draft quality (correctness never
depends on the drafter), accept/reject is a pure length rollback on
the paged cache, the slab writes respect the bounded-damage
discipline, and the drafters honor their contracts."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.inference import (
    EngineDrafter,
    InferenceEngine,
    NGramDrafter,
    ReplayDrafter,
    SlotScheduler,
)
from apex_tpu.inference import kv_cache
from apex_tpu.inference.step_vector import peel_step
from apex_tpu.observability import MetricsRegistry, ServeTelemetry
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


def _gpt():
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                    num_attention_heads=2, max_seq_length=64,
                    hidden_dropout=0.0, attention_dropout=0.0)
    model = gpt_model_provider(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))
    return cfg, params


def _llama_gqa():
    cfg = LlamaConfig(vocab_size=64, hidden_size=32, num_layers=1,
                      num_attention_heads=4, num_kv_heads=2,
                      max_seq_length=64)
    model = llama_model_provider(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))
    return cfg, params


def _serve(kind, cfg, params, prompts, mnt=8, drafter=None, **kw):
    eng = InferenceEngine(kind, cfg, params, slots=2, max_seq=64, **kw)
    tel = ServeTelemetry(MetricsRegistry())
    sched = SlotScheduler(eng, telemetry=tel, drafter=drafter)
    uids = [sched.submit(p, max_new_tokens=mnt) for p in prompts]
    out = sched.run()
    return [out[u] for u in uids], tel


_PAGED = dict(page_size=8, num_pages=24)


@pytest.mark.parametrize("kind", ["gpt", "llama"])
def test_spec_stream_equals_plain_greedy_paged(kind):
    cfg, params = _gpt() if kind == "gpt" else _llama_gqa()
    prompts = [list((np.arange(10) * 3 + i) % 64) for i in range(3)]
    base, _ = _serve(kind, cfg, params, prompts, **_PAGED)
    spec, tel = _serve(kind, cfg, params, prompts, spec_k=3, **_PAGED)
    assert base == spec
    assert int(tel.spec_verify_steps.total()) > 0
    assert int(tel.recompiles.total()) == 0
    # conservation: every emitted token reached a request
    assert int(tel.spec_emitted.total()) == \
        int(tel.tokens_generated.total()) - len(prompts)


def test_spec_stream_equals_plain_greedy_dense():
    """The verify slab machinery is layout-agnostic: the dense slot
    cache rolls back by the same length reset."""
    cfg, params = _gpt()
    prompts = [list((np.arange(10) * 3 + i) % 64) for i in range(3)]
    base, _ = _serve("gpt", cfg, params, prompts)
    spec, _ = _serve("gpt", cfg, params, prompts, spec_k=4)
    assert base == spec


def test_poisoned_drafts_still_emit_target_stream():
    """A drafter that lies (scripted garbage) costs speculation upside
    only: every round rejects and emits the bonus token — the stream
    is still the target's greedy stream, at acceptance 0."""
    cfg, params = _gpt()
    prompts = [list((np.arange(10) * 3) % 64)]
    base, _ = _serve("gpt", cfg, params, prompts, **_PAGED)
    poisoned = ReplayDrafter({tuple(prompts[0]): [63] * 16})
    # a lying script would collide with real greedy tokens only if 63
    # were ever emitted — make sure it is not
    assert 63 not in base[0]
    spec, tel = _serve("gpt", cfg, params, prompts, spec_k=3,
                       drafter=poisoned, **_PAGED)
    assert spec == base
    assert int(tel.spec_accepted.total()) == 0
    assert int(tel.spec_emitted.total()) == len(base[0]) - 1


def test_replay_drafter_reaches_full_acceptance():
    cfg, params = _llama_gqa()
    prompts = [list((np.arange(9) * 5 + i) % 64) for i in range(2)]
    base, _ = _serve("llama", cfg, params, prompts, **_PAGED)
    script = {tuple(p): toks for p, toks in zip(prompts, base)}
    spec, tel = _serve("llama", cfg, params, prompts, spec_k=4,
                       drafter=ReplayDrafter(script), **_PAGED)
    assert spec == base
    drafted = int(tel.spec_drafted.total())
    accepted = int(tel.spec_accepted.total())
    # the script IS the continuation: only the final short round can
    # reject (pad drafts past the budget), so acceptance is near 1 and
    # the 8-token budget needs at most ceil(7 / 5) verify rounds/slot
    assert accepted / drafted >= 0.5
    assert int(tel.spec_verify_steps.total()) <= 2
    # the >= 1.5x effective-tokens-per-step criterion, counted exactly:
    # emitted tokens per slot-step vs the 1-token decode baseline
    emitted = int(tel.spec_emitted.total())
    slot_steps = drafted // 4
    assert emitted / slot_steps >= 1.5


def test_verify_rollback_lengths_and_pages():
    """Direct engine.verify: accepted count advances lengths by
    n_emit, rejected rows stay dead-by-mask, and the page table is
    untouched (rollback releases nothing device-side)."""
    cfg, params = _gpt()
    eng = InferenceEngine("gpt", cfg, params, slots=2, max_seq=64,
                          spec_k=3, **_PAGED)
    alloc = eng.new_allocator()
    cache = eng.init_cache()
    prompt = list((np.arange(10) * 3) % 64)
    toks = []
    for slot in range(2):
        pages = alloc.acquire(alloc.pages_needed(len(prompt) + 8))
        cache, tok, _ = eng.prefill(cache, prompt, slot, pages=pages)
        toks.append(int(tok))
    table_before = np.asarray(cache.page_table).copy()
    len_before = np.asarray(cache.lengths).copy()
    # garbage drafts: everything rejects, n_emit == 1 everywhere
    slab = np.zeros((2, 4), np.int32)
    slab[:, 0] = toks
    slab[:, 1:] = 63
    cache, host, n_emit, truncated = eng.verify(cache, slab)
    n_emit = np.asarray(n_emit)
    # the step's one array for the host: [tokens | n_emit | truncated]
    out, flags, _ = peel_step(np.asarray(host), 8)
    out, (host_emit, host_trunc) = out.reshape(2, 4), flags.reshape(2, 2)
    np.testing.assert_array_equal(host_emit, n_emit)
    np.testing.assert_array_equal(host_trunc, np.asarray(truncated))
    assert not np.asarray(truncated).any()
    np.testing.assert_array_equal(np.asarray(cache.page_table),
                                  table_before)
    np.testing.assert_array_equal(np.asarray(cache.lengths),
                                  len_before + n_emit)
    # full-acceptance round: feed the emitted tokens back as drafts
    slab2 = np.zeros((2, 4), np.int32)
    slab2[:, 0] = out[:, 0]
    cache2 = eng.init_cache()
    for slot in range(2):
        # rebuild the same state and verify with the TRUE continuation
        cache2, _, _ = eng.prefill(cache2, prompt, slot,
                                   pages=[int(p) for p in
                                          table_before[slot]
                                          if p != cache.null_page])
    # continuation oracle: greedy decode 3 steps
    base_stream = []
    c, t = cache2, np.asarray(toks, np.int32)
    for _ in range(3):
        c, t, _, _ = eng.decode(c, t)
        t = peel_step(np.asarray(t), 2)[0]
        base_stream.append(t.copy())
    slab3 = np.zeros((2, 4), np.int32)
    slab3[:, 0] = toks
    for j in range(3):
        slab3[:, 1 + j] = base_stream[j]
    cache3 = eng.init_cache()
    for slot in range(2):
        cache3, _, _ = eng.prefill(cache3, prompt, slot,
                                   pages=[int(p) for p in
                                          table_before[slot]
                                          if p != cache.null_page])
    cache3, host3, n_emit3, _ = eng.verify(cache3, slab3)
    assert (np.asarray(n_emit3) == 4).all()
    out3 = peel_step(np.asarray(host3), 8)[0].reshape(2, 4)
    for j in range(3):
        np.testing.assert_array_equal(out3[:, j], base_stream[j])


def test_append_slab_paged_drops_past_window():
    """Slab rows past the virtual window are DROPPED (never clamped
    onto live rows), and rows inside land at (page, offset) exactly."""
    cache = kv_cache.init_paged_cache(6, 1, 1, 4, 2, slots=1,
                                     max_pages_per_slot=2)
    row = np.asarray([0, 1], np.int32)
    cache = cache.replace(
        page_table=jnp.asarray(row)[None],
        lengths=jnp.asarray([6], jnp.int32),
        capacity=jnp.asarray([8], jnp.int32))
    k = jnp.arange(1 * 1 * 4 * 2, dtype=jnp.float32).reshape(
        1, 1, 4, 2) + 1.0
    before = np.asarray(cache.k).copy()
    cache = kv_cache.append_slab(cache, 0, k, k)
    after = np.asarray(cache.k)
    # positions 6, 7 land in page 1 rows 2, 3; positions 8, 9 are past
    # the 2-page window and vanish (no page may change but 1)
    np.testing.assert_array_equal(after[1, 0, 0, 2], np.asarray(k)[0, 0, 0])
    np.testing.assert_array_equal(after[1, 0, 0, 3], np.asarray(k)[0, 0, 1])
    changed = [p for p in range(6) if not np.array_equal(after[p],
                                                        before[p])]
    assert changed == [1]


def test_advance_by_clamps_and_flags():
    cache = kv_cache.init_paged_cache(6, 1, 1, 4, 2, slots=2,
                                     max_pages_per_slot=2)
    cache = cache.replace(
        lengths=jnp.asarray([6, 0], jnp.int32),
        capacity=jnp.asarray([8, 0], jnp.int32))
    cache, trunc = kv_cache.advance_by(cache, np.asarray([True, True]),
                                       np.asarray([4, 4], np.int32))
    # slot 0 wanted 10 > cap 8: clamped + flagged; slot 1 has capacity
    # 0 (never admitted): clamped to 0, NOT flagged
    np.testing.assert_array_equal(np.asarray(cache.lengths), [8, 0])
    np.testing.assert_array_equal(np.asarray(trunc), [True, False])


def test_set_lengths_rollback():
    cache = kv_cache.init_cache(2, 1, 1, 16, 2)
    cache = cache.replace(lengths=jnp.asarray([9, 4], jnp.int32))
    cache = kv_cache.set_lengths(cache, np.asarray([5, 4], np.int32))
    np.testing.assert_array_equal(np.asarray(cache.lengths), [5, 4])


def test_ngram_drafter_prompt_lookup():
    d = NGramDrafter(max_ngram=3)
    d.begin(0, [1, 2, 3, 4, 1, 2, 3], first_token=4)
    # history ...1,2,3,4,1,2,3,4 — suffix [2,3,4] last occurred at
    # index 1, followed by [1, 2, 3]
    assert d.draft(0, 3) == [1, 2, 3]
    d.observe(0, [9])
    # suffix now ends in 9, never seen before at any ngram length
    assert d.draft(0, 3) == []
    d.retire(0)
    assert d.draft(0, 3) == []


def test_ngram_drafter_min_ngram_refuses_coincidence():
    d = NGramDrafter(max_ngram=3, min_ngram=2)
    d.begin(0, [1, 2, 3], first_token=1)
    # only the single token 1 repeats; min_ngram=2 refuses it
    assert d.draft(0, 2) == []
    d2 = NGramDrafter(max_ngram=3, min_ngram=1)
    d2.begin(0, [1, 2, 3], first_token=1)
    assert d2.draft(0, 2) == [2, 3]


def test_engine_drafter_self_draft_full_acceptance():
    """A draft engine running the SAME weights as the target drafts
    the target's exact stream: acceptance 1.0, and the draft cache's
    rollback keeps it consistent across rounds."""
    cfg, params = _llama_gqa()
    prompts = [list((np.arange(9) * 5 + i) % 64) for i in range(2)]
    base, _ = _serve("llama", cfg, params, prompts, **_PAGED)
    draft = InferenceEngine("llama", cfg, params, slots=2, max_seq=64)
    spec, tel = _serve("llama", cfg, params, prompts, spec_k=3,
                       drafter=EngineDrafter(draft), **_PAGED)
    assert spec == base
    rate = (int(tel.spec_accepted.total())
            / int(tel.spec_drafted.total()))
    assert rate >= 0.7          # only final short rounds reject


def test_engine_drafter_rejects_misconfiguration():
    cfg, params = _gpt()
    paged = InferenceEngine("gpt", cfg, params, slots=2, max_seq=64,
                            **_PAGED)
    with pytest.raises(ValueError):
        EngineDrafter(paged)            # paged draft cache unsupported
    from apex_tpu.inference.sampling import SamplingConfig
    sampled = InferenceEngine("gpt", cfg, params, slots=2, max_seq=64,
                              sampling=SamplingConfig(temperature=0.7))
    with pytest.raises(ValueError):
        EngineDrafter(sampled)


def test_verify_requires_greedy_and_k():
    from apex_tpu.inference.engine import make_verify_fn
    from apex_tpu.inference.sampling import SamplingConfig
    cfg, _ = _gpt()
    with pytest.raises(ValueError):
        make_verify_fn("gpt", cfg, SamplingConfig(), k=0)
    with pytest.raises(ValueError):
        make_verify_fn("gpt", cfg, SamplingConfig(temperature=0.5), k=2)
    cfg2, params = _gpt()
    eng = InferenceEngine("gpt", cfg2, params, slots=2, max_seq=64)
    with pytest.raises(ValueError):
        eng.verify(eng.init_cache(), np.zeros((2, 3), np.int32))


def test_verify_step_histogram_sample_is_per_token():
    """SLO semantics: the decode-latency histogram (which the
    decode_token_p99 objective consumes) must see the EFFECTIVE
    per-token latency for a verify step — step seconds divided by the
    mean tokens emitted per active slot — never the raw multi-token
    step time; the raw wall time lands in the host-side
    spec_step_seconds tally instead (the bench speculation leg's
    clock).  Arming speculation must not read as a latency
    regression."""
    import time

    tel = ServeTelemetry(MetricsRegistry())
    with tel.verify_step(2) as holder:
        time.sleep(0.02)
        holder["tokens"] = 8.0         # 4 tokens per active slot
    assert tel.spec_step_seconds >= 0.02
    assert tel.decode_token_seconds.count() == 1
    # one sample = step_seconds / 4, strictly below the raw step time
    assert tel.decode_token_seconds.sum() <= tel.spec_step_seconds / 2
    assert int(tel.spec_verify_steps.total()) == 1


def test_default_spec_k_env(monkeypatch):
    from apex_tpu.inference.speculative import default_spec_k
    monkeypatch.delenv("APEX_TPU_SPEC_K", raising=False)
    assert default_spec_k() == 0
    monkeypatch.setenv("APEX_TPU_SPEC_K", "4")
    assert default_spec_k() == 4
    monkeypatch.setenv("APEX_TPU_SPEC_K", "-1")
    with pytest.raises(ValueError):
        default_spec_k()
    monkeypatch.setenv("APEX_TPU_SPEC_K", "many")
    with pytest.raises(ValueError):
        default_spec_k()
