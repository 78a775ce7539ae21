"""Fused transformer-block decode (ISSUE 15): the one-kernel-per-layer
lowering serves the SAME greedy tokens as the per-op path, the fused
weight layout is an exact re-slicing of the model tree, and the
dispatch knob resolves statically with the documented precedence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.inference import InferenceEngine, SlotScheduler
from apex_tpu.inference import models as inf_models
from apex_tpu.inference.step_vector import peel_step
from apex_tpu.ops.paged_attention import (
    decode_fusion,
    fusion_min_pages,
    resolve_decode_fusion,
)
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


def _gpt(layers=1):
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=layers,
                    num_attention_heads=2, max_seq_length=64,
                    hidden_dropout=0.0, attention_dropout=0.0)
    model = gpt_model_provider(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))
    return cfg, params


def _llama(kvh):
    cfg = LlamaConfig(vocab_size=64, hidden_size=32, num_layers=1,
                      num_attention_heads=4, num_kv_heads=kvh,
                      max_seq_length=64)
    model = llama_model_provider(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))
    return cfg, params


def _wave(kind, cfg, params, **engine_kw):
    eng = InferenceEngine(kind, cfg, params, slots=2, max_seq=64,
                          page_size=8, num_pages=24, **engine_kw)
    from apex_tpu.observability import MetricsRegistry, ServeTelemetry
    sched = SlotScheduler(eng, telemetry=ServeTelemetry(MetricsRegistry()))
    prompts = [list((np.arange(9) * 5 + i) % cfg.vocab_size)
               for i in range(3)]
    uids = [sched.submit(p, max_new_tokens=8) for p in prompts]
    out = sched.run()
    return [out[u] for u in uids]


def test_fused_gpt_matches_unfused_greedy():
    cfg, params = _gpt()
    assert _wave("gpt", cfg, params) == \
        _wave("gpt", cfg, params, decode_fusion="1")


@pytest.mark.parametrize("kvh", [4, 2, 1], ids=["mha", "gqa", "mqa"])
def test_fused_llama_tracks_unfused_step_locked(kvh):
    """Step-locked fused-vs-unfused parity on the LLaMA layouts: the
    SAME token stream through both lowerings, logits within the fused
    kernel's fp32-residual tolerance, argmax identical except at
    genuine near-ties (free-running greedy streams can diverge at a
    tie on random toy weights — that is the tolerance contract, not a
    bug; bitwise belongs to the fusion-off path)."""
    from apex_tpu.inference.engine import make_decode_fn
    from apex_tpu.inference.sampling import SamplingConfig

    cfg, params = _llama(kvh)
    eng = InferenceEngine("llama", cfg, params, slots=2, max_seq=64,
                          page_size=8, num_pages=24)
    alloc = eng.new_allocator()
    cache_a, cache_b = eng.init_cache(), eng.init_cache()
    prompt = list((np.arange(9) * 5) % 64)
    for slot in range(2):
        pages = alloc.acquire(alloc.pages_needed(len(prompt) + 8))
        cache_a, tok, _ = eng.prefill(cache_a, prompt, slot, pages=pages)
        cache_b, _, _ = eng.prefill(cache_b, prompt, slot, pages=pages)
    fused = inf_models.fused_layer_params("llama", cfg, params)
    unfused_fn = jax.jit(make_decode_fn("llama", cfg, SamplingConfig()),
                         donate_argnums=(0,))
    fused_fn = jax.jit(
        make_decode_fn("llama", cfg, SamplingConfig(), fused=True),
        donate_argnums=(0,))
    toks = np.asarray([int(tok), int(tok)], np.int32)
    key = jax.random.PRNGKey(0)
    active = np.ones((2,), bool)
    for step in range(4):
        cache_a, ta, la, _ = unfused_fn(cache_a, params, toks, active,
                                        key, jnp.int32(step))
        cache_b, _, lb, _ = fused_fn(cache_b, (params, fused), toks,
                                     active, key, jnp.int32(step))
        la, lb = np.asarray(la), np.asarray(lb)
        np.testing.assert_allclose(la, lb, rtol=0, atol=0.15)
        for s in range(2):
            top2 = np.sort(la[s])[-2:]
            if top2[1] - top2[0] > 0.3:         # not a near-tie
                assert la[s].argmax() == lb[s].argmax()
        # lock both paths to one stream
        toks = peel_step(np.asarray(ta), 2)[0]


def test_fused_layer_params_is_exact_reslicing():
    """The fused layout is an EXACT re-slicing of the model tree's
    weights: the deinterleaved q/k/v planes equal the interleaved
    qkv's columns bit for bit, for both weight conventions, and the
    projections through them match the model path's.  (The projections
    were pinned bitwise under jax 0.4; a [5, h] x [h, n] dot and the
    same columns of a [5, h] x [h, 3n] dot are different XLA:CPU
    kernels, and jax 0.9's sum them in different orders — 3.6e-7 apart
    — so the bitwise claim now sits on the weights, where it belongs.)"""
    cfg, params = _gpt(layers=1)
    d = cfg.hidden_size // cfg.num_attention_heads
    p = params["params"]["layer_0"]["self_attention"]["query_key_value"]
    blk = inf_models.fused_layer_params("gpt", cfg, params)[0]
    w = np.asarray(p["weight"]).T.reshape(
        cfg.hidden_size, cfg.num_attention_heads, 3, d)
    b = np.asarray(p["bias"]).reshape(cfg.num_attention_heads, 3, d)
    x = jax.random.normal(jax.random.PRNGKey(3), (5, cfg.hidden_size))
    qkv = (x @ p["weight"].T + p["bias"]).reshape(
        5, cfg.num_attention_heads, 3 * d)
    for i, (name, ref) in enumerate(zip(
            "qkv", jnp.split(qkv, 3, axis=-1))):
        np.testing.assert_array_equal(
            np.asarray(blk["w" + name]),
            w[:, :, i, :].reshape(cfg.hidden_size, -1))
        np.testing.assert_array_equal(
            np.asarray(blk["b" + name]), b[:, i, :].reshape(1, -1))
        np.testing.assert_allclose(
            np.asarray(x @ blk["w" + name] + blk["b" + name]),
            np.asarray(ref.reshape(5, -1)), rtol=1e-5, atol=1e-6)

    cfg2, params2 = _llama(2)
    d2 = cfg2.hidden_size // cfg2.num_attention_heads
    att = params2["params"]["layer_0"]["attention"]
    blk2 = inf_models.fused_layer_params("llama", cfg2, params2)[0]
    kvw = np.asarray(att["kv_proj"]["weight"]).T.reshape(
        cfg2.hidden_size, 2, 2, d2)
    x2 = jax.random.normal(jax.random.PRNGKey(4), (5, cfg2.hidden_size))
    kv = (x2 @ att["kv_proj"]["weight"].T).reshape(5, 2, 2 * d2)
    for i, (name, ref) in enumerate(zip("kv", jnp.split(kv, 2, axis=-1))):
        np.testing.assert_array_equal(
            np.asarray(blk2["w" + name]),
            kvw[:, :, i, :].reshape(cfg2.hidden_size, -1))
        np.testing.assert_allclose(
            np.asarray(x2 @ blk2["w" + name]),
            np.asarray(ref.reshape(5, -1)), rtol=1e-5, atol=1e-6)


def test_fused_decode_logits_close_to_unfused():
    """Beyond greedy-token equality: the fused kernel's logits track
    the per-op path within bf16-accumulation tolerance at every step
    (the residual chain stays fp32 in-kernel, so exact bitwise is NOT
    expected — the XLA fallback owns bitwise)."""
    from apex_tpu.inference.engine import make_decode_fn
    from apex_tpu.inference.sampling import SamplingConfig

    cfg, params = _gpt()
    eng = InferenceEngine("gpt", cfg, params, slots=2, max_seq=64,
                          page_size=8, num_pages=24)
    alloc = eng.new_allocator()
    cache_a = eng.init_cache()
    cache_b = eng.init_cache()
    prompt = list((np.arange(9) * 5) % 64)
    for slot in range(2):
        # one reservation serves BOTH caches: identical page rows in
        # two independent pools make the twin states comparable
        pages = alloc.acquire(alloc.pages_needed(len(prompt) + 8))
        cache_a, tok, _ = eng.prefill(cache_a, prompt, slot, pages=pages)
        cache_b, _, _ = eng.prefill(cache_b, prompt, slot, pages=pages)
    fused = inf_models.fused_layer_params("gpt", cfg, params)
    unfused_fn = jax.jit(make_decode_fn("gpt", cfg, SamplingConfig()),
                         donate_argnums=(0,))
    fused_fn = jax.jit(
        make_decode_fn("gpt", cfg, SamplingConfig(), fused=True),
        donate_argnums=(0,))
    toks = np.asarray([int(tok), int(tok)], np.int32)
    key = jax.random.PRNGKey(0)
    active = np.ones((2,), bool)
    ta, tb = toks, toks
    for step in range(4):
        cache_a, ta, la, _ = unfused_fn(cache_a, params, ta, active,
                                        key, jnp.int32(step))
        cache_b, tb, lb, _ = fused_fn(cache_b, (params, fused), tb,
                                      active, key, jnp.int32(step))
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=0, atol=0.15)
        np.testing.assert_array_equal(np.asarray(ta), np.asarray(tb))
        ta, tb = peel_step(ta, 2)[0], peel_step(tb, 2)[0]


def test_decode_fusion_knob_resolution(monkeypatch):
    monkeypatch.delenv("APEX_TPU_DECODE_FUSION", raising=False)
    assert decode_fusion() == "0"
    monkeypatch.setenv("APEX_TPU_DECODE_FUSION", "auto")
    assert decode_fusion() == "auto"
    assert decode_fusion("1") == "1"            # kwarg beats env
    with pytest.raises(ValueError):
        decode_fusion("maybe")
    monkeypatch.setenv("APEX_TPU_FUSION_MIN_PAGES", "4")
    assert fusion_min_pages() == 4
    assert fusion_min_pages(16) == 16
    # auto: paged window length against the crossover
    assert resolve_decode_fusion("auto", paged=True, max_pages=4)
    assert not resolve_decode_fusion("auto", paged=True, max_pages=3)
    assert not resolve_decode_fusion("auto", paged=False)
    assert not resolve_decode_fusion("0", paged=True, max_pages=99)
    with pytest.raises(ValueError):
        resolve_decode_fusion("1", paged=False)


def test_fused_block_width_is_priced_against_vmem_at_engine_build():
    """ISSUE 21: a layer whose weights do not fit the VMEM the compiler
    grants is refused when the engine is BUILT, with the limit in the
    message — not inside Mosaic on the first decode.  GPT-3 1.3B's 2048
    (16 x 128, ffn 8192, bf16) is the widest that fits on the v5e; the
    same width fits again under tp=4, attention-only."""
    from apex_tpu.ops.paged_attention import (FUSED_BLOCK_VMEM_LIMIT,
                                              fused_block_refusal,
                                              fused_block_vmem_bytes)

    def dims(hidden, tp=1):
        heads = hidden // 128 // tp
        return dict(kind="gpt", hidden=hidden, ffn=4 * hidden // tp,
                    heads=heads, kv_heads=heads, head_dim=128,
                    page_size=64, itemsize=2, fuse_mlp=tp == 1,
                    partial_out=tp > 1)

    # resident weights dominate: 24 * hidden^2 bytes at bf16
    assert 24 * 2048 ** 2 < fused_block_vmem_bytes(**dims(2048)) \
        < 1.04 * 24 * 2048 ** 2
    assert fused_block_refusal(**dims(2048)) is None
    assert resolve_decode_fusion("1", paged=True, dims=dims(2048))
    limit = f"{FUSED_BLOCK_VMEM_LIMIT / 2**20:.0f} MiB"
    for hidden in (2560, 4096):
        assert limit in fused_block_refusal(**dims(hidden))
        with pytest.raises(ValueError, match=f"hidden {hidden}.*{limit}"):
            resolve_decode_fusion("1", paged=True, dims=dims(hidden))
        # auto resolves to the path that fits instead of raising
        assert not resolve_decode_fusion("auto", paged=True, max_pages=64,
                                         dims=dims(hidden))
    assert resolve_decode_fusion("1", paged=True, dims=dims(4096, tp=4))


def test_fusion_requires_paged_engine():
    cfg, params = _gpt(layers=1)
    with pytest.raises(ValueError):
        InferenceEngine("gpt", cfg, params, slots=2, max_seq=64,
                        decode_fusion="1")
