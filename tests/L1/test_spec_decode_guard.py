"""Tier-1 guard (ISSUE 15): speculative decoding and fused-block
decode are LOWERING choices inside the closed executable set —
machine-checked, not claimed.

1. A WARM paged engine serving a speculation wave (drafts accepted,
   rejected, retire/readmit churn) triggers ZERO new XLA compiles:
   the verify step compiles once per (k, engine), the slab/active
   operands are traced, and accept/reject is an in-program length
   rollback — no rollback program, no per-outcome executables.
2. The committed SPMD/comm budget ledger carries the fused decode and
   the verify step as REGISTERED, audited executables (the only
   legitimate way the closed set grows), and the jaxpr auditor pins
   the fused-block kernel op itself.
3. The per-op decode path (fusion off) serves what the DENSE slot
   cache serves: the same greedy tokens step for step, its logits
   within the ``apex_paged_decode`` kernel's tolerance (since ISSUE 31
   every paged decode attends through that kernel; the XLA gather
   whose logits were bit-identical to the dense cache's is gone).
"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..")))

from apex_tpu.inference import InferenceEngine, SlotScheduler
from apex_tpu.inference.step_vector import peel_step
from apex_tpu.observability import MetricsRegistry, ServeTelemetry
from apex_tpu.transformer import parallel_state
from apex_tpu.transformer.testing import GPTConfig, gpt_model_provider


def _engine(**kw):
    parallel_state.destroy_model_parallel()
    parallel_state.initialize_model_parallel(1)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                    num_attention_heads=2, max_seq_length=64,
                    hidden_dropout=0.0, attention_dropout=0.0)
    model = gpt_model_provider(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))
    return InferenceEngine("gpt", cfg, params, slots=2, max_seq=64,
                           page_size=8, num_pages=16, **kw), cfg, params


def test_warm_speculation_wave_adds_zero_compiles():
    eng, _, _ = _engine(spec_k=3)
    prompts = [list((np.arange(12) * 5 + i) % 64) for i in range(5)]

    def wave(sched, ps, mnt=6):
        for p in ps:
            sched.submit(p, max_new_tokens=mnt)
        return sched.run()

    sched = SlotScheduler(eng,
                          telemetry=ServeTelemetry(MetricsRegistry()))
    # warm every program the measured wave uses: the cold prefill
    # bucket and the verify step, then — second wave, prefix cache
    # populated — the hit path's suffix bucket and the COW copy
    wave(sched, prompts[:2])
    wave(sched, prompts[:2])

    events = []
    from jax._src import monitoring as _mon
    saved = {attr: list(getattr(_mon, attr))
             for attr in dir(_mon)
             if attr.endswith("_listeners")
             and isinstance(getattr(_mon, attr), list)}
    jax.monitoring.register_event_listener(
        lambda name, **kw: events.append(name))
    try:
        # more requests than slots (retire/readmit churn), repeated
        # structure (acceptance > 0) and fresh prompts (rejections)
        out = wave(sched, prompts)
    finally:
        for attr, listeners in saved.items():
            getattr(_mon, attr)[:] = listeners
    assert all(len(v) == 6 for v in out.values())
    compiles = [e for e in events if "compile_requests" in e]
    assert not compiles, compiles
    tel = sched.telemetry
    assert int(tel.recompiles.total()) == 0
    assert int(tel.spec_verify_steps.total()) > 0
    # speculation accounting is conserved across every wave this
    # telemetry observed: emitted == generated minus one
    # prefill-sampled first token per finished request
    assert int(tel.spec_emitted.total()) == \
        int(tel.tokens_generated.total()) - int(tel.finished.total())


def test_ledger_carries_fused_and_verify_executables():
    root = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                        "..", ".."))
    from apex_tpu.analysis.spmd_audit import BUDGET_NAME, exec_specs
    with open(os.path.join(root, BUDGET_NAME)) as f:
        committed = json.load(f)["executables"]
    assert "inference_decode_fused_paged" in committed
    assert "inference_verify_paged" in committed
    assert {s.name for s in exec_specs()} == set(committed)
    from apex_tpu.analysis.jaxpr_audit import op_specs
    names = {s.name for s in op_specs()}
    assert {"fused_block_decode", "inference_decode_fused_paged",
            "inference_verify_paged"} <= names


def test_fusion_off_decode_serves_the_dense_caches_tokens():
    """The acceptance criterion's parity half: an engine built with
    fusion OFF (the default) serves the per-op decode — the same greedy
    token, step for step, as the DENSE slot cache on mirrored state,
    logits within the paged kernel's bf16 tolerance (the ISSUE 6 parity
    property, re-pinned through the fusion-capable engine so the knob
    cannot silently perturb the per-op lowering)."""
    eng, cfg, params = _engine()           # decode_fusion default "0"
    assert not eng.decode_fused
    dense = InferenceEngine("gpt", cfg, params, slots=2, max_seq=64)
    alloc = eng.new_allocator()
    cache_p, cache_d = eng.init_cache(), dense.init_cache()
    prompt = list((np.arange(12) * 5) % 64)
    toks = []
    for slot in range(2):
        pages = alloc.acquire(alloc.pages_needed(len(prompt) + 4))
        cache_p, tok, _ = eng.prefill(cache_p, prompt, slot,
                                      pages=pages)
        cache_d, _, _ = dense.prefill(cache_d, prompt, slot)
        toks.append(int(tok))
    toks_p = toks_d = np.asarray(toks, np.int32)
    for _ in range(3):
        cache_p, toks_p, lp, _ = eng.decode(cache_p, toks_p)
        cache_d, toks_d, ld, _ = dense.decode(cache_d, toks_d)
        toks_p = peel_step(np.asarray(toks_p), 2)[0]
        toks_d = peel_step(np.asarray(toks_d), 2)[0]
        np.testing.assert_allclose(np.asarray(lp), np.asarray(ld),
                                   rtol=2e-2, atol=2e-2)
        np.testing.assert_array_equal(np.asarray(toks_p),
                                      np.asarray(toks_d))
