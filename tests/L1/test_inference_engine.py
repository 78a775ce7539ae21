"""Inference engine: structural regression tests (ISSUE 4 acceptance;
structural checks delegated to the analysis auditors in ISSUE 5).

Pins the performance-shape properties the engine buys:

1. decode is ONE donated executable — N steps after warmup trigger zero
   new compiles, and the donated cache buffers are actually reused
   (old buffers invalidated), so no per-step cache reallocation exists
   — the auditor-INDEPENDENT cross-check, measured from compile events
   and live buffers rather than from any jaxpr walk;
2. prefill compiles once per prompt bucket, not once per prompt;
3. the jaxpr auditor's inference entries trace clean (bf16/transfer/
   output-dtype policy, including no host prims in either executable)
   and the SPMD auditor verifies the donation declarations against the
   lowered executables + keeps prefill/decode in the comm/HBM budget.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..")))

from apex_tpu.analysis.jaxpr_audit import run_jaxpr_audit
from apex_tpu.inference import InferenceEngine
from apex_tpu.inference.step_vector import peel_step
from apex_tpu.transformer import parallel_state
from apex_tpu.transformer.testing import GPTConfig, gpt_model_provider


def _engine(slots=2, max_seq=64):
    # 1-layer model: the properties under test are program COUNT/purity,
    # not model size, and the fast lane pays every compile
    parallel_state.destroy_model_parallel()
    parallel_state.initialize_model_parallel(1)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                    num_attention_heads=2, max_seq_length=max_seq,
                    hidden_dropout=0.0, attention_dropout=0.0)
    model = gpt_model_provider(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))
    return cfg, InferenceEngine("gpt", cfg, params, slots=slots,
                                max_seq=max_seq)


def test_spmd_audit_verifies_engine_donation_and_budget():
    """The SPMD auditor owns the donation/structure assertions the
    old hand-rolled jaxpr scans duplicated: both engine executables
    audit clean (donated cache verified against the lowered
    executables, no undonated alias-able buffers) and sit in the
    committed comm/HBM budget ledger."""
    from apex_tpu.analysis.spmd_audit import run_spmd_audit

    findings, report = run_spmd_audit(execs=["inference_prefill",
                                             "inference_decode"])
    assert findings == [], [(f.rule, f.message) for f in findings]
    for name in ("inference_prefill", "inference_decode"):
        entry = report["executables"][name]
        # single-chip serving: NO collective appears in either program
        # (count the primitives, not the bytes — these specs bind no
        # mesh axes, so bytes would be 0 even with a stray collective)
        assert entry["collective_counts"] == {}, entry["collective_counts"]
        assert entry["peak_live_bytes"] > 0


def test_decode_is_one_executable_and_donates():
    """Zero new compiles across a decode run after the first step, and
    the donated cache is consumed — the no-per-step-reallocation
    property measured, not asserted by convention."""
    _, eng = _engine()
    cache = eng.init_cache()
    last = np.zeros((2,), np.int32)
    active = np.ones((2,), bool)

    events = []
    from jax._src import monitoring as _mon
    saved = {attr: list(getattr(_mon, attr))
             for attr in dir(_mon)
             if attr.endswith("_listeners")
             and isinstance(getattr(_mon, attr), list)}
    jax.monitoring.register_event_listener(
        lambda name, **kw: events.append(name))
    try:
        jax.clear_caches()
        events.clear()
        for _ in range(5):
            cache, toks, _, _ = eng.decode(cache, last, active)
            last = peel_step(np.asarray(toks), eng.slots)[0]
        jax.block_until_ready(cache)
        n = sum(1 for e in events if "compile_requests" in e)
        assert n == 1, f"5 decode steps compiled {n} executables"

        # donation: the old cache buffers are invalidated by the call
        cache2 = eng.init_cache()
        kbuf, vbuf = cache2.k, cache2.v
        cache3, _, _, _ = eng.decode(cache2, last, active)
        jax.block_until_ready(cache3)
        assert kbuf.is_deleted() and vbuf.is_deleted(), \
            "decode did not consume the donated cache buffers"

        # prefill: one compile per BUCKET, zero for a second prompt in
        # the same bucket
        jax.clear_caches()
        events.clear()
        c = eng.init_cache()
        c, _, _ = eng.prefill(c, [1, 2, 3], 0)
        c, _, _ = eng.prefill(c, [4, 5, 6, 7, 8], 1)
        jax.block_until_ready(c)
        n = sum(1 for e in events if "compile_requests" in e)
        # init_cache's eager zeros cost a few one-off tiny programs;
        # the two same-bucket prefills must share ONE executable
        assert n <= 1 + 4, n
        events.clear()
        c, _, _ = eng.prefill(c, [9, 9], 0)
        jax.block_until_ready(c)
        assert not any("compile_requests" in e for e in events)
    finally:
        for attr, listeners in saved.items():
            getattr(_mon, attr)[:] = listeners


def test_decode_advances_only_active_slots():
    _, eng = _engine()
    cache = eng.init_cache()
    cache, _, _ = eng.prefill(cache, [1, 2, 3], 0)
    cache, _, _ = eng.prefill(cache, [4, 5], 1)
    lengths0 = np.asarray(cache.lengths).copy()
    cache, _, _, _ = eng.decode(cache, np.zeros((2,), np.int32),
                             np.array([True, False]))
    lengths1 = np.asarray(cache.lengths)
    assert lengths1[0] == lengths0[0] + 1
    assert lengths1[1] == lengths0[1]


def test_audit_covers_inference_entries():
    """The jaxpr auditor's inference ops trace clean — bf16/transfer/
    output-dtype policy holds with an empty baseline."""
    findings = run_jaxpr_audit(["decode_attention", "inference_prefill",
                                "inference_decode"])
    assert findings == [], [f"{f.rule}: {f.message}" for f in findings]
