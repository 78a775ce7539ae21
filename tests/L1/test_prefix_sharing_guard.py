"""Tier-1 guard (ISSUE 12 satellite): prefix sharing is a PAGE-TABLE
edit, not a program change — machine-checked, not claimed.

1. A warm paged engine serving N prefix-sharing requests (extension
   hits, an exact-repeat full-cover hit with its COW, interleaved
   retires) triggers ZERO new XLA compiles: ``prefill_from`` and the
   page rows are traced operands, and the COW copy is one compiled
   program warmed with everything else.
2. The committed SPMD/comm budget ledger is untouched by the serving
   path: exactly the 18 registered executables, no prefix-sharing
   entry added, and the jaxpr-audited executable registry still pins
   the paged prefill/decode (+ COW) programs it always did.
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
from apex_tpu.observability import MetricsRegistry, ServeTelemetry
from apex_tpu.transformer import parallel_state
from apex_tpu.transformer.testing import GPTConfig, gpt_model_provider

# 18 at ISSUE 12; ISSUE 15 consciously added the fused-block decode
# twin and the speculative verify step; ISSUE 17 the three tp=2
# tensor-parallel serving executables; ISSUE 18 the two host-tier
# swap copy programs; ISSUE 34 the latent-attention kind's decode step
# (the only legitimate way this number moves: a new REGISTERED
# executable, never a serving-path side effect)
BUDGETED_EXECUTABLES = 27


def _engine():
    parallel_state.destroy_model_parallel()
    parallel_state.initialize_model_parallel(1)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                    num_attention_heads=2, max_seq_length=64,
                    hidden_dropout=0.0, attention_dropout=0.0)
    model = gpt_model_provider(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))
    return InferenceEngine("gpt", cfg, params, slots=2, max_seq=64,
                           page_size=8, num_pages=16)


def test_warm_prefix_sharing_wave_adds_zero_compiles():
    eng = _engine()
    prefix = list((np.arange(16) * 5 + 2) % 64)

    def wave(sched, prompts, mnt=3):
        for p in prompts:
            sched.submit(p, max_new_tokens=mnt)
        return sched.run()

    sched = SlotScheduler(eng,
                          telemetry=ServeTelemetry(MetricsRegistry()))
    # warm EVERY program the measured wave uses: the cold full-prompt
    # bucket, the decode step, then (second wave, cache populated) the
    # hit path's suffix bucket and the COW copy
    wave(sched, [prefix + [1, 2]])
    wave(sched, [prefix + [1, 2], prefix + [9]])
    assert int(sched.telemetry.prefix_hits.total()) >= 2
    assert int(sched.telemetry.cow_copies.total()) >= 1

    events = []
    from jax._src import monitoring as _mon
    saved = {attr: list(getattr(_mon, attr))
             for attr in dir(_mon)
             if attr.endswith("_listeners")
             and isinstance(getattr(_mon, attr), list)}
    jax.monitoring.register_event_listener(
        lambda name, **kw: events.append(name))
    try:
        # the measured wave: more requests than slots (retire/readmit
        # churn), extension hits, an exact repeat (COW), all warm
        out = wave(sched, [prefix + [10], prefix + [11],
                           prefix + [1, 2], prefix + [12]])
    finally:
        for attr, listeners in saved.items():
            getattr(_mon, attr)[:] = listeners
    assert all(len(v) == 3 for v in out.values())
    compiles = [e for e in events if "compile_requests" in e]
    assert not compiles, compiles
    tel = sched.telemetry
    assert int(tel.recompiles.total()) == 0
    assert int(tel.prefix_hits.total()) >= 6


def test_budget_ledger_untouched_by_prefix_sharing():
    """The committed ledger carries EXACTLY the 18 executables it
    carried before prefix sharing landed — sharing added no device
    programs — and the inference entries it pins are the (audited)
    prefill/decode pair per cache layout."""
    root = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                        "..", ".."))
    from apex_tpu.analysis.spmd_audit import BUDGET_NAME
    with open(os.path.join(root, BUDGET_NAME)) as f:
        committed = json.load(f)["executables"]
    assert len(committed) == BUDGETED_EXECUTABLES, sorted(committed)
    inference_entries = {k for k in committed if "inference" in k}
    assert inference_entries == {
        "inference_prefill", "inference_decode",
        "inference_prefill_paged", "inference_decode_paged",
        "inference_decode_fused_paged", "inference_verify_paged",
        "inference_decode_latent", "inference_decode_select",
        "inference_prefill_paged_tp2", "inference_decode_fused_paged_tp2",
        "inference_verify_paged_tp2",
        "inference_swap_out_paged", "inference_swap_in_paged"}
    # the serving-side program set is closed: the COW copy rides the
    # jaxpr audit (precision/transfer) without a budget entry, and no
    # "prefix" executable exists anywhere in the registry
    from apex_tpu.analysis.jaxpr_audit import op_specs
    names = {s.name for s in op_specs()}
    assert "inference_cow_page" in names
    assert not any("prefix" in n for n in names)

    from apex_tpu.analysis.spmd_audit import exec_specs
    spmd_names = {s.name for s in exec_specs()}
    assert spmd_names == set(committed)
