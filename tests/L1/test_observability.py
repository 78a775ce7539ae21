"""ISSUE 8 acceptance: runtime telemetry instruments serving and
training WITHOUT violating the two sacred invariants — every
instrumented path keeps ONE donated executable per step (zero compiles
after warmup, recompile counters pinned 0), and zero host syncs are
added (device scalars resolve one step late; the serving brackets close
only around host reads the loop performs anyway).

Integration-level: real engine + scheduler serving N requests, real
flat-native training steps, real sinks on disk."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import observability as obs
from apex_tpu import train_step
from apex_tpu.inference import InferenceEngine, SlotScheduler
from apex_tpu.observability import (JsonlSink, MetricsRegistry,
                                    PrometheusSink, ServeTelemetry,
                                    TrainTelemetry, schema)
from apex_tpu.optimizers import functional
from apex_tpu.transformer import parallel_state
from apex_tpu.transformer.testing import GPTConfig, gpt_model_provider

N_REQUESTS = 5


@pytest.fixture(scope="module")
def engine():
    parallel_state.destroy_model_parallel()
    parallel_state.initialize_model_parallel(1)
    cfg = GPTConfig(vocab_size=32, hidden_size=32, num_layers=1,
                    num_attention_heads=2, max_seq_length=64,
                    hidden_dropout=0.0, attention_dropout=0.0)
    model = gpt_model_provider(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))
    eng = InferenceEngine("gpt", cfg, params, slots=2, max_seq=64)
    # warm every executable (prefill bucket + decode) through a
    # throwaway scheduler so the measured waves below see a warm engine
    warm = SlotScheduler(eng, telemetry=ServeTelemetry(MetricsRegistry()))
    for i in range(3):
        warm.submit([1 + i, 2, 3], max_new_tokens=3)
    warm.run()
    return eng


# -- serving ---------------------------------------------------------------

def test_serve_n_requests_metric_consistency(engine, tmp_path):
    """The headline acceptance: N requests through the REAL engine —
    TTFT histogram count == N, recompile counter == 0, and the serve
    adds ZERO compiles to the warm executables (compile count still 1
    per program)."""
    reg = MetricsRegistry()
    jsonl = tmp_path / "telemetry.jsonl"
    prom = tmp_path / "metrics.prom"
    reg.add_sink(JsonlSink(str(jsonl)))
    reg.add_sink(PrometheusSink(str(prom)))
    tel = ServeTelemetry(reg)

    c0 = obs.compile_count()
    sched = SlotScheduler(engine, telemetry=tel)
    uids = [sched.submit([1 + i, 2, 3], max_new_tokens=3)
            for i in range(N_REQUESTS)]
    out = sched.run()
    assert obs.compile_count() == c0, \
        "serving a wave on a warm engine must compile NOTHING"

    assert sorted(out) == sorted(uids)
    # metric consistency
    assert tel.ttft.count() == N_REQUESTS
    assert int(tel.recompiles.total()) == 0
    assert int(tel.admitted.total()) == N_REQUESTS
    assert int(tel.finished.total()) == N_REQUESTS
    assert int(tel.tokens_generated.total()) == \
        sum(len(v) for v in out.values())
    assert tel.decode_token_seconds.count() == \
        int(tel.decode_steps.total()) > 0
    c = tel.conservation()
    assert c["submitted"] == c["finished"] + c["active"] + c["rejected"]
    assert c["active"] == 0

    # JSONL stream: every lifecycle event present, schema-shaped
    events = [json.loads(ln) for ln in
              jsonl.read_text().splitlines()]
    by_kind: dict = {}
    for e in events:
        by_kind.setdefault(e["kind"], []).append(e)
    for kind in ("request_submit", "request_admit",
                 "request_first_token", "request_finish"):
        assert len(by_kind[kind]) == N_REQUESTS, kind
    for e in events:
        declared = schema.EVENT_FIELDS[e["kind"]]
        assert set(e) == {"ts", "kind"} | set(declared), e["kind"]
        for field, ftype in declared.items():
            v = e[field]
            if ftype == "int":
                assert isinstance(v, int) and not isinstance(v, bool)
            elif ftype == "float":
                assert isinstance(v, (int, float))
            elif ftype == "str":
                assert isinstance(v, str)
            elif ftype == "int|null":
                assert v is None or isinstance(v, int)
            elif ftype == "float|null":
                assert v is None or isinstance(v, (int, float))
            elif ftype == "bool":
                assert isinstance(v, bool)
    # TTFT values are physical (the scrub rule bench enforces on
    # captures holds at the source)
    for e in by_kind["request_first_token"]:
        assert 0 < e["ttft_s"] < 3600

    # Prometheus exposition lands on export
    reg.export()
    text = prom.read_text()
    assert f"serve_ttft_seconds_count {N_REQUESTS}" in text
    assert "serve_recompiles_total 0" in text
    assert 'serve_requests_finished_total{reason="length"} 5' in text


def test_trace_and_slo_armed_add_zero_compiles(engine, tmp_path,
                                               monkeypatch):
    """ISSUE 13 acceptance: a warm engine serving a wave with
    APEX_TPU_TRACE=1 and both SLO knobs armed adds ZERO compiles and
    keeps the recompile counter at 0 — tracing and SLO accounting are
    pure host bookkeeping.  The trace_span stream is schema-shaped,
    every trace closes terminal, and the SLO window published burn
    rates off the live histograms."""
    monkeypatch.setenv("APEX_TPU_TRACE", "1")
    monkeypatch.setenv("APEX_TPU_SLO_TTFT_US", "3600000000")
    monkeypatch.setenv("APEX_TPU_SLO_DECODE_US", "1")
    reg = MetricsRegistry()
    jsonl = tmp_path / "telemetry.jsonl"
    reg.add_sink(JsonlSink(str(jsonl)))
    tel = ServeTelemetry(reg)              # trace armed from the env
    assert tel.tracer.sample == 1

    c0 = obs.compile_count()
    sched = SlotScheduler(engine, telemetry=tel)   # SLO specs from env
    uids = [sched.submit([1 + i, 2, 3], max_new_tokens=3)
            for i in range(N_REQUESTS)]
    out = sched.run()
    assert obs.compile_count() == c0, \
        "tracing/SLO accounting must compile NOTHING on a warm engine"
    assert int(tel.recompiles.total()) == 0
    assert sorted(out) == sorted(uids)

    # span conservation at the wave boundary
    sc = tel.tracer.conservation()
    assert sc["started"] == sc["closed"] == N_REQUESTS
    assert sc["dangling"] == [] and sc["orphan_terminals"] == []

    # the JSONL stream carries schema-shaped trace spans for every uid
    events = [json.loads(ln) for ln in jsonl.read_text().splitlines()]
    spans = [e for e in events if e["kind"] == "trace_span"]
    declared = schema.EVENT_FIELDS["trace_span"]
    assert {e["uid"] for e in spans} == set(uids)
    for e in spans:
        assert set(e) == {"ts", "kind"} | set(declared)
    for uid in uids:
        names = [e["span"] for e in spans if e["uid"] == uid]
        assert names[0] == "queued" and names[-1] == "retired"
        assert "first_token" in names and "decode" in names

    # the wave boundary closed an SLO window: a 1h TTFT target is
    # never violated, a 1µs decode target always is — burn rates off
    # the same histograms the lifecycle methods fed
    assert sched.slo.burn_rate.value(slo="ttft_p99") == 0.0
    assert sched.slo.burn_rate.value(slo="decode_token_p99") == \
        pytest.approx(100.0)
    assert sched.slo.budget_remaining.value(slo="ttft_p99") == 1.0
    assert any(e["kind"] == "slo_violation"
               and e["slo"] == "decode_token_p99" for e in events)


def test_serve_telemetry_summary_shape(engine):
    tel = ServeTelemetry(MetricsRegistry())
    sched = SlotScheduler(engine, telemetry=tel)
    sched.submit([1, 2, 3], max_new_tokens=2)
    sched.run()
    s = tel.summary()
    assert s["requests"] == 1 and s["recompiles"] == 0
    assert s["ttft_p50_s"] > 0 and s["decode_token_p50_s"] > 0


# -- training --------------------------------------------------------------

def _make_params(seed=0, n_layers=2):
    rng = np.random.RandomState(seed)
    return {k: jnp.asarray(v, jnp.float32)
            for i in range(n_layers)
            for k, v in ((f"w{i}", rng.randn(8, 8) * 0.3),
                         (f"b{i}", rng.randn(8) * 0.01))}


def _loss_fn(params, batch):
    h = batch["x"]
    for i in range(len(params) // 2):
        h = jnp.tanh(h @ params[f"w{i}"] + params[f"b{i}"])
    return jnp.mean((h - batch["y"]) ** 2)


def _batches(n, seed=1):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, 16, 8).astype(np.float32)
    return {"x": jnp.asarray(x),
            "y": jnp.tanh(jnp.asarray(x) @ jnp.ones((8, 8)) * 0.1)}


def test_instrumented_train_loop_zero_recompiles_and_parity():
    """The instrumented loop: same math as train_loop, ONE donated
    executable (steps after the first add zero compiles), loss gauge
    fed one step late through the deferred collector."""
    n = 6
    params = _make_params()
    tx = functional.fused_adam(lr=1e-2)
    tel = TrainTelemetry(MetricsRegistry())
    run = train_step.instrumented_train_loop(
        _loss_fn, tx, telemetry=tel, tokens_per_batch=16)

    state = train_step.init_train_state(tx, params, loss_scale="dynamic")
    state, metrics = run(state, _batches(n))
    losses = [float(m[0] if isinstance(m, tuple) else m)
              for m in metrics]

    assert int(tel.steps.total()) == n
    assert int(tel.recompiles.total()) == 0, \
        "instrumentation must not break the ONE-executable property"
    assert tel.step_seconds.count() == n
    assert tel.tokens_per_s.value() > 0
    # flush() drained the deferred collector: the loss gauge holds the
    # FINAL step's loss, the scale gauge the live dynamic scale
    assert tel.loss.value() == pytest.approx(losses[-1])
    assert tel.loss_scale.value() == float(state.scaler.loss_scale)
    assert int(tel.overflow_skips.total()) == 0

    # numerical parity with the scanned (uninstrumented) loop
    ref_state = train_step.init_train_state(tx, _make_params(),
                                            loss_scale="dynamic")
    ref_state, ref_losses = train_step.train_loop(_loss_fn, tx)(
        ref_state, _batches(n))
    np.testing.assert_allclose(losses, np.asarray(ref_losses).ravel(),
                               rtol=1e-6)


def test_instrumented_loop_arms_mfu_from_compiled_flops(monkeypatch):
    """mfu_from_compiled=True (ISSUE 10): the gauge is priced from the
    COMPILED step's cost_analysis() FLOPs, and the one AOT compile at
    run start lands outside every step bracket — the recompile counter
    still pins 0."""
    # this host's device kind ("cpu") is not in the chip table, so the
    # gauge's peak is the nominal chip, asked for by name
    from apex_tpu import chip_specs
    monkeypatch.setattr(chip_specs, "local_spec", chip_specs.default_spec)
    params = _make_params()
    tx = functional.fused_adam(lr=1e-2)
    tel = TrainTelemetry(MetricsRegistry())
    run = train_step.instrumented_train_loop(
        _loss_fn, tx, telemetry=tel, tokens_per_batch=16,
        mfu_from_compiled=True)
    state = train_step.init_train_state(tx, params, loss_scale="dynamic")
    run(state, _batches(4))
    assert int(tel.recompiles.total()) == 0
    flops = tel.model_flops_per_step.value()
    assert flops is not None and flops > 0
    assert tel.mfu.value() is not None and tel.mfu.value() > 0
    # the badput decomposition settled at flush: buckets conserve the
    # run's wall clock (everything productive here — no overflow, no
    # recompile)
    g = tel.goodput()
    assert g["overflow_s"] == 0.0 and g["recompile_s"] == 0.0
    assert g["productive_s"] > 0 and g["wall_s"] > 0
    assert g["goodput_fraction"] == pytest.approx(
        g["productive_s"] / g["wall_s"])


def test_instrumented_loop_counts_overflow_skips():
    """found_inf reaches the overflow-skip counter one step late,
    through the deferred collector — never through a blocking read."""
    params = _make_params()
    tx = functional.fused_adam(lr=1e-2)

    def loss_fn(p, b):
        # poison = 0 -> clean loss; huge -> inf grads -> found_inf
        return _loss_fn(p, b) + jnp.sum(p["w0"]) * b["poison"]

    tel = TrainTelemetry(MetricsRegistry())
    run = train_step.instrumented_train_loop(loss_fn, tx, telemetry=tel)
    batches = dict(_batches(3),
                   poison=jnp.asarray([1e38, 0.0, 0.0], jnp.float32))
    state = train_step.init_train_state(tx, params, loss_scale="dynamic")
    scale0 = float(state.scaler.loss_scale)
    state, _ = run(state, batches)
    assert int(tel.overflow_skips.total()) == 1
    assert float(state.scaler.loss_scale) == scale0 * 0.5
    assert tel.loss_scale.value() == float(state.scaler.loss_scale)


def test_gauges_populate_exactly_one_step_late_mid_run():
    """The documented deferral is ONE step: after step k's
    observe_device, the gauges hold step k-1's scalars — without
    waiting for flush()."""
    tel = TrainTelemetry(MetricsRegistry())
    with tel.step():
        pass
    tel.observe_device(loss=jnp.float32(1.0))
    assert tel.loss.value() is None        # nothing strictly older yet
    with tel.step():
        pass
    tel.observe_device(loss=jnp.float32(2.0))
    assert tel.loss.value() == 1.0         # previous step, live mid-run
    with tel.step():
        pass
    tel.observe_device(loss=jnp.float32(3.0))
    assert tel.loss.value() == 2.0


def test_flush_resets_step_interval_chain():
    """Reusing one telemetry across runs: the idle gap between runs is
    never a step sample, AND the boundary-less warm first step of run 2
    publishes no timing at all (its bracket would be pure dispatch —
    the async artifact the interval scheme exists to avoid)."""
    import time as _time
    tel = TrainTelemetry(MetricsRegistry())
    for _ in range(2):
        with tel.step():
            pass
    assert tel.step_seconds.count() == 2   # cold bracket + interval
    tel.flush()                            # run boundary
    _time.sleep(0.25)                      # eval/checkpoint idle gap
    with tel.step():
        pass                               # warm, boundary-less: no sample
    assert tel.step_seconds.count() == 2
    assert int(tel.steps.total()) == 3     # still counted as a step
    with tel.step():
        pass                               # boundary restored: interval
    assert tel.step_seconds.count() == 3
    assert tel.step_seconds.sum() < 0.25, \
        "the inter-run idle gap leaked into a step sample"


def test_train_jsonl_events(tmp_path):
    reg = MetricsRegistry()
    reg.add_sink(JsonlSink(str(tmp_path / "t.jsonl")))
    tel = TrainTelemetry(reg)
    tx = functional.fused_adam(lr=1e-2)
    run = train_step.instrumented_train_loop(_loss_fn, tx, telemetry=tel)
    state = train_step.init_train_state(tx, _make_params(),
                                        loss_scale="dynamic")
    run(state, _batches(3))
    events = [json.loads(ln) for ln in
              (tmp_path / "t.jsonl").read_text().splitlines()]
    steps = [e for e in events if e["kind"] == "train_step"]
    assert [e["step"] for e in steps] == [0, 1, 2]
    assert all(e["recompiled"] is False for e in steps)
    assert all(e["seconds"] > 0 for e in steps)


# -- env-knob configuration -------------------------------------------------

def test_configure_from_env_attaches_sinks(tmp_path, monkeypatch):
    monkeypatch.setenv("APEX_TPU_TELEMETRY", str(tmp_path / "obsdir"))
    reg = MetricsRegistry()
    obs.configure_from_env(reg)
    kinds = {type(s).__name__ for s in reg.sinks}
    assert kinds == {"JsonlSink", "PrometheusSink"}
    reg.declared("train_steps_total").inc()
    reg.export()
    assert (tmp_path / "obsdir" / "metrics.prom").exists()


def test_telemetry_knob_off_means_no_sinks(monkeypatch):
    monkeypatch.setenv("APEX_TPU_TELEMETRY", "0")
    reg = MetricsRegistry()
    obs.configure_from_env(reg)
    assert reg.sinks == ()
