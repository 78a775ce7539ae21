"""The scheduler's profiler spans are annotations only: a wave served with
a profile running gives the tokens and the compiles of one served without,
and the spans need nothing of the engine (the protocol auditor's stub has
none of its own)."""
import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.analysis.protocol_model import StubEngine
from apex_tpu.inference import InferenceEngine, SlotScheduler
from apex_tpu.observability import (MetricsRegistry, ServeTelemetry,
                                    compile_count)
from apex_tpu.transformer import parallel_state
from apex_tpu.transformer.testing import GPTConfig, gpt_model_provider

SCH = "apex_tpu.scheduler."


@pytest.fixture(scope="module")
def engine():
    parallel_state.destroy_model_parallel()
    parallel_state.initialize_model_parallel(1)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                    num_attention_heads=2, max_seq_length=64,
                    hidden_dropout=0.0, attention_dropout=0.0)
    params = gpt_model_provider(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    return InferenceEngine("gpt", cfg, params, slots=2, max_seq=64,
                           page_size=8, num_pages=24)


def _serve(engine, seed=5):
    """One seeded wave over a fresh scheduler, pass by pass: ``(tokens by
    submission order, passes run, requests retired)``."""
    rng = np.random.RandomState(seed)
    sched = SlotScheduler(engine,
                          telemetry=ServeTelemetry(MetricsRegistry()))
    uids = [sched.submit(rng.randint(1, 60, size=rng.randint(3, 20)),
                         max_new_tokens=int(rng.randint(1, 6)))
            for _ in range(7)]
    passes = 0
    sched.begin_run()
    while sched.run_pending():
        sched.run_pass()
        passes += 1
    out = sched.finish_run()
    return [out[u] for u in uids], passes, len(uids)


def _profiled(tmp_path, fn):
    """``fn()`` under a profile as the benchmark takes one; returns its
    result and how often each ``apex_tpu.*`` span was recorded."""
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        result = fn()
    finally:
        jax.profiler.stop_trace()
    found = sorted(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    spans = collections.Counter(
        e.name for plane in ProfileData.from_file(str(found[-1])).planes
        for line in plane.lines for e in line.events
        if e.name.startswith("apex_tpu."))
    return result, spans


def test_a_profile_changes_neither_the_tokens_nor_the_compiles(
        engine, tmp_path):
    _serve(engine)                                  # every shape compiled
    before = compile_count()
    plain, passes, requests = _serve(engine)
    assert compile_count() == before
    (traced, traced_passes, _), spans = _profiled(
        tmp_path, lambda: _serve(engine))
    assert compile_count() == before
    assert traced == plain and traced_passes == passes
    # the profile did run, and holds one span a pass, submit and retire
    assert spans[SCH + "pass"] == spans[SCH + "admit"] == passes
    assert spans[SCH + "submit"] == requests
    assert spans["apex_tpu.inference.evict_slot"] == requests
    assert spans[SCH + "prefill"] == spans["apex_tpu.inference.prefill"] \
        == requests
    # one read a launch over the wave; a decode span holds the launch of
    # a step, the read of the step launched a pass earlier, or both
    # (ISSUE 37), so a wave has as many more of them as it drains
    launches = spans["apex_tpu.inference.decode"]
    assert 0 < launches < spans[SCH + "decode"] <= launches + requests
    assert spans[SCH + "token_read"] == launches + requests


def test_the_spans_work_over_the_auditors_stub_engine(tmp_path):
    def wave():
        sched = SlotScheduler(
            StubEngine(slots=2, num_pages=12, page_size=4,
                       max_pages_per_slot=4),
            telemetry=ServeTelemetry(MetricsRegistry()))
        uids = [sched.submit([1 + i, 2, 3, 4, 5], max_new_tokens=3)
                for i in range(4)]
        out = sched.run()
        return [out[u] for u in uids]
    plain = wave()
    traced, spans = _profiled(tmp_path, wave)
    assert traced == plain and all(len(t) == 3 for t in plain)
    assert spans[SCH + "pass"] > 0 and spans[SCH + "retire"] > 0
    assert spans[SCH + "prefill"] == 4
    # the stub opens no span of its own and needs none
    assert not any(n.startswith("apex_tpu.inference.") for n in spans)


def test_an_event_no_sink_listens_to_is_checked_and_not_built(monkeypatch):
    """Every lifecycle call of a pass goes through ``emit_event``: with no
    sink attached it reads no clock and builds no dict, and an undeclared
    kind is still a programming error."""
    from apex_tpu.observability import registry, schema

    def clock():
        raise AssertionError("an event was built for nobody")
    monkeypatch.setattr(registry.time, "time", clock)
    reg = MetricsRegistry()
    reg.emit_event(next(iter(schema.EVENT_FIELDS)), uid=1)
    with pytest.raises(KeyError, match="not declared"):
        reg.emit_event("no_such_kind")
