"""Measured-truth attribution (ISSUE 14): trace ingestion + category
mapping + interval-overlap exposed-comm math + multi-rank skew +
degradation markers + the profile_capture hardening satellite."""
import json
import subprocess
import sys
from pathlib import Path

import pytest

from apex_tpu.observability.attribution import (COVERAGE_TOLERANCE,
                                                attribute,
                                                interval_measure,
                                                merge_intervals, publish,
                                                subtract_intervals)
from apex_tpu.observability.registry import MetricsRegistry
from apex_tpu.observability.trace_ingest import (PROVENANCE_MEASURED,
                                                 RankTrace, TraceEvent,
                                                 categorize,
                                                 find_trace_files,
                                                 load_profile_dirs,
                                                 parse_trace_file)

GOLDEN_PROFILE = Path(__file__).parent / "fixtures" / "trace_profile"


def _ev(name, cat, start, end):
    return TraceEvent(name=name, category=cat, start_us=float(start),
                      dur_us=float(end - start))


def _rank(events, source="rank.trace.json.gz"):
    return RankTrace(source=source, provenance=PROVENANCE_MEASURED,
                     events=events)


# -- category mapping -------------------------------------------------------

@pytest.mark.parametrize("name,expected", [
    ("dot.6", "dot"),
    ("convolution.2", "dot"),
    ("fusion.123", "fusion"),
    ("loop_fusion.4", "fusion"),
    ("all-gather.3", "collective:all_gather"),
    ("all-gather-start.3", "collective:all_gather"),
    ("all-gather-done.3", "collective:all_gather"),
    ("all-reduce.1", "collective:all_reduce"),
    ("psum.2", "collective:all_reduce"),
    ("reduce-scatter.9", "collective:reduce_scatter"),
    ("collective-permute.1", "collective:ppermute"),
    ("collective-permute-start.1", "collective:ppermute"),
    ("all-to-all.5", "collective:all_to_all"),
    ("copy.8", "copy"),
    ("copy-start.2", "copy"),
    ("infeed.1", "copy"),
    ("outfeed.1", "copy"),
    ("tanh.4.clone", "other"),
    ("reduce.77", "other"),
    ("broadcast.3", "other"),
    ("%dot.5", "dot"),
])
def test_categorize(name, expected):
    assert categorize(name) == expected


def test_wrapper_ops_are_skipped_not_attributed():
    """call/while/conditional wrap their leaves, which are traced
    individually — counting both would attribute the same wall time
    twice."""
    for name in ("call", "while.2", "conditional.1"):
        assert categorize(name) is None


# -- interval arithmetic (the exposed-comm primitive) -----------------------

def test_merge_and_measure():
    assert merge_intervals([]) == []
    merged = merge_intervals([(5, 10), (0, 3), (2, 6), (20, 21),
                              (9, 9)])
    assert merged == [(0, 10), (20, 21)]
    assert interval_measure(merged) == 11


def test_subtract_intervals_exposed_comm_math():
    """Hand-built overlap: collective (50, 70) against compute
    (0, 55) + (60, 100) leaves exactly (55, 60) exposed."""
    coll = merge_intervals([(50, 70)])
    comp = merge_intervals([(0, 55), (60, 100)])
    assert subtract_intervals(coll, comp) == [(55, 60)]
    # fully covered -> nothing; fully exposed -> itself
    assert subtract_intervals([(10, 20)], [(0, 30)]) == []
    assert subtract_intervals([(10, 20)], [(30, 40)]) == [(10, 20)]
    # cover splitting the target twice
    assert subtract_intervals([(0, 10)], [(2, 4), (6, 8)]) == \
        [(0, 2), (4, 6), (8, 10)]


# -- single-rank attribution on hand-built events ---------------------------

def _scenario_rank0():
    return _rank([
        _ev("dot.1", "dot", 0, 40),
        _ev("fusion.2", "fusion", 40, 55),
        _ev("all-gather.3", "collective:all_gather", 50, 70),
        _ev("dot.4", "dot", 60, 100),
        _ev("reduce-scatter.5", "collective:reduce_scatter", 100, 112),
        _ev("copy.6", "copy", 112, 118),
        _ev("tanh.7", "other", 118, 130),
    ], source="rank0.trace.json.gz")


def test_attribute_category_times_and_exposed_comm():
    rec = attribute([_scenario_rank0()])
    assert rec["provenance"] == "measured:trace"
    assert rec["categories"] == {
        "dot": 80.0, "fusion": 15.0, "collective:all_gather": 20.0,
        "collective:reduce_scatter": 12.0, "copy": 6.0, "other": 12.0}
    assert rec["window_us"] == 130.0
    assert rec["busy_us"] == 130.0
    assert rec["host_gap_us"] == 0.0
    # compute = dot + fusion + other union = (0,55)+(60,100)+(118,130)
    assert rec["compute_us"] == 107.0
    # all-gather (50,70): (55,60) exposed; reduce-scatter (100,112):
    # fully exposed -> 5 + 12
    assert rec["exposed_comm_us"] == 17.0
    # attributed category times + host gap sum to the window within the
    # documented tolerance (the acceptance-criterion arithmetic)
    coverage = (sum(rec["categories"].values())
                + rec["host_gap_us"]) / rec["window_us"]
    assert rec["coverage"] == pytest.approx(coverage, abs=1e-3)
    assert abs(coverage - 1.0) <= COVERAGE_TOLERANCE
    assert rec["collectives"]["all_gather"]["count"] == 1
    assert rec["collectives"]["reduce_scatter"]["time_us"] == 12.0
    assert "skew" not in rec          # single rank: no skew block


def test_attribute_steps_mfu_and_model_comparison():
    rec = attribute([_scenario_rank0()], steps=2, flops_per_step=1e9,
                    device_kind="TPU v5 lite",
                    model_exposed_comm_us=10.0)
    assert rec["steps"] == 2
    assert rec["step_us"] == 65.0
    assert rec["step_exposed_comm_us"] == 8.5
    # measured MFU = steps * flops / compute seconds / the NAMED chip's peak
    from apex_tpu.chip_specs import find_spec
    expect = 2e9 / (107e-6) / (find_spec("TPU v5 lite").bf16_tflops * 1e12)
    assert rec["mfu"] == pytest.approx(expect, abs=1e-4)
    assert rec["mfu_provenance"] == "measured:trace"
    # a device kind outside the chip table has no peak: MFU is absent
    # with a marker, never priced against another chip's
    for kind in ("cpu", None):
        rec2 = attribute([_scenario_rank0()], steps=2, flops_per_step=1e9,
                         device_kind=kind)
        assert "mfu" not in rec2
        assert rec2["mfu_provenance"] == \
            "unavailable:device-kind-not-in-chip-specs"
    assert rec["model_exposed_comm_us"] == 10.0
    assert rec["exposed_comm_drift_ratio"] == pytest.approx(0.85)


def test_mfu_degrades_with_marker_not_zero():
    rec = attribute([_scenario_rank0()])
    assert "mfu" not in rec
    assert rec["mfu_provenance"] == "unavailable:no-step-count"
    rec = attribute([_scenario_rank0()], steps=4)
    assert rec["mfu_provenance"] == "unavailable:no-compiled-flops"


# -- multi-rank merge + straggler skew --------------------------------------

def _scenario_rank1():
    return _rank([
        _ev("dot.1", "dot", 1000, 1050),
        _ev("fusion.2", "fusion", 1050, 1070),
        _ev("all-gather.3", "collective:all_gather", 1062, 1090),
        _ev("dot.4", "dot", 1080, 1130),
        _ev("reduce-scatter.5", "collective:reduce_scatter", 1130, 1150),
        _ev("tanh.7", "other", 1150, 1160),
    ], source="rank1.trace.json.gz")


def test_two_rank_merge_headline_is_the_straggler():
    rec = attribute([_scenario_rank0(), _scenario_rank1()])
    assert rec["ranks"] == 2
    # rank1's window (160) > rank0's (130): the straggler sets the step
    assert rec["window_us"] == 160.0
    assert rec["compute_us"] == 130.0
    assert rec["exposed_comm_us"] == 30.0
    skew = rec["skew"]
    assert skew["per_rank_window_us"] == [130.0, 160.0]
    assert skew["slowest_rank"] == 1
    assert skew["slowest_over_median"] == pytest.approx(160 / 130,
                                                        abs=1e-4)
    # start spreads are rebased to each rank's first op: all-gather
    # starts at +50 vs +62, reduce-scatter at +100 vs +130
    assert skew["collective_start_spread_us"] == {
        "all_gather": 12.0, "reduce_scatter": 30.0}


def test_mixed_degraded_and_measured_ranks():
    """A degraded rank drops out of the rollup but stays in sources;
    all-degraded ingestion yields the unavailable record with NO
    numeric fields (never zeros)."""
    bad = RankTrace(source="broken", provenance="unavailable:parse-failed")
    rec = attribute([_scenario_rank0(), bad])
    assert rec["ranks"] == 1
    assert rec["sources"] == ["rank0.trace.json.gz", "broken"]
    assert rec["window_us"] == 130.0

    rec = attribute([bad], steps=4, flops_per_step=1e9)
    assert rec["provenance"] == "unavailable:parse-failed"
    assert rec["ranks"] == 0
    for key in ("window_us", "busy_us", "compute_us", "exposed_comm_us",
                "categories", "mfu", "step_us"):
        assert key not in rec, key


# -- golden CPU-captured fixture --------------------------------------------

def test_golden_cpu_trace_parses_measured():
    """The committed (scrubbed) CPU capture: session-dir layout is
    discovered by globbing, op events come from the args.hlo_op
    convention, dot/other categories land, and the attributed times
    sum to the window within the documented tolerance."""
    files = find_trace_files(str(GOLDEN_PROFILE))
    assert len(files) == 1 and files[0].endswith("host0.trace.json.gz")
    [tr] = load_profile_dirs([str(GOLDEN_PROFILE)])
    assert tr.provenance == "measured:trace"
    assert tr.events == sorted(tr.events, key=lambda e: e.start_us)
    cats = {e.category for e in tr.events}
    assert "dot" in cats and "other" in cats
    rec = attribute([tr], steps=3)
    assert rec["provenance"] == "measured:trace"
    assert rec["window_us"] > 0
    assert rec["categories"]["dot"] > rec["categories"]["other"]
    assert abs(rec["coverage"] - 1.0) <= COVERAGE_TOLERANCE
    # single host, no collectives: a MEASURED zero, not a fabricated one
    assert rec["collectives"] == {}
    assert rec["exposed_comm_us"] == 0.0


def test_trace_ingest_cli_on_golden(tmp_path):
    out = tmp_path / "attribution.json"
    proc = subprocess.run(
        [sys.executable, "-m", "apex_tpu.observability.trace_ingest",
         str(GOLDEN_PROFILE), "--steps", "3", "--out", str(out)],
        capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr
    rec = json.loads(out.read_text(encoding="utf-8"))
    assert rec["provenance"] == "measured:trace"
    assert rec["steps"] == 3


# -- malformed / empty degradation ------------------------------------------

def test_empty_dir_degrades_to_marker(tmp_path):
    [tr] = load_profile_dirs([str(tmp_path)])
    assert tr.provenance == "unavailable:no-trace-files"
    assert tr.events == []


def test_malformed_trace_degrades_to_marker(tmp_path):
    bad = tmp_path / "x.trace.json.gz"
    bad.write_bytes(b"not gzip at all")
    tr = parse_trace_file(str(bad))
    assert tr.provenance.startswith("unavailable:parse-failed:")

    empty = tmp_path / "y.trace.json"
    empty.write_text(json.dumps({"traceEvents": []}), encoding="utf-8")
    assert parse_trace_file(str(empty)).provenance == \
        "unavailable:no-trace-events"

    no_ops = tmp_path / "z.trace.json"
    no_ops.write_text(json.dumps({"traceEvents": [
        {"ph": "X", "name": "python_thing", "ts": 1, "dur": 2,
         "pid": 1, "tid": 1}]}), encoding="utf-8")
    assert parse_trace_file(str(no_ops)).provenance == \
        "unavailable:no-op-events"


def test_host_python_events_are_not_ops(tmp_path):
    """The CPU profiler interleaves thousands of python host events
    with the XLA ops; only hlo_op-carrying (or device-lane) events
    attribute."""
    doc = {"traceEvents": [
        {"ph": "M", "name": "process_name", "pid": 7,
         "args": {"name": "/host:CPU"}},
        {"ph": "X", "name": "$builtins isinstance", "ts": 0, "dur": 50,
         "pid": 7, "tid": 1},
        {"ph": "X", "name": "dot.1", "ts": 10, "dur": 5, "pid": 7,
         "tid": 2, "args": {"hlo_op": "dot.1", "hlo_module": "jit_f"}},
    ]}
    p = tmp_path / "t.trace.json"
    p.write_text(json.dumps(doc), encoding="utf-8")
    tr = parse_trace_file(str(p))
    assert [e.name for e in tr.events] == ["dot.1"]


# -- publish: gauges + the attribution event --------------------------------

class _CaptureSink:
    def __init__(self):
        self.events = []

    def event(self, obj):
        self.events.append(obj)


def test_publish_sets_gauges_and_emits_event():
    reg = MetricsRegistry()
    sink = _CaptureSink()
    reg.add_sink(sink)
    rec = attribute([_scenario_rank0(), _scenario_rank1()], steps=2,
                    flops_per_step=1e9, model_exposed_comm_us=10.0)
    publish(rec, profile_dir="/tmp/p", registry=reg)
    assert reg.declared("trace_window_us").value() == 160.0
    assert reg.declared("trace_step_time_us").value() == 80.0
    assert reg.declared("trace_exposed_comm_us").value() == 30.0
    assert reg.declared("trace_category_time_us").value(
        category="dot") == 100.0
    assert reg.declared("trace_category_time_us").value(
        category="host_gap") == 0.0
    assert reg.declared("trace_rank_step_skew").value() == \
        pytest.approx(160 / 130, abs=1e-4)
    assert reg.declared("trace_collective_start_spread_us").value(
        collective="reduce_scatter") == 30.0
    [ev] = sink.events
    assert ev["kind"] == "attribution"
    assert ev["provenance"] == "measured:trace"
    assert ev["categories"]["dot"] == 100.0
    assert ev["skew"]["slowest_rank"] == 1


def test_publish_degraded_record_sets_no_gauges():
    """The degradation contract downstream: an unavailable record
    emits the event (marker + nulls) and touches NO gauge — a
    dashboard reads the marker, never a fabricated zero."""
    reg = MetricsRegistry()
    sink = _CaptureSink()
    reg.add_sink(sink)
    rec = attribute([RankTrace(source="d",
                               provenance="unavailable:no-trace-files")])
    publish(rec, profile_dir="/tmp/none", registry=reg)
    assert reg.declared("trace_window_us").value() is None
    assert reg.declared("trace_mfu").value() is None
    [ev] = sink.events
    assert ev["provenance"] == "unavailable:no-trace-files"
    assert ev["window_us"] is None and ev["mfu"] is None
    assert ev["categories"] == {}


# -- profile_capture hardening (ISSUE 14 satellite) -------------------------

def test_profile_capture_skips_already_populated_dir(tmp_path,
                                                     monkeypatch,
                                                     capsys):
    """An armed dir already holding a trace session degrades to a
    no-op with a profile_skipped event — it must never silently
    shadow the old trace."""
    from apex_tpu.observability.tracing import (profile_capture,
                                                profile_dir_unusable)
    stale = tmp_path / "prof"
    session = stale / "plugins" / "profile" / "2026_01_01_00_00_00"
    session.mkdir(parents=True)
    (session / "host0.trace.json.gz").write_bytes(b"old")
    assert profile_dir_unusable(str(stale)) == "already-populated"
    monkeypatch.setenv("APEX_TPU_PROFILE_DIR", str(stale))
    reg = MetricsRegistry()
    sink = _CaptureSink()
    reg.add_sink(sink)
    with profile_capture(tag="leg", registry=reg) as started:
        assert started is False
    [ev] = sink.events
    assert ev["kind"] == "profile_skipped"
    assert ev["reason"] == "already-populated"
    assert ev["dir"] == str(stale) and ev["tag"] == "leg"
    # the old trace is untouched
    assert (session / "host0.trace.json.gz").read_bytes() == b"old"
    assert "skipped" in capsys.readouterr().err


def test_profile_capture_skips_unwritable_target(tmp_path, monkeypatch):
    """A capture dir that cannot be created (the path is a file)
    degrades the same way instead of raising."""
    from apex_tpu.observability.tracing import (profile_capture,
                                                profile_dir_unusable)
    blocker = tmp_path / "not_a_dir"
    blocker.write_text("i am a file", encoding="utf-8")
    assert profile_dir_unusable(str(blocker)) == "unwritable"
    monkeypatch.setenv("APEX_TPU_PROFILE_DIR", str(blocker))
    reg = MetricsRegistry()
    sink = _CaptureSink()
    reg.add_sink(sink)
    with profile_capture(tag="leg", registry=reg) as started:
        assert started is False
    [ev] = sink.events
    assert ev["kind"] == "profile_skipped"
    assert ev["reason"] == "unwritable"


def test_profile_capture_fresh_dir_still_captures(tmp_path,
                                                  monkeypatch):
    """The hardening must not break the happy path: a fresh dir still
    starts a real capture and drops a parseable trace."""
    import jax
    import jax.numpy as jnp
    from apex_tpu.observability.tracing import profile_capture
    fresh = tmp_path / "prof"
    monkeypatch.setenv("APEX_TPU_PROFILE_DIR", str(fresh))
    reg = MetricsRegistry()
    sink = _CaptureSink()
    reg.add_sink(sink)
    with profile_capture(tag="leg", registry=reg) as started:
        if not started:          # profiler busy elsewhere in-process
            pytest.skip("profiler unavailable in this process")
        x = jnp.ones((64, 64))
        jax.block_until_ready(jax.jit(lambda a: a @ a)(x))
    kinds = [e["kind"] for e in sink.events]
    assert kinds == ["profile_start", "profile_stop"]
    assert find_trace_files(str(fresh))
    # and a SECOND armed capture into the now-populated dir skips
    with profile_capture(tag="leg2", registry=reg) as started2:
        assert started2 is False
    assert sink.events[-1]["kind"] == "profile_skipped"
    assert sink.events[-1]["reason"] == "already-populated"


def test_profile_capture_survives_unwritable_telemetry_target(
        tmp_path, monkeypatch, capsys):
    """The never-raises contract holds even when the registry-less
    event path itself fails: an unwritable APEX_TPU_TELEMETRY target
    drops the profile event with a warning instead of crashing the
    bench leg mid-capture."""
    from apex_tpu.observability import reset_global_registry
    from apex_tpu.observability.tracing import profile_capture
    blocker = tmp_path / "tfile"
    blocker.write_text("not a dir", encoding="utf-8")
    monkeypatch.setenv("APEX_TPU_TELEMETRY", str(blocker / "sub"))
    stale = tmp_path / "prof"
    (stale / "plugins" / "profile" / "s").mkdir(parents=True)
    (stale / "plugins" / "profile" / "s" / "x.trace.json.gz"). \
        write_bytes(b"old")
    monkeypatch.setenv("APEX_TPU_PROFILE_DIR", str(stale))
    reset_global_registry()
    try:
        with profile_capture(tag="leg") as started:   # registry=None
            assert started is False
    finally:
        reset_global_registry()
    err = capsys.readouterr().err
    assert "skipped" in err and "dropped" in err


# -- capture-hygiene extension (ISSUE 14 satellite) -------------------------

def test_hygiene_rejects_non_physical_measured_fields():
    from apex_tpu.observability.capture_hygiene import \
        scrub_capture_values
    payload = {
        "measured_mfu": 1.7,                    # > 1.0: not physics
        "mfu": 0.0,                             # RTT-collapse face
        "bert_mfu": -0.2,                       # negative garbage
        "measured_window_us": 5e9,              # > 1 h attributed time
        "measured_compute_us": -5.0,            # negative
        "measured_exposed_comm_us": 0.0,        # collapsed measurement
        "keep_mfu": 0.43,
        "measured_step_us": 81.25,
        "exposed_comm_drift_ratio": 1.5,        # ratio: not us-bounded
    }
    out = scrub_capture_values(payload)
    assert out == {"keep_mfu": 0.43, "measured_step_us": 81.25,
                   "exposed_comm_drift_ratio": 1.5}


def test_committed_capture_history_survives_mfu_rule():
    """The new (0, 1] MFU bound must not scrub any committed capture
    (they are all plausible) — the rule targets future artifacts."""
    from apex_tpu.observability.capture_hygiene import \
        scrub_capture_values
    capdir = Path(__file__).parents[3] / "bench_captures"
    checked = 0
    for path in sorted(capdir.glob("r*_*.json")):
        payload = json.loads(path.read_text(encoding="utf-8"))
        if not isinstance(payload, dict):
            continue

        def _mfu_keys(obj, prefix=""):
            if isinstance(obj, dict):
                for k, v in obj.items():
                    if isinstance(v, (dict, list)):
                        yield from _mfu_keys(v, prefix + k + ".")
                    elif "mfu" in k:
                        yield prefix + k, v
            elif isinstance(obj, list):
                for v in obj:
                    yield from _mfu_keys(v, prefix)

        before = dict(_mfu_keys(payload))
        after = dict(_mfu_keys(scrub_capture_values(payload)))
        assert before == after, path.name
        checked += len(before)
    assert checked > 0        # the history does carry mfu stamps
