"""The per-layer metrics that read the program's own spans and kernel names:
their arithmetic on a hand-built trace, and the span tree the program really
opens, read back from a profile of the toy serving cell on the CPU."""
import dataclasses
import types
from pathlib import Path

import pytest

from benchmark import harness, spans, trace, traffic
from benchmark.drivers import serve as D

REPO = Path(__file__).resolve().parents[2]
MS = 1e6                                  # the trace's clock is nanoseconds
SPAN_METRICS = ["sched_host_ms_per_pass", "sched_admit_ms_mean",
                "engine_dispatch_ms_per_pass", "dispatches_per_pass",
                "idle_ms_per_pass.scheduler", "idle_ms_per_pass.engine",
                "idle_ms_per_pass.harness"]
KERNEL_METRICS = ["lamb_kernel_ms", "unscale_kernel_ms"]


def _ev(name, lo, hi):
    return trace.Event(name, lo * MS, hi * MS)


def _hand_trace():
    """100 ms of device window, four passes (milliseconds below):

    * D 2..8     admits only (traced, not decoding)
    * A 10..40   decode, then a retire that evicts a slot
    * B 45..95   admit with a copy-on-write, a prefill, decode, retire
    * C 96..110  ends after the last device operation: not a traced pass
    """
    sch, eng = "apex_tpu.scheduler.", "apex_tpu.inference."
    host = [
        _ev("bench.run_pass", 1.5, 8.5), _ev(sch + "pass", 2, 8),
        _ev(sch + "admit", 2, 4),
        _ev("bench.run_pass", 9.5, 40.5), _ev(sch + "pass", 10, 40),
        _ev(sch + "admit", 10, 12), _ev(sch + "decode", 12, 35),
        _ev(eng + "decode", 13, 15), _ev(sch + "token_read", 15, 34),
        _ev(sch + "retire", 35, 40), _ev(eng + "evict_slot", 36, 38),
        _ev("bench.stamp_tokens", 40.6, 44),
        _ev("bench.run_pass", 44.5, 95.5), _ev(sch + "pass", 45, 95),
        _ev(sch + "admit", 45, 50), _ev(eng + "cow_page", 46, 47),
        _ev(sch + "prefill", 50, 70), _ev(eng + "prefill", 51, 54),
        _ev(sch + "token_read", 54, 68), _ev(sch + "decode", 70, 90),
        _ev(eng + "decode", 71, 72), _ev(sch + "token_read", 72, 89),
        _ev(sch + "retire", 90, 95),
        _ev("bench.run_pass", 95.8, 111), _ev(sch + "pass", 96, 110),
        _ev(sch + "admit", 96, 97), _ev(sch + "decode", 97, 109),
        _ev(eng + "decode", 97.5, 98.5), _ev(sch + "token_read", 98.5, 108),
    ]
    host.sort(key=lambda e: e.start)
    ops = [_ev("%fusion.1 = f32[8]{0} fusion(%p)", 0, 9),
           _ev("%fusion.2 = f32[8]{0} fusion(%p)", 14, 34),
           _ev("%fusion.3 = f32[8]{0} fusion(%p)", 37, 37.5),
           _ev("%copy.1 = f32[8]{0} copy(%p)", 46.5, 47),
           _ev("%fusion.4 = f32[8]{0} fusion(%p)", 52, 68),
           _ev("%fusion.2 = f32[8]{0} fusion(%p)", 71.5, 89),
           _ev("%fusion.2 = f32[8]{0} fusion(%p)", 99, 100)]
    return trace.Trace(ops={0: ops}, modules={0: []}, host=host)


def _run(tr, facts=None):
    return types.SimpleNamespace(
        cell=types.SimpleNamespace(root=REPO), trace=tr, facts=facts or {})


def _read(name, tr, facts=None):
    """Through the harness's own loader: the reader file, found by name."""
    return harness.read_metric(name, _run(tr, facts))


def test_traced_passes_lie_wholly_inside_the_device_window():
    got = spans.traced_passes(_hand_trace())
    assert [(p.start / MS, p.end / MS) for p, _ in got] == [
        (2, 8), (10, 40), (45, 95)]
    assert [len(inside) for _, inside in got] == [1, 6, 9]


@pytest.mark.parametrize("name,want", [
    # A: 30 - (2 + 19 + 2) = 7; B: 50 - (1 + 3 + 14 + 1 + 17) = 14
    ("sched_host_ms_per_pass", (7 + 14) / 2),
    # B is the one traced pass that prefilled: admit 5 less the copy's 1
    ("sched_admit_ms_mean", 4.0),
    # A: decode 2 + evict 2; B: copy 1 + prefill 3 + decode 1
    ("engine_dispatch_ms_per_pass", (4 + 5) / 2),
    ("dispatches_per_pass", (0 + 2 + 3) / 3),
    # admit 7, decode 4.5, retire 8, prefill 3, token_read (pass C) 0.5
    ("idle_ms_per_pass.scheduler", 23.0 / 3),
    # decode 2.5, evict_slot 1.5, cow_page 0.5, prefill 1
    ("idle_ms_per_pass.engine", 5.5 / 3),
    # bench.run_pass 2.2, bench.stamp_tokens 3.4, no span at all 1.4
    ("idle_ms_per_pass.harness", 7.0 / 3),
])
def test_span_reader_by_hand(name, want):
    assert _read(name, _hand_trace()) == pytest.approx(want)


def test_the_three_idle_metrics_sum_to_all_idle_time_over_traced_passes():
    tr = _hand_trace()
    busy = trace.union((e.start, e.end) for e in tr.ops[0])
    idle_ms = trace.total(trace.gaps(busy, *trace.window(tr))) / MS
    assert idle_ms == pytest.approx(35.5)
    parts = [_read(f"idle_ms_per_pass.{layer}", tr)
             for layer in ("scheduler", "engine", "harness")]
    assert sum(parts) == pytest.approx(idle_ms / 3)
    # and they are the rows of the result line's own breakdown, by layer
    rows = trace.idle_gaps_by_host_span(tr, n=100)
    assert sum(s for _, s in rows) * 1e3 == pytest.approx(idle_ms)
    assert sum(s for n, s in rows if n.startswith("apex_tpu.scheduler.")) \
        * 1e3 / 3 == pytest.approx(parts[0])


def _no_whole_pass():
    tr = _hand_trace()
    tr.ops[0] = tr.ops[0][1:2]           # the window shrinks to 14..34
    return tr


def _program_without_spans():
    tr = _hand_trace()
    tr.host = [e for e in tr.host if not e.name.startswith(
        "apex_tpu.scheduler.")]          # as the parent commit traces
    return tr


def _no_device_plane():
    tr = _hand_trace()
    tr.ops, tr.modules = {}, {}          # a CPU rehearsal's profile
    return tr


@pytest.mark.parametrize("name", SPAN_METRICS)
@pytest.mark.parametrize("make", [lambda: None, _no_whole_pass,
                                  _program_without_spans, _no_device_plane],
                         ids=["no-trace", "no-whole-pass",
                              "program-without-spans", "no-device-plane"])
def test_span_reader_reads_nothing_rather_than_raise(name, make):
    assert _read(name, make()) is None


def test_admit_mean_needs_a_pass_that_prefilled():
    tr = _hand_trace()
    tr.host = [e for e in tr.host
               if e.name != "apex_tpu.scheduler.prefill"]
    assert _read("sched_admit_ms_mean", tr) is None
    assert _read("sched_host_ms_per_pass", tr) is not None


def _train_trace(lamb, unscale):
    n = "334820352"
    return trace.Trace(host=[], modules={}, ops={0: [
        _ev(f"%{lamb} = (f32[{n}]{{0}}, f32[{n}]{{0}}) custom-call(%p), "
            f"custom_call_target=\"tpu_custom_call\"", 0, 3),
        _ev(f"%{lamb} = (f32[{n}]{{0}}, f32[{n}]{{0}}) custom-call(%p), "
            f"custom_call_target=\"tpu_custom_call\"", 10, 14),
        _ev(f"%{unscale} = (f32[{n}]{{0}}, f32[1024]{{0}}) custom-call(%g)",
            4, 5),
        _ev(f"%{unscale} = (f32[{n}]{{0}}, f32[1024]{{0}}) custom-call(%g)",
            15, 17),
        _ev("%jvp_apex_layer_norm_fwd_.7 = bf16[4096,1024]{1,0} "
            "custom-call(%x)", 6, 7)]})


def test_kernel_readers_find_the_kernels_by_their_names():
    tr = _train_trace("apex_lamb_stage1.1", "apex_amp_unscale.1")
    facts = {"traced_steps": 2}
    assert _read("lamb_kernel_ms", tr, facts) == pytest.approx(3.5)
    assert _read("unscale_kernel_ms", tr, facts) == pytest.approx(1.5)


@pytest.mark.parametrize("name", KERNEL_METRICS)
def test_kernel_readers_read_nothing_from_unnamed_kernels(name):
    # the parent commit's instruction names, and a run without a trace
    tr = _train_trace("step.3", "step.2")
    assert _read(name, tr, {"traced_steps": 2}) is None
    assert _read(name, None, {"traced_steps": 2}) is None
    assert _read(name, tr, {"traced_steps": 0}) is None


# -- the tree the program really opens ---------------------------------------

@pytest.fixture(scope="module")
def profiled(toy_root):
    """The toy chat cell measured under a real profiler window that covers
    most of the run: ``(facts, host spans)``."""
    cell = harness.load_cell("toy.chat", toy_root)
    cell = dataclasses.replace(cell, mix=dict(cell.mix, trace_seconds=2.5))
    requests = traffic.serve_requests(cell.mix, 11, 3.0,
                                      cell.config["token_ids"])
    engine, sched, _ = D.build(cell, 11)
    D.warm_up(sched, cell, traffic.rng_for(11, stream=2))
    profiler = harness.ProfilerWindow(toy_root)
    facts = D.measure(cell, sched, requests, 3.0, profiler)["facts"]
    return facts, profiler.load().host


def _inside(outer, e):
    return outer.start <= e.start and e.end <= outer.end


def test_every_dispatch_and_token_read_lies_in_exactly_one_pass(profiled):
    _, host = profiled
    passes = [e for e in host if e.name == spans.PASS]
    inner = [e for e in host if e.name.startswith(spans.ENGINE)
             or e.name == spans.TOKEN_READ]
    assert passes and inner
    for e in inner:
        assert sum(_inside(p, e) for p in passes) == 1, e
    # the benchmark opens none of the program's names on its behalf
    assert {e.name for e in host if e.name.startswith("bench.")} <= {
        "bench.submit", "bench.run_pass", "bench.stamp_tokens",
        "bench.wait_for_request"}


def test_the_tree_of_one_pass(profiled):
    _, host = profiled
    sch, eng = spans.SCHEDULER, spans.ENGINE
    prefills = 0
    for p in (e for e in host if e.name == spans.PASS):
        inside = [e for e in host if e is not p and _inside(p, e)]
        names = [e.name for e in inside]
        assert names.count(sch + "admit") == 1
        assert names.count(sch + "decode") <= 1
        assert names.count(eng + "decode") == names.count(sch + "decode")
        assert names.count(eng + "prefill") == names.count(sch + "prefill")
        assert names.count(sch + "token_read") == names.count(
            sch + "decode") + names.count(sch + "prefill")
        parents = {eng + "decode": sch + "decode",
                   eng + "prefill": sch + "prefill",
                   eng + "evict_slot": sch + "retire",
                   sch + "token_read": (sch + "decode", sch + "prefill")}
        for e in inside:
            if e.name in parents:
                assert any(_inside(o, e) for o in inside
                           if o.name in parents[e.name]), e
        prefills += names.count(sch + "prefill")
    assert prefills > 2


def test_pass_and_evict_spans_match_the_drivers_own_stamps(profiled):
    facts, host = profiled
    lo, hi = facts["trace_started"], facts["trace_stopped"]
    stamped = [p for p in facts["passes"] if lo <= p[0] and p[1] <= hi]
    assert len(stamped) > 10
    assert sum(e.name == spans.PASS for e in host) == len(stamped)
    retired = [r for r in facts["requests"] if r["reason"] is not None
               and lo <= r["token_times"][-1] <= hi]
    assert len(retired) > 2
    assert sum(e.name == spans.ENGINE + "evict_slot" for e in host) \
        == len(retired)
    submitted = [r for r in facts["requests"] if lo <= r["sent"] <= hi]
    assert sum(e.name == spans.SCHEDULER + "submit" for e in host) \
        == len(submitted)


def test_the_rehearsal_prints_the_metric_set_it_printed_before(run_toy):
    r = run_toy("toy.chat", seconds=2.0, trace=1)
    assert r["correct"]
    pool = {f"pool_pages_{what}_{stat}" for what in ("live", "attended")
            for stat in ("peak", "mean")}
    assert set(r["metrics"]) == pool | {
        "compiles_in_window.serve", "slot_occupancy", "toy_passes"}
