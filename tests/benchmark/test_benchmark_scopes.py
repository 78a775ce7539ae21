"""``benchmark/scopes.py`` on traces made by hand: which executable an
operation belongs to, which table names it, and that every device
nanosecond counts once, for the innermost operation covering it."""
import types
from pathlib import Path

import pytest

from benchmark import harness, scopes, trace
from apex_tpu.observability.xla_stats import ScopeTable

REPO = Path(__file__).resolve().parents[2]
NS = 1.0                     # the events below are in nanoseconds

NEW_METRICS = ("moe_ffn_ms_per_pass", "moe_dispatch_ms_per_pass",
               "prefill_insert_ms_per_pass", "train_forward_ms",
               "train_backward_ms", "train_optimizer_ms")


def _ev(name, lo, hi):
    return trace.Event(name, lo * NS, hi * NS)


def _table(module, fp, rows):
    """rows: instruction -> (chain, backward, result type)."""
    return ScopeTable(module, fp,
                      {n: (c, b) for n, (c, b, _) in rows.items()},
                      {n: t for n, (_, _, t) in rows.items()}, frozenset())


def _op(name, rtype, lo, hi, kind="fusion"):
    return _ev(f"%{name} = {rtype} {kind}(f32[8]{{0}} %p)", lo, hi)


STEP = _table("jit_step", "a", {
    "while.1": (("apex_train_forward",), False, "(s32[], f32[8]{0})"),
    "fusion.1": (("apex_train_forward", "apex_layer_norm_fwd"), False,
                 "f32[8]{0}"),
    "fusion.2": (("apex_train_forward",), True, "f32[8]{0}"),
    "fusion.3": (("apex_train_optimizer", "apex_lamb_stage1"), False,
                 "f32[8]{0}"),
    "param.1": ((), False, "f32[8]{0}"),
})


def _step_trace():
    """One step, 0..100 ns: a while (10..70) holding two body operations,
    an operation with no scope, one the table does not know."""
    ops = [_op("while.1", "(s32[], f32[8]{0})", 10, 70, "while"),
           _op("fusion.1", "f32[8]{0}", 20, 30),
           _op("fusion.2", "f32[8]{0}", 40, 55),
           _op("fusion.3", "f32[8]{0}", 72, 90),
           _op("param.1", "f32[8]{0}", 90, 93),
           _op("copy.9", "f32[8]{0}", 95, 99, "copy")]
    return trace.Trace(ops={0: ops}, modules={0: [
        _ev("jit_step(123)", 0, 100)]}, host=[])


def test_innermost_operation_takes_the_time_under_a_while():
    got = scopes.attribute(_step_trace(), [STEP])
    s = {k: v * 1e9 for k, v in got["scopes"].items()}
    assert s[(("apex_train_forward",), False)] == pytest.approx(35)
    assert s[(("apex_train_forward", "apex_layer_norm_fwd"), False)] \
        == pytest.approx(10)
    assert s[(("apex_train_forward",), True)] == pytest.approx(15)
    assert s[(("apex_train_optimizer", "apex_lamb_stage1"), False)] \
        == pytest.approx(18)
    # a scope-less instruction and one with no entry are unattributed
    assert got["unattributed"] * 1e9 == pytest.approx(3 + 4)
    # every nanosecond once: the parts sum to the union of the events
    union = trace.total(trace.union(
        (e.start, e.end) for e in _step_trace().ops[0]))
    total = sum(got["scopes"].values()) + got["unattributed"]
    assert total * 1e9 == pytest.approx(union)
    assert got["modules"]["jit_step"][0] * 1e9 == pytest.approx(union)


def test_an_operation_overlapping_its_neighbour_is_counted_once():
    ops = [_op("fusion.1", "f32[8]{0}", 0, 10), _op("fusion.2", "f32[8]{0}",
                                                    8, 20)]
    tr = trace.Trace(ops={0: ops}, modules={0: [_ev("jit_step(1)", 0, 20)]},
                     host=[])
    got = scopes.attribute(tr, [STEP])
    total = sum(got["scopes"].values()) + got["unattributed"]
    assert total * 1e9 == pytest.approx(20)


def test_two_buckets_of_one_jit_name_keep_their_own_tables():
    small = _table("jit_prefill", "s", {
        "fusion.1": (("apex_prefill_forward",), False, "bf16[64,8]{1,0}"),
        "fusion.2": (("apex_prefill_cache_insert",), False,
                     "bf16[64,8]{1,0}")})
    large = _table("jit_prefill", "l", {
        "fusion.1": (("apex_prefill_cache_insert",), False,
                     "bf16[128,8]{1,0}"),
        "fusion.2": (("apex_prefill_forward",), False, "bf16[128,8]{1,0}")})
    ops = [_op("fusion.1", "bf16[64,8]{1,0}", 0, 10),      # small: forward
           _op("fusion.2", "bf16[64,8]{1,0}", 10, 12),     # small: insert
           _op("fusion.1", "bf16[128,8]{1,0}", 20, 27),    # large: insert
           _op("fusion.2", "bf16[128,8]{1,0}", 27, 40),    # large: forward
           _op("fusion.1", "bf16[64,8]{1,0}", 50, 60),     # small again
           _op("fusion.2", "bf16[64,8]{1,0}", 60, 62)]
    mods = [_ev("jit_prefill(1)", 0, 12), _ev("jit_prefill(2)", 20, 40),
            _ev("jit_prefill(1)", 50, 62)]
    tr = trace.Trace(ops={0: ops}, modules={0: mods}, host=[])
    for tables in ([small, large], [large, small]):
        got = scopes.attribute(tr, tables)
        s = {k[0][0]: v * 1e9 for k, v in got["scopes"].items()}
        assert s == {"apex_prefill_forward": pytest.approx(10 + 13 + 10),
                     "apex_prefill_cache_insert": pytest.approx(2 + 7 + 2)}


def test_a_head_cut_short_by_the_trace_matches_the_type_it_begins():
    long_type = "(" + ", ".join(["f32[1024,1024]{1,0:T(8,128)}"] * 12) + ")"
    t = _table("jit_decode_fn", "x", {
        "while.3": (("apex_decode_forward",), False, long_type)})
    head = ("%while.3 = " + long_type)[:trace.NAME_CHARS]
    assert scopes._matches(t, [("while.3", head.split(" = ", 1)[1])]) == 1
    assert scopes._matches(t, [("while.3", "f32[8]{0} while(")]) == 0


def test_no_table_for_a_module_leaves_its_time_unattributed():
    tr = trace.Trace(ops={0: [_op("fusion.1", "f32[8]{0}", 0, 10)]},
                     modules={0: [_ev("jit_other(5)", 0, 10)]}, host=[])
    got = scopes.attribute(tr, [STEP])
    assert got["scopes"] == {} and got["unattributed"] * 1e9 == \
        pytest.approx(10)
    assert got["modules"]["jit_other"] == [pytest.approx(10e-9), 0.0]


def test_nothing_to_join_reads_none():
    assert scopes.attribute(None, [STEP]) is None
    assert scopes.attribute(trace.Trace(ops={}, modules={}, host=[]),
                            [STEP]) is None
    assert scopes.attribute(_step_trace(), []) is None


def _run(tr, facts=None):
    return types.SimpleNamespace(
        cell=types.SimpleNamespace(root=REPO), trace=tr, facts=facts or {})


@pytest.mark.parametrize("name", NEW_METRICS)
def test_each_new_reader_reads_none_without_a_trace(name):
    assert harness.read_metric(name, _run(None, {"traced_steps": 3})) \
        is None


def test_train_readers_split_the_step(monkeypatch):
    monkeypatch.setattr(scopes, "program_tables", lambda: (STEP,))
    monkeypatch.setattr(scopes, "_CACHE", [])
    run = _run(_step_trace(), {"traced_steps": 2})
    ms = {n: harness.read_metric(n, run) for n in (
        "train_forward_ms", "train_backward_ms", "train_optimizer_ms")}
    assert ms["train_forward_ms"] == pytest.approx((35 + 10) * 1e-6 / 2)
    assert ms["train_backward_ms"] == pytest.approx(15 * 1e-6 / 2)
    assert ms["train_optimizer_ms"] == pytest.approx(18 * 1e-6 / 2)
    # a scope the step does not hold reads nothing rather than zero
    assert scopes.scope_seconds(run.trace, ("apex_moe_route",)) is None
