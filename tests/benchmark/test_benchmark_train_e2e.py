"""The training driver end to end on the CPU at a toy size: a rehearsal is
correct, a measurement without the chip is refused, the control and each
planted fault come out NOT correct."""
import json
import time

import numpy as np
import pytest

from benchmark import harness, traffic
from benchmark.drivers import train as D


def test_rehearsal_is_correct_and_prints_counts_only(run_toy):
    r = run_toy("toy.train", trace=1)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 3
    assert r["rehearsal"] and r["device"]["platform"] == "cpu"
    # a count may be printed off the chip; a time, rate or share never
    assert set(r["metrics"]) == {"compiles_in_window.train"}
    assert r["metrics"]["compiles_in_window.train"]["value"] == 0.0
    assert "breakdown" not in r and "busy_s" not in r["device"]
    names = [c["name"] for c in r["checks"]]
    assert names[:3] == ["loss1_rel", "loss2_rel", "loss3_rel"]
    assert list(r)[-1] == "checks"
    json.dumps(r)


class _Profiler:
    """Stands in for ``harness.ProfilerWindow``: what the driver calls."""
    started = stopped = None

    def start(self):
        self.started = time.perf_counter()

    def stop(self):
        self.stopped = time.perf_counter()


@pytest.mark.parametrize("trace", [0, 1])
def test_window_sends_ahead_and_closes_after_all_it_sent(
        toy_root, monkeypatch, trace):
    """Steps are dispatched ahead of the loss that is waited for, as deep
    as the mix says; when the time is up nothing more is sent, all that was
    sent is waited for and the clock read after that wait, so every step
    sent counts, over all of that time.  A traced run closes its untraced
    part the same way before the profiler comes on, and the trace holds
    whole steps only."""
    cell = harness.load_cell("toy.train", toy_root)
    depths, real = [], D.steps_ahead
    monkeypatch.setattr(D, "steps_ahead", lambda s, step_s: depths.append(
        real(s, step_s)) or depths[-1])
    profiler = _Profiler() if trace else None
    out = D.run(cell=cell, devices=harness.find_devices(cell.chips, True),
                seed=4, seconds=1.5, profiler=profiler,
                t_process=time.perf_counter())
    starts, ends = out["facts"]["step_starts"], out["facts"]["step_ends"]
    n = len(starts)
    assert out["correct"] and out["attempted"] == n == len(ends) > 3
    assert ends == sorted(ends) and ends[-1] >= starts[-1]
    mix = cell.mix
    assert depths[0] >= 2 and mix["ahead_seconds"] > mix[
        "trace_ahead_seconds"]
    k = n - out["facts"]["traced_steps"]        # the untraced steps
    if trace:
        assert 0 < k < n and len(depths) == 2 and 1 <= depths[1] < depths[0]
        # all that was sent had been waited for when the profiler came on,
        # and again when it went off
        assert ends[k - 1] <= profiler.started <= starts[k]
        assert ends[-1] <= profiler.stopped
        # the traced tail lasts the mix's ``trace_seconds``, wait and all,
        # and the untraced part stopped sending in time for its own wait
        assert n - k > depths[1]
        assert profiler.stopped - profiler.started >= mix["trace_seconds"]
        assert starts[k - 1] - starts[0] <= 1.5 - mix["trace_seconds"]
    else:
        assert k == n and len(depths) == 1
    for lo, hi, d in ((0, k, depths[0]), (k, n, depths[-1])):
        for i in range(lo, hi):
            # a step is seen to end once ``d`` later ones are sent, and
            # before the one after them goes out
            if i + d < hi:
                assert ends[i] >= starts[i + d]
            if i + d + 1 < hi:
                assert ends[i] <= starts[i + d + 1]


def test_steps_ahead_is_the_mix_seconds_in_whole_steps():
    assert D.steps_ahead(6.0, 0.125) == 48
    assert D.steps_ahead(0.25, 0.125) == 2
    assert D.steps_ahead(0.25, 3.0) == 1        # never none


def test_measurement_without_the_chip_is_refused(run_toy, capsys):
    with pytest.raises(harness.Refused, match="not a TPU"):
        run_toy("toy.train", rehearse=False)


def test_main_exits_nonzero_and_prints_no_result_without_the_chip(capsys):
    from benchmark import run as bench_run
    rc = bench_run.main(["--workload", "bert-large.pretrain-b32",
                         "--seed", "1", "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc == 2 and out.out == "" and "refused" in out.err


def test_unknown_workload_is_refused(toy_root):
    with pytest.raises(harness.Refused, match="no workload"):
        harness.load_cell("no.such.cell", toy_root)


def _broken(monkeypatch, wrap):
    """Break the timed path underneath: the program's own step maker."""
    from apex_tpu import train_step
    real = train_step.make_train_step
    monkeypatch.setattr(train_step, "make_train_step",
                        lambda *a, **k: wrap(real(*a, **k)))


def test_fault_state_returned_unchanged(run_toy, monkeypatch):
    def wrap(step):
        def lazy(state, batch):
            new, loss = step(state, batch)
            return state, loss
        return lazy
    _broken(monkeypatch, wrap)
    r = run_toy("toy.train")
    assert not r["correct"]
    failed = {c["name"] for c in r["checks"] if c["value"] > c["limit"]}
    assert {"grad1_worst_leaf", "delta_worst_leaf"} <= failed


def test_fault_half_of_the_batch_left_out(run_toy, monkeypatch):
    def wrap(step):
        def half(state, batch):
            n = batch["tokens"].shape[0] // 2
            return step(state, {k: v[:n] for k, v in batch.items()})
        return half
    _broken(monkeypatch, wrap)
    r = run_toy("toy.train")
    assert not r["correct"]


@pytest.mark.parametrize("quant", ["fp8"])
def test_control_in_the_precision_below_is_not_correct(toy_root, quant):
    """The reference in fp8, put in the program's place, fails a limit."""
    cell = harness.load_cell("toy.train", toy_root)
    _, _, shapes = D.build(cell, 5)
    feed = traffic.TrainBatches(cell.mix, 5, cell.config["token_ids"])
    batches = [feed.next() for _ in range(D.CHECK_STEPS)]
    want = D.reference_readings(cell, shapes, 5, batches)
    low = D.reference_readings(cell, shapes, 5, batches, quant=quant)
    limits = cell.config["correct"]["limits"]
    assert all(c["value"] <= c["limit"]
               for c in D.compare(want, want, limits))
    assert any(c["value"] > c["limit"]
               for c in D.compare(low, want, limits))


def test_worst_leaf_gap_measures_against_the_median_leaf():
    want = np.array([1.0, 1.0, 1e-6])
    got = np.array([1.1, 1.0, 2e-6])
    # the tiny leaf's gap is taken against the median leaf, not itself
    assert D.worst_leaf_gap(got, want) == pytest.approx(0.1)
    assert D.worst_leaf_gap(got, want, np.array([False, True, True])) \
        == pytest.approx(1e-6)
