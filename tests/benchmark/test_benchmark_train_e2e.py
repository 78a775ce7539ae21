"""The training driver end to end on the CPU at a toy size: a rehearsal is
correct, a measurement without the chip is refused, the control and each
planted fault come out NOT correct."""
import json

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
