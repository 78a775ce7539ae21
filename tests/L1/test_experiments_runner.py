"""The r5 on-chip experiment runner drives real bench.py legs via
subprocess; these tests cover its salvage/resume plumbing with a
stubbed runner (the legs themselves are covered by test_bench_fallback
and the bench CPU lane).
"""
import importlib.util
import json
import os
import subprocess
import sys

_SPEC = importlib.util.spec_from_file_location(
    "r5_experiments",
    os.path.join(os.path.dirname(__file__), "..", "..",
                 "bench_captures", "r5_experiments.py"))
exp = importlib.util.module_from_spec(_SPEC)
_SPEC.loader.exec_module(exp)


def test_last_json_line():
    assert exp.last_json_line('x\n{"a": 1}\n{"b": 2}\n') == {"b": 2}
    assert exp.last_json_line("nothing") is None
    assert exp.last_json_line("{broken") is None


def test_experiments_drive_bench_legs_not_snippets():
    """Contract from r4 verdict weak #7: every experiment is a bench.py
    invocation (no inline model source to drift)."""
    for key, args, timeout in exp.EXPERIMENTS:
        assert "--leg" in args, key
        assert timeout > 0
    # the quick row is the BERT north-star leg
    assert exp.EXPERIMENTS[0][0] == "bert"


def test_main_resumes_and_writes_incrementally(monkeypatch, tmp_path):
    out = tmp_path / "out.json"
    monkeypatch.setattr(exp, "OUT", out)
    out.write_text(json.dumps({"bert": {"bert_mfu": 0.5}}))
    calls = []

    def fake_run(key, args, timeout):
        calls.append(key)
        return {"ok": key}

    monkeypatch.setattr(exp, "run_experiment", fake_run)
    monkeypatch.setattr(sys, "argv", ["r5_experiments.py"])
    exp.main()
    # already-captured bert skipped; everything else ran and was written
    assert "bert" not in calls
    written = json.loads(out.read_text())
    assert written["bert"] == {"bert_mfu": 0.5}
    assert all(written[k] == {"ok": k} for k in calls)
    assert len(calls) == len(exp.EXPERIMENTS) - 1


def test_timeout_entries_are_retried_and_not_clobbered(monkeypatch,
                                                       tmp_path, capsys):
    out = tmp_path / "out.json"
    monkeypatch.setattr(exp, "OUT", out)
    salvaged = {"moe_us": 7, "_timeout": True}
    out.write_text(json.dumps({k: {"ok": 1} for k, _, _ in exp.EXPERIMENTS}
                              | {"moe": salvaged}))
    calls = []

    def fail_again(key, args, timeout):
        calls.append(key)
        return {"_error": "timeout after 1s"}

    monkeypatch.setattr(exp, "run_experiment", fail_again)
    monkeypatch.setattr(sys, "argv", ["r5_experiments.py"])
    exp.main()
    # the salvaged partial was retried, and the worse retry (bare
    # _error) did not clobber the salvaged data
    assert calls == ["moe"]
    assert json.loads(out.read_text())["moe"] == salvaged
    assert "ALL_COMPLETE" not in capsys.readouterr().out

    def succeed(key, args, timeout):
        return {"moe_us": 7, "moe_dispatch_sweep": []}

    monkeypatch.setattr(exp, "run_experiment", succeed)
    exp.main()
    assert json.loads(out.read_text())["moe"]["moe_dispatch_sweep"] == []
    # every experiment clean -> the full-batch marker prints
    assert "ALL_COMPLETE" in capsys.readouterr().out


def test_run_experiment_salvages_timeout(monkeypatch):
    def fake_subprocess_run(cmd, **kw):
        raise subprocess.TimeoutExpired(
            cmd, 1, output='{"moe_us": 7, "_leg": "moe"}\n')

    monkeypatch.setattr(exp.subprocess, "run", fake_subprocess_run)
    res = exp.run_experiment("moe", ["--leg", "moe"], 1)
    assert res["moe_us"] == 7 and res["_timeout"] is True
