"""Driven by data: cells, configurations, mixes, a per-layer metric and a
second model kind (its binding, reference and driver) added as NEW FILES
(plus entries in ``BENCHMARK.json``) run end to end through the one command,
with no existing file edited."""
import hashlib
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]


def _digest(root: Path) -> dict:
    return {str(f.relative_to(root)): hashlib.sha1(f.read_bytes())
            .hexdigest() for f in (root / "benchmark").rglob("*")
            if f.is_file() and "__pycache__" not in f.parts}


def test_toy_cells_are_only_new_files(toy_root):
    ours, theirs = _digest(REPO), _digest(toy_root)
    assert all(theirs[k] == v for k, v in ours.items())
    added = set(theirs) - set(ours)
    assert added == {
        "benchmark/configs/toy-bert.json", "benchmark/configs/toy-gpt.json",
        "benchmark/traffic/toy-train.json",
        "benchmark/traffic/toy-chat.json",
        "benchmark/traffic/toy-backlog.json",
        "benchmark/metrics/toy_passes.py",
        # the second model kind
        "benchmark/configs/toy-llama.json",
        "benchmark/bindings/llama.py", "benchmark/references/llama_lm.py",
        "benchmark/drivers/toy_serve.py",
        "benchmark/traffic/toy-llama-backlog.json"}
    index = json.loads((toy_root / "BENCHMARK.json").read_text())
    llama = next(c for c in index["configs"] if c["name"] == "toy-llama")
    assert llama["reduced"] == ["num_hidden_layers"]


def _run(root, *argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmark/run.py", *argv], cwd=root, env=env,
        capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("cell", ["toy.backlog", "toy.llama"])
def test_the_one_command_runs_an_added_cell(toy_root, cell):
    p = _run(toy_root, "--workload", cell, "--seed",
             str(2 ** 31 + 77), "--seconds", "1.5", "--trace", "1",
             "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["correct"] and last["rehearsal"]
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(
        last)
    # the reader added as a file was found by its name alone
    assert last["metrics"]["toy_passes"]["value"] > 0
    assert p.stderr.strip().splitlines()[-1].startswith(
        "checks: served_token_gap=")


def test_the_command_refuses_a_cpu_without_the_rehearsal_flag(toy_root):
    p = _run(toy_root, "--workload", "toy.train", "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_the_command_refuses_a_checkout_without_the_program(tmp_path):
    import shutil
    shutil.copytree(REPO / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    p = _run(tmp_path, "--workload", "bert-large.pretrain-b32", "--seed",
             "1", "--seconds", "1", "--trace", "0", "--rehearse")
    assert p.returncode != 0 and p.stdout.strip() == ""
