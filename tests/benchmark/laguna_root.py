"""``toyroot.make``'s temporary checkout plus one more toy cell, added the
same way — as files and appended entries: the ``laguna`` model kind at toy
sizes (``fixtures_laguna/``: a configuration in the published keys and a
backlog mix).  Its binding, reference, driver, counts and readers are the
benchmark's own new files, which the copy already holds."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

import toyroot

FIXTURES = Path(__file__).resolve().parent / "fixtures_laguna"
CELL = "toy.laguna"
ADDED = {"benchmark/configs/toy-laguna.json",
         "benchmark/traffic/toy-code-batch.json"}


def make(tmp: Path) -> Path:
    root = toyroot.make(tmp)
    shutil.copy(FIXTURES / "toy-laguna.json", root / "benchmark" / "configs")
    shutil.copy(FIXTURES / "toy-code-batch.json",
                root / "benchmark" / "traffic")
    index = json.loads((root / "BENCHMARK.json").read_text())
    real = "laguna-xs.2.code-batch"
    index["configs"].append({
        "name": "toy-laguna", "source": "fixture",
        "file": "benchmark/configs/toy-laguna.json",
        "reduced": json.loads((FIXTURES / "toy-laguna.json").read_text())[
            "reduced"], "why": "fixture"})
    index["workloads"].append({
        "name": CELL, "config": "toy-laguna", "traffic": "toy-code-batch",
        "chips": 1, "why": "fixture"})
    # the toy cell reports what the real cell of its kind reports
    for m in index["end_to_end"] + index["per_layer"]:
        if real in m.get("workloads", []):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(index, indent=1))
    return root
