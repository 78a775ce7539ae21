"""``toyroot.make``'s temporary checkout plus one more toy cell, added the
same way — as files and appended entries: the ``axk1`` model kind at toy
sizes (``fixtures_axk1/``: a configuration in the published keys that holds
8 of its router's 16 experts, and a backlog mix).  Its binding, reference,
counts and readers are the benchmark's own new files, which the copy already
holds; its driver is Laguna's, unedited."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

import toyroot

FIXTURES = Path(__file__).resolve().parent / "fixtures_axk1"
CELL = "toy.axk1"
ADDED = {"benchmark/configs/toy-axk1.json",
         "benchmark/traffic/toy-analysis-batch.json"}


def make(tmp: Path) -> Path:
    root = toyroot.make(tmp)
    shutil.copy(FIXTURES / "toy-axk1.json", root / "benchmark" / "configs")
    shutil.copy(FIXTURES / "toy-analysis-batch.json",
                root / "benchmark" / "traffic")
    index = json.loads((root / "BENCHMARK.json").read_text())
    real = "a.x-k1.analysis-batch"
    index["configs"].append({
        "name": "toy-axk1", "source": "fixture",
        "file": "benchmark/configs/toy-axk1.json",
        "reduced": json.loads((FIXTURES / "toy-axk1.json").read_text())[
            "reduced"], "why": "fixture"})
    index["workloads"].append({
        "name": CELL, "config": "toy-axk1",
        "traffic": "toy-analysis-batch", "chips": 1, "why": "fixture"})
    # the toy cell reports what the real cell of its kind reports
    for m in index["end_to_end"] + index["per_layer"]:
        if real in m.get("workloads", []):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(index, indent=1))
    return root
