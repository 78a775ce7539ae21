"""``toyroot.make``'s temporary checkout plus one more toy cell, added the
same way — as files and appended entries: the ``keye`` model kind at toy
sizes (``fixtures_keye/``: a configuration in the published keys whose
indexer picks 16 positions of contexts up to 96, and a backlog mix).  Its
binding, reference, driver, counts and readers are the benchmark's own new
files, which the copy already holds."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

import toyroot

FIXTURES = Path(__file__).resolve().parent / "fixtures_keye"
CELL = "toy.keye"
ADDED = {"benchmark/configs/toy-keye.json",
         "benchmark/traffic/toy-longdoc-batch.json"}


def make(tmp: Path) -> Path:
    root = toyroot.make(tmp)
    shutil.copy(FIXTURES / "toy-keye.json", root / "benchmark" / "configs")
    shutil.copy(FIXTURES / "toy-longdoc-batch.json",
                root / "benchmark" / "traffic")
    index = json.loads((root / "BENCHMARK.json").read_text())
    real = "keye-vl-2.0.longdoc-batch"
    index["configs"].append({
        "name": "toy-keye", "source": "fixture",
        "file": "benchmark/configs/toy-keye.json",
        "reduced": json.loads((FIXTURES / "toy-keye.json").read_text())[
            "reduced"], "why": "fixture"})
    index["workloads"].append({
        "name": CELL, "config": "toy-keye",
        "traffic": "toy-longdoc-batch", "chips": 1, "why": "fixture"})
    # the toy cell reports what the real cell of its kind reports
    for m in index["end_to_end"] + index["per_layer"]:
        if real in m.get("workloads", []):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(index, indent=1))
    return root
