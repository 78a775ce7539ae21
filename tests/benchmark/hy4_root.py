"""``toyroot.make``'s temporary checkout plus one more toy cell, added the
same way — as files and appended entries: the ``hy4`` model kind at toy
sizes (``fixtures_hy4/``: a configuration in the published keys whose
indexers — full, full, shared, shared, shared, full — pick 16 positions of
contexts up to 104, and a backlog mix).  Its
binding, reference, driver, counts and readers are the benchmark's own new
files, which the copy already holds."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

import toyroot

FIXTURES = Path(__file__).resolve().parent / "fixtures_hy4"
CELL = "toy.hy4"
ADDED = {"benchmark/configs/toy-hy4.json",
         "benchmark/traffic/toy-longctx-batch.json"}


def make(tmp: Path) -> Path:
    root = toyroot.make(tmp)
    shutil.copy(FIXTURES / "toy-hy4.json", root / "benchmark" / "configs")
    shutil.copy(FIXTURES / "toy-longctx-batch.json",
                root / "benchmark" / "traffic")
    index = json.loads((root / "BENCHMARK.json").read_text())
    real = "hy4-preview.longctx-batch"
    index["configs"].append({
        "name": "toy-hy4", "source": "fixture",
        "file": "benchmark/configs/toy-hy4.json",
        "reduced": json.loads((FIXTURES / "toy-hy4.json").read_text())[
            "reduced"], "why": "fixture"})
    index["workloads"].append({
        "name": CELL, "config": "toy-hy4",
        "traffic": "toy-longctx-batch", "chips": 1, "why": "fixture"})
    # the toy cell reports what the real cell of its kind reports
    for m in index["end_to_end"] + index["per_layer"]:
        if real in m.get("workloads", []):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(index, indent=1))
    return root
