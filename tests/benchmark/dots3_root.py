"""``toyroot.make``'s temporary checkout plus one more toy cell, added the
same way — as files and appended entries: the ``dots3`` model kind at toy
sizes (``fixtures_dots3/``: a configuration in the published keys whose
two full layers' indexers pick 16 positions and whose three window layers
attend 9, of contexts up to 104, and a backlog mix).  Its
binding, reference, driver, counts and readers are the benchmark's own new
files, which the copy already holds."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

import toyroot

FIXTURES = Path(__file__).resolve().parent / "fixtures_dots3"
CELL = "toy.dots3"
ADDED = {"benchmark/configs/toy-dots3.json",
         "benchmark/traffic/toy-notes-batch.json"}


def make(tmp: Path) -> Path:
    root = toyroot.make(tmp)
    shutil.copy(FIXTURES / "toy-dots3.json", root / "benchmark" / "configs")
    shutil.copy(FIXTURES / "toy-notes-batch.json",
                root / "benchmark" / "traffic")
    index = json.loads((root / "BENCHMARK.json").read_text())
    real = "dots3-note-prev.notes-batch"
    index["configs"].append({
        "name": "toy-dots3", "source": "fixture",
        "file": "benchmark/configs/toy-dots3.json",
        "reduced": json.loads((FIXTURES / "toy-dots3.json").read_text())[
            "reduced"], "why": "fixture"})
    index["workloads"].append({
        "name": CELL, "config": "toy-dots3",
        "traffic": "toy-notes-batch", "chips": 1, "why": "fixture"})
    # the toy cell reports what the real cell of its kind reports
    for m in index["end_to_end"] + index["per_layer"]:
        if real in m.get("workloads", []):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(index, indent=1))
    return root
