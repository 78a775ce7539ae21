"""A temporary checkout with toy cells ADDED AS FILES: the benchmark's own
files copied unchanged, the program linked in, the fixtures' configurations,
mixes, metric reader and — for a second model kind — binding, reference and
driver dropped beside them, and entries appended to the copy's
``BENCHMARK.json``.  No file that exists is edited: this is the path a
``model_config`` PR walks."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
FIXTURES = Path(__file__).resolve().parent / "fixtures"

TOY_CELLS = [
    {"name": "toy.train", "config": "toy-bert", "traffic": "toy-train",
     "chips": 1, "why": "fixture"},
    {"name": "toy.chat", "config": "toy-gpt", "traffic": "toy-chat",
     "chips": 1, "why": "fixture"},
    {"name": "toy.backlog", "config": "toy-gpt", "traffic": "toy-backlog",
     "chips": 1, "why": "fixture"},
    {"name": "toy.llama", "config": "toy-llama",
     "traffic": "toy-llama-backlog", "chips": 1, "why": "fixture"},
]
KINDS = ("configs", "traffic", "metrics", "bindings", "references", "drivers")


def make(tmp: Path) -> Path:
    root = Path(tmp) / "checkout"
    shutil.copytree(REPO / "benchmark", root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for linked in ("apex_tpu", "examples"):
        (root / linked).symlink_to(REPO / linked)
    for kind in KINDS:
        for f in (FIXTURES / kind).iterdir():
            target = root / "benchmark" / kind / f.name
            assert not target.exists(), f"{target} would be overwritten"
            shutil.copy(f, target)
    index = json.loads((REPO / "BENCHMARK.json").read_text())
    for name in ("toy-bert", "toy-gpt", "toy-llama"):
        file = f"benchmark/configs/{name}.json"
        index["configs"].append({
            "name": name, "source": "fixture", "file": file,
            "reduced": json.loads((root / file).read_text())["reduced"],
            "why": "fixture"})
    index["workloads"].extend(TOY_CELLS)
    serve = ["toy.chat", "toy.backlog", "toy.llama"]
    for m in index["end_to_end"]:
        if "workloads" not in m:
            continue
        if m["name"].startswith("train"):
            m["workloads"].append("toy.train")
        elif m["name"] == "serve_tokens_per_s":
            m["workloads"].extend(serve)
        else:
            m["workloads"].append("toy.chat")
    for m in index["per_layer"]:
        if m["moves"].startswith("train"):
            m["workloads"].append("toy.train")
        elif m["moves"] == "serve_tokens_per_s":
            m["workloads"].extend(serve)
        else:
            m["workloads"].append("toy.chat")
    index["per_layer"].append({
        "name": "toy_passes", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "fixture",
        "moves": "serve_tokens_per_s", "workloads": serve})
    (root / "BENCHMARK.json").write_text(json.dumps(index, indent=1))
    return root
