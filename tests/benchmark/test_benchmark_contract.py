"""``BENCHMARK.json`` against the contract the driver checks before a run."""
import json
import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
INDEX = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in INDEX["workloads"]]
E2E = {m["name"]: m for m in INDEX["end_to_end"]}
METRICS = INDEX["end_to_end"] + INDEX["per_layer"]


def test_top_level_keys_and_size():
    assert set(INDEX) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert (REPO / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= INDEX["run_seconds"] <= 51
    # 2 + 14 runs a cell at run_seconds + 60, 180 s a cell to compile and
    # 1200 s spare must fit 43200 s with the full 24 cells
    assert (2 + 14 * 24) * (INDEX["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200


def test_command_and_paths():
    assert INDEX["command"][:2] == ["python3", "benchmark/run.py"]
    assert all(not w.startswith("/") and ".." not in w
               for w in INDEX["command"])
    assert 1 <= len(INDEX["paths"]) <= 16
    assert all((REPO / p).is_dir() for p in INDEX["paths"])


@pytest.mark.parametrize("cfg", INDEX["configs"], ids=lambda c: c["name"])
def test_configuration_entry(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(cfg["name"])
    assert any(cfg["file"].startswith(p + "/") for p in INDEX["paths"])
    doc = json.loads((REPO / cfg["file"]).read_text())
    assert doc["reduced"] == cfg["reduced"] and len(cfg["reduced"]) <= 16
    widths = re.compile(r"(hidden|intermediate|_dim$|_rank$|head_dim)")
    assert not any(widths.search(k) for k in cfg["reduced"])
    assert all(1 <= len(cfg[k]) <= 200 and "\n" not in cfg[k]
               for k in ("source", "why"))
    assert any(w["config"] == cfg["name"] for w in INDEX["workloads"])


@pytest.mark.parametrize("cell", INDEX["workloads"], ids=lambda w: w["name"])
def test_cell_entry(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert all(NAME.match(cell[k]) for k in ("name", "config", "traffic"))
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    assert cell["config"] in {c["name"] for c in INDEX["configs"]}
    mix = REPO / "benchmark" / "traffic" / f"{cell['traffic']}.json"
    assert json.loads(mix.read_text())["driver"] in ("train", "serve")
    mine = [m for m in INDEX["end_to_end"]
            if cell["name"] in m.get("workloads", CELLS)]
    assert "setup_s" in {m["name"] for m in mine} and len(mine) >= 2
    assert any(cell["name"] in m.get("workloads", CELLS)
               for m in INDEX["per_layer"])
    # a whole-step share of the chip's peak beside the kernels' rooflines
    assert any("mfu" in m["name"] and cell["name"] in m.get(
        "workloads", CELLS) for m in INDEX["per_layer"])


def test_cells_are_unique_and_few_take_four_chips():
    pairs = [(w["config"], w["traffic"]) for w in INDEX["workloads"]]
    assert len(set(pairs)) == len(pairs) == len(set(CELLS))
    four = sum(w["chips"] == 4 for w in INDEX["workloads"])
    assert four <= max(1, len(CELLS) // 4)


@pytest.mark.parametrize("m", METRICS, ids=lambda m: m["name"])
def test_metric_entry(m):
    per_layer = m in INDEX["per_layer"]
    keys = {"name", "unit", "better", "source"}
    keys |= {"layer", "moves"} if per_layer else {"bound"}
    assert set(m) - {"workloads"} == keys
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    assert set(m.get("workloads", [])) <= set(CELLS)
    reader = REPO / "benchmark" / "metrics" / f"{m['name']}.py"
    assert "def read(run)" in reader.read_text()
    if per_layer:
        moved = E2E[m["moves"]]
        assert m["workloads"], "a per-layer metric names its cells"
        assert set(m["workloads"]) <= set(moved.get("workloads", CELLS))
        assert 1 <= len(m["layer"]) <= 200
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    else:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1


def test_metric_names_are_unique_and_setup_is_there():
    names = [m["name"] for m in METRICS]
    assert len(set(names)) == len(names)
    assert E2E["setup_s"]["bound"] <= 0.1 and "workloads" not in E2E[
        "setup_s"]


def test_files_under_paths_are_named_from_allowed_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in INDEX["paths"]:
        for f in (REPO / p).rglob("*"):
            if "__pycache__" in f.parts or f.suffix == ".pyc":
                continue
            assert ok.match(str(f.relative_to(REPO))), f


def test_every_traffic_mix_is_a_data_file():
    for f in (REPO / "benchmark" / "traffic").iterdir():
        assert f.suffix in (".json", ".jsonl", ".toml", ".txt", ".csv")
