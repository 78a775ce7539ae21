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
#: keys ``reduced`` may never name: widths, and only widths (depth is
#: listed under its published name, ``num_hidden_layers``)
WIDTHS = re.compile(r"(hidden_size|intermediate|_dim$|_rank$|head_dim|"
                    r"_width$|sliding_window|experts_per_tok)")


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
    assert not any(WIDTHS.search(k) for k in cfg["reduced"])
    assert all(1 <= len(cfg[k]) <= 200 and "\n" not in cfg[k]
               for k in ("source", "why"))
    assert any(w["config"] == cfg["name"] for w in INDEX["workloads"])
    if "binding" in doc:       # a serving configuration names its model's
        binding = (REPO / "benchmark" / "bindings"
                   / f"{doc['binding']}.py").read_text()
        assert all(f"\ndef {f}(" in binding for f in (
            "check_supported", "model_of", "engine", "reference_weights",
            "reference_logits"))


#: every top-level key of the published ``config.json`` of a mixture-of-
#: experts model with window layers (poolside/Laguna-XS.2), widths first
LAGUNA_WIDTHS = ["hidden_size", "intermediate_size", "head_dim",
                 "moe_intermediate_size", "shared_expert_intermediate_size",
                 "sliding_window", "num_experts_per_tok"]
LAGUNA_OTHERS = ["model_type", "vocab_size", "num_hidden_layers",
                 "num_attention_heads", "num_key_value_heads",
                 "max_position_embeddings", "attention_bias",
                 "rms_norm_eps", "num_experts", "tie_word_embeddings",
                 "gating", "rope_parameters", "layer_types",
                 "moe_apply_router_weight_on_input",
                 "partial_rotary_factor", "mlp_layer_types",
                 "moe_routed_scaling_factor",
                 "num_attention_heads_per_layer"]


def test_reduced_may_list_depth_and_never_a_width():
    """Both directions: each width key of both configurations and of that
    ``config.json`` is refused, each depth key is taken."""
    ours = [set(json.loads((REPO / c["file"]).read_text()))
            for c in INDEX["configs"]]
    for keys in ours:
        assert {"hidden_size", "intermediate_size", "num_hidden_layers",
                "max_position_embeddings"} <= keys
    refused = {k for keys in ours + [set(LAGUNA_WIDTHS + LAGUNA_OTHERS)]
               for k in keys if WIDTHS.search(k)}
    assert refused == set(LAGUNA_WIDTHS)
    for depth in ("num_hidden_layers", "num_layers", "layer_types",
                  "mlp_layer_types", "num_attention_heads_per_layer"):
        assert not WIDTHS.search(depth)
    for width in ("kv_lora_rank", "qk_rope_head_dim", "expert_width",
                  "ffn_hidden_size", "d_state_dim"):
        assert WIDTHS.search(width)


@pytest.mark.parametrize("cell", INDEX["workloads"], ids=lambda w: w["name"])
def test_cell_entry(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert all(NAME.match(cell[k]) for k in ("name", "config", "traffic"))
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    assert cell["config"] in {c["name"] for c in INDEX["configs"]}
    mix = REPO / "benchmark" / "traffic" / f"{cell['traffic']}.json"
    driver = REPO / "benchmark" / "drivers" / (
        json.loads(mix.read_text())["driver"] + ".py")
    assert "\ndef run(" in driver.read_text()
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
