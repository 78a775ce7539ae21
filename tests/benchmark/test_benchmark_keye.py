"""The ``keye`` kind enters the benchmark as files (ISSUE 36): binding,
reference, driver, counts, thirteen readers, a calibration script, a
configuration and a mix.  A toy cell of the kind is rehearsed on the CPU
through the one command; the control in the precision below AND the two
wrong selections fail the toy limits; the toy cell is new files only and
``BENCHMARK.json`` holds the kind by name;
every count stands against a brute-force loop at toy size; every new reader
finds nothing — ``None``, never 0 — where its counters or spans are absent."""
import argparse
import hashlib
import importlib
import json
import os
import re
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

import keye_root
from benchmark import counts_dsa, harness, weights
from benchmark.drivers import moe_serve as M

REPO = Path(__file__).resolve().parents[2]
CELL = keye_root.CELL
REAL = "keye-vl-2.0.longdoc-batch"
NEW_METRICS = ["serve_step_mfu.dsa", "decode_roofline.dsa",
               "prefill_roofline.dsa", "dsa_index_ms_per_pass",
               "dsa_select_ms_per_pass", "dsa_attend_ms_per_pass",
               "dsa_attend_roofline", "dsa_index_roofline",
               "dsa_selected_share", "moe_products_ms_per_pass.dsa",
               "moe_products_roofline.dsa", "moe_experts_hit_share.dsa",
               "moe_load_max_over_mean.dsa"]
NEW_FILES = ["bindings/dsa_keye.py", "references/keye_lm.py", "counts_dsa.py",
             "drivers/dsa_serve.py", "calibrate_dsa.py",
             "traffic/longdoc-batch.json",
             "configs/keye-vl-2.0-30b-a3b-serve.json"] \
    + [f"metrics/{m}.py" for m in NEW_METRICS]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return keye_root.make(tmp_path_factory.mktemp("keye"))


@pytest.fixture(scope="module")
def run_cell(root):
    from benchmark import run as bench_run

    def go(workload=CELL, seed=3, seconds=1.5, trace=1):
        args = argparse.Namespace(workload=workload, seed=seed,
                                  seconds=seconds, trace=trace,
                                  rehearse=True)
        return bench_run.run_cell(args, time.perf_counter(), root=root)
    return go


@pytest.fixture(scope="module")
def cell(root):
    return harness.load_cell(CELL, root)


@pytest.fixture(scope="module")
def published():
    return json.loads((REPO / "benchmark" / "configs"
                       / "keye-vl-2.0-30b-a3b-serve.json").read_text())


def _digest(root: Path) -> dict:
    return {str(f.relative_to(root)): hashlib.sha1(f.read_bytes())
            .hexdigest() for f in (root / "benchmark").rglob("*")
            if f.is_file() and "__pycache__" not in f.parts}


def test_the_toy_cell_is_only_new_files(root):
    ours, theirs = _digest(REPO), _digest(root)
    assert all(theirs[k] == v for k, v in ours.items())
    added = set(theirs) - set(ours)
    assert keye_root.ADDED <= added
    assert not any("keye" in f or "dsa" in f
                   for f in added - keye_root.ADDED)
    assert all(f"benchmark/{f}" in ours for f in NEW_FILES)


OLDER_CELLS = {"bert-large.pretrain-b32": "bert-large-phase1",
               "gpt3-1.3b.chat": "gpt3-1.3b-serve",
               "gpt3-1.3b.docs-batch": "gpt3-1.3b-serve",
               "laguna-xs.2.code-batch": "laguna-xs.2-serve",
               "a.x-k1.analysis-batch": "a.x-k1-serve"}


def test_the_index_holds_the_kind_by_name():
    """``BENCHMARK.json``, asked by NAME (the next kind's entries come after
    these, so no place in a list is held): the configuration, the cell and
    the thirteen readers with what each has to say, the cell's name in the
    lists of the kind-blind metrics, and every older configuration and cell
    still there.  That no file the benchmark had was edited is the driver's
    check of a PR, not a test of the tree."""
    index = json.loads((REPO / "BENCHMARK.json").read_text())
    configs = {e["name"]: e for e in index["configs"]}
    cells = {e["name"]: e for e in index["workloads"]}
    metrics = {m["name"]: m for m in index["per_layer"]}
    config = configs["keye-vl-2.0-30b-a3b-serve"]
    assert config["reduced"] == ["num_hidden_layers",
                                 "max_position_embeddings"]
    assert (REPO / config["file"]).is_file()
    entry = cells[REAL]
    assert entry == dict(entry, config="keye-vl-2.0-30b-a3b-serve",
                         traffic="longdoc-batch", chips=1)
    assert len(entry["why"]) <= 200 and len(config["why"]) <= 200
    for name, of in OLDER_CELLS.items():
        assert cells[name]["config"] == of and of in configs
    layers = {m["layer"] for name, m in metrics.items()
              if name not in NEW_METRICS}
    for name in NEW_METRICS:
        m = metrics[name]
        assert REAL in m["workloads"]
        assert m["moves"] == "serve_tokens_per_s" and m["layer"] in layers
        assert (m["unit"] == "%") == bool(re.search(
            "mfu|roofline|share", name))
        assert (REPO / "benchmark" / "metrics" / f"{name}.py").is_file()
    e2e = {m["name"]: m for m in index["end_to_end"]}
    assert REAL in e2e["serve_tokens_per_s"]["workloads"]
    kind_blind = [name for name, m in metrics.items()
                  if name not in NEW_METRICS and REAL in m["workloads"]]
    assert len(kind_blind) == 14 and not any(
        "moe" in n or "mla" in n for n in kind_blind)
    assert all("a.x-k1.analysis-batch" in metrics[n]["workloads"]
               for n in kind_blind)
    # every per-layer metric that moves tokens/s names its cells
    assert all("workloads" in m for m in index["per_layer"]
               if m["moves"] == "serve_tokens_per_s")
    assert (REPO / "BENCHMARK.json").stat().st_size < 64 * 1024


def test_the_configuration_keeps_every_published_number(published):
    """Every key of the catalog's ``config`` for the model, number for
    number (``sa_config`` and ``rope_scaling`` whole), but the two under
    ``reduced``; ``published`` states those."""
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.is_file():
        pytest.skip("no catalog on this machine")
    row = next(json.loads(line) for line in catalog.read_text().splitlines()
               if json.loads(line)["name"] == "Keye-VL-2.0-30B-A3B")
    assert published["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items() if published.get(k) != v}
    assert differ == set(published["reduced"]) == {
        "num_hidden_layers", "max_position_embeddings"}
    assert published["published"] == {k: row["config"][k]
                                      for k in published["reduced"]}
    assert published["sa_config"] == row["config"]["sa_config"]
    assert set(published["assumed"]) >= {
        "a_qk_norm", "b_indexer", "c_index_rope", "d_chunks",
        "e_rope_pairing"}
    assert "deployment" in published and published["binding"] == "dsa_keye"
    mix = json.loads((REPO / "benchmark" / "traffic"
                      / "longdoc-batch.json").read_text())
    # a prompt of the longest kind and its answer fit a slot's table
    assert published["max_position_embeddings"] >= (
        mix["prompt_tokens"]["max"] + mix["new_tokens"]["max"])
    assert published["max_position_embeddings"] % mix["page_size"] == 0
    assert mix["prompt_tokens"]["min"] >= 2 * published["sa_config"]["topk"]


WIDTHS = re.compile(r"(hidden_size|intermediate|_dim$|_rank$|head_dim|"
                    r"_width$|sliding_window|experts_per_tok)")


def test_reduced_names_no_width_of_this_family(published):
    """The contract's rule on ``reduced`` (``test_benchmark_contract.py``'s
    expression), at this configuration's keys — and inside its nested
    groups, where the indexer's widths live: every width key is refused
    and the two cuts are not."""
    refused = {k for k in published if WIDTHS.search(k)}
    assert refused == {"hidden_size", "intermediate_size",
                       "moe_intermediate_size", "num_experts_per_tok",
                       "head_dim", "sliding_window",
                       "use_sliding_window"}
    nested = {k for k in published["sa_config"] if WIDTHS.search(k)}
    assert nested == {"indexer_head_dim"}
    assert not any(WIDTHS.search(k) for k in published["reduced"])
    # the groups that hold widths (and topk, heads) are whole, unlisted
    assert not {"sa_config", "rope_scaling"} & set(published["reduced"])
    assert published["sa_config"]["topk"] == 2048
    assert published["sa_config"]["indexer_num_heads"] == 16


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 5, 11])
def test_rehearsal_of_the_kind_is_correct(run_cell, cell, seed):
    assert harness.load_binding(cell).__name__ \
        == "benchmark.bindings.dsa_keye"
    assert harness.load_driver(cell).__name__ \
        == "benchmark.drivers.dsa_serve"
    r = run_cell(seed=seed)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 8
    assert [c["name"] for c in r["checks"]] == [
        "served_token_gap_mean", "served_token_gap_tail_share",
        "requests_unfinished", "token_count_wrong"]
    m = r["metrics"]
    assert m["compiles_in_window.serve"]["value"] == 0.0
    # contexts of 20-96 under a selection of 16: a third to a half
    assert 25.0 < m["dsa_selected_share"]["value"] < 60.0
    assert 30.0 < m["moe_experts_hit_share.dsa"]["value"] <= 100.0
    assert m["moe_load_max_over_mean.dsa"]["value"] >= 1.0
    # off the chip no time, rate or share of a peak is printed
    assert not {"serve_step_mfu.dsa", "decode_roofline.dsa",
                "dsa_index_ms_per_pass", "dsa_attend_roofline"} & set(m)


def test_the_one_command_runs_the_cell(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(2 ** 31 + 99), "--seconds", "1.5", "--trace", "1",
         "--rehearse"], cwd=root, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["correct"] and last["rehearsal"]
    assert last["metrics"]["dsa_selected_share"]["value"] > 0


@pytest.mark.parametrize("control", ["fp8", "attend_all", "recent_topk"])
@pytest.mark.parametrize("seed", [7, 9, 10])
def test_every_control_fails_the_toy_limits(cell, seed, control):
    """The precision below, and the two wrong selections in FULL precision
    (every causal position; the most recent 16): each is refused by both
    toy limits."""
    binding = harness.load_binding(cell)
    _, shapes = binding.model_of(cell.config)
    rng = np.random.RandomState(seed)
    seqs = [(rng.randint(0, 128, size=60).astype(np.int32),
             rng.randint(0, 128, size=60).astype(np.int32))
            for _ in range(3)]
    limits = cell.config["correct"]["limits"]
    low = M.served_token_gaps(cell, shapes, seed, seqs, quant=control)
    assert low["tokens"] == 180
    assert low["mean"] > 2 * limits["served_token_gap_mean"], low
    # 33-39 judged tokens a rehearsal: a sound tail share is 0 to 6 tokens,
    # so the toy limit leaves fp8 (0.31-0.33 here) 1.4x and not 2x
    assert low["tail_share"] > 1.3 * limits["served_token_gap_tail_share"]


def test_an_unknown_control_is_refused(cell):
    binding = harness.load_binding(cell)
    with pytest.raises(harness.Refused, match="unknown control"):
        binding.reference_logits(cell.config, {}, np.zeros(256, np.int32),
                                 0, 4, quant="int4")


def test_a_program_without_the_kind_is_refused_before_any_weight(
        run_cell, monkeypatch):
    """As on the parent commit: its ``check_supported`` knows no ``keye``
    (there the import of ``standalone_keye`` fails first, the same
    ``Refused``)."""
    from apex_tpu.inference import models

    def not_served(kind, cfg):
        raise ValueError(f"unknown generative model kind {kind!r}")

    def no_weights(shapes, seed):
        raise AssertionError("weights were made before support was asked")
    monkeypatch.setattr(models, "check_supported", not_served)
    monkeypatch.setattr(weights, "make", no_weights)
    with pytest.raises(harness.Refused, match="does not serve"):
        run_cell()


def _bare_run(cell, facts=None, trace=None):
    devices = harness.Devices("tpu", "TPU v5 lite", [], harness.peaks_for(
        "TPU v5 lite"))
    base = {"window": (0.0, 1.0), "requests": [], "passes": [],
            "trace_started": None, "trace_stopped": None}
    return harness.Run(cell=cell, devices=devices,
                       facts=dict(base, **(facts or {})), trace=trace,
                       setup_s=1.0)


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_a_new_reader_finds_nothing_where_nothing_is(root, cell, metric):
    """No counters in ``facts`` (the parent's program), no trace, an empty
    trace, zero counters, a dense configuration, Laguna's, A.X-K1's:
    ``None`` each time."""
    from benchmark import trace as trace_mod
    empty = trace_mod.Trace(ops={}, modules={}, host=[])
    gpt = harness.load_cell("toy.chat", root)
    laguna = harness.load_cell("laguna-xs.2.code-batch", REPO)
    axk1 = harness.load_cell("a.x-k1.analysis-batch", REPO)
    zero = {"moe": {ph: dict(passes=0.0, assignments=0.0, experts_hit=0.0,
                             load_max=0.0) for ph in ("prefill", "decode")},
            "dsa": {ph: dict(rows=0.0, rows_sparse=0.0, selected=0.0)
                    for ph in ("prefill", "decode")}}
    some = {"moe": {ph: dict(passes=3.0, assignments=9.0, experts_hit=4.0,
                             load_max=2.0) for ph in ("prefill", "decode")},
            "dsa": {ph: dict(rows=9.0, rows_sparse=3.0, selected=50.0)
                    for ph in ("prefill", "decode")}}
    for run in (_bare_run(cell), _bare_run(cell, trace=empty),
                _bare_run(cell, facts=zero, trace=empty),
                _bare_run(gpt, facts=some, trace=empty),
                _bare_run(laguna, facts=some, trace=empty),
                _bare_run(axk1, facts=some, trace=empty)):
        assert harness.read_metric(metric, run) is None


def test_the_other_kinds_readers_find_nothing_in_this_cell(cell):
    """Laguna's expert readers key on ``num_experts``, which this
    configuration has too: their model needs Laguna's per-layer keys, so
    they are off this cell's lists — and A.X-K1's read ``None`` here."""
    some = {"moe": {ph: dict(passes=3.0, assignments=9.0, experts_hit=4.0,
                             load_max=2.0) for ph in ("prefill", "decode")}}
    run = _bare_run(cell, facts=some)
    for metric in ("moe_landed_share", "moe_experts_hit_share.mla",
                   "decode_roofline.mla", "serve_step_mfu.mla"):
        assert harness.read_metric(metric, run) is None
    index = json.loads((REPO / "BENCHMARK.json").read_text())
    mine = {m["name"] for m in index["per_layer"] if REAL in m["workloads"]}
    assert not {"moe_experts_hit_share", "moe_products_ms_per_pass",
                "serve_step_mfu.moe", "window_pages_live_peak"} & mine


def test_selected_share_from_the_counters_and_the_stamps(cell):
    """``dsa_selected_share``: the counters' positions attended of both
    phases over the contexts of the queries the passes carried (3 layers);
    the expert shares over 8 experts a layer."""
    reqs = [{"prompt_len": 30, "token_times": [0.1, 0.2, 0.3]},
            {"prompt_len": 50, "token_times": [0.2]},
            {"prompt_len": 70, "token_times": []}]      # never prefilled
    moe = {"prefill": dict(passes=2.0, assignments=480.0, experts_hit=40.0,
                           load_max=60.0),
           "decode": dict(passes=2.0, assignments=12.0, experts_hit=10.0,
                          load_max=2.0)}
    dsa = {"prefill": dict(rows=240.0, rows_sparse=144.0, selected=3000.0),
           "decode": dict(rows=6.0, rows_sparse=6.0, selected=96.0)}
    run = _bare_run(cell, facts={"requests": reqs, "moe": moe, "dsa": dsa})
    could = (30 * 31 // 2 + 50 * 51 // 2) + (31 + 32)
    assert counts_dsa.dsa_selected_share(run) == pytest.approx(
        100.0 * 3096.0 / (3 * could))
    assert counts_dsa.moe_experts_hit_share(run) == pytest.approx(
        100.0 * (10.0 / 2) / (8 * 3))
    assert counts_dsa.moe_load_max_over_mean(run) == pytest.approx(
        60.0 / (480.0 / (8 * 3)))


def test_counts_of_the_published_sizes(published):
    """``counts_dsa`` at the configuration as run, against the arithmetic of
    ISSUE 36 section 2: attention 18.87M a layer, the indexer 2.26M, an
    expert 4.72M, 3,749M parameters held (7.50 GB), a cached position of
    2,176 B a layer."""
    m = counts_dsa.model(published)
    assert counts_dsa.attention_params(m) == (
        2 * 2048 * 4096 + 2 * 2048 * 512) == 18_874_368
    assert counts_dsa.indexer_params(m) == 2048 * (1024 + 64 + 16) \
        == 2_260_992
    assert counts_dsa.expert_params(m) == 4_718_592
    assert counts_dsa.row_bytes(m) + counts_dsa.index_key_bytes(m) == 2176
    assert round(counts_dsa.total_params(m) / 1e6) == 3749
    # the program's own shape function agrees, to the norms
    from benchmark.bindings import dsa_keye as binding
    import jax
    _, shapes = binding.model_of(published)
    held = sum(x.size for x in jax.tree.leaves(shapes))
    norms = 5 * (2 * 2048 + 2 * 128 + 2 * 64) + 2048
    assert held == counts_dsa.total_params(m) + norms
    assert held * 2 == pytest.approx(7.50e9, rel=2e-3)
    assert counts_dsa.index_flops(m) == 2 * 16 * 64
    assert counts_dsa.attend_flops(m) == 2 * 32 * (128 + 128)
    # a query under topk scores nothing and attends everything
    assert (counts_dsa.scored(2048, m), counts_dsa.picked(2048, m)) \
        == (0, 2048)
    assert (counts_dsa.scored(2049, m), counts_dsa.picked(2049, m)) \
        == (2049, 2048)
    assert counts_dsa.cache_bytes_read(10_000, m) == 5 * (
        128 * 10_000 + 2048 * 2048)
    assert counts_dsa.cache_bytes_written(4096, m) == 5 * 4096 * 2176
    n = 8192
    assert counts_dsa.prefill_flops(n, m) == pytest.approx(
        2 * n * counts_dsa.active_params(m)
        + 5 * (2048 * (n * (n + 1) / 2 - 2048 * 2049 / 2)
               + 16384 * (2048 * 2049 / 2 + (n - 2048) * 2048))
        + 2 * 2048 * 151936)


def test_every_count_against_a_brute_force_loop(cell):
    """At toy size, parameter counts against the served tree itself and
    the scored and picked positions against a loop over (query, key)."""
    import jax
    cfg = cell.config
    m = counts_dsa.model(cfg)
    binding = harness.load_binding(cell)
    _, shapes = binding.model_of(cfg)
    p = shapes["params"]

    def size(tree):
        return sum(x.size for x in jax.tree.leaves(tree)
                   if len(x.shape) > 1)
    lp = p["layer_1"]
    assert counts_dsa.attention_params(m) == size(lp["attention"])
    assert counts_dsa.indexer_params(m) == size(lp["indexer"])
    assert counts_dsa.expert_params(m) * m["experts"] == size(
        lp["moe"]["experts"])
    assert counts_dsa.layer_resident_params(m) == size(lp["attention"]) \
        + size(lp["indexer"]) + size(lp["moe"]["router"])
    assert counts_dsa.total_params(m) == size(p)
    topk = m["topk"]
    for n in (5, topk, topk + 1, 50):
        scored = picked = 0
        for t in range(n):
            context = t + 1
            for _s in range(context):
                scored += context > topk
            picked += min(context, topk)
        assert counts_dsa.prompt_scored(n, m) == scored
        assert counts_dsa.prompt_picked(n, m) == picked
        base = 2 * n * counts_dsa.active_params(m) \
            + 2 * m["hidden"] * m["vocab"]
        per_pair = 2 * m["index_heads"] * m["index_dim"]
        per_pick = 2 * m["heads"] * 2 * m["head_dim"]
        assert counts_dsa.prefill_flops(n, m) == base + m["layers"] * (
            per_pair * scored + per_pick * picked)
    assert counts_dsa.decode_flops(50, m) == 2 * counts_dsa.active_params(
        m) + 2 * m["hidden"] * m["vocab"] + m["layers"] * (
        2 * m["index_heads"] * m["index_dim"] * 50
        + 2 * m["heads"] * 2 * m["head_dim"] * topk)


#: heads of device operations' names as a v5e profile gives them for this
#: kind's kernels, selection and expert FFN, and whether the readers' rules
#: find them
FOUND = [
    ("products", "%ragged-dot-none.2 = bf16[512,2048]{1,0:T(8,128)(2,1)} "
                 "custom-call(s32[1]{0:T(128)} %get-tuple-element.2"),
    ("products", "%fusion.9 = f32[16,128]{1,0:T(8,128)S(1)} fusion(f32[16,"
                 "128]{1,0:T(8,128)S(1)} %get-tuple-element.23"),
    ("products", "%sort.2 = (f32[8192,128]{0,1:T(8,128)S(1)}, s32[8192,128]"
                 "{0,1:T(8,128)S(1)}) sort(%broadcast_select_fusion.4"),
    ("index", "%apex_dsa_index.3 = f32[16,264,1,128]{3,2,1,0} custom-call("),
    ("index", "%apex_dsa_index_fwd.7 = f32[512,8192]{1,0} custom-call(%q"),
    ("index_decode", "%apex_dsa_index.3 = f32[16,264,1,128]{3,2,1,0} "
                     "custom-call("),
    ("index_decode", "%apex_dsa_index = f32[16,264,1,128]{3,2,1,0} custom"),
    ("attend", "%apex_dsa_attend.1 = bf16[16,32,128]{2,1,0} custom-call("),
    ("select", "%fusion.131 = s32[16]{0:T(128)} fusion(u32[16,33792]{1,0:T("
               "8,128)S(1)} %get-tuple-element.77, u32[16]{0} %or.3"),
    ("select", "%select_fusion.3 = u32[512,16384]{1,0:T(8,128)} fusion(f32["
               "512,16384]{1,0} %apex_dsa_index_fwd.7"),
]
NOT_FOUND = [
    ("products", "%fusion.74 = bf16[8192,2048]{1,0:T(8,128)(2,1)} fusion("),
    # a head's and a page's width is the router's too (128)
    ("products", "%fusion.3 = bf16[16,32,128]{2,1,0:T(8,128)(2,1)} fusion("),
    ("products", "%fusion.5 = f32[16,264,1,128]{3,2,1,0} fusion(%apex"),
    ("products", "%copy.9 = bf16[8192,4,128]{2,1,0:T(8,128)(2,1)} copy("),
    ("index_decode", "%apex_dsa_index_fwd.7 = f32[512,8192]{1,0} custom-"),
    ("attend", "%apex_paged_decode.2 = bf16[32,48,128]{2,1,0} custom-call("),
    ("index", "%apex_flash_fwd.1 = bf16[32,512,128]{2,1,0} custom-call(%q"),
    ("select", "%fusion.8 = u32[2]{0} fusion(u32[2]{0} %key)"),
    ("select", "%fusion.77 = s32[4224]{0} fusion(s32[16,264]{1,0} %table"),
    # the loop spans its passes: the passes are counted, not the loop
    ("select", "%while.16 = (s32[]{:T(128)}, u32[16]{0:T(128)S(1)}, u32[16,"
               "33792]{1,0:T(8,128)S(1)}, s32[16]{0:T(128)S(1)}, s32[]{:T("
               "128)}, /*index=5*/u32[]{:T(128)}, s32[]{:T(128)}) while(%tu"),
]


@pytest.mark.parametrize("rule,op,found",
                         [(r, op, True) for r, op in FOUND]
                         + [(r, op, False) for r, op in NOT_FOUND])
def test_what_the_trace_readers_find(rule, op, found):
    run = _bare_run(harness.load_cell(REAL, REPO))
    pattern = {"products": counts_dsa.products_pattern(run),
               "index": counts_dsa.INDEX_KERNELS,
               "index_decode": counts_dsa.INDEX_DECODE_KERNEL,
               "attend": counts_dsa.ATTEND_KERNEL,
               "select": counts_dsa.SELECT_OPS}[rule]
    assert bool(re.search(pattern, op[:240])) == found


@pytest.mark.parametrize("phase", ["prefill", "decode"])
def test_the_type_rules_find_their_stage_and_nothing_else(phase,
                                                          monkeypatch):
    """A step of the published widths compiled for a described v5e: every
    operation the router's width rule finds was made under
    ``apex_moe_route`` (128 is also a head's and a page's width, and those
    arrays ARE in the program), and every operation the selection's rule
    finds under ``apex_dsa_select`` — and each rule finds some."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever says "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    chip = SingleDeviceSharding(topo.devices[0])
    from apex_tpu.inference import kv_cache
    from apex_tpu.inference.engine import make_decode_fn, make_prefill_fn
    from apex_tpu.inference.sampling import SamplingConfig
    from apex_tpu.transformer.testing import standalone_keye as SK

    # the process's backend is the CPU, so the wrappers would interpret
    for mod in ("attention", "layer_norm", "paged_attention"):
        monkeypatch.setattr(importlib.import_module(f"apex_tpu.ops.{mod}"),
                            "interpret_mode", lambda: False)
    run = _bare_run(harness.load_cell(REAL, REPO))
    experts = counts_dsa.model(run.cell.config)["experts"]
    layers, slots, ps, pages, mpps, prompt = 2, 8, 128, 2048, 40, 4096
    cfg = SK.KeyeConfig(
        vocab_size=256, hidden_size=256, num_layers=layers, num_heads=32,
        num_kv_heads=4, head_dim=128, mrope_section=(16, 24, 24),
        index_heads=16, index_head_dim=64, index_topk=2048,
        index_q_chunk=512, moe_ffn_hidden_size=128, num_experts=experts,
        experts_per_token=8, max_seq_length=ps * mpps,
        params_dtype=jnp.bfloat16)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def i32(*shape):
        return spec(shape, jnp.int32)

    params = {"params": jax.tree.map(
        lambda shape: spec(shape, jnp.bfloat16), SK.keye_param_shapes(cfg),
        is_leaf=lambda x: isinstance(x, tuple))}
    cache = jax.tree.map(lambda x: spec(x.shape, x.dtype), jax.eval_shape(
        lambda: kv_cache.init_paged_cache(
            pages, layers, 4, ps, 128, slots=slots, max_pages_per_slot=mpps,
            index=64)))
    key = spec((2,), jnp.uint32)
    if phase == "prefill":
        step = make_prefill_fn("keye", cfg, SamplingConfig(), paged=True)
        args = (cache, params, i32(prompt), i32(), i32(), i32(mpps), i32(),
                key, i32())
    else:
        step = make_decode_fn("keye", cfg, SamplingConfig())
        args = (cache, params, i32(slots), spec((slots,), bool), key, i32())
    hlo = jax.jit(step, donate_argnums=(0,)).lower(*args).compile().as_text()

    router = re.compile(counts_dsa.products_pattern(run))
    select = re.compile(counts_dsa.SELECT_OPS)
    # a profile's events are the operations of the entry computation and
    # of the loops' bodies: not those inside a fusion
    fused = set(re.findall(r"calls=%([\w.\-]+)", hlo))
    scope, routed, selected = None, 0, 0
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if head:
            scope = head.group(1)
            continue
        op = re.sub(r"^ROOT ", "", line.strip())[:240]
        if scope in fused or not re.match(r"%\S+ = ", op):
            continue
        made = re.search(r'op_name="([^"]*)"', line)
        free = re.search(r" (bitcast|get-tuple-element|parameter|tuple|"
                         r"constant)\(", op)      # no time, no event
        if router.search(op) and "ragged-dot" not in op:
            if made and not free:
                assert "/apex_moe_route/" in made.group(1), line[:300]
                routed += 1
        if select.search(op) and made and not free:
            assert "/apex_dsa_select/" in made.group(1), line[:300]
            selected += 1
    assert routed and selected, (routed, selected)
