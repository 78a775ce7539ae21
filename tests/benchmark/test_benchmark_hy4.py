"""The ``hy4`` kind enters the benchmark as files: binding,
reference, driver, counts, nine readers, a calibration script, a
configuration and a mix.  A toy cell of the kind is rehearsed on the CPU
through the one command; the control in the precision below and the three
wrong selections fail the toy limits; the toy cell is new files only and
``BENCHMARK.json`` holds the kind by name; every count stands against the
arithmetic of the cut and a brute-force loop at toy size; every new
reader finds nothing — ``None``, never 0 — where its counters or spans are
absent."""
import argparse
import hashlib
import json
import os
import re
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

import hy4_root
from benchmark import counts_hy4, harness, weights
from benchmark.drivers import dsa_reuse_serve, dsa_serve
from benchmark.drivers import moe_serve as M

REPO = Path(__file__).resolve().parents[2]
CELL = hy4_root.CELL
REAL = "hy4-preview.longctx-batch"
NEW_METRICS = ["serve_step_mfu.hy4", "decode_roofline.hy4",
               "prefill_roofline.hy4", "hc_ms_per_pass",
               "dsa_attend_latent_ms_per_pass", "dsa_attend_latent_roofline",
               "dsa_index_ms_per_pass.hy4", "dsa_index_roofline.hy4",
               "dsa_rows_reused_share"]
NEW_FILES = ["bindings/mla_dsa_hy4.py", "references/hy4_lm.py",
             "counts_hy4.py", "drivers/dsa_reuse_serve.py",
             "calibrate_hy4.py", "traffic/longctx-batch.json",
             "configs/hy4-preview-serve.json"] \
    + [f"metrics/{m}.py" for m in NEW_METRICS]
#: what every cell of a serving kind reads whatever its model: listed for
#: this cell too (``dsa_index_ms_per_pass`` is keye's, keyed on its config
#: keys: this cell has ``dsa_index_ms_per_pass.hy4``)
KIND_BLIND = {"compiles_in_window.serve", "device_idle_share.serve",
              "idle_ms_per_pass.scheduler", "idle_ms_per_pass.engine",
              "idle_ms_per_pass.harness", "peak_hbm_gib.serve",
              "slot_occupancy", "pool_pages_live_peak",
              "pool_pages_live_mean", "pool_pages_attended_peak",
              "pool_pages_attended_mean", "sched_host_ms_per_pass",
              "engine_dispatch_ms_per_pass", "dispatches_per_pass",
              "prefill_insert_ms_per_pass", "moe_ffn_ms_per_pass",
              "moe_dispatch_ms_per_pass"}
#: the controls that have to fail the limits at the toy's seeded weights
SEPARATING = ["fp8", "attend_all", "recent_topk", "self_select"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return hy4_root.make(tmp_path_factory.mktemp("hy4"))


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
                       / "hy4-preview-serve.json").read_text())


def _digest(root: Path) -> dict:
    return {str(f.relative_to(root)): hashlib.sha1(f.read_bytes())
            .hexdigest() for f in (root / "benchmark").rglob("*")
            if f.is_file() and "__pycache__" not in f.parts}


def test_the_toy_cell_is_only_new_files(root):
    ours, theirs = _digest(REPO), _digest(root)
    assert all(theirs[k] == v for k, v in ours.items())
    added = set(theirs) - set(ours)
    assert hy4_root.ADDED <= added
    assert not any("hy4" in f for f in added - hy4_root.ADDED)
    assert all(f"benchmark/{f}" in ours for f in NEW_FILES)


def test_the_index_holds_the_kind_by_name():
    """``BENCHMARK.json``, asked by NAME: the configuration, the cell, the
    nine readers with what each has to say, the cell's name in the lists of
    the kind-blind metrics and of ``serve_tokens_per_s``, and no reader of
    another kind's widths."""
    index = json.loads((REPO / "BENCHMARK.json").read_text())
    configs = {e["name"]: e for e in index["configs"]}
    cells = {e["name"]: e for e in index["workloads"]}
    metrics = {m["name"]: m for m in index["per_layer"]}
    config = configs["hy4-preview-serve"]
    assert config["reduced"] == [
        "num_hidden_layers", "layer_types", "mlp_layer_types",
        "indexer_types", "n_routed_experts", "vocab_size",
        "max_position_embeddings"]
    assert (REPO / config["file"]).is_file()
    entry = cells[REAL]
    assert entry == dict(entry, config="hy4-preview-serve",
                         traffic="longctx-batch", chips=1)
    assert len(entry["why"]) <= 200 and len(config["why"]) <= 200
    layers = {m["layer"] for name, m in metrics.items()
              if name not in NEW_METRICS}
    for name in NEW_METRICS:
        m = metrics[name]
        assert m["workloads"] == [REAL]
        assert m["moves"] == "serve_tokens_per_s" and m["layer"] in layers
        assert (m["unit"] == "%") == bool(re.search(
            "mfu|roofline|share", name))
        assert (REPO / "benchmark" / "metrics" / f"{name}.py").is_file()
    e2e = {m["name"]: m for m in index["end_to_end"]}
    assert REAL in e2e["serve_tokens_per_s"]["workloads"]
    mine = {name for name, m in metrics.items()
            if name not in NEW_METRICS and REAL in m["workloads"]}
    assert mine == KIND_BLIND
    assert (REPO / "BENCHMARK.json").stat().st_size < 64 * 1024


def test_the_configuration_states_its_cuts(published):
    """Every key under ``reduced`` has its published value under
    ``published``, and the cut lists are the published lists' first
    five."""
    assert set(published["published"]) == set(published["reduced"])
    assert published["published"]["num_hidden_layers"] == 78
    assert published["published"]["n_routed_experts"] == 256
    assert published["published"]["vocab_size"] == 8 * published[
        "vocab_size"]
    for key in ("layer_types", "mlp_layer_types", "indexer_types"):
        assert published[key] == published["published"][key][:5]
    assert published["indexer_types"] == ["full", "full", "shared",
                                          "shared", "shared"]
    assert set(published["assumed"]) >= {
        "a_hyper_connections", "b_mix_weights", "c_gate", "d_swiglu_limit",
        "e_indexer", "f_reuse", "g_sink", "i_mtp"}
    assert "deployment" in published and published["binding"] == \
        "mla_dsa_hy4"
    mix = json.loads((REPO / "benchmark" / "traffic"
                      / "longctx-batch.json").read_text())
    # a prompt of the longest kind and its answer fit a slot's table
    assert published["max_position_embeddings"] == (
        mix["prompt_tokens"]["max"] + mix["new_tokens"]["max"])
    assert published["max_position_embeddings"] % mix["page_size"] == 0
    assert mix["prompt_tokens"]["min"] >= 2 * published["index_topk"]
    assert mix["pool_pages"] == 16 * 136 + 128


WIDTHS = re.compile(r"(hidden_size|intermediate|_dim$|_rank$|head_dim|"
                    r"_width$|sliding_window|experts_per_tok)")


def test_reduced_names_no_width_of_this_family(published):
    refused = {k for k in published if WIDTHS.search(k)}
    assert refused >= {"hidden_size", "intermediate_size",
                       "moe_intermediate_size", "num_experts_per_tok",
                       "head_dim", "q_lora_rank", "kv_lora_rank",
                       "qk_nope_head_dim", "qk_rope_head_dim", "qk_head_dim",
                       "v_head_dim", "index_head_dim"}
    assert not any(WIDTHS.search(k) for k in published["reduced"])
    assert published["index_topk"] == 2048 and published["index_n_heads"] \
        == 32 and published["hc_mult"] == 4


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 5])
def test_rehearsal_of_the_kind_is_correct(run_cell, cell, seed):
    assert harness.load_binding(cell).__name__ \
        == "benchmark.bindings.mla_dsa_hy4"
    assert harness.load_driver(cell).__name__ \
        == "benchmark.drivers.dsa_reuse_serve"
    r = run_cell(seed=seed)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 4
    assert [c["name"] for c in r["checks"]] == [
        "served_token_gap_mean", "served_token_gap_tail_share",
        "requests_unfinished", "token_count_wrong"]
    m = r["metrics"]
    assert m["compiles_in_window.serve"]["value"] == 0.0
    # 3 of the toy's 6 layers attend layer 1's picks
    assert m["dsa_rows_reused_share"]["value"] == 50.0
    # off the chip no time, rate or share of a peak is printed
    assert not {"serve_step_mfu.hy4", "decode_roofline.hy4",
                "hc_ms_per_pass", "dsa_attend_latent_roofline"} & set(m)
    # the driver put the older driver's table back
    assert dsa_serve.FAMILIES == {"rows": "dsa_rows",
                                  "rows_sparse": "dsa_rows_sparse",
                                  "selected": "dsa_selected"}


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
    assert last["metrics"]["dsa_rows_reused_share"]["value"] == 50.0


def _control(cell, seed, control):
    binding = harness.load_binding(cell)
    _, shapes = binding.model_of(cell.config)
    rng = np.random.RandomState(seed)
    seqs = [(rng.randint(0, 128, size=60).astype(np.int32),
             rng.randint(0, 128, size=60).astype(np.int32))
            for _ in range(3)]
    low = M.served_token_gaps(cell, shapes, seed, seqs, quant=control)
    assert low["tokens"] == 180
    return low


@pytest.mark.parametrize("control", SEPARATING)
@pytest.mark.parametrize("seed", [7, 9])
def test_every_control_fails_the_toy_limits(cell, seed, control):
    """The precision below, and the three wrong selections in FULL
    precision (every causal position; the most recent 16; every layer
    picking for itself): each is refused by both toy limits — the wrong
    selections by 7x and more, fp8 narrowly (its smallest readings, seed
    9, are 1.07x the limits: the toy's bands touch, the chip's limits are
    the cell's own)."""
    low = _control(cell, seed, control)
    limits = cell.config["correct"]["limits"]
    assert low["mean"] > limits["served_token_gap_mean"], low
    assert low["tail_share"] > limits["served_token_gap_tail_share"], low


def test_static_mixes_do_not_separate_at_the_seeded_weights(cell):
    """The control without the mixes' input terms is NOT refused here, and
    this says why: the benchmark's weights give each mix's ``alpha``
    normal(0, 0.02), so the input terms move a mix by about a hundredth
    and no served token; at the toy's seeded weights the reference reads
    0 to 3e-7.  ``test_hy4_parity.py`` shows the program's mixes ARE
    input-dependent, at weights where they matter."""
    low = _control(cell, 9, "static_hc")
    limits = cell.config["correct"]["limits"]
    assert low["mean"] < limits["served_token_gap_mean"]
    assert low["tail_share"] <= limits["served_token_gap_tail_share"]


def test_an_unknown_control_is_refused(cell):
    binding = harness.load_binding(cell)
    with pytest.raises(harness.Refused, match="unknown control"):
        binding.reference_logits(cell.config, {}, np.zeros(256, np.int32),
                                 0, 4, quant="int4")


def test_a_program_without_the_kind_is_refused_before_any_weight(
        run_cell, monkeypatch):
    """As on the parent commit: its ``check_supported`` knows no ``hy4``
    (there the import of ``standalone_hy4`` fails first, the same
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


def test_the_driver_puts_the_table_back_when_the_run_fails(monkeypatch):
    def boom(**kw):
        assert dsa_serve.FAMILIES["rows_reused"] == "dsa_rows_reused"
        raise RuntimeError("boom")
    monkeypatch.setattr(dsa_serve, "run", boom)
    with pytest.raises(RuntimeError):
        dsa_reuse_serve.run()
    assert "rows_reused" not in dsa_serve.FAMILIES


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
    trace, zero counters, a dense configuration, A.X-K1's, Keye's: ``None``
    each time."""
    from benchmark import trace as trace_mod
    empty = trace_mod.Trace(ops={}, modules={}, host=[])
    gpt = harness.load_cell("toy.chat", root)
    axk1 = harness.load_cell("a.x-k1.analysis-batch", REPO)
    keye = harness.load_cell("keye-vl-2.0.longdoc-batch", REPO)
    zero = {"moe": {ph: dict(passes=0.0, assignments=0.0, experts_hit=0.0,
                             load_max=0.0) for ph in ("prefill", "decode")},
            "dsa": {ph: dict(rows=0.0, rows_sparse=0.0, selected=0.0,
                             rows_reused=0.0)
                    for ph in ("prefill", "decode")}}
    some = {"moe": {ph: dict(passes=3.0, assignments=9.0, experts_hit=4.0,
                             load_max=2.0) for ph in ("prefill", "decode")},
            "dsa": {ph: dict(rows=9.0, rows_sparse=3.0, selected=50.0,
                             rows_reused=4.0)
                    for ph in ("prefill", "decode")}}
    for run in (_bare_run(cell), _bare_run(cell, trace=empty),
                _bare_run(cell, facts=zero, trace=empty),
                _bare_run(gpt, facts=some, trace=empty),
                _bare_run(axk1, facts=some, trace=empty),
                _bare_run(keye, facts=some, trace=empty)):
        assert harness.read_metric(metric, run) is None


def test_reused_share_from_the_counters(cell):
    dsa = {"prefill": dict(rows=600.0, rows_sparse=0.0, selected=0.0,
                           rows_reused=300.0),
           "decode": dict(rows=60.0, rows_sparse=0.0, selected=0.0,
                          rows_reused=30.0)}
    run = _bare_run(cell, facts={"dsa": dsa})
    assert counts_hy4.dsa_rows_reused_share(run) == pytest.approx(50.0)
    # the older driver's facts, without the reuse counter: nothing
    older = {ph: {k: v for k, v in c.items() if k != "rows_reused"}
             for ph, c in dsa.items()}
    assert counts_hy4.dsa_rows_reused_share(
        _bare_run(cell, facts={"dsa": older})) is None


def test_counts_of_the_published_sizes(published):
    """``counts_hy4`` at the configuration as run, against the arithmetic
    of the configuration's cut: MLA 165.0M and the gate 100.7M a layer, the
    indexer 9.4M, the mixes 1.2M, an expert 37.7M, layer 0 616.0M, a full
    sparse layer 919.6M, a shared one 910.2M, 4,451.7M held (9.09 GB with
    the float32 head), a cached position of 5 x 1,152 + 2 x 256 B."""
    m = counts_hy4.model(published)
    assert counts_hy4.attention_params(m) == 165_019_648 + 100_663_296
    assert round(counts_hy4.indexer_params(m) / 1e5) == 94
    assert 2 * counts_hy4.mix_params(m) == 1_179_648
    assert counts_hy4.expert_params(m) == 37_748_736
    layers = [counts_hy4.layer_resident_params(m, i, f)
              for i, f in enumerate(m["indexer_full"])]
    held = 16 * counts_hy4.expert_params(m)
    assert [layers[0], layers[1] + held, layers[2] + held] == \
        pytest.approx([616.0e6, 919.6e6, 910.2e6], rel=1e-4)
    assert round(counts_hy4.total_params(m) / 1e5) == 44517
    assert counts_hy4.held_bytes(m) == pytest.approx(9.09e9, rel=1e-3)
    assert 5 * counts_hy4.row_bytes(m) + 2 * counts_hy4.index_key_bytes(m) \
        == 6272
    # the program's own shape function agrees, to the rank-1 leaves
    from benchmark.bindings import mla_dsa_hy4 as binding
    import jax
    _, shapes = binding.model_of(published)
    matrices = sum(x.size for x in jax.tree.leaves(shapes)
                   if len(x.shape) > 1)
    assert matrices == counts_hy4.total_params(m)
    assert sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes)) \
        == pytest.approx(9.09e9, rel=1e-3)
    assert counts_hy4.latent_flops(m) == 2 * 64 * (576 + 512)
    assert counts_hy4.index_flops(m) == 2 * 32 * 128
    assert counts_hy4.pick_flops(m) == 2 * 64 * 512
    assert counts_hy4.cache_bytes_read(10_000, m) == 2 * 256 * 10_000 \
        + 5 * 1152 * 2048


def test_every_count_against_a_brute_force_loop(cell):
    """At toy size: parameter counts against the served tree itself and
    the scored and picked positions against a loop over (query, key)."""
    import jax
    cfg = cell.config
    m = counts_hy4.model(cfg)
    binding = harness.load_binding(cell)
    _, shapes = binding.model_of(cfg)
    p = shapes["params"]

    def size(tree):
        return sum(x.size for x in jax.tree.leaves(tree)
                   if len(x.shape) > 1)
    lp, shared = p["layer_1"], p["layer_2"]
    att = size(lp["attention"])
    assert counts_hy4.attention_params(m) == att
    assert counts_hy4.indexer_params(m) == size(lp["indexer"])
    assert 2 * counts_hy4.mix_params(m) == size(lp["hc_attention"]) \
        + size(lp["hc_ffn"])
    assert counts_hy4.expert_params(m) * m["held"] == size(
        lp["moe"]["experts"])
    assert counts_hy4.layer_resident_params(m, 2, False) == size(shared) \
        - size(shared["moe"]["experts"])
    assert counts_hy4.layer_resident_params(m, 0, True) == size(p["layer_0"])
    assert counts_hy4.total_params(m) == size(p)
    topk = m["topk"]
    for n in (5, topk, topk + 1, 50):
        scored = picked = 0
        for t in range(n):
            context = t + 1
            scored += context if context > topk else 0
            picked += min(context, topk)
        base = n * (2 * (counts_hy4.resident_params(m)
                         + counts_hy4.head_mix_params(m))
                    + counts_hy4.stream_flops(m)) + 2 * m["hidden"] * m[
                        "vocab"]
        assert counts_hy4.prefill_flops(n, m) == base + (
            m["layers"] * counts_hy4.pick_flops(m) * picked
            + m["full"] * counts_hy4.index_flops(m) * scored)
