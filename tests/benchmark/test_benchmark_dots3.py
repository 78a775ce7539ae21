"""The ``dots3`` kind enters the benchmark as files: binding, reference,
driver, counts, eight readers, a calibration script, a configuration and a
mix.  A toy cell of the kind is rehearsed on the CPU through the one
command; the control in the precision below, the two wrong selections and
window layers that attend their whole context fail the toy limits; the toy
cell is new files only and ``BENCHMARK.json`` holds the kind by name; every
count stands against the arithmetic of the cut and a brute-force loop at toy
size; every new reader finds nothing — ``None``, never 0 — where its
counters or spans are absent."""
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

import dots3_root
from benchmark import counts_dots3, harness, weights
from benchmark.drivers import dsa_serve, dsa_swa_serve
from benchmark.drivers import moe_serve as M

REPO = Path(__file__).resolve().parents[2]
CELL = dots3_root.CELL
REAL = "dots3-note-prev.notes-batch"
NEW_METRICS = ["serve_step_mfu.dots3", "decode_roofline.dots3",
               "prefill_roofline.dots3", "swa_latent_ms_per_pass",
               "swa_latent_decode_roofline", "swa_flash_roofline",
               "dsa_attend_latent_roofline.dots3",
               "dsa_index_roofline.dots3"]
NEW_FILES = ["bindings/mla_swa_dots3.py", "references/dots3_lm.py",
             "counts_dots3.py", "drivers/dsa_swa_serve.py",
             "calibrate_dots3.py", "traffic/notes-batch.json",
             "configs/dots3-note-prev-serve.json"] \
    + [f"metrics/{m}.py" for m in NEW_METRICS]
#: what every cell of a serving kind reads whatever its model: listed for
#: this cell too (``dsa_attend_latent_ms_per_pass`` and the ``.mla`` /
#: ``.hy4`` families are keyed on another kind's configuration keys)
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
SEPARATING = ["fp8", "attend_all", "recent_topk", "swa_all"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return dots3_root.make(tmp_path_factory.mktemp("dots3"))


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
                       / "dots3-note-prev-serve.json").read_text())


def _digest(root: Path) -> dict:
    return {str(f.relative_to(root)): hashlib.sha1(f.read_bytes())
            .hexdigest() for f in (root / "benchmark").rglob("*")
            if f.is_file() and "__pycache__" not in f.parts}


def test_the_toy_cell_is_only_new_files(root):
    ours, theirs = _digest(REPO), _digest(root)
    assert all(theirs[k] == v for k, v in ours.items())
    added = set(theirs) - set(ours)
    assert dots3_root.ADDED <= added
    assert not any("dots3" in f for f in added - dots3_root.ADDED)
    assert all(f"benchmark/{f}" in ours for f in NEW_FILES)


def test_the_index_holds_the_kind_by_name():
    """``BENCHMARK.json``, asked by NAME: the configuration, the cell, the
    eight readers with what each has to say, the cell's name in the lists
    of the kind-blind metrics and of ``serve_tokens_per_s``, and no reader
    of another kind's widths."""
    index = json.loads((REPO / "BENCHMARK.json").read_text())
    configs = {e["name"]: e for e in index["configs"]}
    cells = {e["name"]: e for e in index["workloads"]}
    metrics = {m["name"]: m for m in index["per_layer"]}
    config = configs["dots3-note-prev-serve"]
    assert config["reduced"] == [
        "num_hidden_layers", "layer_types", "n_routed_experts",
        "vocab_size", "max_position_embeddings"]
    assert (REPO / config["file"]).is_file()
    entry = cells[REAL]
    assert entry == dict(entry, config="dots3-note-prev-serve",
                         traffic="notes-batch", chips=1)
    assert len(entry["why"]) <= 200 and len(config["why"]) <= 200
    assert len(config["source"]) <= 200
    layers = {m["layer"] for name, m in metrics.items()
              if name not in NEW_METRICS}
    for name in NEW_METRICS:
        m = metrics[name]
        assert m["workloads"] == [REAL]
        assert m["moves"] == "serve_tokens_per_s" and m["layer"] in layers
        assert (m["unit"] == "%") == bool(re.search("mfu|roofline", name))
        assert (REPO / "benchmark" / "metrics" / f"{name}.py").is_file()
    e2e = {m["name"]: m for m in index["end_to_end"]}
    assert REAL in e2e["serve_tokens_per_s"]["workloads"]
    mine = {name for name, m in metrics.items()
            if name not in NEW_METRICS and REAL in m["workloads"]}
    assert mine == KIND_BLIND
    assert (REPO / "BENCHMARK.json").stat().st_size < 64 * 1024


def test_the_configuration_states_its_cuts(published):
    """Every key under ``reduced`` has its published value under
    ``published``, the layer types are the published list's first five,
    and the cut keeps to the floors: a whole period after the dense layer,
    32 experts held, an eighth of the vocabulary."""
    assert set(published["published"]) == set(published["reduced"])
    assert published["published"]["num_hidden_layers"] == 46
    assert published["published"]["n_routed_experts"] == 256
    assert published["n_routed_experts"] == 32
    assert published["published"]["vocab_size"] == 8 * published[
        "vocab_size"] == 8 * published["token_ids"]
    assert published["layer_types"] == published["published"][
        "layer_types"][:5]
    assert published["layer_types"] == [
        "full_attention", "full_attention", "sliding_attention",
        "sliding_attention", "sliding_attention"]
    types = published["published"]["layer_types"]
    assert types.count("full_attention") == 13
    assert types.count("sliding_attention") == 33
    assert set(published["assumed"]) >= {
        "a_lora_rescale", "b_window", "c_indexer", "d_gate", "e_scale",
        "f_left_out"}
    assert "deployment" in published and published["binding"] == \
        "mla_swa_dots3"
    assert "16384" in published["compiled"]
    mix = json.loads((REPO / "benchmark" / "traffic"
                      / "notes-batch.json").read_text())
    assert mix["driver"] == "dsa_swa_serve"
    # a prompt of the longest kind and its answer fit a slot's table
    assert published["max_position_embeddings"] >= (
        mix["prompt_tokens"]["max"] + mix["new_tokens"]["max"])
    assert published["max_position_embeddings"] % mix["page_size"] == 0
    # every prompt is past both the window and the picks
    assert mix["prompt_tokens"]["min"] >= 2 * published["index_topk"] \
        > published["sliding_window_size"]
    assert mix["pool_pages"] == 16 * 136 + 128


WIDTHS = re.compile(r"(hidden_size|intermediate|_dim$|_rank$|head_dim|"
                    r"_width$|sliding_window|experts_per_tok)")


def test_reduced_names_no_width_of_this_family(published):
    refused = {k for k in published if WIDTHS.search(k)}
    assert refused >= {"hidden_size", "intermediate_size",
                       "moe_intermediate_size", "num_experts_per_tok",
                       "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                       "qk_rope_head_dim", "v_head_dim", "index_head_dim",
                       "swa_q_lora_rank", "swa_kv_lora_rank",
                       "swa_qk_nope_head_dim", "swa_qk_rope_head_dim",
                       "swa_v_head_dim", "sliding_window_size"}
    assert not any(WIDTHS.search(k) for k in published["reduced"])
    assert (published["index_topk"], published["index_n_heads"],
            published["sliding_window_size"], published["swa_kv_lora_rank"],
            published["num_attention_heads"],
            published["swa_num_attention_heads"]) == (2048, 64, 513, 1024,
                                                      128, 64)


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 5])
def test_rehearsal_of_the_kind_is_correct(run_cell, cell, seed,
                                          monkeypatch):
    """The toy cell through the one command's code, and the window layers'
    counter in the driver's facts: per row ``min(context, window)``, three
    window layers, over every prompt and generated token stamped."""
    assert harness.load_binding(cell).__name__ \
        == "benchmark.bindings.mla_swa_dots3"
    assert harness.load_driver(cell).__name__ \
        == "benchmark.drivers.dsa_swa_serve"
    seen, plain = {}, dsa_swa_serve.run

    def run(**kw):
        seen.update(plain(**kw))
        return seen
    monkeypatch.setattr(dsa_swa_serve, "run", run)
    r = run_cell(seed=seed)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 4
    dsa, window = seen["facts"]["dsa"], cell.config["sliding_window_size"]
    done = [q for q in seen["facts"]["requests"] if q["token_times"]]
    assert dsa["prefill"]["swa_attended"] == 3 * sum(
        sum(min(t + 1, window) for t in range(q["prompt_len"]))
        for q in done)
    assert dsa["decode"]["swa_attended"] == 3 * sum(
        min(q["prompt_len"] + j + 1, window) for q in done
        for j in range(len(q["token_times"]) - 1))
    assert [c["name"] for c in r["checks"]] == [
        "served_token_gap_mean", "served_token_gap_tail_share",
        "requests_unfinished", "token_count_wrong"]
    m = r["metrics"]
    assert m["compiles_in_window.serve"]["value"] == 0.0
    # off the chip no time, rate or share of a peak is printed
    assert not set(NEW_METRICS) & set(m)
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
    """The precision below, and the three wrong attentions in FULL
    precision (every causal position and the most recent 16 in the full
    layers; every causal position in the window layers): each is refused
    — the wrong attentions by both toy limits and by 12x and more on the
    mean, fp8 by the mean (its smallest reading, seed 7, is 1.35x the
    limit; its tail share lies in the sound band at the toy)."""
    low = _control(cell, seed, control)
    limits = cell.config["correct"]["limits"]
    assert low["mean"] > limits["served_token_gap_mean"], low
    if control != "fp8":
        assert low["tail_share"] > limits["served_token_gap_tail_share"], \
            low


def test_the_unbiased_router_does_not_separate_at_the_seeded_weights(cell):
    """The control whose router chooses without its selection bias is NOT
    refused here, and this says why: it chooses other experts for most
    tokens, but at the benchmark's weights (normal(0, 0.02)) the held
    experts' part moves the residual by too little to move a served token;
    the reference reads inside the sound band.  ``test_dots3_parity.py``
    shows the program's bias DOES choose, at weights where it matters."""
    low = _control(cell, 9, "no_bias")
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
    """As on the parent commit: its ``check_supported`` knows no ``dots3``
    (there the import of ``standalone_dots3`` fails first, the same
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
        assert dsa_serve.FAMILIES["swa_attended"] == "swa_attended"
        raise RuntimeError("boom")
    monkeypatch.setattr(dsa_serve, "run", boom)
    with pytest.raises(RuntimeError):
        dsa_swa_serve.run()
    assert "swa_attended" not in dsa_serve.FAMILIES


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
    trace, zero counters, a dense configuration, A.X-K1's, Hy4's: ``None``
    each time."""
    from benchmark import trace as trace_mod
    empty = trace_mod.Trace(ops={}, modules={}, host=[])
    gpt = harness.load_cell("toy.chat", root)
    axk1 = harness.load_cell("a.x-k1.analysis-batch", REPO)
    hy4 = harness.load_cell("hy4-preview.longctx-batch", REPO)
    zero = {"moe": {ph: dict(passes=0.0, assignments=0.0, experts_hit=0.0,
                             load_max=0.0) for ph in ("prefill", "decode")},
            "dsa": {ph: dict(rows=0.0, rows_sparse=0.0, selected=0.0,
                             swa_attended=0.0)
                    for ph in ("prefill", "decode")}}
    some = {"moe": {ph: dict(passes=3.0, assignments=9.0, experts_hit=4.0,
                             load_max=2.0) for ph in ("prefill", "decode")},
            "dsa": {ph: dict(rows=9.0, rows_sparse=3.0, selected=50.0,
                             swa_attended=40.0)
                    for ph in ("prefill", "decode")}}
    for run in (_bare_run(cell), _bare_run(cell, trace=empty),
                _bare_run(cell, facts=zero, trace=empty),
                _bare_run(gpt, facts=some, trace=empty),
                _bare_run(axk1, facts=some, trace=empty),
                _bare_run(hy4, facts=some, trace=empty)):
        assert harness.read_metric(metric, run) is None


def test_counts_of_the_published_sizes(published):
    """``counts_dots3`` at the configuration as run, against the arithmetic
    of the cut: MLA of a full layer 134.7M and its indexer 9.4M, of a window
    layer 90.8M (gates in both), an expert 23.6M, layer 0 356.4M, a full MoE
    layer 923.9M, a window one 870.7M, 4,087.1M in matrices held (8.17 GB
    with the rank-1 leaves); a pooled
    position of 2 x (1,152 + 256) B, a ring row of 2,176 B."""
    m = counts_dots3.model(published)
    full, swa = m["full_geo"], m["swa_geo"]
    # matrices only: the q and kv latents' norm gains (1,536 and 2,048)
    # are rank-1 leaves
    assert counts_dots3.attention_params(m, full) == 134_676_480
    assert counts_dots3.attention_params(m, swa) == 90_832_896
    assert round(counts_dots3.indexer_params(m) / 1e4) == 937
    assert counts_dots3.expert_params(m) == 23_592_960
    layers = [counts_dots3.layer_resident_params(m, i) for i in range(5)]
    held = 32 * counts_dots3.expert_params(m)
    assert [layers[0], layers[1] + held, layers[2] + held] == \
        pytest.approx([356.4e6, 923.9e6, 870.7e6], rel=2e-4)
    assert round(counts_dots3.total_params(m) / 1e5) == 40871
    assert counts_dots3.held_bytes(m) == pytest.approx(8.17e9, rel=1e-3)
    assert 2 * (counts_dots3.row_bytes(full)
                + counts_dots3.index_key_bytes(m)) == 2816
    assert counts_dots3.row_bytes(swa) == 2176
    # the program's own shape function agrees, to the rank-1 leaves
    from benchmark.bindings import mla_swa_dots3 as binding
    import jax
    _, shapes = binding.model_of(published)
    matrices = sum(x.size for x in jax.tree.leaves(shapes)
                   if len(x.shape) > 1)
    assert matrices == counts_dots3.total_params(m)
    assert sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes)) \
        == pytest.approx(8.17e9, rel=1e-3)
    assert counts_dots3.latent_flops(full) == 2 * 128 * (576 + 512)
    assert counts_dots3.latent_flops(swa) == 2 * 64 * (1088 + 1024)
    assert counts_dots3.pair_flops(swa) == 2 * 64 * (256 + 128)
    assert counts_dots3.index_flops(m) == 2 * 64 * 128
    assert counts_dots3.cache_bytes_read(10_000, m) == 2 * (
        256 * 10_000 + 1152 * 2048) + 3 * 2176 * 513


def test_every_count_against_a_brute_force_loop(cell):
    """At toy size: parameter counts against the served tree itself and
    the scored, picked and windowed positions against a loop over (query,
    key)."""
    import jax
    cfg = cell.config
    m = counts_dots3.model(cfg)
    binding = harness.load_binding(cell)
    _, shapes = binding.model_of(cfg)
    p = shapes["params"]

    def size(tree):
        return sum(x.size for x in jax.tree.leaves(tree)
                   if len(x.shape) > 1)
    full, swa = p["layer_1"], p["layer_2"]
    assert counts_dots3.attention_params(m, m["full_geo"]) == size(
        full["attention"])
    assert counts_dots3.attention_params(m, m["swa_geo"]) == size(
        swa["attention"])
    assert counts_dots3.indexer_params(m) == size(full["indexer"])
    assert counts_dots3.expert_params(m) * m["held"] == size(
        full["moe"]["experts"])
    assert counts_dots3.layer_resident_params(m, 2) == size(swa) \
        - size(swa["moe"]["experts"])
    assert counts_dots3.layer_resident_params(m, 0) == size(p["layer_0"])
    assert counts_dots3.total_params(m) == size(p)
    topk, window = m["topk"], m["window"]
    for n in (5, window, topk, topk + 1, 50):
        scored = picked = windowed = 0
        for t in range(n):
            context = t + 1
            scored += context if context > topk else 0
            picked += min(context, topk)
            windowed += sum(1 for s in range(context) if s > t - window)
        assert counts_dots3.prompt_in_window(n, m) == windowed
        base = n * 2 * counts_dots3.resident_params(m) \
            + 2 * m["hidden"] * m["vocab"]
        assert counts_dots3.prefill_flops(n, m) == base + (
            m["full"] * counts_dots3.pair_flops(m["full_geo"]) * picked
            + m["full"] * counts_dots3.index_flops(m) * scored
            + m["swa"] * counts_dots3.pair_flops(m["swa_geo"]) * windowed)
