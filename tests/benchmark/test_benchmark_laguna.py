"""The ``laguna`` kind enters the benchmark as files (ISSUE 30): binding,
reference, driver, counts, eight readers, a configuration and a mix.  A toy
cell of the kind is rehearsed on the CPU through the one command; the control
in the precision below fails the toy limit; no file the benchmark had is
edited; every new reader finds nothing — ``None``, never 0 — where its
counters or spans are absent."""
import argparse
import hashlib
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

import laguna_root
from benchmark import counts_moe, harness, weights
from benchmark.drivers import moe_serve as M
from benchmark.drivers import serve as D

REPO = Path(__file__).resolve().parents[2]
CELL = laguna_root.CELL
NEW_METRICS = ["serve_step_mfu.moe", "decode_roofline.moe",
               "prefill_roofline.moe", "moe_products_ms_per_pass",
               "moe_products_roofline", "moe_experts_hit_share",
               "moe_load_max_over_mean", "window_pages_live_peak"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return laguna_root.make(tmp_path_factory.mktemp("laguna"))


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


def _digest(root: Path) -> dict:
    return {str(f.relative_to(root)): hashlib.sha1(f.read_bytes())
            .hexdigest() for f in (root / "benchmark").rglob("*")
            if f.is_file() and "__pycache__" not in f.parts}


def test_the_toy_cell_is_only_new_files(root):
    ours, theirs = _digest(REPO), _digest(root)
    assert all(theirs[k] == v for k, v in ours.items())
    added = set(theirs) - set(ours)
    assert laguna_root.ADDED <= added
    assert not any("laguna" in f or "moe" in f
                   for f in added - laguna_root.ADDED)
    # the kind's own files are the benchmark's, found by name
    for f in ("bindings/moe_laguna.py", "references/laguna_lm.py",
              "drivers/moe_serve.py", "counts_moe.py",
              "traffic/code-batch.json", "configs/laguna-xs.2-serve.json"):
        assert f"benchmark/{f}" in ours
    assert all(f"benchmark/metrics/{m}.py" in ours for m in NEW_METRICS)


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 5, 11])
def test_rehearsal_of_the_kind_is_correct(run_cell, cell, seed):
    assert harness.load_binding(cell).__name__ \
        == "benchmark.bindings.moe_laguna"
    assert harness.load_driver(cell).__name__ \
        == "benchmark.drivers.moe_serve"
    r = run_cell(seed=seed)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 8
    assert [c["name"] for c in r["checks"]] == [
        "served_token_gap_mean", "served_token_gap_tail_share",
        "requests_unfinished", "token_count_wrong"]
    m = r["metrics"]
    assert m["compiles_in_window.serve"]["value"] == 0.0
    # what the program counted on the device and handed over with tokens
    assert 0 < m["moe_experts_hit_share"]["value"] <= 100.0
    assert m["moe_load_max_over_mean"]["value"] >= 1.0
    ring_pages = 16 // 8 + 1
    assert 0 < m["window_pages_live_peak"]["value"] <= 4 * ring_pages
    # off the chip no time, rate or share of a peak is printed
    assert not {"serve_step_mfu.moe", "decode_roofline.moe",
                "moe_products_ms_per_pass"} & set(m)


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
    assert last["metrics"]["window_pages_live_peak"]["value"] > 0


@pytest.mark.parametrize("seed", [7, 8, 9])
def test_control_in_the_precision_below_fails_the_toy_limit(cell, seed):
    binding = harness.load_binding(cell)
    _, shapes = binding.model_of(cell.config)
    rng = np.random.RandomState(seed)
    seqs = [(rng.randint(0, 128, size=40).astype(np.int32),
             rng.randint(0, 128, size=60).astype(np.int32))
            for _ in range(3)]
    limits = cell.config["correct"]["limits"]
    low = M.served_token_gaps(cell, shapes, seed, seqs, quant="fp8")
    assert low["tokens"] == 180
    assert low["mean"] > 2 * limits["served_token_gap_mean"], low
    # the share of tokens beyond correct.tail_gap is the gaps' own
    assert low["gaps"].shape == (180,)
    assert low["tail_share"] == np.mean(
        low["gaps"] > cell.config["correct"]["tail_gap"])
    assert low["tail_share"] > 2 * limits["served_token_gap_tail_share"]
    # the widest gap is the one drivers/serve.py reads, number for number
    assert low["widest"] == D.served_token_gap(cell, shapes, seed, seqs,
                                               quant="fp8")["widest"]


def test_a_program_without_the_kind_is_refused_before_any_weight(
        run_cell, monkeypatch):
    """As on the parent commit: its ``check_supported`` knows no
    ``laguna``."""
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
    """No counters in ``facts`` (a program without the telemetry families),
    no trace, an empty trace, a dense configuration: ``None`` each time."""
    from benchmark import trace as trace_mod
    empty = trace_mod.Trace(ops={}, modules={}, host=[])
    gpt = harness.load_cell("toy.chat", root)
    zero = {"moe": {ph: dict(passes=0.0, assignments=0.0, experts_hit=0.0,
                             load_max=0.0) for ph in ("prefill", "decode")}}
    for run in (_bare_run(cell), _bare_run(cell, trace=empty),
                _bare_run(cell, facts=zero, trace=empty),
                _bare_run(gpt, trace=empty)):
        assert harness.read_metric(metric, run) is None


def test_counts_of_the_published_sizes():
    """``counts_moe`` at the configuration as run, against the arithmetic
    of ISSUE 30: 338M parameters active a token, a routed expert 6.29 MB, a
    5.2k-token prefill 4.4 TFLOP, window layers capped at 512 keys."""
    cfg = json.loads((REPO / "benchmark" / "configs"
                      / "laguna-xs.2-serve.json").read_text())
    m = counts_moe.model(cfg)
    assert counts_moe.expert_layers(m) == 4
    assert counts_moe.attention_params(m, 0) == 2 * 2048 * 48 * 128 \
        + 2 * 2048 * 8 * 128 + 2048 * 48
    assert counts_moe.expert_params(m) * 2 == 6_291_456
    assert round(counts_moe.active_params(m) / 1e6, 1) == 338.2
    assert counts_moe.keys_seen(512, 512) == 512 * 513 // 2
    assert counts_moe.keys_seen(1000, 512) == 512 * 513 // 2 + 488 * 512
    assert 4.3e12 < counts_moe.prefill_flops(5200, m) < 4.6e12
    # a token at 8k reads 8k keys in 2 full layers, 512 in 3 window ones
    assert counts_moe.kv_bytes_attended(8192, m) \
        == 2 * 8 * 128 * 2 * (2 * 8192 + 3 * 512)
    full = counts_moe.decode_flops(8192, m) - counts_moe.decode_flops(512, m)
    assert full == 4 * 128 * 2 * 48 * (8192 - 512)


#: heads of device operations' names as the v5e's profile gives them for the
#: expert FFN of a decode step at the published sizes (32 slots, 256 experts
#: of 512, 8 a token: my chip run, PR 30), and whether the readers' rule
#: finds them
FOUND = [
    "%fusion.9 = f32[32,256]{1,0:T(8,128)S(1)} fusion(f32[32,256]{1,0:T(8,128)"
    "S(1)} %get-tuple-element.23, f32[32]{0:T(128)S(1)} %fusion.10",
    "%iota = s32[32,256]{1,0:T(8,128)S(1)} iota(), iota_dimension=1",
    "%sort = (f32[32,256]{1,0:T(8,128)}, s32[32,256]{1,0:T(8,128)S(1)}) "
    "sort(f32[32,256]{1,0:T(8,128)S(1)} %fusion.9, s32[32,256]",
    "%ragged-dot-metadata = (s32[257]{0:T(512)S(1)}, s32[256]{0:T(256)S(1)}, "
    "s32[256]{0:T(256)S(1)}, s32[1]{0:T(128)}) custom-call(",
    "%ragged-dot-none.2 = bf16[256,512]{1,0:T(8,128)(2,1)S(1)} custom-call("
    "s32[1]{0:T(128)} %get-tuple-element.2, s32[257]{0:T(512)S(1)}",
    "%ragged-dot-none = bf16[32768,2048]{1,0:T(8,128)(2,1)} custom-call("
    "s32[1]{0:T(128)} %get-tuple-element.2, s32[257]{0:T(512)S(1)}",
    "%multiply_multiply_fusion = bf16[256,512]{1,0:T(8,128)(2,1)S(1)} fusion("
    "bf16[256,512]{1,0:T(8,128)(2,1)S(1)} %ragged-dot-none.1, bf16[256,512]",
]
NOT_FOUND = [
    # of the expert FFN, and without a mark of their own: the gather of the
    # sorted rows, the un-sort, the combine, the shared expert
    "%fusion = bf16[32768,2048]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[4096,2048]"
    "{1,0:T(8,128)(2,1)S(1)} %copy-done.1, s32[131072]{0:T(1024)S(1)} %pad",
    "%sort.8 = (s32[32768]{0:T(1024)}, s32[32768]{0:T(1024)S(1)}) sort("
    "s32[32768]{0:T(1024)S(1)} %reshape.4, s32[32768]{0:T(1024)S(1)} %iota.2)",
    "%fusion.74 = bf16[4096,2048]{1,0:T(8,128)(2,1)} fusion(bf16[32768,2048]",
    "%fusion.260 = bf16[4096,512]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[4096,2048]",
    # the router's product where XLA fuses it with the row maximum into a
    # tuple whose FIRST element is not experts wide (1.2 us a decode step)
    "%fusion.21 = (f32[32]{0:T(128)S(1)}, f32[32,256]{1,0:T(8,128)S(1)}) "
    "fusion(bf16[32,2048]{1,0:T(8,128)(2,1)} %x.1, bf16[256,2048]",
    # and of the rest of the step
    "%fusion.9 = bf16[32,64]{1,0:T(8,128)(2,1)} fusion(%p0), kind=kLoop",
    "%apex_flash_fwd.1 = bf16[4,128,16]{2,1,0} custom-call(%q, %k)",
    "%apex_paged_decode.2 = bf16[32,48,128]{2,1,0} custom-call(%q, %pool)",
    "%slice-done.29 = bf16[256,2048]{1,0:T(8,128)(2,1)S(1)} slice-done(",
]


@pytest.fixture(scope="module")
def published_run():
    return _bare_run(harness.load_cell("laguna-xs.2.code-batch", REPO))


@pytest.mark.parametrize("op,found", [(op, True) for op in FOUND]
                         + [(op, False) for op in NOT_FOUND])
def test_what_the_products_readers_find(published_run, op, found):
    """The rule the readers state: XLA's ``%ragged-dot-*`` among the first
    characters of an operation's name, or the expert count as the last
    dimension of its result type."""
    import re
    rx = re.compile(counts_moe.products_pattern(published_run))
    assert bool(rx.search(op[:240])) == found
