"""The ``axk1`` kind enters the benchmark as files (ISSUE 34): binding,
reference, counts, nine readers, a configuration and a mix — and Laguna's
driver, unedited.  A toy cell of the kind is rehearsed on the CPU through
the one command; the control in the precision below fails the toy limit; no
file the benchmark had is edited; every count stands against a brute-force
loop at toy size; every new reader finds nothing — ``None``, never 0 — where
its counters or spans are absent."""
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

import axk1_root
from benchmark import counts_mla, harness, weights
from benchmark.drivers import moe_serve as M

REPO = Path(__file__).resolve().parents[2]
CELL = axk1_root.CELL
NEW_METRICS = ["serve_step_mfu.mla", "decode_roofline.mla",
               "prefill_roofline.mla", "mla_decode_ms_per_pass",
               "mla_decode_roofline", "mla_flash_roofline",
               "moe_products_ms_per_pass.mla", "moe_products_roofline.mla",
               "moe_landed_share", "moe_experts_hit_share.mla",
               "moe_load_max_over_mean.mla"]
NEW_FILES = ["bindings/axk1.py", "references/axk1_lm.py", "counts_mla.py",
             "traffic/analysis-batch.json", "configs/a.x-k1-serve.json"] \
    + [f"metrics/{m}.py" for m in NEW_METRICS]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return axk1_root.make(tmp_path_factory.mktemp("axk1"))


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
                       / "a.x-k1-serve.json").read_text())


def _digest(root: Path) -> dict:
    return {str(f.relative_to(root)): hashlib.sha1(f.read_bytes())
            .hexdigest() for f in (root / "benchmark").rglob("*")
            if f.is_file() and "__pycache__" not in f.parts}


def test_the_toy_cell_is_only_new_files(root):
    ours, theirs = _digest(REPO), _digest(root)
    assert all(theirs[k] == v for k, v in ours.items())
    added = set(theirs) - set(ours)
    assert axk1_root.ADDED <= added
    assert not any("axk1" in f or "mla" in f
                   for f in added - axk1_root.ADDED)
    assert all(f"benchmark/{f}" in ours for f in NEW_FILES)


def test_no_file_the_benchmark_had_changed():
    """Against the parent commit: under ``benchmark/`` and
    ``tests/benchmark/`` this PR only ADDS files."""
    out = subprocess.run(
        ["git", "status", "--porcelain", "--", "benchmark",
         "tests/benchmark"], cwd=REPO, capture_output=True, text=True)
    if out.returncode:
        pytest.skip("not a git checkout")
    base = subprocess.run(
        ["git", "diff", "--name-status", "31ea539f37c16b9857f733d390408a10"
         "c160814c", "--", "benchmark", "tests/benchmark"], cwd=REPO,
        capture_output=True, text=True)
    if base.returncode:
        pytest.skip("the parent commit is not in this checkout")
    changed = [line for line in base.stdout.splitlines()
               if line and not line.startswith("A")]
    # the index may hold a new file ("A ", "AM") or not yet ("??")
    changed += [line for line in out.stdout.splitlines()
                if line and line[0] not in "A?"]
    assert not changed, changed


def test_the_index_gains_only_entries():
    """``BENCHMARK.json``: one configuration, one cell, the new per-layer
    entries at the end of their lists, the cell's name appended to lists."""
    index = json.loads((REPO / "BENCHMARK.json").read_text())
    real = "a.x-k1.analysis-batch"
    assert index["configs"][-1]["name"] == "a.x-k1-serve"
    assert index["configs"][-1]["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "max_position_embeddings"]
    assert index["workloads"][-1] == dict(
        index["workloads"][-1], name=real, config="a.x-k1-serve",
        traffic="analysis-batch", chips=1)
    assert [m["name"] for m in index["per_layer"][-len(NEW_METRICS):]] == NEW_METRICS
    for m in index["per_layer"][-len(NEW_METRICS):]:
        assert m["workloads"] == [real]
        assert m["moves"] == "serve_tokens_per_s"
    e2e = {m["name"]: m for m in index["end_to_end"]}
    assert e2e["serve_tokens_per_s"]["workloads"][-1] == real
    with_laguna = [m for m in index["per_layer"][:-len(NEW_METRICS)]
                   if "laguna-xs.2.code-batch" in m["workloads"]]
    kind_blind = [m["name"] for m in with_laguna
                  if m["workloads"][-1] == real]
    assert len(kind_blind) == 14 and not any(
        "moe" in n or n.startswith("window") for n in kind_blind)


def test_the_configuration_keeps_every_published_number(published):
    """Every key of the catalog's ``config`` for A.X-K1, number for number,
    but the four under ``reduced``; ``published`` states those."""
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.is_file():
        pytest.skip("no catalog on this machine")
    row = next(json.loads(line) for line in catalog.read_text().splitlines()
               if json.loads(line)["name"] == "A.X-K1")
    assert published["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items() if published.get(k) != v}
    assert differ == set(published["reduced"])
    assert published["published"] == {k: row["config"][k]
                                      for k in published["reduced"]}
    assert set(published["assumed"]) >= {"a_topk_method", "b_group_score",
                                         "c_rope_pairing", "d_mscale"}
    assert published["n_routed_experts"] == 12 \
        and published["held_experts_first"] == 0


WIDTHS = re.compile(r"(hidden_size|intermediate|_dim$|_rank$|head_dim|"
                    r"_width$|sliding_window|experts_per_tok)")


def test_reduced_names_no_width_of_either_family(published):
    """The contract's rule on ``reduced`` (``test_benchmark_contract.py``'s
    expression), at this configuration's keys: every width key of the
    latent-attention family is refused and the four cuts are not."""
    refused = {k for k in published if WIDTHS.search(k)}
    assert refused == {"hidden_size", "intermediate_size",
                       "moe_intermediate_size", "num_experts_per_tok",
                       "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim",
                       "qk_rope_head_dim", "v_head_dim"}
    assert not any(WIDTHS.search(k) for k in published["reduced"])


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 5, 11])
def test_rehearsal_of_the_kind_is_correct(run_cell, cell, seed):
    assert harness.load_binding(cell).__name__ \
        == "benchmark.bindings.axk1"
    assert harness.load_driver(cell).__name__ \
        == "benchmark.drivers.moe_serve"
    r = run_cell(seed=seed)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 8
    assert [c["name"] for c in r["checks"]] == [
        "served_token_gap_mean", "served_token_gap_tail_share",
        "requests_unfinished", "token_count_wrong"]
    m = r["metrics"]
    assert m["compiles_in_window.serve"]["value"] == 0.0
    # half the experts are held: about half of the assignments land
    assert 20.0 < m["moe_landed_share"]["value"] < 80.0
    # off the chip no time, rate or share of a peak is printed
    assert not {"serve_step_mfu.mla", "decode_roofline.mla",
                "mla_decode_ms_per_pass", "mla_flash_roofline"} & set(m)


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
    assert last["metrics"]["moe_landed_share"]["value"] > 0


@pytest.mark.parametrize("seed", [7, 9, 10])
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
    assert low["tail_share"] > 2 * limits["served_token_gap_tail_share"]


def test_a_program_without_the_kind_is_refused_before_any_weight(
        run_cell, monkeypatch):
    """As on the parent commit: its ``check_supported`` knows no ``axk1``
    (there the import of ``standalone_axk1`` fails first, the same
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
    trace, zero counters, a dense configuration, Laguna's: ``None`` each
    time."""
    from benchmark import trace as trace_mod
    empty = trace_mod.Trace(ops={}, modules={}, host=[])
    gpt = harness.load_cell("toy.chat", root)
    laguna = harness.load_cell("laguna-xs.2.code-batch", REPO)
    zero = {"moe": {ph: dict(passes=0.0, assignments=0.0, experts_hit=0.0,
                             load_max=0.0) for ph in ("prefill", "decode")}}
    some = {"moe": {ph: dict(passes=3.0, assignments=9.0, experts_hit=4.0,
                             load_max=2.0) for ph in ("prefill", "decode")}}
    for run in (_bare_run(cell), _bare_run(cell, trace=empty),
                _bare_run(cell, facts=zero, trace=empty),
                _bare_run(gpt, facts=some, trace=empty),
                _bare_run(laguna, facts=some, trace=empty)):
        assert harness.read_metric(metric, run) is None


def test_landed_share_from_the_counters_and_the_stamps(cell):
    """``moe_landed_share``: the counters' assignments of both phases over 8
    (here 4) a token an expert layer of the tokens the passes carried."""
    reqs = [{"prompt_len": 30, "token_times": [0.1, 0.2, 0.3]},
            {"prompt_len": 50, "token_times": [0.2]},
            {"prompt_len": 70, "token_times": []}]      # never prefilled
    moe = {"prefill": dict(passes=2.0, assignments=400.0, experts_hit=9.0,
                           load_max=60.0),
           "decode": dict(passes=2.0, assignments=6.0, experts_hit=4.0,
                          load_max=2.0)}
    run = _bare_run(cell, facts={"requests": reqs, "moe": moe})
    carried = (30 + 50) + 2
    assert counts_mla.moe_landed_share(run) == pytest.approx(
        100.0 * 406.0 / (4 * 2 * carried))
    assert counts_mla._landed_per_token(run, "prefill") == pytest.approx(
        400.0 / 80)
    assert counts_mla._landed_per_token(run, "decode") == pytest.approx(
        6.0 / 2)
    # of the 8 held in each of the 2 expert layers: 4 hit over 2 decode
    # steps; the busiest held expert's 60 over the mean's 400 / (8 x 2)
    assert counts_mla.moe_experts_hit_share(run) == pytest.approx(
        100.0 * (4.0 / 2) / (8 * 2))
    assert counts_mla.moe_load_max_over_mean(run) == pytest.approx(
        60.0 / (400.0 / (8 * 2)))


def test_counts_of_the_published_sizes(published):
    """``counts_mla`` at the configuration as run, against the arithmetic of
    ISSUE 34 section 2: attention 101.1M a layer, an expert 44.0M, 4,166M
    parameters held (8.33 GB), a cache row of 1,152 B."""
    m = counts_mla.model(published)
    assert counts_mla.expert_layers(m) == 5 and m["held"] == 12
    assert counts_mla.attention_params(m) == (
        7168 * 1536 + 1536 * 12288 + 7168 * 576 + 512 * 16384
        + 8192 * 7168) == 101_122_048
    assert counts_mla.expert_params(m) == 44_040_192
    assert counts_mla.row_bytes(m) == 1152
    assert round(counts_mla.total_params(m) / 1e6) == 4166
    # the program's own shape function agrees, to the norm gains
    from benchmark.bindings import axk1 as binding
    import jax
    _, shapes = binding.model_of(published)
    held = sum(x.size for x in jax.tree.leaves(shapes))
    gains = 7168 * (2 * 6 + 1) + (1536 + 512) * 6
    assert held == counts_mla.total_params(m) + gains
    assert counts_mla.position_flops(m) == 2 * 64 * (576 + 512)
    assert counts_mla.pair_flops(m) == 2 * 64 * (192 + 128)
    # a 3k prompt: 2 FLOPs a resident parameter a token, the causal pairs
    n = 3072
    assert counts_mla.prefill_flops(n, m) == pytest.approx(
        2 * n * counts_mla.resident_params(m)
        + 6 * 2 * 64 * 320 * n * (n + 1) / 2 + 2 * 7168 * 20480)
    assert counts_mla.decode_flops(4000, m, landed=2.5) \
        - counts_mla.decode_flops(4000, m) == 2.5 * 2 * 44_040_192
    assert counts_mla.rows_bytes_attended(4000, m) == 1152 * 4000 * 6


def test_every_count_against_a_brute_force_loop(cell):
    """At toy size, parameter counts against the served tree itself and
    attention FLOPs against a loop over (layer, head, query, key)."""
    import jax
    cfg = cell.config
    m = counts_mla.model(cfg)
    binding = harness.load_binding(cell)
    _, shapes = binding.model_of(cfg)
    p = shapes["params"]

    def size(tree):
        return sum(x.size for x in jax.tree.leaves(tree)
                   if len(x.shape) > 1)
    att = size(p["layer_1"]["attention"])
    assert counts_mla.attention_params(m) == att
    moe = p["layer_1"]["moe"]
    assert counts_mla.expert_params(m) * m["held"] == size(moe["experts"])
    assert counts_mla.layer_resident_params(m, 1) == att + size(
        moe["router"]) + size(moe["shared"])
    assert counts_mla.layer_resident_params(m, 0) == att + size(
        p["layer_0"]["mlp"])
    assert counts_mla.total_params(m) == size(p)
    n, pairs, positions = 13, 0, 0
    for _layer in range(m["layers"]):
        for _head in range(m["heads"]):
            for q in range(n):
                for _k in range(q + 1):
                    pairs += 2 * (m["nope"] + m["rope"]) + 2 * m["v_dim"]
            for _k in range(n):             # one decode query, n rows
                positions += 2 * (m["kv_rank"] + m["rope"]) \
                    + 2 * m["kv_rank"]
    base = 2 * n * counts_mla.resident_params(m) \
        + 2 * m["hidden"] * m["vocab"]
    assert counts_mla.prefill_flops(n, m) == base + pairs
    assert counts_mla.decode_flops(n, m) == 2 * counts_mla.resident_params(
        m) + 2 * m["hidden"] * m["vocab"] + positions


#: heads of device operations' names as a v5e profile gives them for this
#: kind's expert FFN and kernels, and whether the readers' rules find them
FOUND = [
    ("products", "%ragged-dot-none.2 = bf16[512,2048]{1,0:T(8,128)(2,1)} "
                 "custom-call(s32[1]{0:T(128)} %get-tuple-element.2"),
    ("products", "%fusion.9 = f32[64,192]{1,0:T(8,128)S(1)} fusion(f32[64,"
                 "192]{1,0:T(8,128)S(1)} %get-tuple-element.23"),
    ("products", "%sort.2 = (f32[4096,192]{0,1:T(8,128)S(1)}, s32[4096,192]"
                 "{0,1:T(8,128)S(1)}) sort(%broadcast_select_fusion.4"),
    ("latent", "%apex_paged_decode_latent.3 = bf16[64,64,512]{2,1,0:T(8,128)"
               "(2,1)} custom-call(%bitcast.17, %copy-done.1"),
    ("flash", "%apex_flash_fwd.1 = bf16[64,4096,128]{2,1,0} custom-call(%q"),
]
NOT_FOUND = [
    ("products", "%fusion.74 = bf16[4096,7168]{1,0:T(8,128)(2,1)} fusion("),
    ("products", "%fusion.3 = f32[64,12]{1,0:T(8,128)} fusion(s32[64,8]"),
    # a query's and a key's width is the router's too (128 + 64 = 192)
    ("products", "%maximum_bitcast_fusion.2 = bf16[64,4096,192]{2,1,0:T(8,"
                 "128)(2,1)S(1)} fusion(%broadcast_in_dim.117"),
    ("products", "%convolution_bitcast_fusion.5 = bf16[4096,1,64,192]{3,0,2,"
                 "1:T(8,128)(2,1)} fusion(%bitcast.233"),
    ("products", "%reshape.39 = bf16[64,64,192]{2,1,0:T(8,128)(2,1)S(1)} "
                 "reshape(%fusion.1"),
    ("latent", "%apex_paged_decode.2 = bf16[32,48,128]{2,1,0} custom-call("),
    ("flash", "%apex_flash_bwd.1 = bf16[4,128,16]{2,1,0} custom-call(%q)"),
]


@pytest.mark.parametrize("rule,op,found",
                         [(r, op, True) for r, op in FOUND]
                         + [(r, op, False) for r, op in NOT_FOUND])
def test_what_the_trace_readers_find(rule, op, found):
    run = _bare_run(harness.load_cell("a.x-k1.analysis-batch", REPO))
    pattern = {"products": counts_mla.products_pattern(run),
               "latent": counts_mla.LATENT_KERNEL,
               "flash": counts_mla.FLASH_KERNEL}[rule]
    assert bool(re.search(pattern, op[:240])) == found


@pytest.mark.parametrize("phase", ["prefill", "decode"])
def test_the_router_rule_finds_no_attention_op_of_the_same_width(
        phase, monkeypatch):
    """The published widths make a query and a key as wide as the router
    (``qk_nope_head_dim + qk_rope_head_dim`` = 192 = experts).  A step of
    those widths compiled for a described v5e: every operation the width
    rule of ``products_pattern`` finds was made under ``apex_moe_route``,
    and the attention's 192-wide assemblies — which ARE in the program —
    are not found."""
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
    from apex_tpu.transformer.testing import standalone_axk1 as SA
    from apex_tpu.transformer.testing.standalone_laguna import YarnRope

    # the process's backend is the CPU, so the wrappers would interpret
    for mod in ("attention", "layer_norm", "paged_attention"):
        monkeypatch.setattr(importlib.import_module(f"apex_tpu.ops.{mod}"),
                            "interpret_mode", lambda: False)
    run = _bare_run(harness.load_cell("a.x-k1.analysis-batch", REPO))
    experts = counts_mla.model(run.cell.config)["experts"]
    layers, slots, ps, pages, mpps, prompt = 2, 8, 256, 16, 2, 256
    cfg = SA.AXK1Config(
        vocab_size=256, hidden_size=256, num_layers=layers, num_heads=8,
        q_lora_rank=128, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, ffn_hidden_size=256,
        moe_ffn_hidden_size=128, shared_ffn_hidden_size=128,
        num_experts=experts, held=(0, 12), experts_per_token=8, n_group=8,
        topk_group=4, max_seq_length=ps * mpps,
        rope=YarnRope(theta=10000.0, rotary_dim=64, factor=32.0,
                      original_max_position=4096, beta_fast=32.0,
                      beta_slow=1.0, attention_factor=1.0),
        params_dtype=jnp.bfloat16)
    assert cfg.qk_nope_head_dim + cfg.qk_rope_head_dim == experts

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def i32(*shape):
        return spec(shape, jnp.int32)

    params = {"params": jax.tree.map(
        lambda shape: spec(shape, jnp.bfloat16), SA.axk1_param_shapes(cfg),
        is_leaf=lambda x: isinstance(x, tuple))}
    cache = jax.tree.map(lambda x: spec(x.shape, x.dtype), jax.eval_shape(
        lambda: kv_cache.init_paged_cache(
            pages, layers, 0, ps, 0, slots=slots, max_pages_per_slot=mpps,
            latent=cfg.latent_dim)))
    key = spec((2,), jnp.uint32)
    if phase == "prefill":
        step = make_prefill_fn("axk1", cfg, SamplingConfig(), paged=True)
        args = (cache, params, i32(prompt), i32(), i32(), i32(mpps), i32(),
                key, i32())
    else:
        step = make_decode_fn("axk1", cfg, SamplingConfig())
        args = (cache, params, i32(slots), spec((slots,), bool), key, i32())
    hlo = jax.jit(step, donate_argnums=(0,)).lower(*args).compile().as_text()

    rule = re.compile(counts_mla.products_pattern(run))
    wide = re.compile(r"^%%\S+ = \(?\w+\[(\d+,)*%d\]" % experts)
    # a profile's events are the operations of the entry computation and
    # of the loops' bodies: not those inside a fusion
    fused = set(re.findall(r"calls=%([\w.\-]+)", hlo))
    scope, routed, attention = None, 0, 0
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if head:
            scope = head.group(1)
            continue
        op = re.sub(r"^ROOT ", "", line.strip())[:240]
        if scope in fused or not wide.search(op):
            continue
        made = re.search(r'op_name="([^"]*)"', line)
        if rule.search(op):
            if made:
                assert "/apex_moe_route/" in made.group(1), line[:300]
                routed += 1
            else:           # takes no time on the device: no event
                assert re.search(r" (bitcast|get-tuple-element)\(", op), op
        else:
            assert made and "apex_moe" not in made.group(1), line[:300]
            attention += 1
    assert routed and attention, (routed, attention)
