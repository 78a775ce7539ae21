"""A serving configuration of a second model kind (the program's ``llama``)
enters as files: a binding the configuration names, its plain reference, a
driver file a mix names.  A binding the program does not serve stops the run
before a weight is made; a name with no file is refused, with no default."""
import dataclasses

import numpy as np
import pytest

from benchmark import harness, weights
from benchmark.drivers import serve as D


@pytest.fixture(scope="module")
def llama(toy_root):
    cell = harness.load_cell("toy.llama", toy_root)
    return cell, harness.load_binding(cell)


def test_rehearsal_of_the_second_kind_is_correct(run_toy, llama):
    cell, binding = llama
    assert binding.__name__ == "benchmark.bindings.llama"
    assert harness.load_driver(cell).__name__ \
        == "benchmark.drivers.toy_serve"
    r = run_toy("toy.llama", seconds=1.5, trace=1)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 8
    assert [c["name"] for c in r["checks"]] == [
        "served_token_gap", "requests_unfinished", "token_count_wrong"]
    assert r["metrics"]["compiles_in_window.serve"]["value"] == 0.0
    assert r["metrics"]["toy_passes"]["value"] > 0


@pytest.mark.parametrize("workload", ["toy.llama", "toy.chat"])
def test_a_kind_the_program_does_not_serve_stops_before_any_weight(
        run_toy, monkeypatch, workload):
    """As in a parent commit whose program lacks the kind."""
    from apex_tpu.inference import models

    def not_served(kind, cfg):
        raise ValueError(f"unknown generative model kind {kind!r}")

    def no_weights(shapes, seed):
        raise AssertionError("weights were made before support was asked")
    monkeypatch.setattr(models, "check_supported", not_served)
    monkeypatch.setattr(weights, "make", no_weights)
    with pytest.raises(harness.Refused, match="does not serve"):
        run_toy(workload)


def test_a_configuration_names_its_binding_and_nothing_stands_in(llama):
    cell, _ = llama
    nameless = dataclasses.replace(
        cell, config={k: v for k, v in cell.config.items()
                      if k != "binding"})
    with pytest.raises(harness.Refused, match="names no \"binding\""):
        D.build(nameless, 1)
    absent = dataclasses.replace(cell, config=dict(cell.config,
                                                   binding="no_such"))
    with pytest.raises(harness.Refused, match="gpt, llama"):
        D.build(absent, 1)


def test_a_mix_that_names_no_driver_file_is_refused_with_those_there(llama):
    cell, _ = llama
    lost = dataclasses.replace(cell, mix=dict(cell.mix, driver="no_such"))
    with pytest.raises(harness.Refused, match="serve, toy_serve, train"):
        harness.load_driver(lost)


@pytest.mark.parametrize("seed", [7, 8, 9])
def test_control_in_the_precision_below_is_not_correct(llama, seed):
    cell, binding = llama
    _, shapes = binding.model_of(cell.config)
    rng = np.random.RandomState(seed)
    seqs = [(rng.randint(0, 128, size=40).astype(np.int32),
             rng.randint(0, 128, size=60).astype(np.int32))
            for _ in range(3)]
    limit = cell.config["correct"]["limits"]["served_token_gap"]
    low = D.served_token_gap(cell, shapes, seed, seqs, quant="fp8")
    assert low["tokens"] == 180 and low["widest"] > limit


def test_the_toy_reference_follows_the_program_where_positions_matter(
        llama, monkeypatch):
    """At the toy's weights attention is all but uniform and rotary
    positions move no token, so the reference is held to the program's
    float32 forward at ten times the weights, where they do."""
    import jax
    import jax.numpy as jnp
    from apex_tpu.inference import models
    from benchmark.references import llama_lm

    cell, binding = llama
    cfg = cell.config
    lcfg, shapes = binding.model_of(cfg)
    params = jax.tree.map(
        lambda x: (x.astype(jnp.float32) * (10.0 if x.ndim == 2 else 1.0)),
        weights.make(shapes, 11))
    tokens = np.random.RandomState(11).randint(0, 128, size=48)
    got = models.prefill_forward(
        "llama", dataclasses.replace(lcfg, params_dtype=jnp.float32),
        params, jnp.asarray(tokens[None], jnp.int32))[0][:, 0]
    w = binding.reference_weights(cfg, params)
    want = binding.reference_logits(cfg, w, tokens, 0, 48)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) < 2e-3 * scale
    monkeypatch.setattr(llama_lm, "rope", lambda x, theta: x)
    llama_lm.logits.clear_cache()
    flat = binding.reference_logits(cfg, w, tokens, 0, 48)
    llama_lm.logits.clear_cache()
    assert float(jnp.max(jnp.abs(got - flat))) > 0.05 * scale
