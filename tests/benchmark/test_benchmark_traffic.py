"""Traffic and weights come from ``--seed`` and from nothing else."""
import json

import numpy as np
import pytest

from benchmark import harness, traffic, weights

BIG = 2 ** 31 + 12345          # the driver's seeds pass 32 signed bits


def _mix(name):
    with open(harness.HERE / "traffic" / f"{name}.json") as f:
        return json.load(f)


@pytest.mark.parametrize("mix", ["chat", "docs-batch"])
@pytest.mark.parametrize("seed", [0, 7, BIG])
def test_serve_requests_reproduce_from_the_seed(mix, seed):
    a = traffic.serve_requests(_mix(mix), seed, 10.0, 50257)
    b = traffic.serve_requests(_mix(mix), seed, 10.0, 50257)
    assert [(r.due_s, r.new_tokens) for r in a] \
        == [(r.due_s, r.new_tokens) for r in b]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))


@pytest.mark.parametrize("mix", ["chat", "docs-batch"])
def test_every_seed_gets_the_same_sizes_in_another_order(mix):
    a = traffic.serve_requests(_mix(mix), 1, 10.0, 50257)
    b = traffic.serve_requests(_mix(mix), BIG, 10.0, 50257)
    assert sorted(len(r.prompt) for r in a) \
        == sorted(len(r.prompt) for r in b)
    assert sorted(r.new_tokens for r in a) == sorted(r.new_tokens for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]


@pytest.mark.parametrize("mix", ["chat", "docs-batch"])
def test_lengths_stay_inside_the_mix_and_the_context(mix):
    m = _mix(mix)
    reqs = traffic.serve_requests(m, 3, 40.0, 50257)
    p, n = m["prompt_tokens"], m["new_tokens"]
    assert all(p["min"] <= len(r.prompt) <= p["max"] for r in reqs)
    assert all(n["min"] <= r.new_tokens <= n["max"] for r in reqs)
    assert all(len(r.prompt) + r.new_tokens <= 2048 for r in reqs)
    assert all(0 <= r.prompt.min() and r.prompt.max() < 50257
               for r in reqs)


def test_open_loop_offers_a_fixed_count_inside_the_window():
    m = _mix("chat")
    reqs = traffic.serve_requests(m, 9, 40.0, 50257)
    assert len(reqs) == round(m["arrivals"]["rate_per_s"] * 40.0)
    due = [r.due_s for r in reqs]
    assert due == sorted(due) and due[0] == 0.0 and due[-1] < 40.0


def test_backlog_is_due_at_once():
    reqs = traffic.serve_requests(_mix("docs-batch"), 9, 5.0, 50257)
    assert {r.due_s for r in reqs} == {0.0}


@pytest.mark.parametrize("seed", [0, BIG])
def test_train_batches_reproduce_and_rows_differ(seed):
    m = _mix("pretrain-b32")
    a, b = (traffic.TrainBatches(m, seed, 30522) for _ in range(2))
    x, y = a.next(), b.next()
    assert (x["tokens"] == y["tokens"]).all()
    assert (x["labels"] == y["labels"]).all()
    assert x["tokens"].shape == (32, 128) and x["tokens"].dtype == np.int32
    assert len({row.tobytes() for row in x["tokens"]}) == 32
    assert ((x["labels"] >= 0).sum(axis=1) == a.labels_per_row).all()
    assert a.tokens_per_step == 4096
    assert not (a.next()["tokens"] == x["tokens"]).all()


def test_weights_are_the_seeds_alone():
    import jax
    import jax.numpy as jnp
    shapes = {"params": {
        "final_layernorm": {"weight": jax.ShapeDtypeStruct((64,),
                                                           jnp.float32),
                            "bias": jax.ShapeDtypeStruct((64,),
                                                         jnp.float32)},
        "dense": {"weight": jax.ShapeDtypeStruct((64, 64), jnp.bfloat16)}}}
    a, b, c = (weights.make(shapes, s) for s in (BIG, BIG, BIG + 1))
    la, lb, lc = (jax.tree.leaves(t) for t in (a, b, c))
    assert all((x == y).all() for x, y in zip(la, lb))
    assert not all((x == y).all() for x, y in zip(la, lc))
    p = a["params"]
    assert p["dense"]["weight"].dtype == jnp.bfloat16
    assert abs(float(p["final_layernorm"]["weight"].mean()) - 1.0) < 0.02
    assert abs(float(p["final_layernorm"]["bias"].mean())) < 0.02
