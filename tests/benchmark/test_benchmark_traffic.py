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


def _parent_serve_requests(mix, seed, seconds, vocab):
    """The generator as it stood before the backlog lapped (commit 9b4a79d),
    kept as the reference the first lap is held to."""
    arr = mix["arrivals"]
    if arr["kind"] == "poisson":
        n = max(1, int(round(arr["rate_per_s"] * seconds)))
    else:
        n = max(1, int(np.ceil(arr["requests_per_s_bound"] * seconds)))
    shape = traffic.rng_for(mix["shape_seed"], stream=n)
    prompts = traffic._draw(mix["prompt_tokens"], n, shape)
    news = traffic._draw(mix["new_tokens"], n, shape)
    gaps = shape.exponential(1.0, n)
    rng = traffic.rng_for(seed)
    order = rng.permutation(n)
    prompts, news = prompts[order], news[order]
    if arr["kind"] == "poisson":
        gaps = gaps[rng.permutation(n)]
        due = np.cumsum(gaps) - gaps[0]
        due = due * (seconds * (n - 1) / n) / max(due[-1], 1e-9)
    else:
        due = np.zeros(n)
    return [traffic.Request(i, float(due[i]),
                            rng.randint(0, vocab, size=int(prompts[i]))
                            .astype(np.int32), int(news[i]))
            for i in range(n)]


@pytest.mark.parametrize("mix", ["chat", "docs-batch"])
@pytest.mark.parametrize("seed", [0, 7, BIG])
def test_the_first_lap_is_the_list_the_parent_made(mix, seed):
    ours = traffic.serve_requests(_mix(mix), seed, 50.0, 50257)
    theirs = _parent_serve_requests(_mix(mix), seed, 50.0, 50257)
    assert len(ours) == len(theirs) == (400 if mix == "docs-batch" else 90)
    for a, b in zip(ours, theirs):
        assert (a.index, a.due_s, a.new_tokens) \
            == (b.index, b.due_s, b.new_tokens)
        assert a.prompt.dtype == b.prompt.dtype
        assert (a.prompt == b.prompt).all()


def test_a_backlog_goes_round_again_with_fresh_prompts():
    m = _mix("docs-batch")
    reqs = traffic.serve_requests(m, BIG, 5.0, 50257)
    lap = len(reqs)
    assert lap == reqs.lap == 40
    third = reqs[3 * lap - 1]              # asked for first: laps 1 and 2 made
    assert len(reqs) == 3 * lap and third.index == 3 * lap - 1
    assert [r.index for r in reqs] == list(range(3 * lap))
    for k in (1, 2):
        for i in range(lap):
            a, b = reqs[i], reqs[k * lap + i]
            assert (len(a.prompt), a.new_tokens, b.due_s) \
                == (len(b.prompt), b.new_tokens, 0.0)
            assert b.prompt.dtype == np.int32 and b.prompt.max() < 50257
    # no lap hands the prefix cache anything: no two prompts share a start
    assert len({r.prompt[:8].tobytes() for r in reqs}) == 3 * lap
    again = traffic.serve_requests(m, BIG, 5.0, 50257)
    assert (again[2 * lap + 5].prompt == reqs[2 * lap + 5].prompt).all()


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


def test_norm_scales_are_found_by_name_in_every_kind_of_tree():
    """LayerNorm and RMSNorm scales read 1 + noise."""
    import jax
    import jax.numpy as jnp
    leaf = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa
    shapes = {"params": {
        "final_layernorm": {"weight": leaf(8), "bias": leaf(8)},
        "layer_0": {"input_norm": {"weight": leaf(8)},
                    "q_norm": {"weight": leaf(8)},
                    "norm_proj": {"weight": leaf(8, 8)},
                    "dense": {"weight": leaf(8, 8), "bias": leaf(8)}}}}
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    scales = {"/".join(str(k.key) for k in path)
              for path, x in flat if weights._is_norm_scale(path, x)}
    assert scales == {"params/final_layernorm/weight",
                      "params/layer_0/input_norm/weight",
                      "params/layer_0/q_norm/weight"}


@pytest.mark.parametrize("workload", ["toy.train", "toy.chat"])
def test_both_configurations_trees_keep_the_scales_they_had(toy_root,
                                                            workload):
    """Before RMSNorm was taken too the rule was ``"layernorm" in`` the
    module's name: on the BERT and GPT trees both rules pick the same
    leaves, so their weights are the numbers they were."""
    import jax
    cell = harness.load_cell(workload, toy_root)
    if workload == "toy.train":
        from benchmark.drivers import train
        shapes = train.build(cell, 1)[2]
    else:
        shapes = harness.load_binding(cell).model_of(cell.config)[1]
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    names = [[str(k.key) for k in path] for path, _ in flat]
    was = [n[-1] == "weight" and "layernorm" in n[-2] for n in names]
    now = [weights._is_norm_scale(path, x) for path, x in flat]
    assert was == now and sum(now) >= 5


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
