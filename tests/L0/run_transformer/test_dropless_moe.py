"""The expert FFN that drops no token (ISSUE 30,
``transformer/moe/dropless.py``) against the plain dense formulation of the
benchmark's reference (``benchmark/references/laguna_lm.py::expert_ffn``:
every expert over every token, weighted), at any imbalance."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[3]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from apex_tpu.transformer.moe import dropless_moe_ffn, route_top_k  # noqa: E402
from benchmark.references import laguna_lm  # noqa: E402

T, HID, FFN, EXPERTS, TOP_K, SCALE = 48, 32, 16, 16, 4, 2.5
SPEC = laguna_lm.Spec(
    heads=(), sliding=(), sparse=(), kv_heads=1, head_dim=1, window=1,
    top_k=TOP_K, scale=SCALE, eps=1e-6, rotary=2, theta_full=1e4,
    yarn_factor=1.0, yarn_original=1, beta_fast=1.0, beta_slow=1.0,
    attention_factor=1.0, theta_sliding=1e4)


def weights(seed, router=None):
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    n = lambda i, *s: 0.3 * jax.random.normal(k[i], s, jnp.float32)  # noqa
    return {"router": n(0, EXPERTS, HID) if router is None else router,
            "e_gate": n(1, EXPERTS, HID, FFN), "e_up": n(2, EXPERTS, HID, FFN),
            "e_down": n(3, EXPERTS, FFN, HID), "s_gate": n(4, FFN, HID),
            "s_up": n(5, FFN, HID), "s_down": n(6, HID, FFN)}


def program(x, fw, valid=None):
    shared = {"gate_proj": {"weight": fw["s_gate"]},
              "up_proj": {"weight": fw["s_up"]},
              "down_proj": {"weight": fw["s_down"]}}
    return jax.jit(lambda x, v: dropless_moe_ffn(
        x, fw["router"], fw["e_gate"], fw["e_up"], fw["e_down"],
        top_k=TOP_K, scale=SCALE, shared=shared, valid=v))(x, valid)


def close(got, want):
    return float(jnp.max(jnp.abs(got - want))) < 1e-5 * float(
        jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_equals_the_reference_on_random_routing(seed):
    fw = weights(seed)
    x = jax.random.normal(jax.random.PRNGKey(100 + seed), (T, HID))
    got, stats = program(x, fw)
    assert close(got, laguna_lm.expert_ffn(x, fw, SPEC, None))
    assert int(stats["assignments"]) == T * TOP_K
    assert 1 <= int(stats["experts_hit"]) <= EXPERTS
    assert int(stats["load_max"]) >= T * TOP_K // EXPERTS


def test_every_token_on_the_same_experts_drops_nothing():
    """All 48 tokens choose experts 3, 5, 8, 13: groups of 48 rows beside
    twelve empty ones — no capacity exists to overflow."""
    chosen = [3, 5, 8, 13]
    router = (-jnp.ones((EXPERTS, HID))).at[jnp.asarray(chosen)].set(
        jnp.linspace(1.0, 1.3, TOP_K)[:, None] * jnp.ones((TOP_K, HID)))
    fw = weights(7, router=router)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(9), (T, HID))) + 0.1
    _, experts = route_top_k(x, router, TOP_K, SCALE)
    assert sorted(set(np.asarray(experts).ravel())) == chosen
    got, stats = program(x, fw)
    assert close(got, laguna_lm.expert_ffn(x, fw, SPEC, None))
    assert int(stats["experts_hit"]) == TOP_K
    assert int(stats["load_max"]) == T
    assert int(stats["assignments"]) == T * TOP_K


def test_router_weights_are_renormalised_and_scaled():
    fw = weights(3)
    x = jax.random.normal(jax.random.PRNGKey(4), (T, HID))
    w, experts = route_top_k(x, fw["router"], TOP_K, SCALE)
    assert w.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(w.sum(-1)), SCALE, rtol=1e-6)
    p = jax.nn.softmax(x @ fw["router"].T, axis=-1)
    np.testing.assert_array_equal(np.asarray(experts),
                                  np.asarray(jax.lax.top_k(p, TOP_K)[1]))


def test_padding_rows_are_routed_nowhere():
    """Rows marked invalid cost no expert work and hit no expert: they
    return the shared expert's output alone, and the valid rows' outputs
    do not move."""
    fw = weights(5)
    x = jax.random.normal(jax.random.PRNGKey(6), (T, HID))
    valid = jnp.arange(T) < 30
    got, stats = program(x, fw, valid)
    whole, _ = program(x, fw)
    assert close(got[:30], whole[:30])
    shared = laguna_lm.swiglu(x[30:], fw["s_gate"], fw["s_up"],
                              fw["s_down"], None)
    assert close(got[30:], shared)
    assert int(stats["assignments"]) == 30 * TOP_K
    only, s30 = program(x[:30], fw)
    assert int(stats["experts_hit"]) == int(s30["experts_hit"])
    assert int(stats["load_max"]) == int(s30["load_max"])


@pytest.mark.parametrize("stage,op", [
    ("route", "top_k"), ("sort", "jit(argsort)"), ("sort", "jit(_take)"),
    ("experts", "ragged_dot_general"), ("combine", "scatter"),
    ("combine", "reduce_sum"), ("shared", "dot_general")])
def test_every_stage_stands_under_its_scope(stage, op):
    """``apex_moe_<stage>`` stands in the ``op_name`` of what the stage
    lowers to: what an HLO dump and XProf's own views name the stage by
    (the v5e's profile events carry no ``op_name``: PERF.md section 7)."""
    x = jnp.zeros((T, HID), jnp.float32)
    text = jax.jit(lambda x, fw: program(x, fw, valid=jnp.ones((T,), bool))
                   ).lower(x, weights(0)).as_text(debug_info=True)
    assert f"apex_moe_{stage}/{op}" in text, (stage, op)
