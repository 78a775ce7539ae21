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


# --------------------------------------------------------------------------
# the experts held here, and the group-limited sigmoid router (ISSUE 34)
# --------------------------------------------------------------------------

from apex_tpu.transformer.moe import dropless  # noqa: E402
from apex_tpu.transformer.moe.dropless import route_group_limited  # noqa: E402
from benchmark.references import axk1_lm  # noqa: E402

WIDE, SHARE, GROUPS, KEPT, K8 = 192, 12, 8, 4, 8


def wide_weights(seed):
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    n = lambda i, *s: 0.3 * jax.random.normal(k[i], s, jnp.float32)  # noqa
    return {"router": n(0, WIDE, HID), "e_gate": n(1, WIDE, HID, FFN),
            "e_up": n(2, WIDE, HID, FFN), "e_down": n(3, WIDE, FFN, HID),
            "s_gate": n(4, FFN, HID), "s_up": n(5, FFN, HID),
            "s_down": n(6, HID, FFN)}


def group_router(x, w):
    return route_group_limited(x, w, K8, SCALE, n_group=GROUPS,
                               topk_group=KEPT)


def held_call(x, fw, held, valid=None, shared=True, router=group_router):
    """The layer told it holds ``held`` (None: every expert)."""
    lo, n = held or (0, fw["e_gate"].shape[0])
    sh = {"gate_proj": {"weight": fw["s_gate"]},
          "up_proj": {"weight": fw["s_up"]},
          "down_proj": {"weight": fw["s_down"]}} if shared else None
    return jax.jit(lambda x, v: dropless_moe_ffn(
        x, fw["router"], fw["e_gate"][lo:lo + n], fw["e_up"][lo:lo + n],
        fw["e_down"][lo:lo + n], top_k=K8, scale=SCALE, shared=sh,
        valid=v, held=held, router=router))(x, valid)


@pytest.mark.parametrize("seed,block", [(0, 512), (1, 16), (2, 7)])
def test_the_sixteen_shares_add_up_to_the_uncut_layer(seed, block,
                                                      monkeypatch):
    """The one test tying the share to the model: 16 chips of 12 experts,
    routed parts summed and the shared expert counted once, equal the
    layer with every expert held — at a row block that takes one trip,
    several, and one that divides nothing."""
    monkeypatch.setattr(dropless, "HELD_ROW_BLOCK", block)
    fw = wide_weights(seed)
    x = jax.random.normal(jax.random.PRNGKey(200 + seed), (T, HID))
    whole, stats = held_call(x, fw, None)
    assert int(stats["assignments"]) == T * K8
    parts, landed = [], 0
    for r in range(WIDE // SHARE):
        y, st = held_call(x, fw, (SHARE * r, SHARE), shared=r == 0)
        parts.append(y)
        landed += int(st["assignments"])
        assert int(st["experts_hit"]) <= SHARE
    assert landed == T * K8               # every assignment lands once
    assert close(sum(parts), whole)
    # and a share is the reference's share (every held expert over every
    # token, weighted by the router's w)
    spec = axk1_lm.Spec(
        layers=1, dense_layers=0, heads=1, q_rank=1, kv_rank=1, nope=1,
        rope=2, v_dim=1, router_experts=WIDE, held=(24, SHARE), top_k=K8,
        n_group=GROUPS, topk_group=KEPT, scale=SCALE, eps=1e-6, theta=1e4,
        yarn_factor=1.0, yarn_original=1, beta_fast=1.0, beta_slow=1.0,
        mscale=1.0, mscale_all_dim=1.0)
    ref_w = dict(fw, **{k: fw[k][24:24 + SHARE]
                        for k in ("e_gate", "e_up", "e_down")})
    assert close(held_call(x, fw, (24, SHARE))[0],
                 axk1_lm.expert_ffn(x, ref_w, spec, None))


def numpy_group_router(sig):
    """The router as a loop: groups of consecutive experts scored by their
    two best, the best groups kept (lowest index first on a tie), then the
    largest scores among their experts (lowest index first on a tie)."""
    t, e = sig.shape
    per = e // GROUPS
    out_w, out_e = np.zeros((t, K8)), np.zeros((t, K8), np.int64)
    for i in range(t):
        score = [np.sort(sig[i, g * per:(g + 1) * per])[-2:].sum()
                 for g in range(GROUPS)]
        keep = sorted(range(GROUPS), key=lambda g: (-score[g], g))[:KEPT]
        cand = [j for g in sorted(keep) for j in range(g * per,
                                                       (g + 1) * per)]
        chosen = sorted(cand, key=lambda j: (-sig[i, j], j))[:K8]
        out_e[i] = chosen
        out_w[i] = SCALE * sig[i, chosen] / (sig[i, chosen].sum() + 1e-20)
    return out_w, out_e


def test_group_limited_router_against_a_numpy_loop():
    """Logits through an identity 'hidden' so that scores can be SET: a
    random batch, ties within a group and between groups, a token whose 8
    all lie in experts 0..11, and a token with none there."""
    rng = np.random.RandomState(0)
    logits = rng.randn(40, WIDE).astype(np.float32)
    logits[1, :] = 0.0                            # every score tied
    logits[2, 24:48] = logits[2, 0:24]            # two groups tied
    logits[3] = -4.0
    logits[3, :8] = 3.0 + np.arange(8) * 0.01     # all 8 land in 0..11
    logits[3, [30, 60, 100]] = 2.0                # keeps 4 groups apart
    logits[4] = -4.0
    logits[4, [50, 51, 75, 76, 100, 101, 125, 126]] = 2.0   # none in 0..11
    w, e = jax.jit(group_router)(jnp.asarray(logits),
                                 jnp.eye(WIDE, dtype=jnp.float32))
    sig = np.asarray(jax.nn.sigmoid(jnp.asarray(logits)), np.float64)
    want_w, want_e = numpy_group_router(sig)
    np.testing.assert_array_equal(np.asarray(e), want_e)
    np.testing.assert_allclose(np.asarray(w), want_w, rtol=1e-5)
    assert np.asarray(w).sum(-1) == pytest.approx(SCALE, rel=1e-5)
    assert set(np.asarray(e)[3]) == set(range(8))
    assert not (np.asarray(e)[4] < SHARE).any()
    assert list(np.asarray(e)[1]) == list(range(8))     # ties: lowest first


def test_every_token_on_one_held_expert_drops_nothing(monkeypatch):
    """All T tokens choose expert 5 first (and seven others elsewhere):
    one group T long, many trips of the row loop, nothing dropped; padding
    rows add nothing and count nowhere."""
    monkeypatch.setattr(dropless, "HELD_ROW_BLOCK", 16)
    fw = wide_weights(3)
    x = jax.random.normal(jax.random.PRNGKey(7), (T, HID))

    def router(x, w):
        experts = jnp.broadcast_to(
            jnp.asarray([5, 30, 31, 60, 61, 100, 101, 150]), (T, K8))
        weights = jnp.broadcast_to(jnp.arange(1.0, 9.0) / 10.0, (T, K8))
        return weights, experts.astype(jnp.int32)

    y, st = held_call(x, fw, (0, SHARE), shared=False, router=router)
    assert int(st["assignments"]) == T and int(st["experts_hit"]) == 1
    assert int(st["load_max"]) == T
    want = 0.1 * axk1_lm.swiglu(x, fw["e_gate"][5].T, fw["e_up"][5].T,
                                fw["e_down"][5].T, None)
    assert close(y, want)
    valid = jnp.arange(T) % 3 != 0
    y2, st2 = held_call(x, fw, (0, SHARE), valid=valid, shared=False,
                        router=router)
    assert int(st2["assignments"]) == int(valid.sum())
    assert close(y2, jnp.where(valid[:, None], want, 0.0))
    # a share nobody chose: no trip of the loop, zeros, zero counters
    y3, st3 = held_call(x, fw, (12, SHARE), shared=False, router=router)
    assert not np.asarray(y3).any() and int(st3["assignments"]) == 0


def _pr30_layer(x, router_w, w_gate, w_up, w_down, top_k, scale, shared):
    """``dropless_moe_ffn`` as PR 30 wrote it, transcribed: what
    ``held=None`` must stay, bit for bit."""
    t, hidden = x.shape
    n_exp = router_w.shape[0]
    weights, experts = route_top_k(x, router_w, top_k, scale)
    flat = experts.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.bincount(flat, length=n_exp + 1)[:n_exp].astype(jnp.int32)
    xs = jnp.take(x, order // top_k, axis=0)
    act = jax.nn.silu(jax.lax.ragged_dot(xs, w_gate, sizes)) \
        * jax.lax.ragged_dot(xs, w_up, sizes)
    ys = jax.lax.ragged_dot(act.astype(x.dtype), w_down, sizes)
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype))
    y = jnp.take(ys, inverse, axis=0).reshape(
        t, top_k, hidden).astype(jnp.float32)
    y = jnp.sum(y * weights[..., None], axis=1).astype(x.dtype)
    return y + dropless.swiglu(x, shared["gate_proj"]["weight"],
                               shared["up_proj"]["weight"],
                               shared["down_proj"]["weight"])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_held_none_is_bit_identical_to_the_layer_as_it_was(dtype):
    fw = jax.tree.map(lambda a: a.astype(dtype), weights(4))
    x = jax.random.normal(jax.random.PRNGKey(11), (T, HID)).astype(dtype)
    shared = {"gate_proj": {"weight": fw["s_gate"]},
              "up_proj": {"weight": fw["s_up"]},
              "down_proj": {"weight": fw["s_down"]}}
    got, _ = jax.jit(lambda x: dropless_moe_ffn(
        x, fw["router"], fw["e_gate"], fw["e_up"], fw["e_down"],
        top_k=TOP_K, scale=SCALE, shared=shared))(x)
    want = jax.jit(lambda x: _pr30_layer(
        x, fw["router"], fw["e_gate"], fw["e_up"], fw["e_down"], TOP_K,
        SCALE, shared))(x)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    # and holding everything gives the same layer, to rounding
    all_held, _ = jax.jit(lambda x: dropless_moe_ffn(
        x, fw["router"], fw["e_gate"], fw["e_up"], fw["e_down"],
        top_k=TOP_K, scale=SCALE, shared=shared, held=(0, EXPERTS)))(x)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(all_held, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)
