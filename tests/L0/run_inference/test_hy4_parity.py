"""The ``hy4`` kind against the benchmark's plain reference,
``benchmark/references/hy4_lm.py`` — the same file the chip runs judge the
served tokens with.  Tiny sizes (``topk`` 16 far under contexts of 70-100,
pages of 8, six layers whose indexers are full, full, shared, shared,
shared, full), seeded float32 weights.

Tolerance: both sides compute in float32 on the CPU (the Pallas kernels in
interpret mode, the reference at ``Precision.HIGHEST``); what differs is the
order of accumulation (blockwise online softmax, the sink added after the
flash kernel, the absorbed form of the reference, grouped products over
sorted rows).  ``TOL`` = 2e-4 of the largest reference logit holds that with
room.  The toy's weights are scaled (std 0.2, the toy's ``swiglu_limit`` 1)
so that each mechanism matters, and a reference with any ONE of them left
out misses that tolerance by far (checked below).
"""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[3]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from apex_tpu.inference import InferenceEngine, SamplingConfig  # noqa: E402
from apex_tpu.inference import kv_cache, models  # noqa: E402
from apex_tpu.inference.step_vector import peel_step  # noqa: E402
from apex_tpu.ops.attention import flash_attention, with_sink  # noqa: E402
from apex_tpu.ops.paged_attention import (  # noqa: E402
    paged_decode_attention, paged_select_attention_latent, paged_work_list)
from apex_tpu.transformer.moe.dropless import (  # noqa: E402
    dropless_moe_ffn, swiglu)
from apex_tpu.transformer.testing import standalone_hy4 as SH  # noqa: E402
from benchmark.bindings import mla_dsa_hy4 as binding  # noqa: E402
from benchmark.references import hy4_lm  # noqa: E402

TOL = 2e-4
PAD = 128
TOPK = 16
LAYERS = 6
REUSED = 3          # layers 2-4 attend layer 1's picks

#: a configuration file in the published keys, at toy sizes: 4 heads of
#: 16 + 8 (values 16) over a latent of 32, 4 index heads of 16 picking 16
#: positions, 4 streams, a dense layer then 8 of a router's 16 experts held
TINY = {
    "model_type": "hy_v4", "vocab_size": 96, "hidden_size": 64,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_hidden_layers": LAYERS, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 8, "q_lora_rank": 48,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "qk_head_dim": 24, "v_head_dim": 16, "index_n_heads": 4,
    "index_head_dim": 16, "index_topk": TOPK,
    "indexer_types": ["full", "full", "shared", "shared", "shared", "full"],
    "layer_types": ["deepseek_sparse_attention"] * LAYERS,
    "mlp_layer_types": ["dense"] + ["sparse"] * (LAYERS - 1),
    "n_routed_experts": 8, "held_experts_first": 4,
    "published": {"n_routed_experts": 16},
    "num_experts_per_tok": 4, "n_group": 1, "topk_group": 1,
    "norm_topk_prob": True, "routed_scaling_factor": 2.827,
    "n_shared_experts": 1, "swiglu_limit": 1.0, "hc_mult": 4,
    "hc_magnitude": 2, "hc_eps": 1e-6, "enable_ihc": True,
    "enable_lm_head_fp32": True, "gated_mla": True,
    "gating_type": "elementwise", "learnable_sink": True,
    "learnable_sink_init": 0, "use_mla": True, "use_dsa": True,
    "rope_parameters": {"rope_theta": 10000000, "rope_type": "default"},
    "rms_norm_eps": 1e-5, "attention_bias": False,
    "tie_word_embeddings": False, "max_position_embeddings": 128,
}
SPEC = hy4_lm.spec_from_config(TINY)


def seeded(shapes, seed, std=0.2):
    """float32 weights large enough that positions, sinks and mixes decide
    tokens; rank-1 leaves (gains, sinks, the mixes' alpha and bias) 1 +
    noise."""
    leaves, treedef = jax.tree_util.tree_flatten(shapes)
    key = jax.random.PRNGKey(seed)
    out = []
    for n, leaf in enumerate(leaves):
        x = std * jax.random.normal(jax.random.fold_in(key, n), leaf.shape,
                                    jnp.float32)
        out.append(1.0 + 0.1 * x if leaf.ndim == 1 else x)
    return jax.tree_util.tree_unflatten(treedef, out)


@pytest.fixture(scope="module")
def tiny():
    hcfg, shapes = binding.model_of(TINY)
    hcfg = dataclasses.replace(hcfg, params_dtype=jnp.float32)
    params = seeded(shapes, 5)
    return hcfg, params, binding.reference_weights(TINY, params)


def reference(w, tokens, **control):
    """The reference's logits of every real position of ``tokens``."""
    padded = np.zeros((PAD,), np.int32)
    padded[:len(tokens)] = tokens
    return np.asarray(hy4_lm.logits(w, jnp.asarray(padded), 0, len(tokens),
                                    spec=SPEC, **control))


def off_by(got, want):
    """The gap in units of the largest reference logit (NaN reads inf)."""
    gap = float(np.abs(got - want).max() / np.abs(want).max())
    return gap if np.isfinite(gap) else float("inf")


def test_the_binding_maps_the_published_keys(tiny):
    hcfg, params, _ = tiny
    assert (hcfg.index_heads, hcfg.index_head_dim, hcfg.index_topk) == (
        4, 16, TOPK)
    assert SH.SINKHORN_ROUNDS == hy4_lm.SINKHORN
    assert hcfg.index_sources == (0, 1, 1, 1, 1, 5)
    assert hcfg.index_layers == (0, 1, 5)
    assert (hcfg.held, hcfg.num_experts, hcfg.swiglu_limit) == ((4, 8), 16,
                                                                 1.0)
    p = params["params"]
    assert "indexer" in p["layer_1"] and "indexer" not in p["layer_2"]
    assert p["layer_1"]["indexer"]["q_proj"]["weight"].shape == (4 * 16, 48)
    assert p["layer_0"]["attention"]["sink"].shape == (4,)
    assert p["layer_0"]["hc_attention"]["phi"].shape == (24, 4 * 64)
    assert p["hc_head"]["phi"].shape == (4, 4 * 64)
    rec = models.KINDS["hy4"]
    assert rec.latent and rec.select and rec.residual
    assert rec.stats == (models.EXPERT_STATS + models.SELECT_STATS
                         + models.REUSE_STATS)
    assert all(models.KINDS[k].residual is None for k in models.KINDS
               if k != "hy4")


@pytest.mark.parametrize("n", [70, 13])
def test_prefill_matches_the_reference(tiny, n):
    """A context far over ``topk`` and one under it (plain causal rows)."""
    hcfg, params, w = tiny
    tokens = np.random.RandomState(n).randint(0, 96, size=n)
    want = reference(w, tokens)
    pre = models.prefill_forward("hy4", hcfg, params,
                                 jnp.asarray(tokens[None], jnp.int32))
    assert off_by(np.asarray(pre[0])[:, 0], want) < TOL
    # one latent row a position a layer; an index key for the 3 full
    # layers only; no values, no rings
    assert pre[1].shape == (LAYERS, n, 40) and pre[5].shape == (3, n, 16)
    assert pre[2] is None and pre[3] is None and pre[4] is None
    stats = {k: int(v) for k, v in pre[6].items()}
    assert stats["dsa_rows"] == LAYERS * n
    assert stats["dsa_rows_reused"] == REUSED * n
    assert stats["dsa_selected"] == LAYERS * sum(min(t + 1, TOPK)
                                                 for t in range(n))


#: each mechanism left out of the reference, one at a time
LEFT_OUT = {"no_sink": dict(drop=("sink",)), "no_gate": dict(drop=("gate",)),
            "static_mixes": dict(static=True),
            "no_sinkhorn": dict(drop=("sinkhorn",)),
            "every_layer_selects": dict(select="self"),
            "no_clamp": dict(drop=("clamp",))}


@pytest.fixture(scope="module")
def served(tiny):
    """Three slots at unlike lengths in one step — a prompt far over
    ``topk`` that ends mid-page, one under ``topk`` and one of a few pages
    — prefilled, then 12 decode steps through the engine's paged pools."""
    hcfg, params, w = tiny
    eng = InferenceEngine("hy4", hcfg, params, slots=3, max_seq=128,
                          page_size=8, num_pages=48,
                          cache_dtype=jnp.float32,
                          sampling=SamplingConfig())
    assert eng.stats_tail == 8 and not eng.supports_prefix_sharing
    alloc = eng.new_allocator()
    cache = eng.init_cache()
    # the latent pool keeps every layer; the index pool the 3 full ones
    assert cache.k.shape == (49, LAYERS, 40, 8) and cache.v is None
    assert cache.ik.shape == (49, 3, 16, 8)
    assert eng.page_host_bytes() == 8 * 4 * (LAYERS * 40 + 3 * 16)
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, 96, size=n) for n in (75, 3, 29)]
    steps = 12
    seqs, last, prefill_logits, tails = [], np.zeros((3,), np.int32), [], []
    for slot, p in enumerate(prompts):
        pages = alloc.acquire(alloc.pages_needed(len(p) + steps + 1))
        cache, tok, logits = eng.prefill(cache, p, slot, pages=pages)
        tok = np.asarray(tok)
        prefill_logits.append(np.asarray(logits))
        tails.append(tok[1:])
        seqs.append(list(p) + [int(tok[0])])
        last[slot] = tok[0]
    step_logits = []
    for _ in range(steps):
        cache, toks, logits, truncated = eng.decode(cache, last)
        toks, flags, tail = peel_step(np.asarray(toks), 3, eng.stats_tail)
        assert not flags.any() and not np.asarray(truncated).any()
        for slot in range(3):
            seqs[slot].append(int(toks[slot]))
        last = toks.copy()
        step_logits.append(np.asarray(logits))
        tails.append(tail)
    return dict(w=w, prompts=prompts, seqs=seqs, steps=steps,
                prefill_logits=prefill_logits, step_logits=step_logits,
                tails=tails)


def test_prefill_then_decode_through_the_paged_pools(served):
    """Every step's greedy token and the logits of several steps against
    the reference's full forward over prompt + generated."""
    w, seqs, steps = served["w"], served["seqs"], served["steps"]
    for slot, p in enumerate(served["prompts"]):
        want = reference(w, np.asarray(seqs[slot][:-1]))
        assert off_by(served["prefill_logits"][slot], want[len(p) - 1]) < TOL
        for step in (0, 4, 5, steps - 1):
            assert off_by(served["step_logits"][step][slot],
                          want[len(p) + step]) < TOL
        assert list(want[len(p) - 1:].argmax(-1)) == seqs[slot][len(p):]
    # the counters rode the token read: rows, sparse rows, picks, reused
    first, final = served["tails"][0], served["tails"][-1]
    assert list(first[4:]) == [LAYERS * 75, LAYERS * (75 - TOPK),
                               LAYERS * sum(min(t + 1, TOPK)
                                            for t in range(75)),
                               REUSED * 75]
    lengths = [len(p) + steps for p in served["prompts"]]
    assert list(final[4:]) == [LAYERS * 3,
                               LAYERS * sum(n > TOPK for n in lengths),
                               LAYERS * sum(min(n, TOPK) for n in lengths),
                               REUSED * 3]


@pytest.mark.parametrize("mechanism", list(LEFT_OUT))
def test_leaving_a_mechanism_out_fails_the_tolerance(served, mechanism):
    """The same served run judged against the reference with ONE mechanism
    left out — no sink, no gate, static mixes (their input terms dropped),
    ``H_res`` without Sinkhorn, every layer selecting for itself, no clamp:
    the long slot's prefill and last decode logits are out by far more than
    the tolerance, so each is something the program does."""
    w, seqs, p = served["w"], served["seqs"], served["prompts"][0]
    seq = np.asarray(seqs[0][:-1])
    other = reference(w, seq, **LEFT_OUT[mechanism])
    assert off_by(served["prefill_logits"][0], other[len(p) - 1]) > 50 * TOL
    assert off_by(served["step_logits"][-1][0], other[-1]) > 50 * TOL


def test_under_topk_every_selection_is_every_position(tiny):
    """Below ``topk`` the picks are every causal position, so selecting for
    itself or reusing changes nothing."""
    _, _, w = tiny
    short = np.random.RandomState(4).randint(0, 96, size=TOPK)
    np.testing.assert_array_equal(reference(w, short),
                                  reference(w, short, select="self"))


# --------------------------------------------------------------------------
# the pieces
# --------------------------------------------------------------------------

@pytest.mark.parametrize("spread", [0.1, 0.25, 0.5])
def test_sinkhorn_rows_and_columns_sum_to_one(spread):
    """``H_res = Sinkhorn(exp(L))`` over 500 mixes whose logits spread as
    ``spread``: after the 20 rounds every row and every column sums to 1
    within ``hc_eps`` — and the float32 rounding of a sum of four, an ulp
    of 1 a term.  (Twenty rounds do not converge rows of logits spread
    much wider: at 1.0 a row is 2e-4 off, at 2.0 3e-2; the mixes of the
    benchmark's seeded weights spread by a few hundredths.)"""
    logits = spread * jax.random.normal(jax.random.PRNGKey(0), (500, 4, 4))
    res = np.asarray(SH.sinkhorn(jnp.exp(logits), 20, 1e-6), np.float64)
    atol = 1e-6 + 4 * np.finfo(np.float32).eps
    assert (res > 0).all()
    np.testing.assert_allclose(res.sum(-2), 1.0, rtol=0, atol=atol)
    np.testing.assert_allclose(res.sum(-1), 1.0, rtol=0, atol=atol)
    raw = np.asarray(SH.sinkhorn(jnp.exp(logits), 0, 1e-6))
    assert np.abs(raw.sum(-1) - 1.0).max() > 0.5


def test_the_mixes_of_seeded_streams_are_doubly_stochastic(tiny):
    """``hc_pre`` at the benchmark's weight scale (normal(0, 0.02)): ``H_res``
    doubly stochastic within ``hc_eps``, ``H_post`` inside (0, magnitude)."""
    hcfg, params, _ = tiny
    lp = seeded(params["params"]["layer_2"], 9, std=0.02)
    x = jax.random.normal(jax.random.PRNGKey(2), (40, 4, 64), jnp.float32)
    _, (post, res) = SH.hc_pre(hcfg, lp, "attention", x)
    res, atol = np.asarray(res, np.float64), hcfg.hc_eps + 2.4e-7
    assert res.shape == (40, 4, 4)
    np.testing.assert_allclose(res.sum(-2), 1.0, rtol=0, atol=atol)
    np.testing.assert_allclose(res.sum(-1), 1.0, rtol=0, atol=atol)
    post = np.asarray(post)
    assert ((post > 0) & (post < hcfg.hc_magnitude)).all()


def test_the_streams_mixes_against_the_reference(tiny):
    """One sublayer's mixes and the streams after it, program against
    reference, with the sublayer's output given."""
    hcfg, params, w = tiny
    lp, lw = params["params"]["layer_3"], w["layers"][3]
    x = jax.random.normal(jax.random.PRNGKey(6), (30, 4, 64), jnp.float32)
    y = jax.random.normal(jax.random.PRNGKey(7), (30, 64), jnp.float32)
    u, mix = SH.hc_pre(hcfg, lp, "ffn", x)
    got = SH.hc_post(hcfg, mix, x, y)
    seen = {}

    def fn(v):
        seen["u"] = v
        return y
    want = hy4_lm.sublayer(x, lw["hc_ffn"], fn, SPEC, None, False, ())
    np.testing.assert_allclose(np.asarray(u), np.asarray(seen["u"]),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


def test_the_clamp_bites_and_is_off_for_other_kinds(tiny):
    """``swiglu_limit`` clamps the dense and the expert FFN alike; without a
    limit the expert layer is the plain one, bit for bit."""
    hcfg, params, w = tiny
    h = 3.0 * jax.random.normal(jax.random.PRNGKey(4), (50, 64), jnp.float32)
    for i in (0, 2):
        lp, lw = params["params"][f"layer_{i}"], w["layers"][i]
        got, _ = SH.ffn(hcfg, i, lp, h)
        want = hy4_lm.ffn(h, lw, SPEC, SPEC.dense[i], None, ())
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)
        loose = hy4_lm.ffn(h, lw, SPEC, SPEC.dense[i], None, ("clamp",))
        assert np.abs(np.asarray(loose) - np.asarray(want)).max() > 0.1
    m = params["params"]["layer_2"]["moe"]
    args = (h, m["router"]["weight"], m["experts"]["w_gate"],
            m["experts"]["w_up"], m["experts"]["w_down"])
    kw = dict(top_k=4, scale=2.0, shared=m["shared"], held=(4, 8))
    plain, _ = dropless_moe_ffn(*args, **kw)
    unclamped, _ = dropless_moe_ffn(*args, limit=None, **kw)
    assert np.array_equal(np.asarray(plain), np.asarray(unclamped))


def test_the_shares_of_sixteen_chips_add_up_to_the_uncut_layer(tiny):
    """The expert layer cut as the deployment cuts it — each of 16 chips
    holding 1 of the router's 16 experts (here), the shared expert and
    router whole on each — adds up to the uncut layer: the routed parts
    sum, the shared expert counted once."""
    hcfg, params, _ = tiny
    m = params["params"]["layer_2"]["moe"]
    rng = jax.random.PRNGKey(11)
    gate, up, down = (jax.random.normal(jax.random.fold_in(rng, i), s,
                                        jnp.float32) * 0.2
                      for i, s in enumerate(((16, 64, 32), (16, 64, 32),
                                             (16, 32, 64))))
    h = jax.random.normal(jax.random.PRNGKey(12), (40, 64), jnp.float32)
    cfg = dataclasses.replace(hcfg, held=(0, 16))
    whole, stats = SH.ffn(cfg, 2, {"moe": dict(m, experts=dict(
        w_gate=gate, w_up=up, w_down=down))}, h)
    parts, landed = [], 0
    for chip in range(16):
        ccfg = dataclasses.replace(hcfg, held=(chip, 1))
        y, st = SH.ffn(ccfg, 2, {"moe": dict(m, experts=dict(
            w_gate=gate[chip:chip + 1], w_up=up[chip:chip + 1],
            w_down=down[chip:chip + 1]))}, h)
        parts.append(np.asarray(y))
        landed += int(st["assignments"])
    shared = np.asarray(swiglu(h, m["shared"]["gate_proj"]["weight"],
                               m["shared"]["up_proj"]["weight"],
                               m["shared"]["down_proj"]["weight"],
                               limit=hcfg.swiglu_limit))
    total = sum(parts) - 15 * shared
    np.testing.assert_allclose(total, np.asarray(whole), rtol=1e-4,
                               atol=1e-4)
    assert landed == int(stats["assignments"]) == 40 * hcfg.experts_per_token


def test_the_index_pool_keeps_the_full_layers_only(tiny):
    """5 layers of which 2 pick (the published pattern's first five): the
    index pool holds 2 layers, a page's bytes follow from it, and a shared
    layer's token row is appended without a key."""
    hcfg = dataclasses.replace(
        tiny[0], num_layers=5, indexer_types=("full", "full", "shared",
                                              "shared", "shared"))
    d = models.model_dims("hy4", hcfg)
    assert d["pool_layers"] == 5 and d["index_layers"] == 2
    # 5 latent rows of 40 and 2 index keys of 16 a position
    assert models.cache_position_values(d, 0) == 5 * 40 + 2 * 16
    published = {"pool_layers": 5, "latent": 576, "head_dim": 256,
                 "index": 128, "index_layers": 2}
    assert 2 * models.cache_position_values(published, 0) == 6272
    # the kinds whose every layer keeps a key count as they did
    keye = {"pool_layers": 5, "latent": 0, "head_dim": 128, "index": 64}
    assert models.cache_position_values(keye, 4) == 5 * \
        models.cache_row_values(keye, 4)
    c = kv_cache.init_paged_cache(10, 5, 0, 8, 40, slots=2,
                                  max_pages_per_slot=4, dtype=jnp.float32,
                                  latent=40, index=16, index_layers=2)
    assert c.k.shape == (11, 5, 40, 8) and c.ik.shape == (11, 2, 16, 8)
    row = jnp.ones((2, 40), jnp.float32)
    c = kv_cache.append_layer(c, 3, row, None)
    with pytest.raises(ValueError, match="append_index"):
        kv_cache.append_layer(c, 1, row, None, jnp.ones((2, 16)))
    c = kv_cache.append_index(c, 1, 2 * jnp.ones((2, 16), jnp.float32))
    # both empty slots write row 0 of the trash page: one key of 2s
    assert float(c.ik[c.null_page, 1].sum()) == 2 * 16
    with pytest.raises(ValueError, match="index keys must be"):
        kv_cache.insert_tokens(c, 0, jnp.zeros((5, 8, 40)), None, 8,
                               jnp.zeros((4,), jnp.int32), 0,
                               jnp.zeros((5, 8, 16)))


# --------------------------------------------------------------------------
# the kernels
# --------------------------------------------------------------------------

def _latent_pool(seed, lengths, ps=8, width=40, layers=2, mpps=6):
    """A latent pool with ``lengths`` positions a slot in scrambled pages."""
    rng = np.random.RandomState(seed)
    slots = len(lengths)
    pages = slots * mpps
    pool = jnp.asarray(rng.randn(pages + 1, layers, width, ps), jnp.float32)
    order = list(rng.permutation(pages))
    table = np.full((slots, mpps), pages, np.int32)
    for s, n in enumerate(lengths):
        for j in range(-(-n // ps)):
            table[s, j] = order.pop()
    return pool, jnp.asarray(table), jnp.asarray(lengths, jnp.int32)


def _dense_latent(q, pool, table, lengths, picked, layer, scale, values,
                  sink):
    """Float64 oracle: each slot's live rows gathered, softmax over the
    picked ones with the sink in the denominator, the values summed."""
    q, pool = np.asarray(q, np.float64), np.asarray(pool, np.float64)
    out = np.zeros((q.shape[0], q.shape[1], values))
    ps = pool.shape[3]
    for s, n in enumerate(np.asarray(lengths)):
        if not n:
            continue
        rows = np.concatenate([pool[np.asarray(table)[s, j], layer].T
                               for j in range(-(-n // ps))])[:n]
        z = q[s] @ rows.T * scale                           # [h, n]
        keep = np.asarray(picked)[s, :n]
        z = np.where(keep[None], z, -np.inf)
        m = np.maximum(z.max(-1, keepdims=True), np.asarray(sink)[:, None])
        e = np.exp(z - m)
        den = e.sum(-1, keepdims=True) + np.exp(np.asarray(sink)[:, None]
                                                - m)
        out[s] = (e / den) @ rows[:, :values]
    return out


def test_attend_latent_over_picks_with_a_sink_against_float64():
    lengths = [20, 5, 0, 41]
    pool, table, lengths_a = _latent_pool(0, lengths)
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(4, 3, 40), jnp.float32)
    picked = jnp.asarray(rng.rand(4, 48) < 0.4) \
        & (jnp.arange(48)[None] < lengths_a[:, None])
    picked = picked.at[:, 0].set(lengths_a > 0)     # a pick in every slot
    sink = jnp.asarray([0.5, -1.0, 2.0], jnp.float32)
    work = paged_work_list(table, lengths_a, page_size=8)
    got = paged_select_attention_latent(q, pool, picked, work, layer=1,
                                        sm_scale=0.3, values=32, sink=sink)
    want = _dense_latent(q, pool, table, lengths_a, picked, 1, 0.3, 32,
                         sink)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
    assert not np.asarray(got)[2].any()             # the empty slot


def test_attend_latent_picking_everything_without_sink_is_the_latent_kernel():
    """Every live position picked and the sink at -inf: the answer of
    ``apex_paged_decode_latent``."""
    lengths = [20, 5, 1, 41]
    pool, table, lengths_a = _latent_pool(2, lengths)
    q = jnp.asarray(np.random.RandomState(3).randn(4, 3, 40), jnp.float32)
    work = paged_work_list(table, lengths_a, page_size=8)
    every = jnp.arange(48)[None] < lengths_a[:, None]
    got = paged_select_attention_latent(
        q, pool, every, work, layer=0, sm_scale=0.3, values=32,
        sink=jnp.full((3,), -jnp.inf, jnp.float32))
    want = paged_decode_attention(q, pool, None, table, lengths_a, layer=0,
                                  sm_scale=0.3, values=32, work=work)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6,
                               atol=1e-6)


def test_flash_sink_from_the_kernels_log_sum_exp():
    """``flash_attention(return_lse=True)`` gives each row's natural
    log-sum-exp, and ``with_sink`` the softmax with one more term in its
    denominator — against a float64 oracle, masked rows and all."""
    rng = np.random.RandomState(5)
    q, k = (jnp.asarray(rng.randn(1, 2, 40, 24), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.randn(1, 2, 40, 16), jnp.float32)
    # no row fully masked: its diagonal is always kept
    mask = jnp.asarray(rng.rand(1, 1, 40, 40) < 0.3) & ~jnp.eye(40,
                                                              dtype=bool)
    sink = jnp.asarray([0.7, -0.4], jnp.float32)
    out, lse = flash_attention(q, k, v, causal=True, mask=mask, sm_scale=0.2,
                               return_lse=True)
    got = np.asarray(with_sink(out, lse, sink))
    z = np.einsum("bhqd,bhkd->bhqk", np.asarray(q, np.float64),
                  np.asarray(k, np.float64)) * 0.2
    keep = np.tril(np.ones((40, 40), bool))[None, None] & ~np.asarray(mask)
    z = np.where(keep, z, -np.inf)
    np.testing.assert_allclose(
        np.asarray(lse), np.log(np.exp(z).sum(-1)), rtol=1e-5, atol=1e-5)
    e = np.exp(z)
    den = e.sum(-1, keepdims=True) + np.exp(np.asarray(sink))[None, :, None,
                                                               None]
    want = (e / den) @ np.asarray(v, np.float64)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    plain = flash_attention(q, k, v, causal=True, mask=mask, sm_scale=0.2)
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(out))


# --------------------------------------------------------------------------
# under the scheduler: the normal path, the counters by name
# --------------------------------------------------------------------------

def test_the_scheduler_serves_the_kind_and_reads_its_counters_by_name(tiny):
    """``InferenceEngine("hy4", paged)`` under ``SlotScheduler``: four
    requests over three slots finish by length, every page comes back, and
    the reuse counter reached the telemetry by name at 3/6 of the rows x
    layers."""
    from apex_tpu.inference import SlotScheduler
    from apex_tpu.observability import MetricsRegistry
    from apex_tpu.observability.serve import ServeTelemetry
    hcfg, params, _ = tiny
    eng = InferenceEngine("hy4", hcfg, params, slots=3, max_seq=128,
                          page_size=8, num_pages=40,
                          cache_dtype=jnp.float32,
                          sampling=SamplingConfig())
    sched = SlotScheduler(eng, telemetry=ServeTelemetry(MetricsRegistry()))
    rng = np.random.RandomState(8)
    lengths = [40, 9, 70, 22]
    sched.begin_run()
    for n in lengths:
        sched.submit(rng.randint(0, 96, size=n), max_new_tokens=5)
    while sched.run_pending():
        sched.run_pass()
    out = sched.finish_run()
    assert sorted(len(v) for v in out.values()) == [5] * 4
    assert sched.alloc.live_pages == 0
    tel = sched.telemetry
    for phase, rows in (("prefill", sum(lengths)), ("decode", 4 * 4)):
        assert tel.dsa_rows.value(phase=phase) == LAYERS * rows
        assert tel.dsa_rows_reused.value(phase=phase) == REUSED * rows
    assert tel.moe_passes.value(phase="prefill") == 4
