"""The ``keye`` kind (ISSUE 36) against the benchmark's plain reference,
``benchmark/references/keye_lm.py`` — the same file the chip runs judge the
served tokens with.  Tiny sizes (``topk`` 16 far under contexts of 70-100,
pages of 8), seeded float32 weights.

Tolerance: both sides compute in float32 on the CPU (the Pallas kernels in
interpret mode, the reference at ``Precision.HIGHEST``); what differs is the
order of accumulation (blockwise online softmax, grouped products over
sorted rows).  ``TOL`` = 2e-4 of the largest reference logit holds that with
room; a program that attended EVERY position, or the most recent ``topk``,
where the learned selection belongs moves the logits by percents (checked
below), so the tolerance has teeth.
"""
import dataclasses
import hashlib
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
from apex_tpu.ops.attention import (  # noqa: E402
    index_scores, index_scores_reference, select_attention,
    select_top_mask)
from apex_tpu.ops.paged_attention import (  # noqa: E402
    paged_index_scores, paged_work_list)
from apex_tpu.transformer.moe.dropless import dropless_moe_ffn  # noqa: E402
from apex_tpu.transformer.testing import standalone_keye as SK  # noqa: E402
from benchmark.bindings import dsa_keye as binding  # noqa: E402
from benchmark.references import keye_lm  # noqa: E402

TOL = 2e-4
PAD = keye_lm.ROW_BLOCK
TOPK = 16

#: a configuration file in the published keys, at toy sizes: 4 query / 2 KV
#: heads of 16 (2 + 3 + 3 rotary pairs by axis), 4 index heads of 8 picking
#: 16 positions, 8 experts of 16 (2 a token) in each of 3 layers
TINY = {
    "model_type": "KeyeVL2", "vocab_size": 96, "hidden_size": 32,
    "intermediate_size": 64, "moe_intermediate_size": 16,
    "num_hidden_layers": 3, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16,
    "max_position_embeddings": 128, "max_window_layers": 3,
    "attention_bias": False, "hidden_act": "silu", "rms_norm_eps": 1e-6,
    "num_experts": 8, "num_local_experts": 8, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [],
    "tie_word_embeddings": False, "rope_theta": 10000000,
    "rope_scaling": {"mrope_section": [2, 3, 3], "rope_type": "default",
                     "type": "default"},
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 4,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 16,
                  "q_chunk_size": 16, "topk": TOPK},
    "sliding_window": None, "use_sliding_window": False,
}
SPEC = keye_lm.spec_from_config(TINY)


def seeded(shapes, seed, std=0.2):
    """float32 weights large enough that positions decide tokens (at 0.02
    attention is all but uniform); norm gains 1 + noise."""
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
    kcfg, shapes = binding.model_of(TINY)
    kcfg = dataclasses.replace(kcfg, params_dtype=jnp.float32)
    params = seeded(shapes, 5)
    return kcfg, params, binding.reference_weights(TINY, params)


def reference(w, tokens, select="learned"):
    """The reference's logits of every real position of ``tokens``."""
    padded = np.zeros((PAD,), np.int32)
    padded[:len(tokens)] = tokens
    return np.asarray(keye_lm.logits(w, jnp.asarray(padded), 0, len(tokens),
                                     spec=SPEC, select=select))


def test_the_binding_maps_the_published_keys(tiny):
    kcfg, params, _ = tiny
    assert (kcfg.index_heads, kcfg.index_head_dim, kcfg.index_topk,
            kcfg.index_q_chunk) == (4, 8, 16, 16)
    assert kcfg.mrope_section == (2, 3, 3) and kcfg.rope_theta == 1e7
    lp = params["params"]["layer_1"]
    assert lp["indexer"]["q_proj"]["weight"].shape == (4 * 8, 32)
    assert lp["indexer"]["k_proj"]["weight"].shape == (8, 32)
    assert lp["indexer"]["w_proj"]["weight"].shape == (4, 32)
    assert lp["moe"]["experts"]["w_gate"].shape == (8, 32, 16)
    assert "shared" not in lp["moe"]
    rec = models.KINDS["keye"]
    assert rec.select is not None and rec.latent is None
    assert rec.stats == models.EXPERT_STATS + models.SELECT_STATS
    assert set(rec.refuses) == {"dense", "tp", "verify", "host_tier",
                                "fused", "prefix_sharing"}
    assert all("'keye'" in why for why in rec.refuses.values())


@pytest.mark.parametrize("n", [70, 96, 13])
def test_full_forward_and_prefill_match_the_reference(tiny, n):
    """Contexts far over ``topk`` (a prompt that ends mid-block, one that
    fills its blocks) and one under it, which is plain causal attention."""
    kcfg, params, w = tiny
    tokens = np.random.RandomState(n).randint(0, 96, size=n)
    want = reference(w, tokens)
    scale = np.abs(want).max()
    model = SK.keye_model_provider(kcfg)
    got = np.asarray(jax.jit(model.apply)(params,
                                            jnp.asarray(tokens[None])))[0]
    assert np.abs(got - want).max() < TOL * scale
    pre = models.prefill_forward("keye", kcfg, params,
                                 jnp.asarray(tokens[None], jnp.int32))
    assert np.abs(np.asarray(pre[0])[:, 0] - want).max() < TOL * scale
    # k, v and ONE index key a position a layer; no rings
    assert pre[1].shape == pre[2].shape == (3, 2, n, 16)
    assert pre[3] is None and pre[4] is None and pre[5].shape == (3, n, 8)
    stats = {k: int(v) for k, v in pre[6].items()}
    assert stats["dsa_rows"] == 3 * n
    assert stats["dsa_rows_sparse"] == 3 * max(n - TOPK, 0)
    assert stats["dsa_selected"] == 3 * sum(min(t + 1, TOPK)
                                            for t in range(n))
    if n > 4 * TOPK:        # ... and the wrong selections are far off
        for wrong in ("all", "recent"):
            off = np.abs(got - reference(w, tokens, wrong)).max()
            assert off > 50 * TOL * scale, (wrong, off / scale)


@pytest.fixture(scope="module")
def served(tiny):
    """Three slots at unlike lengths in one step — a prompt far over
    ``topk`` that ends mid-page, one under ``topk`` (it attends
    everything) and one of a few pages — prefilled, then 20 decode steps
    (over two pages of 8) through the engine's paged cache."""
    kcfg, params, w = tiny
    eng = InferenceEngine("keye", kcfg, params, slots=3, max_seq=128,
                          page_size=8, num_pages=48,
                          cache_dtype=jnp.float32,
                          sampling=SamplingConfig())
    assert eng.stats_names == models.EXPERT_STATS + models.SELECT_STATS
    assert eng.stats_tail == 7 and not eng.supports_prefix_sharing
    alloc = eng.new_allocator()
    cache = eng.init_cache()
    assert cache.k.shape == cache.v.shape == (49, 3, 2, 8, 16)
    assert cache.ik.shape == (49, 3, 8, 8)
    assert eng.page_host_bytes() == 8 * (2 * 2 * 16 + 8) * 4 * 3
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, 96, size=n) for n in (75, 3, 29)]
    steps = 20
    seqs, last, prefill_logits, tails = [], np.zeros((3,), np.int32), [], []
    for slot, p in enumerate(prompts):
        pages = alloc.acquire(alloc.pages_needed(len(p) + steps + 1))
        cache, tok, logits = eng.prefill(cache, p, slot, pages=pages)
        tok = np.asarray(tok)
        assert tok.shape == (1 + 7,)              # the token, the counters
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
    the reference's full forward over prompt + generated, per mode."""
    w, seqs, steps = served["w"], served["seqs"], served["steps"]
    for slot, p in enumerate(served["prompts"]):
        want = reference(w, np.asarray(seqs[slot][:-1]))
        scale = np.abs(want).max()
        assert np.abs(served["prefill_logits"][slot]
                      - want[len(p) - 1]).max() < TOL * scale
        for step in (0, 7, 8, steps - 1):        # incl. a page's first row
            got = served["step_logits"][step][slot]
            assert np.abs(got - want[len(p) + step]).max() < TOL * scale
        greedy = want[len(p) - 1:].argmax(-1)
        assert list(greedy) == seqs[slot][len(p):]
    # the counters rode the token read, behind the expert counters
    first, final = served["tails"][0], served["tails"][-1]
    assert list(first[4:]) == [3 * 75, 3 * (75 - TOPK),
                               3 * sum(min(t + 1, TOPK) for t in range(75))]
    lengths = [len(p) + steps for p in served["prompts"]]
    assert list(final[4:]) == [3 * 3, 3 * sum(n > TOPK for n in lengths),
                               3 * sum(min(n, TOPK) for n in lengths)]
    assert final[0] == 3 * 2 * 3 and final[3] == 0      # 2 experts a token


@pytest.mark.parametrize("wrong", ["all", "recent"])
def test_a_wrong_selection_fails_the_same_tolerance(served, wrong):
    """The same run judged against a reference that attends everything, or
    the most recent ``topk``: the long slots' decode logits are out by far
    more than the tolerance (a program that skipped the indexer would be
    noticed); the slot under ``topk`` at its first steps is not, since
    there every selection is every position."""
    w, seqs = served["w"], served["seqs"]
    for slot, p in enumerate(served["prompts"]):
        seq = np.asarray(seqs[slot][:-1])
        want, other = reference(w, seq), reference(w, seq, wrong)
        scale = np.abs(want).max()
        got = served["step_logits"][-1][slot]
        assert np.abs(got - want[-1]).max() < TOL * scale
        if len(p) > TOPK:
            assert np.abs(got - other[-1]).max() > 50 * TOL * scale
    short = np.asarray(seqs[1][:10])
    assert np.array_equal(reference(w, short), reference(w, short, wrong))


# --------------------------------------------------------------------------
# the selection alone, in float32
# --------------------------------------------------------------------------

def _index_parts(tiny, s, seed=9):
    """Program and reference index parts of one layer over the same random
    hidden states."""
    kcfg, params, w = tiny
    lp, lw = params["params"]["layer_1"], w["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(seed), (s, kcfg.hidden_size),
                          jnp.float32)
    pos = jnp.arange(s, dtype=jnp.int32)
    got = SK.index_project(kcfg, lp, h, *SK.index_rope_cos_sin(kcfg, pos))
    want = keye_lm.index_parts(h, lw, SPEC, jnp.broadcast_to(pos, (3, s)),
                               None)
    return got, want


def test_index_scores_against_a_numpy_loop(tiny):
    (qi, wi, ki), (rqi, rwi, rki) = _index_parts(tiny, 40)
    for a, b in ((qi, rqi), (wi, rwi), (ki, rki)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                                   atol=2e-6)
    q, wt, k = (np.asarray(x, np.float64) for x in (qi, wi, ki))
    want = np.zeros((40, 40))
    for t in range(40):
        for s in range(40):
            for j in range(q.shape[1]):
                want[t, s] += wt[t, j] * max(q[t, j] @ k[s], 0.0)
    for got in (index_scores(qi, wi, ki),
                index_scores_reference(qi, wi, ki)):
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                                   atol=1e-6)


def test_prefill_rows_pick_the_references_sets(tiny):
    """Row for row the program's picked set equals the reference's, as a
    set, over 96 rows of which 80 are cut by the selection."""
    s = 96
    (qi, wi, ki), (rqi, rwi, rki) = _index_parts(tiny, s)
    scores = index_scores(qi, wi, ki)
    causal = jnp.tril(jnp.ones((s, s), bool))
    got = np.asarray(select_top_mask(scores, TOPK, causal))
    padded = [jnp.pad(x, ((0, PAD - s),) + ((0, 0),) * (x.ndim - 1))
              for x in (rqi, rwi, rki)]
    want = np.asarray(keye_lm.picked_rows(*padded, 0, SPEC, None,
                                          "learned"))[:s, :s]
    assert np.array_equal(got, want)
    assert list(got.sum(1)) == [min(t + 1, TOPK) for t in range(s)]
    # ... and select_attention's own count of what each row attended
    q = jnp.zeros((1, 2, s, 16), jnp.float32)
    _, picked = select_attention(q, q, q, qi, wi, ki, topk=TOPK, block_q=16)
    assert list(np.asarray(picked)) == list(got.sum(1))


def test_ties_go_to_the_lower_position_as_top_k_does():
    """Equal scores — whole rows of them, as an index whose products are
    all rectified to zero gives — go to the lower position; candidates are
    picked before anything dead, whatever the dead hold."""
    rng = np.random.RandomState(0)
    scores = rng.randint(-3, 4, size=(12, 40)).astype(np.float32)
    scores[3] = 0.0
    scores[4, :] = -np.inf
    scores[5, ::2] = np.inf
    live = rng.rand(12, 40) < 0.8
    live[6] = False
    live[7, 5:] = False
    for k in (1, 7, 16, 40, 64):
        got = np.asarray(select_top_mask(jnp.asarray(scores), k,
                                         jnp.asarray(live)))
        for r in range(12):
            masked = np.where(live[r], scores[r], -np.inf)
            n = min(k, int(live[r].sum()))
            # top_k's order: by score, equal scores by position
            order = np.lexsort((np.arange(40), -masked))
            want = np.zeros(40, bool)
            live_first = [i for i in order if live[r, i]][:n]
            want[live_first] = True
            assert np.array_equal(got[r], want), (k, r)
    idx = np.asarray(jax.lax.top_k(jnp.asarray(scores[3]), 7)[1])
    assert list(idx) == list(range(7))


def test_decode_slots_pick_the_references_sets(tiny):
    """Index keys written through the paged pool (pages in a scrambled
    order), then the decode stages' index and select: slots at exactly
    ``topk``, at ``topk + 1``, far over it, under it and empty pick the
    reference's sets for their newest row."""
    s, ps, layer = 96, 8, 1
    (qi, wi, ki), (rqi, rwi, rki) = _index_parts(tiny, s)
    lengths = np.asarray([TOPK, TOPK + 1, 96, 5, 0, 41], np.int32)
    slots, mpps = len(lengths), 12
    cache = kv_cache.init_paged_cache(80, 3, 2, ps, 16, slots=slots,
                                      max_pages_per_slot=mpps,
                                      dtype=jnp.float32, index=8)
    free = list(np.random.RandomState(1).permutation(80))
    kv = jnp.zeros((3, 2, s, 16), jnp.float32)
    iks = jnp.zeros((3, s, 8), jnp.float32).at[layer].set(ki)
    for slot, n in enumerate(lengths):
        if n:
            pages = [int(free.pop()) for _ in range(-(-int(n) // ps))]
            cache = kv_cache.insert_tokens(
                cache, slot, kv, kv, int(n),
                kv_cache.page_row(pages, mpps, cache.null_page), 0, iks)
    last = np.maximum(lengths - 1, 0)
    work = paged_work_list(cache.page_table, jnp.asarray(lengths),
                           page_size=ps)
    scores = paged_index_scores(qi[last], wi[last], cache.ik, work,
                                layer=layer)
    assert scores.shape == (slots, mpps * ps)
    live = np.arange(mpps * ps)[None] < lengths[:, None]
    dense = np.asarray(index_scores_reference(qi[last], wi[last], ki))
    np.testing.assert_allclose(np.asarray(scores)[:, :s][live[:, :s]],
                               dense[live[:, :s]], rtol=1e-5, atol=1e-6)
    got = np.asarray(select_top_mask(scores, TOPK, jnp.asarray(live)))
    padded = [jnp.pad(x, ((0, PAD - s),) + ((0, 0),) * (x.ndim - 1))
              for x in (rqi, rwi, rki)]
    want = np.asarray(keye_lm.picked_rows(*padded, 0, SPEC, None,
                                          "learned"))
    for slot, n in enumerate(lengths):
        if n:
            assert np.array_equal(got[slot, :s], want[n - 1, :s]), slot
        assert got[slot].sum() == min(n, TOPK) and not got[slot, s:].any()


# --------------------------------------------------------------------------
# the pieces
# --------------------------------------------------------------------------

def test_three_section_rope_against_the_references_formula(tiny):
    """Three unlike axes: pair ``i`` turns by its section's axis; equal
    axes are plain RoPE, bit for bit."""
    kcfg = tiny[0]
    rng = np.random.RandomState(2)
    pos = jnp.asarray(rng.randint(0, 500, size=(3, 21)), jnp.int32)
    x = jnp.asarray(rng.randn(21, 4, 16), jnp.float32)
    cos, sin = SK.mrope_cos_sin(kcfg, *pos)
    from apex_tpu.transformer.functional.fused_rope import (
        fused_apply_rotary_pos_emb_cached as apply)
    got = apply(x, cos[:, None, :], sin[:, None, :])
    want = keye_lm.rope(x, SPEC, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # by hand: pair 1 is temporal, pair 3 height, pair 7 width
    for pair, axis in ((1, 0), (3, 1), (7, 2)):
        ang = np.asarray(pos[axis], np.float64) * 1e7 ** (-pair / 8)
        a, b = np.asarray(x[..., pair]), np.asarray(x[..., pair + 8])
        np.testing.assert_allclose(
            np.asarray(got[..., pair]),
            a * np.cos(ang)[:, None] - b * np.sin(ang)[:, None], atol=2e-5)
    text = pos[0]
    plain_inv = 1e7 ** (-jnp.arange(8, dtype=jnp.float32) / 8)
    freqs = text.astype(jnp.float32)[:, None] * plain_inv
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    for got, want in zip(SK.rope_cos_sin(kcfg, text),
                         (jnp.cos(emb), jnp.sin(emb))):
        assert np.array_equal(np.asarray(got), np.asarray(want))
    # the record's piece takes either form
    rec = models.KINDS["keye"]
    three = rec.rope(kcfg, None, tuple(pos), 21)
    assert np.array_equal(np.asarray(three[models.FULL][0]), np.asarray(cos))
    one = rec.rope(kcfg, None, text, 21)
    assert np.array_equal(np.asarray(one[models.FULL][0]),
                          np.asarray(SK.rope_cos_sin(kcfg, text)[0]))
    assert one[models.INDEX][0].shape == (21, 8)


def test_the_expert_layer_without_a_shared_expert(tiny):
    kcfg, params, w = tiny
    lp, lw = params["params"]["layer_2"], w["layers"][2]
    h = jax.random.normal(jax.random.PRNGKey(4), (50, 32), jnp.float32)
    got, stats = SK.ffn(kcfg, lp, h)
    want = keye_lm.expert_ffn(h, lw, SPEC, None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    assert int(stats["assignments"]) == 50 * 2
    m = lp["moe"]
    same, _ = dropless_moe_ffn(
        h, m["router"]["weight"], m["experts"]["w_gate"],
        m["experts"]["w_up"], m["experts"]["w_down"], top_k=2, scale=1.0,
        shared=None, held=None)
    assert np.array_equal(np.asarray(got), np.asarray(same))


#: sha1 of the text of the steps' jaxprs: the other expert kinds trace
#: what they traced before this kind came.  Until ISSUE 37 the parent's
#: (606b07b: f31617db…, 5df58311…); then every kind's steps carried one
#: more cache leaf (``last_tokens``: 7f0c9d20…, 1dedd3e7…); now a prefill
#: of a kind that never resumes writes its K/V as whole pages, with no
#: gather of the pages it writes (the decode steps are unchanged), and
#: these are the digests with it
PARENT_JAXPRS = {
    "laguna": "4283809ef6580852bfe719214b0715a2b51a3733",
    "axk1": "3789250dc24b4f4e9e356a3ee791799b2d3d3dd2",
}


def step_jaxpr_digest(kind: str) -> str:
    """Prefill and decode of ``kind``'s default toy config, as text."""
    from apex_tpu.inference.engine import make_decode_fn, make_prefill_fn
    from apex_tpu.transformer.testing import standalone_axk1 as SA
    from apex_tpu.transformer.testing import standalone_laguna as SL
    cfg, shapes = {
        "laguna": (SL.LagunaConfig(), SL.laguna_param_shapes),
        "axk1": (SA.AXK1Config(), SA.axk1_param_shapes)}[kind]
    params = {"params": jax.tree.map(
        lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32),
        shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))}
    eng_dims = models.model_dims(kind, cfg)
    cache = jax.eval_shape(lambda: kv_cache.init_paged_cache(
        20, eng_dims["pool_layers"], eng_dims["kv_heads"], 8,
        eng_dims["head_dim"], slots=2, max_pages_per_slot=8,
        window_layers=eng_dims["window_layers"], window=eng_dims["window"],
        latent=eng_dims["latent"]))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    decode = jax.make_jaxpr(make_decode_fn(kind, cfg, SamplingConfig()))(
        cache, params, i32(2), jax.ShapeDtypeStruct((2,), bool), key, i32())
    prefill = jax.make_jaxpr(make_prefill_fn(
        kind, cfg, SamplingConfig(), paged=True))(
        cache, params, i32(64), i32(), i32(), i32(8), i32(), key, i32())
    return hashlib.sha1((str(decode) + str(prefill)).encode()).hexdigest()


@pytest.mark.parametrize("kind", ["laguna", "axk1"])
def test_the_other_expert_kinds_trace_what_they_traced(kind):
    assert step_jaxpr_digest(kind) == PARENT_JAXPRS[kind]


# --------------------------------------------------------------------------
# under the scheduler: the normal path, the counters by name
# --------------------------------------------------------------------------

def test_the_scheduler_serves_the_kind_and_reads_its_counters_by_name(tiny):
    """``InferenceEngine("keye", paged)`` under ``SlotScheduler``: five
    requests over three slots finish by length, every page comes back, and
    the step's tail reached the telemetry BY NAME — the expert counters
    under their old names and labels, the selection's beside them."""
    from apex_tpu.inference import SlotScheduler
    from apex_tpu.observability import MetricsRegistry
    from apex_tpu.observability.serve import ServeTelemetry
    kcfg, params, _ = tiny
    eng = InferenceEngine("keye", kcfg, params, slots=3, max_seq=128,
                          page_size=8, num_pages=40,
                          cache_dtype=jnp.float32,
                          sampling=SamplingConfig())
    # a registry of its own: the global one carries the counts of every
    # scheduler the worker process ran before this test
    sched = SlotScheduler(eng, telemetry=ServeTelemetry(MetricsRegistry()))
    rng = np.random.RandomState(8)
    lengths = [40, 9, 70, 22, 31]
    sched.begin_run()
    for n in lengths:
        sched.submit(rng.randint(0, 96, size=n), max_new_tokens=6)
    while sched.run_pending():
        sched.run_pass()
    out = sched.finish_run()
    assert sorted(len(v) for v in out.values()) == [6] * 5
    assert set(sched.finish_reasons.values()) == {"length"}
    assert sched.alloc.live_pages == 0
    tel = sched.telemetry
    assert tel.moe_passes.value(phase="prefill") == 5
    assert tel.moe_passes.value(phase="decode") == tel.decode_steps.total()
    assert tel.moe_assignments.value(phase="prefill") == sum(lengths) * 2 * 3
    assert tel.dsa_rows.value(phase="prefill") == sum(lengths) * 3
    assert tel.dsa_rows_sparse.value(phase="prefill") == 3 * sum(
        max(n - TOPK, 0) for n in lengths)
    assert tel.dsa_selected.value(phase="prefill") == 3 * sum(
        min(t + 1, TOPK) for n in lengths for t in range(n))
    # five decoded tokens a request (the first came with the prefill)
    assert tel.dsa_rows.value(phase="decode") == 3 * 5 * 5
    assert tel.dsa_selected.value(phase="decode") == 3 * sum(
        min(n + j, TOPK) for n in lengths for j in range(1, 6))
    assert tel.window_pages_live_peak.value() == 0
    with pytest.raises(AttributeError):
        tel.step_counters("decode", {"no_such_counter": 1})


def test_every_select_family_is_in_the_pinned_schema():
    import json
    from apex_tpu.observability.schema import SCHEMA_NAME, current_schema
    from apex_tpu.observability.serve import (EXPERT_METRIC_FAMILIES,
                                              SELECT_METRIC_FAMILIES)
    pinned = json.loads((REPO / SCHEMA_NAME).read_text())
    assert pinned == current_schema()
    for name in SELECT_METRIC_FAMILIES + EXPERT_METRIC_FAMILIES:
        assert name in pinned["prometheus"], name
        if name.endswith("_total") and "dsa" in name:
            assert pinned["prometheus"][name]["labels"] == ["phase"]
