"""The ``axk1`` kind (ISSUE 34) against the benchmark's plain reference,
``benchmark/references/axk1_lm.py`` — the same file the chip runs judge the
served tokens with.  Tiny sizes, seeded float32 weights.

Tolerance: both sides compute in float32 on the CPU (the Pallas kernels in
interpret mode, the reference at ``Precision.HIGHEST``); what differs is the
order of accumulation (blockwise online softmax, the absorbed form's
re-association of the up-projections, grouped products over sorted rows).
``TOL`` = 2e-4 of the largest reference logit holds that with room; plain
frequencies where YaRN's belong, or a softmax scale without ``mscale**2``,
move the logits by percents (checked below), so the tolerance has teeth.
"""
import dataclasses
import json
import math
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
from apex_tpu.inference import models  # noqa: E402
from apex_tpu.inference.step_vector import peel_step  # noqa: E402
from apex_tpu.transformer.testing import standalone_axk1 as SA  # noqa: E402
from apex_tpu.transformer.testing.standalone_laguna import (  # noqa: E402
    YarnRope, yarn_inv_freq)
from benchmark.bindings import axk1 as binding  # noqa: E402
from benchmark.references import axk1_lm  # noqa: E402

TOL = 2e-4
PAD = axk1_lm.ROW_BLOCK

#: a configuration file in the published keys, at toy sizes: 4 heads of
#: 16 + 8 over a latent of 24 + 8, a dense layer then two expert layers
#: whose router scores 16 experts in 4 groups (2 kept, 4 a token) and
#: whose chip holds experts 4..11
TINY = {
    "model_type": "axk1", "vocab_size": 96, "hidden_size": 32,
    "intermediate_size": 64, "moe_intermediate_size": 16,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "moe_layer_freq": 1, "num_attention_heads": 4,
    "num_key_value_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 24,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "max_position_embeddings": 128, "attention_bias": False,
    "hidden_act": "silu", "rms_norm_eps": 1e-6, "n_routed_experts": 8,
    "held_experts_first": 4, "published": {"n_routed_experts": 16},
    "n_shared_experts": 1, "num_experts_per_tok": 4, "n_group": 4,
    "topk_group": 2, "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "topk_method": "none",
    "tie_word_embeddings": False, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 32,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 16,
                     "type": "yarn"},
}


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
    acfg, shapes = binding.model_of(TINY)
    acfg = dataclasses.replace(acfg, params_dtype=jnp.float32)
    params = seeded(shapes, 5)
    return acfg, params, binding.reference_weights(TINY, params)


def reference(w, tokens, spec=None):
    """The reference's logits of every real position of ``tokens``."""
    padded = np.zeros((PAD,), np.int32)
    padded[:len(tokens)] = tokens
    return np.asarray(axk1_lm.logits(
        w, jnp.asarray(padded), 0, len(tokens),
        spec=spec or axk1_lm.spec_from_config(TINY)))


def test_the_binding_maps_the_published_keys(tiny):
    acfg, params, _ = tiny
    assert acfg.held == (4, 8) and acfg.num_experts == 16
    assert acfg.latent_dim == 32 and acfg.qk_head_dim == 24
    moe = params["params"]["layer_1"]["moe"]
    # the router keeps every expert's row, the stacks the held ones
    assert moe["router"]["weight"].shape == (16, 32)
    assert moe["experts"]["w_gate"].shape == (8, 32, 16)
    assert SA.softmax_scale(acfg) == pytest.approx(
        24 ** -0.5 * (0.1 * math.log(32) + 1) ** 2)


def test_full_forward_and_prefill_match_the_reference(tiny):
    acfg, params, w = tiny
    tokens = np.random.RandomState(1).randint(0, 96, size=40)
    want = reference(w, tokens)
    scale = np.abs(want).max()
    model = SA.axk1_model_provider(acfg)
    got = np.asarray(jax.jit(model.apply)(params,
                                            jnp.asarray(tokens[None])))[0]
    assert np.abs(got - want).max() < TOL * scale
    pre = models.prefill_forward("axk1", acfg, params,
                                 jnp.asarray(tokens[None], jnp.int32))
    assert np.abs(np.asarray(pre[0])[:, 0] - want).max() < TOL * scale
    # what is cached is the latent row, never the expanded k/v
    assert pre[1].shape == (3, 40, 32) and pre[2] is None
    assert pre[3] is None and pre[4] is None


def test_the_tolerance_has_teeth(tiny):
    """Plain frequencies where YaRN's belong, a scale without mscale**2, a
    group more kept by the router: each moves the reference by far more
    than ``TOL``."""
    _, _, w = tiny
    tokens = np.random.RandomState(2).randint(0, 96, size=40)
    spec = axk1_lm.spec_from_config(TINY)
    want = reference(w, tokens)
    scale = np.abs(want).max()
    for other in (spec._replace(yarn_factor=1.0),
                  spec._replace(mscale_all_dim=0.0),
                  spec._replace(topk_group=3)):
        assert np.abs(reference(w, tokens, other) - want).max() \
            > 50 * TOL * scale


def test_prefill_then_decode_through_the_latent_pool(tiny):
    """Three slots at unlike lengths in one step — a prompt that ends
    mid-page, one of a single page, one of many — then 20 decode steps
    (five pages of 4): every step's greedy token and the last step's
    logits against the reference's full forward over prompt + generated."""
    acfg, params, w = tiny
    eng = InferenceEngine("axk1", acfg, params, slots=3, max_seq=128,
                          page_size=4, num_pages=60,
                          cache_dtype=jnp.float32,
                          sampling=SamplingConfig())
    assert eng.stats_tail == 4 and not eng.supports_prefix_sharing
    alloc = eng.new_allocator()
    cache = eng.init_cache()
    assert cache.k.shape == (61, 3, 32, 4) and cache.v is None
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, 96, size=n) for n in (5, 31, 4)]
    steps = 20
    seqs, last = [], np.zeros((3,), np.int32)
    for slot, p in enumerate(prompts):
        pages = alloc.acquire(alloc.pages_needed(len(p) + steps + 1))
        cache, tok, logits = eng.prefill(cache, p, slot, pages=pages)
        tok = np.asarray(tok)
        assert tok.shape == (1 + 4,)              # the token, the counters
        want = reference(w, p)[-1]
        assert np.abs(np.asarray(logits) - want).max() \
            < TOL * np.abs(want).max()
        seqs.append(list(p) + [int(tok[0])])
        last[slot] = tok[0]
    for _ in range(steps):
        cache, toks, logits, truncated = eng.decode(cache, last)
        toks, flags, tail = peel_step(np.asarray(toks), 3, eng.stats_tail)
        assert (toks.shape, flags.shape, tail.shape) == ((3,), (3,), (4,))
        assert not flags.any() and not np.asarray(truncated).any()
        for slot in range(3):
            seqs[slot].append(int(toks[slot]))
        last = toks.copy()
        step_logits = np.asarray(logits)
    for slot, p in enumerate(prompts):
        seq = np.asarray(seqs[slot][:-1])
        want = reference(w, seq)
        scale = np.abs(want).max()
        assert np.abs(step_logits[slot] - want[-1]).max() < TOL * scale
        greedy = want[len(p) - 1:].argmax(-1)
        assert list(greedy) == seqs[slot][len(p):]
    # the counters rode the token read: of 3 tokens x 4 x 2 expert layers
    # only those that landed on the 8 held experts are counted
    assert 0 <= tail[0] <= 3 * 4 * 2 and tail[1] <= 2 * 8
    assert tail[2] <= 3 and tail[3] == 0          # no window rings


def test_absorbed_attention_is_expanded_attention(tiny):
    """The two forms are the same function: one layer's attention output
    for the LAST position of a sequence, expanded over the whole sequence
    against absorbed over the latent rows, float32, 1e-5 relative."""
    acfg, params, _ = tiny
    lp = params["params"]["layer_1"]
    s = 37
    h = jax.random.normal(jax.random.PRNGKey(9), (s, acfg.hidden_size),
                          jnp.float32)
    cos, sin = (c[:, None, :] for c in SA.rope_cos_sin(
        acfg, jnp.arange(s, dtype=jnp.int32)))
    scale = SA.softmax_scale(acfg)
    with jax.default_matmul_precision("highest"):
        q, k, v, rows = SA.attn_expand(acfg, lp, h, cos, sin)
        sc = jnp.einsum("hd,khd->hk", q[-1], k) * scale
        expanded = jnp.einsum("hk,khd->hd", jax.nn.softmax(sc, -1), v)
        qa, row = SA.attn_absorb(acfg, lp, h[-1:], cos[-1:], sin[-1:])
        np.testing.assert_allclose(row[0], rows[-1], rtol=1e-6, atol=1e-6)
        sa = jnp.einsum("hc,kc->hk", qa[0], rows) * scale
        u = jnp.einsum("hk,kc->hc", jax.nn.softmax(sa, -1),
                       rows[:, :acfg.kv_lora_rank])
        absorbed = SA.attn_value_up(acfg, lp, u)
    np.testing.assert_allclose(np.asarray(sa), np.asarray(sc), rtol=1e-5,
                               atol=1e-5 * float(jnp.abs(sc).max()))
    np.testing.assert_allclose(
        np.asarray(absorbed), np.asarray(expanded), rtol=1e-5,
        atol=1e-5 * float(jnp.abs(expanded).max()))


def test_yarn_table_at_the_published_parameters():
    """Base 1e4 over 64 roped channels, factor 32, original 4096,
    beta_fast 32, beta_slow 1.  ``c(r) = 64 ln(4096 / (2 pi r)) / (2 ln
    1e4)``: c(32) = 10.47 -> low 10; c(1) = 22.51 -> high 23."""
    cfg = json.loads((REPO / "benchmark" / "configs"
                      / "a.x-k1-serve.json").read_text())
    spec = axk1_lm.spec_from_config(cfg)
    acfg = binding._program_config(cfg)
    assert acfg.rope == YarnRope(
        theta=10000.0, rotary_dim=64, factor=32.0,
        original_max_position=4096, beta_fast=32.0, beta_slow=1.0,
        attention_factor=1.0)
    inv = yarn_inv_freq(acfg.rope)
    c32 = 64 * math.log(4096 / (2 * math.pi * 32)) / (2 * math.log(1e4))
    c1 = 64 * math.log(4096 / (2 * math.pi)) / (2 * math.log(1e4))
    assert math.floor(c32) == 10 and math.ceil(c1) == 23
    for i in (0, 10):                     # at or under low: plain RoPE
        assert inv[i] == pytest.approx(1e4 ** (-2 * i / 64), rel=1e-12)
    for i in (23, 31):                    # at or over high: divided by 32
        assert inv[i] == pytest.approx(1e4 ** (-2 * i / 64) / 32, rel=1e-12)
    extra = 1e4 ** (-32 / 64)             # i = 16: ramp 6/13
    assert inv[16] == pytest.approx(
        extra / 32 * (6 / 13) + extra * (7 / 13), rel=1e-12)
    # the reference computes the same table, and the same scale
    assert axk1_lm.yarn_inv_freq(spec) == pytest.approx(inv, rel=1e-12)
    assert axk1_lm.softmax_scale(spec) == pytest.approx(
        SA.softmax_scale(acfg), rel=1e-12)
    assert SA.softmax_scale(acfg) == pytest.approx(
        192 ** -0.5 * 1.3465735902799727 ** 2, rel=1e-9)
