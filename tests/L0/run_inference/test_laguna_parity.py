"""The ``laguna`` kind (ISSUE 30) against the benchmark's plain reference,
``benchmark/references/laguna_lm.py`` — the same file the chip runs judge
the served tokens with.  Tiny sizes, seeded float32 weights.

Tolerance: both sides compute in float32 on the CPU (the Pallas kernels in
interpret mode, the reference at ``Precision.HIGHEST``); what differs is the
order of accumulation (blockwise online softmax, grouped products over
sorted rows, RMSNorm through the fused kernel) — a few 1e-6 of the largest
logit a layer.  ``TOL`` = 2e-4 of the largest reference logit holds that with
room; one window position more or less, or an unrotated channel, moves the
logits by percents (checked below), so the tolerance has teeth.
"""
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
from apex_tpu.transformer.testing import standalone_laguna as SL  # noqa: E402
from benchmark.bindings import moe_laguna as binding  # noqa: E402
from benchmark.references import laguna_lm  # noqa: E402

TOL = 2e-4
PAD = laguna_lm.ROW_BLOCK

#: a configuration file in the published keys, at toy sizes: heads per
#: layer that differ (4 / 6 over 2 KV heads), window 8 under page 4 (a ring
#: of 12 rows), a dense layer then expert layers, full / sliding / sliding /
#: full
TINY = {
    "vocab_size": 96, "hidden_size": 32, "intermediate_size": 64,
    "num_hidden_layers": 4, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16,
    "max_position_embeddings": 128, "attention_bias": False,
    "rms_norm_eps": 1e-6, "num_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 16, "shared_expert_intermediate_size": 16,
    "tie_word_embeddings": False, "gating": True, "sliding_window": 8,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 4,
            "original_max_position_embeddings": 32, "beta_slow": 1,
            "beta_fast": 4, "attention_factor": 1.1386294361119891,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    "layer_types": ["full_attention", "sliding_attention",
                    "sliding_attention", "full_attention"],
    "moe_apply_router_weight_on_input": False,
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse"],
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [4, 6, 6, 4],
}


def seeded(shapes, seed, std=0.2):
    """float32 weights large enough that positions and the window decide
    tokens (at 0.02 attention is all but uniform); norm gains 1 + noise."""
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
    import dataclasses
    lcfg, shapes = binding.model_of(TINY)
    lcfg = dataclasses.replace(lcfg, params_dtype=jnp.float32)
    params = seeded(shapes, 5)
    return lcfg, params, binding.reference_weights(TINY, params)


def reference(w, tokens, spec=None):
    """The reference's logits of every real position of ``tokens``."""
    padded = np.zeros((PAD,), np.int32)
    padded[:len(tokens)] = tokens
    return np.asarray(laguna_lm.logits(
        w, jnp.asarray(padded), 0, len(tokens),
        spec=spec or laguna_lm.spec_from_config(TINY)))


def test_full_forward_and_prefill_match_the_reference(tiny):
    lcfg, params, w = tiny
    tokens = np.random.RandomState(1).randint(0, 96, size=40)
    want = reference(w, tokens)
    scale = np.abs(want).max()
    model = SL.laguna_model_provider(lcfg)
    got = np.asarray(jax.jit(model.apply)(params,
                                            jnp.asarray(tokens[None])))[0]
    assert np.abs(got - want).max() < TOL * scale
    pre = models.prefill_forward("laguna", lcfg, params,
                                 jnp.asarray(tokens[None], jnp.int32))
    assert np.abs(np.asarray(pre[0])[:, 0] - want).max() < TOL * scale
    # the pool's layers and the rings' layers, by layer type
    assert pre[1].shape == (2, 2, 40, 16) and pre[3].shape == (2, 2, 40, 16)


def test_the_tolerance_has_teeth(tiny):
    """One window position more, or plain frequencies where YaRN's belong,
    moves the reference by far more than ``TOL``."""
    _, _, w = tiny
    tokens = np.random.RandomState(2).randint(0, 96, size=40)
    spec = laguna_lm.spec_from_config(TINY)
    want = reference(w, tokens)
    scale = np.abs(want).max()
    wider = reference(w, tokens, spec._replace(window=spec.window + 1))
    plain = reference(w, tokens, spec._replace(yarn_factor=1.0))
    assert np.abs(wider - want).max() > 50 * TOL * scale
    assert np.abs(plain - want).max() > 50 * TOL * scale


def test_prefill_then_decode_through_both_pools(tiny):
    """Three slots at unequal lengths — one prompt shorter than the window,
    one longer than window and ring — then 48 decode steps: the ring of 12
    rows wraps four times.  Every step's logits against the reference's
    full forward over prompt + generated tokens."""
    lcfg, params, w = tiny
    eng = InferenceEngine("laguna", lcfg, params, slots=3, max_seq=128,
                          page_size=4, num_pages=90,
                          cache_dtype=jnp.float32,
                          sampling=SamplingConfig())
    assert eng.stats_tail == 4 and not eng.supports_prefix_sharing
    alloc = eng.new_allocator()
    cache = eng.init_cache()
    # two pools in one cache: 2 full layers paged, 2 window layers ringed
    assert cache.k.shape == (91, 2, 2, 4, 16)
    assert cache.wk.shape == (2, 3, 2, 12, 16) and cache.ring == 12
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, 96, size=n) for n in (5, 31, 14)]
    steps = 48
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
    # the last step's logits of every slot, and each slot's whole greedy
    # stream, against one reference pass per slot
    for slot, p in enumerate(prompts):
        seq = np.asarray(seqs[slot][:-1])
        want = reference(w, seq)
        scale = np.abs(want).max()
        assert np.abs(step_logits[slot] - want[-1]).max() < TOL * scale
        greedy = want[len(p) - 1:].argmax(-1)
        assert list(greedy) == seqs[slot][len(p):]
    # the counters rode the token read: assignments = live tokens x top_k
    # x expert layers; window pages never above slots x ring pages
    assert tail[0] == 3 * 2 * 3 and 1 <= tail[1] <= 3 * 8
    assert tail[2] <= 3 and tail[3] == 3 * 3


def test_yarn_table_against_hand_computed_values():
    """Published parameters: base 5e5 over 64 rotated channels, factor 64,
    original 4096, beta_fast 64, beta_slow 1.  ``c(r) = 64 ln(4096 / (2 pi
    r)) / (2 ln 5e5)``: c(64) = 5.66 -> low 5; c(1) = 15.80 -> high 16."""
    rope = SL.YarnRope()
    inv = SL.yarn_inv_freq(rope)
    assert len(inv) == 32
    c64 = 64 * math.log(4096 / (2 * math.pi * 64)) / (2 * math.log(5e5))
    c1 = 64 * math.log(4096 / (2 * math.pi)) / (2 * math.log(5e5))
    assert math.floor(c64) == 5 and math.ceil(c1) == 16
    for i in (0, 5):                      # at or under low: plain RoPE
        assert inv[i] == pytest.approx(5e5 ** (-2 * i / 64), rel=1e-12)
    for i in (16, 31):                    # at or over high: divided by 64
        assert inv[i] == pytest.approx(5e5 ** (-2 * i / 64) / 64, rel=1e-12)
    # half way up the ramp, by hand: i = 10 -> ramp 5/11
    extrap = 5e5 ** (-20 / 64)
    assert inv[10] == pytest.approx(
        extrap / 64 * (5 / 11) + extrap * (6 / 11), rel=1e-12)
    # the reference file computes the same table from the published keys
    import json
    cfg = json.loads((REPO / "benchmark" / "configs"
                      / "laguna-xs.2-serve.json").read_text())
    spec = laguna_lm.spec_from_config(cfg)
    assert laguna_lm.yarn_inv_freq(spec) == pytest.approx(inv, rel=1e-12)
    assert spec.attention_factor == 1.4158883083359672
    # cos / sin carry the attention factor; sliding layers rotate the head
    lcfg = binding._program_config(cfg)
    cos, _ = SL.rope_cos_sin(lcfg, SL.FULL, jnp.zeros((1,), jnp.int32))
    assert cos.shape == (1, 64)
    assert float(cos[0, 0]) == pytest.approx(1.4158883083359672, rel=1e-6)
    cos, _ = SL.rope_cos_sin(lcfg, SL.SLIDING, jnp.arange(2))
    assert cos.shape == (2, 128) and float(cos[0, 0]) == 1.0


#: every (kind, feature) some record refuses, and the words each reason
#: has carried since ISSUE 30 — kept letter for letter by the records
REFUSED = [(kind, feature) for kind, rec in models.KINDS.items()
           for feature in rec.refuses]
WORDS = {
    "laguna": {
        "dense": "serves from the paged cache only",
        "tp": "tp > 1 is not built for the 'laguna' kind",
        "verify": "speculative verify is not built for the 'laguna' kind",
        "host_tier": "the host KV tier is not built for the 'laguna' kind",
        "fused": "fused_block_decode is not built for the 'laguna' kind",
        "prefix_sharing": "the 'laguna' kind prefills a prompt whole"},
    # ISSUE 34: each with the module that would have to change
    "axk1": {
        "dense": "serves from the paged cache only",
        "tp": "tp > 1 is not built for the 'axk1' kind: "
              "models.param_partition_specs",
        "verify": "speculative verify is not built for the 'axk1' kind: "
                  "ops/paged_attention.py",
        "host_tier": "the host KV tier is not built for the 'axk1' kind: "
                     "kv_cache.HostPageStore",
        "fused": "fused_block_decode is not built for the 'axk1' kind: "
                 "ops/paged_attention.py",
        "prefix_sharing": "chunked prefill, is not built for the 'axk1' "
                          "kind: models._suffix_attend"},
    # ISSUE 36: likewise
    "keye": {
        "dense": "serves from the paged cache only",
        "tp": "tp > 1 is not built for the 'keye' kind: "
              "models.param_partition_specs",
        "verify": "speculative verify is not built for the 'keye' kind: "
                  "ops/paged_attention.py",
        "host_tier": "the host KV tier is not built for the 'keye' kind: "
                     "kv_cache.HostPageStore",
        "fused": "fused_block_decode is not built for the 'keye' kind: "
                 "ops/paged_attention.py",
        "prefix_sharing": "chunked prefill, is not built for the 'keye' "
                          "kind: models._suffix_attend"},
    # and the sixth kind, likewise
    "hy4": {
        "dense": "serves from the paged cache only",
        "tp": "tp > 1 is not built for the 'hy4' kind: "
              "models.param_partition_specs",
        "verify": "speculative verify is not built for the 'hy4' kind: "
                  "ops/paged_attention.py",
        "host_tier": "the host KV tier is not built for the 'hy4' kind: "
                     "kv_cache.HostPageStore",
        "fused": "fused_block_decode is not built for the 'hy4' kind: "
                 "ops/paged_attention.py",
        "prefix_sharing": "chunked prefill, is not built for the 'hy4' "
                          "kind: models._suffix_attend"},
    # and the seventh, likewise
    "dots3": {
        "dense": "serves from the paged cache only",
        "tp": "tp > 1 is not built for the 'dots3' kind: "
              "models.param_partition_specs",
        "verify": "speculative verify is not built for the 'dots3' kind: "
                  "ops/paged_attention.py",
        "host_tier": "the host KV tier is not built for the 'dots3' kind: "
                     "kv_cache.HostPageStore",
        "fused": "fused_block_decode is not built for the 'dots3' kind: "
                 "ops/paged_attention.py",
        "prefix_sharing": "chunked prefill, is not built for the 'dots3' "
                          "kind: models._suffix_attend"}}
#: how each feature is asked of an engine at construction
ASKED = {"dense": dict(page_size=None, num_pages=None), "tp": dict(tp=2),
         "verify": dict(spec_k=2),
         "host_tier": dict(host_tier_bytes=1 << 20),
         "fused": dict(decode_fusion="1")}


@pytest.fixture(scope="module")
def toys(tiny):
    """kind -> (config, params) at toy size; a kind new here: its toy."""
    from apex_tpu.transformer.testing import standalone_axk1 as SA
    from apex_tpu.transformer.testing import standalone_dots3 as SD
    from apex_tpu.transformer.testing import standalone_hy4 as SH
    from apex_tpu.transformer.testing import standalone_keye as SK
    acfg, kcfg, hcfg = SA.AXK1Config(), SK.KeyeConfig(), SH.HY4Config()
    dcfg = SD.Dots3Config()
    tokens = jnp.zeros((1, 8), jnp.int32)

    def filled(shapes):
        return {"params": jax.tree.map(
            lambda shape: jnp.full(shape, 0.02, jnp.float32), shapes,
            is_leaf=lambda x: isinstance(x, tuple))}
    return {"laguna": tiny[:2],
            "hy4": (hcfg, filled(SH.hy4_param_shapes(hcfg))),
            "dots3": (dcfg, filled(SD.dots3_param_shapes(dcfg))),
            "axk1": (acfg, SA.axk1_model_provider(acfg).init(
                jax.random.PRNGKey(0), tokens)),
            "keye": (kcfg, SK.keye_model_provider(kcfg).init(
                jax.random.PRNGKey(0), tokens))}


@pytest.mark.parametrize("kind,feature", REFUSED)
def test_what_is_not_built_for_the_kind_is_refused_with_its_reason(
        toys, kind, feature):
    import re
    from apex_tpu.inference import SlotScheduler
    lcfg, params = toys[kind]
    why = models.KINDS[kind].refuses[feature]
    assert WORDS[kind][feature] in why
    kw = dict(slots=2, max_seq=64, page_size=4, num_pages=40)
    if feature in ASKED:
        with pytest.raises(ValueError, match=re.escape(why)):
            InferenceEngine(kind, lcfg, params, **dict(kw, **ASKED[feature]))
    if feature == "tp":
        with pytest.raises(ValueError, match=re.escape(why)):
            models.tp_dims(kind, lcfg, 2)
    if feature == "verify":
        with pytest.raises(ValueError, match=re.escape(why)):
            models.verify_forward(kind, lcfg, params, None,
                                  jnp.zeros((2, 3), jnp.int32))
    if feature == "fused":
        with pytest.raises(ValueError, match=re.escape(why)):
            models.fused_layer_params(kind, lcfg, params)
    if feature == "prefix_sharing":
        eng = InferenceEngine(kind, lcfg, params, **kw)
        assert not eng.supports_prefix_sharing
        with pytest.raises(ValueError, match="prefix sharing is not built"):
            SlotScheduler(eng, prefix_cache=True)
        with pytest.raises(ValueError, match="chunked prefill is not built"):
            SlotScheduler(eng, prefill_chunk=8)
        assert SlotScheduler(eng).prefix is None
        cache = eng.init_cache()
        with pytest.raises(ValueError, match=re.escape(why)):
            eng.prefill(cache, list(range(9)), 0, pages=[0, 1, 2],
                        prefill_from=4)
        with pytest.raises(ValueError, match=re.escape(why)):
            models.prefill_forward(kind, lcfg, params,
                                   jnp.zeros((1, 8), jnp.int32), 5,
                                   cache=cache, row=cache.page_table[0],
                                   prefill_from=0)


def _step_jaxprs(eng):
    """The engine's own prefill and decode step bodies, traced."""
    from apex_tpu.inference import kv_cache
    cache = eng.init_cache()
    row = kv_cache.page_row([0, 1], eng.max_pages_per_slot, eng.num_pages)
    key, step = eng._key, np.int32(0)
    pre = jax.make_jaxpr(eng._prefill_raw)(
        cache, eng.params, np.zeros((8,), np.int32), np.int32(0),
        np.int32(5), row, np.int32(0), key, step)
    dec = jax.make_jaxpr(eng._decode_raw)(
        cache, eng.params, np.zeros((eng.slots,), np.int32),
        np.ones((eng.slots,), bool), key, step)
    return cache, pre.jaxpr, dec.jaxpr


def _token_eqn(jaxpr, cache):
    """The equation that writes a step's token output (the first output
    after the cache's leaves)."""
    tok = jaxpr.outvars[len(jax.tree_util.tree_leaves(cache))]
    return tok, next(e for e in jaxpr.eqns if tok in e.outvars)


def test_a_kind_without_stats_or_rings_traces_neither(tiny):
    """What keeps GPT's program what it was before the kinds shared one
    step body: both are static facts of the record, so a kind without
    them has no counters behind its tokens and no ring among its operands
    (a prefill's token is a bare scalar, a decode step's one array for the
    host is ``[tokens | truncated]`` and no longer); ``laguna``'s tail is
    as long as its record's ``stats``."""
    from apex_tpu.transformer import parallel_state
    from apex_tpu.transformer.testing import GPTConfig, gpt_model_provider
    parallel_state.destroy_model_parallel()
    parallel_state.initialize_model_parallel(1)
    gcfg = GPTConfig(num_layers=2, hidden_size=32, num_attention_heads=2,
                     vocab_size=96, max_seq_length=64)
    gparams = gpt_model_provider(gcfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    kw = dict(slots=2, max_seq=64, page_size=4, num_pages=40)
    eng = InferenceEngine("gpt", gcfg, gparams, **kw)
    rec = models.KINDS["gpt"]
    assert rec.stats == () and eng.stats_tail == 0
    assert not rec.dims(gcfg)["window_layers"]
    cache, pre, dec = _step_jaxprs(eng)
    assert cache.wk is None and cache.wv is None        # no ring operand
    ring_free = len(jax.tree_util.tree_leaves((cache, eng.params))) + 4
    assert len(dec.invars) == ring_free
    tok, eqn = _token_eqn(pre, cache)
    assert tok.aval.shape == () and eqn.primitive.name != "concatenate"
    tok, eqn = _token_eqn(dec, cache)
    assert tok.aval.shape == (2 + 2,) and len(eqn.invars) == 2

    lcfg, params, _ = tiny
    eng = InferenceEngine("laguna", lcfg, params, **kw)
    tail = len(models.KINDS["laguna"].stats)
    assert eng.stats_tail == tail == 4
    cache, pre, dec = _step_jaxprs(eng)
    assert cache.wk is not None
    for jaxpr, shape, parts in ((pre, (1 + tail,), 2),
                                (dec, (2 + 2 + tail,), 3)):
        tok, eqn = _token_eqn(jaxpr, cache)
        assert tok.aval.shape == shape
        assert eqn.primitive.name == "concatenate"
        assert len(eqn.invars) == parts
