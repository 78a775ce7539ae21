"""Binding of the program's ``gpt`` kind (``transformer/testing``'s
standalone GPT under ``InferenceEngine("gpt", paged)``): everything the
serving driver has to know of THIS model and nothing of the loop.  A
configuration names its binding under ``"binding"``; another model kind is
another file here with the same five functions.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import harness as H
from ..drivers.train import LAYER_LEAVES, dig
from ..references import gpt_lm
from ..references.transformer import LAYER_KEYS

_TOP_LEAVES = {
    ("embedding", "word_embeddings", "weight"): "wte",
    ("embedding", "position_embeddings"): "wpe",
    ("final_layernorm", "weight"): "lnf_g",
    ("final_layernorm", "bias"): "lnf_b",
}


def _program_config(cfg):
    from apex_tpu.transformer.testing import GPTConfig

    return GPTConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        max_seq_length=cfg["max_position_embeddings"], hidden_dropout=0.0,
        attention_dropout=0.0, params_dtype=jnp.bfloat16)


def check_supported(cfg) -> None:
    """Does this checkout's program serve the kind?  Asked before any
    weight is made, so that a commit without it exits in seconds."""
    try:
        from apex_tpu.inference.models import check_supported as serves
        serves("gpt", _program_config(cfg))
    except (ImportError, TypeError, ValueError) as e:
        raise H.Refused(f"this checkout's program does not serve the "
                        f"'gpt' kind as configured: {e}") from e


def model_of(cfg):
    """The program's model config and the tree of served shapes."""
    from apex_tpu.transformer import parallel_state
    from apex_tpu.transformer.testing import gpt_model_provider

    parallel_state.destroy_model_parallel()
    parallel_state.initialize_model_parallel(1)
    gcfg = _program_config(cfg)
    model = gpt_model_provider(gcfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    # every leaf in the type it is served in (the engine would round the
    # float32 LayerNorm leaves itself; the reference must see what is served)
    return gcfg, jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16), shapes)


def engine(cfg, gcfg, mix, params, seed: int):
    from apex_tpu.inference import InferenceEngine, SamplingConfig

    return InferenceEngine(
        "gpt", gcfg, params, slots=mix["slots"],
        max_seq=cfg["max_position_embeddings"],
        page_size=mix["page_size"], num_pages=mix["pool_pages"],
        dtype=jnp.bfloat16, sampling=SamplingConfig(),
        seed=seed & 0x7FFFFFFF)


def reference_weights(cfg, params) -> dict:
    """The benchmark's own weights, regrouped as ``gpt_lm`` names them."""
    p = params["params"]
    f32 = lambda x: jnp.asarray(x, jnp.float32)         # noqa: E731
    out = {ref: f32(dig(p, prog)) for prog, ref in _TOP_LEAVES.items()}
    out["layers"] = {
        ref: jnp.stack([f32(dig(p[f"layer_{i}"], prog))
                        for i in range(cfg["num_hidden_layers"])])
        for prog, ref in LAYER_LEAVES.items()}
    assert set(out["layers"]) == set(LAYER_KEYS)
    return out


def reference_logits(cfg, w, padded, first: int, rows: int, quant=None):
    """The reference's float32 logits ``[rows, vocab]`` of the ``rows``
    positions from ``first`` on (held to the last one) of the one sequence
    ``padded``.  ``gpt_lm`` makes the whole sequence's; a binding whose
    sequences are long computes the judged rows alone."""
    ref = gpt_lm.logits(w, jnp.asarray(padded),
                        heads=cfg["num_attention_heads"], quant=quant)
    return ref[jnp.clip(first + jnp.arange(rows), 0, ref.shape[0] - 1)]
