"""Binding of the program's ``llama`` kind (``transformer/testing``'s
standalone LLaMA under ``InferenceEngine("llama", paged)``): RMSNorm, RoPE,
grouped-query attention, SwiGLU.  Added as a file beside ``gpt.py``; the
configuration's ``"binding": "llama"`` finds it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import harness as H
from ..drivers.train import dig
from ..references import llama_lm

_TOP_LEAVES = {
    ("embed_tokens", "weight"): "embed",
    ("final_norm", "weight"): "final_norm",
    ("lm_head", "weight"): "lm_head",
}
_LAYER_LEAVES = {
    ("input_norm", "weight"): "norm1",
    ("attention", "q_proj", "weight"): "w_q",
    ("attention", "kv_proj", "weight"): "w_kv",
    ("attention", "o_proj", "weight"): "w_o",
    ("post_attention_norm", "weight"): "norm2",
    ("mlp", "gate_proj", "weight"): "w_gate",
    ("mlp", "up_proj", "weight"): "w_up",
    ("mlp", "down_proj", "weight"): "w_down",
}


def _program_config(cfg):
    from apex_tpu.transformer.testing.standalone_llama import LlamaConfig

    return LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        ffn_hidden_size=cfg["intermediate_size"],
        max_seq_length=cfg["max_position_embeddings"],
        rope_theta=cfg["rope_theta"], rms_eps=cfg["rms_norm_eps"],
        params_dtype=jnp.bfloat16)


def check_supported(cfg) -> None:
    try:
        from apex_tpu.inference.models import check_supported as serves
        serves("llama", _program_config(cfg))
    except (ImportError, TypeError, ValueError) as e:
        raise H.Refused(f"this checkout's program does not serve the "
                        f"'llama' kind as configured: {e}") from e


def model_of(cfg):
    from apex_tpu.transformer import parallel_state
    from apex_tpu.transformer.testing.standalone_llama import (
        llama_model_provider)

    parallel_state.destroy_model_parallel()
    parallel_state.initialize_model_parallel(1)
    lcfg = _program_config(cfg)
    shapes = jax.eval_shape(llama_model_provider(lcfg).init,
                            jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    return lcfg, jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16), shapes)


def engine(cfg, lcfg, mix, params, seed: int):
    from apex_tpu.inference import InferenceEngine, SamplingConfig

    return InferenceEngine(
        "llama", lcfg, params, slots=mix["slots"],
        max_seq=cfg["max_position_embeddings"],
        page_size=mix["page_size"], num_pages=mix["pool_pages"],
        dtype=jnp.bfloat16, sampling=SamplingConfig(),
        seed=seed & 0x7FFFFFFF)


def reference_weights(cfg, params) -> dict:
    p = params["params"]
    f32 = lambda x: jnp.asarray(x, jnp.float32)         # noqa: E731
    out = {ref: f32(dig(p, prog)) for prog, ref in _TOP_LEAVES.items()}
    out["layers"] = {
        ref: jnp.stack([f32(dig(p[f"layer_{i}"], prog))
                        for i in range(cfg["num_hidden_layers"])])
        for prog, ref in _LAYER_LEAVES.items()}
    assert set(out["layers"]) == set(llama_lm.LAYER_KEYS)
    return out


def reference_logits(cfg, w, padded, first: int, rows: int, quant=None):
    """Only the judged rows go through the output projection."""
    at = jnp.clip(first + jnp.arange(rows), 0, len(padded) - 1)
    return llama_lm.logits(
        w, jnp.asarray(padded), at, heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], theta=cfg["rope_theta"],
        eps=cfg["rms_norm_eps"], quant=quant)
