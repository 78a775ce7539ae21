"""Binding of the program's ``laguna`` kind (``transformer/testing``'s
standalone Laguna under ``InferenceEngine("laguna", paged)``): a decoder
with an expert FFN, window layers beside full ones and a head count per
layer.  The same five functions as ``bindings/gpt.py``, and nothing of the
loop.  The configuration file is written in the published ``config.json``'s
own keys; this file maps them to the program's config.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import harness as H
from ..references import laguna_lm

_TYPES = {"full_attention": "full", "sliding_attention": "sliding"}


def _program_config(cfg):
    from apex_tpu.transformer.testing.standalone_laguna import (
        LagunaConfig, YarnRope)

    rope = cfg["rope_parameters"]
    full, sliding = rope["full_attention"], rope["sliding_attention"]
    n = cfg["num_hidden_layers"]
    assert n == len(cfg["layer_types"]) == len(cfg["mlp_layer_types"]) \
        == len(cfg["num_attention_heads_per_layer"]), "depth keys disagree"
    return LagunaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        heads_per_layer=tuple(cfg["num_attention_heads_per_layer"]),
        layer_types=tuple(_TYPES[t] for t in cfg["layer_types"]),
        mlp_types=tuple(cfg["mlp_layer_types"]),
        ffn_hidden_size=cfg["intermediate_size"],
        moe_ffn_hidden_size=cfg["moe_intermediate_size"],
        shared_ffn_hidden_size=cfg["shared_expert_intermediate_size"],
        num_experts=cfg["num_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        routed_scale=float(cfg["moe_routed_scaling_factor"]),
        sliding_window=cfg["sliding_window"],
        max_seq_length=cfg["max_position_embeddings"],
        rms_eps=float(cfg["rms_norm_eps"]),
        rope_full=YarnRope(
            theta=float(full["rope_theta"]),
            rotary_dim=int(cfg["head_dim"] * full["partial_rotary_factor"]),
            factor=float(full["factor"]),
            original_max_position=int(
                full["original_max_position_embeddings"]),
            beta_fast=float(full["beta_fast"]),
            beta_slow=float(full["beta_slow"]),
            attention_factor=float(full["attention_factor"])),
        rope_sliding_theta=float(sliding["rope_theta"]),
        params_dtype=jnp.bfloat16)


def check_supported(cfg) -> None:
    """Does this checkout's program serve the kind?  Asked before any
    weight is made, so that a commit without it exits in seconds."""
    try:
        from apex_tpu.inference.models import check_supported as serves
        serves("laguna", _program_config(cfg))
    except (ImportError, TypeError, ValueError) as e:
        raise H.Refused(f"this checkout's program does not serve the "
                        f"'laguna' kind as configured: {e}") from e


def model_of(cfg):
    """The program's model config and the tree of served shapes (from the
    program's own shape function: nothing is initialised)."""
    from apex_tpu.transformer.testing.standalone_laguna import (
        laguna_model_provider)

    lcfg = _program_config(cfg)
    shapes = jax.eval_shape(laguna_model_provider(lcfg).init,
                            jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    return lcfg, jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16), shapes)


def engine(cfg, lcfg, mix, params, seed: int):
    from apex_tpu.inference import InferenceEngine, SamplingConfig

    return InferenceEngine(
        "laguna", lcfg, params, slots=mix["slots"],
        max_seq=cfg["max_position_embeddings"],
        page_size=mix["page_size"], num_pages=mix["pool_pages"],
        dtype=jnp.bfloat16, sampling=SamplingConfig(),
        seed=seed & 0x7FFFFFFF)


def reference_weights(cfg, params) -> dict:
    """The benchmark's own weights, regrouped as ``laguna_lm`` names them —
    the served leaves themselves, in the type they are served in: the
    reference up-casts one layer (one expert) at a time."""
    p = params["params"]

    def w(node):
        return node["weight"]

    layers = []
    for i in range(cfg["num_hidden_layers"]):
        lp = p[f"layer_{i}"]
        att = lp["attention"]
        if cfg["mlp_layer_types"][i] == "sparse":
            m = lp["moe"]
            ffn = {"router": w(m["router"]),
                   "e_gate": m["experts"]["w_gate"],
                   "e_up": m["experts"]["w_up"],
                   "e_down": m["experts"]["w_down"],
                   "s_gate": w(m["shared"]["gate_proj"]),
                   "s_up": w(m["shared"]["up_proj"]),
                   "s_down": w(m["shared"]["down_proj"])}
        else:
            m = lp["mlp"]
            ffn = {"w_gate": w(m["gate_proj"]), "w_up": w(m["up_proj"]),
                   "w_down": w(m["down_proj"])}
        layers.append({
            "ln1": w(lp["input_norm"]), "wq": w(att["q_proj"]),
            "wk": w(att["k_proj"]), "wv": w(att["v_proj"]),
            "wg": w(att["g_proj"]), "wo": w(att["o_proj"]),
            "ln2": w(lp["post_attention_norm"]), "ffn": ffn})
    return {"embed": w(p["embed_tokens"]), "layers": layers,
            "final_norm": w(p["final_norm"]), "head": w(p["lm_head"])}


def reference_logits(cfg, w, padded, first: int, rows: int, quant=None):
    """The reference's float32 logits ``[rows, vocab]`` of the ``rows``
    positions from ``first`` on of the one sequence ``padded``: the judged
    rows alone go through the vocabulary projection."""
    return laguna_lm.logits(w, jnp.asarray(padded), first, rows,
                            spec=laguna_lm.spec_from_config(cfg),
                            quant=quant)
