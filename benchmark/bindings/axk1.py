"""Binding of the program's ``axk1`` kind (``transformer/testing``'s
standalone A.X-K1 under ``InferenceEngine("axk1", paged)``): a decoder with
latent attention over a pool with no KV-head axis and an expert FFN that
holds a share of its experts.  The same five functions as ``bindings/gpt.py``,
and nothing of the loop.  The configuration file is written in the published
``config.json``'s own keys; this file maps them to the program's config.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import harness as H
from ..references import axk1_lm


def _program_config(cfg):
    from apex_tpu.transformer.testing.standalone_axk1 import AXK1Config
    from apex_tpu.transformer.testing.standalone_laguna import YarnRope

    spec = axk1_lm.spec_from_config(cfg)
    return AXK1Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=spec.layers, num_heads=spec.heads,
        q_lora_rank=spec.q_rank, kv_lora_rank=spec.kv_rank,
        qk_nope_head_dim=spec.nope, qk_rope_head_dim=spec.rope,
        v_head_dim=spec.v_dim, dense_layers=spec.dense_layers,
        ffn_hidden_size=cfg["intermediate_size"],
        moe_ffn_hidden_size=cfg["moe_intermediate_size"],
        shared_ffn_hidden_size=(cfg["n_shared_experts"]
                                * cfg["moe_intermediate_size"]),
        num_experts=spec.router_experts, held=spec.held,
        experts_per_token=spec.top_k, n_group=spec.n_group,
        topk_group=spec.topk_group, routed_scale=spec.scale,
        max_seq_length=cfg["max_position_embeddings"], rms_eps=spec.eps,
        rope=YarnRope(
            theta=spec.theta, rotary_dim=spec.rope,
            factor=spec.yarn_factor,
            original_max_position=spec.yarn_original,
            beta_fast=spec.beta_fast, beta_slow=spec.beta_slow,
            attention_factor=(
                axk1_lm.yarn_mscale(spec.yarn_factor, spec.mscale)
                / axk1_lm.yarn_mscale(spec.yarn_factor,
                                      spec.mscale_all_dim))),
        mscale_all_dim=spec.mscale_all_dim, params_dtype=jnp.bfloat16)


def check_supported(cfg) -> None:
    """Does this checkout's program serve the kind?  Asked before any
    weight is made, so that a commit without it exits in seconds."""
    try:
        from apex_tpu.inference.models import check_supported as serves
        serves("axk1", _program_config(cfg))
    except (ImportError, TypeError, ValueError) as e:
        raise H.Refused(f"this checkout's program does not serve the "
                        f"'axk1' kind as configured: {e}") from e


def model_of(cfg):
    """The program's model config and the tree of served shapes (from the
    program's own shape function: nothing is initialised)."""
    from apex_tpu.transformer.testing.standalone_axk1 import (
        axk1_param_shapes)

    acfg = _program_config(cfg)
    shapes = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.bfloat16),
        axk1_param_shapes(acfg), is_leaf=lambda s: isinstance(s, tuple))
    return acfg, {"params": shapes}


def engine(cfg, acfg, mix, params, seed: int):
    from apex_tpu.inference import InferenceEngine, SamplingConfig

    return InferenceEngine(
        "axk1", acfg, params, slots=mix["slots"],
        max_seq=cfg["max_position_embeddings"],
        page_size=mix["page_size"], num_pages=mix["pool_pages"],
        dtype=jnp.bfloat16, sampling=SamplingConfig(),
        seed=seed & 0x7FFFFFFF)


def reference_weights(cfg, params) -> dict:
    """The benchmark's own weights, regrouped as ``axk1_lm`` names them —
    the served leaves themselves, in the type they are served in: the
    reference up-casts one layer (one expert) at a time."""
    p = params["params"]

    def w(node):
        return node["weight"]

    layers = []
    for i in range(cfg["num_hidden_layers"]):
        lp = p[f"layer_{i}"]
        att = lp["attention"]
        if i >= cfg["first_k_dense_replace"]:
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
            "ln1": w(lp["input_norm"]), "w_dq": w(att["q_a_proj"]),
            "q_norm": w(att["q_a_norm"]), "w_uq": w(att["q_b_proj"]),
            "w_dkv": w(att["kv_a_proj"]), "kv_norm": w(att["kv_a_norm"]),
            "w_ukv": w(att["kv_b_proj"]), "w_o": w(att["o_proj"]),
            "ln2": w(lp["post_attention_norm"]), "ffn": ffn})
    return {"embed": w(p["embed_tokens"]), "layers": layers,
            "final_norm": w(p["final_norm"]), "head": w(p["lm_head"])}


def reference_logits(cfg, w, padded, first: int, rows: int, quant=None):
    """The reference's float32 logits ``[rows, vocab]`` of the ``rows``
    positions from ``first`` on of the one sequence ``padded``: the judged
    rows alone go through the vocabulary projection."""
    return axk1_lm.logits(w, jnp.asarray(padded), first, rows,
                          spec=axk1_lm.spec_from_config(cfg), quant=quant)
