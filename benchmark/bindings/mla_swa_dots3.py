"""Binding of the program's ``dots3`` kind (``transformer/testing``'s
standalone dots3-note under ``InferenceEngine("dots3", paged)``): latent
attention of two geometries — the full layers' over the positions a learned
indexer picks, the window layers' over rings of their own latent rows — a
headwise gate on both, a sigmoid router that chooses under a selection
bias, held experts.  The same five functions as ``bindings/gpt.py``, and
nothing of the loop.  The configuration file is written in the published
``config.json``'s own keys; this file maps them to the program's config.

The router's selection bias (``e_score_correction_bias``) is made by
``weights.py`` like every other leaf, normal(0, 0.02), and scaled here to
the spread the configuration states (``e_score_correction_bias_std``) for
the program and the reference alike (:func:`biased`).  At the published
router's width and the weights' spread, a token keeps 6.2 of its 8 experts
under a bias of 0.02 and 4.7 under one of 0.05, on average.

``quant`` of ``reference_logits`` names the CONTROL the reference is run as
(``calibrate_dots3.py``): ``"fp8"`` is the precision below; ``"attend_all"``
and ``"recent_topk"`` are WRONG SELECTIONS of the full layers (every causal
position; the most recent ``topk``), ``"swa_all"`` window layers that
attend their whole context, ``"no_bias"`` a router that chooses by its
sigmoid alone, each in full precision.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import harness as H
from .. import weights
from ..references import dots3_lm

#: ``quant`` -> the reference's (quant, what a full layer attends, what a
#: window layer attends, mechanisms dropped)
CONTROLS = {None: (None, "learned", "window", ()),
            "fp8": ("fp8", "learned", "window", ()),
            "attend_all": (None, "all", "window", ()),
            "recent_topk": (None, "recent", "window", ()),
            "swa_all": (None, "learned", "all", ()),
            "no_bias": (None, "learned", "window", ("bias",))}

#: a sequence handed to the reference is cut to a whole number of these
#: beyond its last judged row (causal: later rows change nothing)
CUT = 4096

#: query rows a prefill picks for at a time (the program's tiling of the
#: selection: it changes no result)
PICK_ROWS = 512

#: the program's names of the layer types
_TYPES = {dots3_lm.FULL: "full", dots3_lm.SLIDING: "sliding"}


def _program_config(cfg):
    from apex_tpu.transformer.testing.standalone_dots3 import Dots3Config

    spec = dots3_lm.spec_from_config(cfg)
    return Dots3Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=tuple(_TYPES[t] for t in spec.layer_types),
        num_heads=spec.full.heads, q_lora_rank=cfg["q_lora_rank"],
        kv_lora_rank=spec.full.kv_lora, qk_nope_head_dim=spec.full.nope,
        qk_rope_head_dim=spec.full.rope, v_head_dim=spec.full.v,
        rope_theta=spec.full.theta, swa_num_heads=spec.swa.heads,
        swa_q_lora_rank=cfg["swa_q_lora_rank"],
        swa_kv_lora_rank=spec.swa.kv_lora,
        swa_qk_nope_head_dim=spec.swa.nope,
        swa_qk_rope_head_dim=spec.swa.rope, swa_v_head_dim=spec.swa.v,
        swa_rope_theta=spec.swa.theta, sliding_window=spec.window,
        index_heads=spec.index_heads, index_head_dim=spec.index_dim,
        index_topk=spec.topk, index_q_chunk=min(PICK_ROWS, spec.topk),
        dense_layers=cfg["first_k_dense_replace"],
        ffn_hidden_size=cfg["intermediate_size"],
        moe_ffn_hidden_size=cfg["moe_intermediate_size"],
        shared_ffn_hidden_size=cfg["moe_intermediate_size"],
        num_experts=cfg["published"]["n_routed_experts"],
        held=(spec.held_first, cfg["n_routed_experts"]),
        experts_per_token=spec.top_k, routed_scale=spec.scale,
        max_seq_length=cfg["max_position_embeddings"], rms_eps=spec.eps,
        params_dtype=jnp.bfloat16)


def check_supported(cfg) -> None:
    """Does this checkout's program serve the kind?  Asked before any
    weight is made, so that a commit without it exits in seconds."""
    try:
        from apex_tpu.inference.models import check_supported as serves
        serves("dots3", _program_config(cfg))
    except (ImportError, TypeError, ValueError) as e:
        raise H.Refused(f"this checkout's program does not serve the "
                        f"'dots3' kind as configured: {e}") from e


def model_of(cfg):
    """The program's model config and the tree of served shapes (from the
    program's own shape function: nothing is initialised)."""
    from apex_tpu.transformer.testing.standalone_dots3 import (
        dots3_param_shapes)

    dcfg = _program_config(cfg)
    shapes = jax.tree_util.tree_map(
        lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16),
        dots3_param_shapes(dcfg), is_leaf=lambda s: isinstance(s, tuple))
    return dcfg, {"params": shapes}


def biased(cfg, params):
    """``params`` with every router's selection bias scaled from the
    weights' spread to the configuration's."""
    factor = cfg["e_score_correction_bias_std"] / weights.STD

    def scale(path, leaf):
        name = getattr(path[-1], "key", None)
        return (leaf * factor).astype(leaf.dtype) \
            if name == "e_score_correction_bias" else leaf

    return jax.tree_util.tree_map_with_path(scale, params)


def engine(cfg, dcfg, mix, params, seed: int):
    from apex_tpu.inference import InferenceEngine, SamplingConfig

    return InferenceEngine(
        "dots3", dcfg, biased(cfg, params), slots=mix["slots"],
        max_seq=cfg["max_position_embeddings"],
        page_size=mix["page_size"], num_pages=mix["pool_pages"],
        dtype=jnp.bfloat16, sampling=SamplingConfig(),
        seed=seed & 0x7FFFFFFF)


def reference_weights(cfg, params) -> dict:
    """The benchmark's own weights, regrouped as ``dots3_lm`` names them —
    the served leaves themselves, in the type they are served in, the
    selection bias scaled as the program's is."""
    p = biased(cfg, params)["params"]

    def w(node):
        return node["weight"]

    layers = []
    for i in range(cfg["num_hidden_layers"]):
        lp = p[f"layer_{i}"]
        att = lp["attention"]
        lw = {"ln1": w(lp["input_norm"]), "wdq": w(att["q_a_proj"]),
              "q_gain": w(att["q_a_norm"]), "wuq": w(att["q_b_proj"]),
              "wdkv": w(att["kv_a_proj"]), "kv_gain": w(att["kv_a_norm"]),
              "wukv": w(att["kv_b_proj"]), "wg": w(att["g_proj"]),
              "wo": w(att["o_proj"]), "ln2": w(lp["post_attention_norm"])}
        if "indexer" in lp:
            ix = lp["indexer"]
            lw["indexer"] = {"wqi": w(ix["q_proj"]), "wki": w(ix["k_proj"]),
                             "ki_gain": w(ix["k_norm"]),
                             "ki_bias": ix["k_norm"]["bias"],
                             "ww": w(ix["w_proj"])}
        if "mlp" in lp:
            m = lp["mlp"]
            lw.update(gate=w(m["gate_proj"]), up=w(m["up_proj"]),
                      down=w(m["down_proj"]))
        else:
            m = lp["moe"]
            lw.update(router=w(m["router"]),
                      router_bias=m["router"]["e_score_correction_bias"],
                      e_gate=m["experts"]["w_gate"],
                      e_up=m["experts"]["w_up"],
                      e_down=m["experts"]["w_down"],
                      s_gate=w(m["shared"]["gate_proj"]),
                      s_up=w(m["shared"]["up_proj"]),
                      s_down=w(m["shared"]["down_proj"]))
        layers.append(lw)
    return {"embed": w(p["embed_tokens"]), "layers": layers,
            "final_norm": w(p["final_norm"]), "head": w(p["lm_head"])}


def reference_logits(cfg, w, padded, first: int, rows: int, quant=None):
    """The reference's float32 logits ``[rows, vocab]`` of the ``rows``
    positions from ``first`` on of the one sequence ``padded``: the judged
    rows alone go through the vocabulary projection, and the sequence is
    cut behind them (to a whole number of ``CUT`` positions)."""
    if quant not in CONTROLS:
        raise H.Refused(f"unknown control {quant!r}; there is "
                        f"{', '.join(map(str, CONTROLS))}")
    low, select, swa, drop = CONTROLS[quant]
    keep = min(len(padded), -(-(first + rows) // CUT) * CUT)
    keep -= keep % dots3_lm.ROW_BLOCK
    return dots3_lm.logits(w, jnp.asarray(padded[:keep]), first, rows,
                           spec=dots3_lm.spec_from_config(cfg), quant=low,
                           select=select, swa=swa, drop=drop)
