"""Binding of the program's ``keye`` kind (``transformer/testing``'s
standalone Keye-VL-2.0 language model under ``InferenceEngine("keye",
paged)``): grouped-query attention over the positions a learned indexer
picks, an index-key pool beside the K/V pool, an expert FFN in every layer.
The same five functions as ``bindings/gpt.py``, and nothing of the loop.  The
configuration file is written in the published ``config.json``'s own keys;
this file maps them to the program's config.

``quant`` of ``reference_logits`` names the CONTROL the reference is run as
(``calibrate_dsa.py``): ``"fp8"`` is the precision below, ``"attend_all"``
and ``"recent_topk"`` are the two WRONG SELECTIONS (every causal position;
the most recent ``topk``) in full precision.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import harness as H
from ..references import keye_lm

#: ``quant`` -> the reference's (quant, select)
CONTROLS = {None: (None, "learned"), "fp8": ("fp8", "learned"),
            "attend_all": (None, "all"), "recent_topk": (None, "recent")}

#: a sequence handed to the reference is cut to a whole number of these
#: beyond its last judged row (causal: later rows change nothing), so that
#: its cost follows the sequence and few lengths are compiled
CUT = 8192


def _program_config(cfg):
    from apex_tpu.transformer.testing.standalone_keye import KeyeConfig

    spec = keye_lm.spec_from_config(cfg)
    return KeyeConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"], num_heads=spec.heads,
        num_kv_heads=spec.kv_heads, head_dim=spec.head_dim,
        mrope_section=spec.sections, rope_theta=spec.theta,
        index_heads=spec.index_heads, index_head_dim=spec.index_dim,
        index_topk=spec.topk,
        index_q_chunk=cfg["sa_config"]["q_chunk_size"],
        moe_ffn_hidden_size=cfg["moe_intermediate_size"],
        num_experts=cfg["num_experts"], experts_per_token=spec.top_k,
        max_seq_length=cfg["max_position_embeddings"], rms_eps=spec.eps,
        params_dtype=jnp.bfloat16)


def check_supported(cfg) -> None:
    """Does this checkout's program serve the kind?  Asked before any
    weight is made, so that a commit without it exits in seconds."""
    try:
        from apex_tpu.inference.models import check_supported as serves
        serves("keye", _program_config(cfg))
    except (ImportError, TypeError, ValueError) as e:
        raise H.Refused(f"this checkout's program does not serve the "
                        f"'keye' kind as configured: {e}") from e


def model_of(cfg):
    """The program's model config and the tree of served shapes (from the
    program's own shape function: nothing is initialised)."""
    from apex_tpu.transformer.testing.standalone_keye import (
        keye_param_shapes)

    kcfg = _program_config(cfg)
    shapes = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.bfloat16),
        keye_param_shapes(kcfg), is_leaf=lambda s: isinstance(s, tuple))
    return kcfg, {"params": shapes}


def engine(cfg, kcfg, mix, params, seed: int):
    from apex_tpu.inference import InferenceEngine, SamplingConfig

    return InferenceEngine(
        "keye", kcfg, params, slots=mix["slots"],
        max_seq=cfg["max_position_embeddings"],
        page_size=mix["page_size"], num_pages=mix["pool_pages"],
        dtype=jnp.bfloat16, sampling=SamplingConfig(),
        seed=seed & 0x7FFFFFFF)


def reference_weights(cfg, params) -> dict:
    """The benchmark's own weights, regrouped as ``keye_lm`` names them —
    the served leaves themselves, in the type they are served in: the
    reference up-casts one layer (one expert) at a time."""
    p = params["params"]

    def w(node):
        return node["weight"]

    layers = []
    for i in range(cfg["num_hidden_layers"]):
        lp = p[f"layer_{i}"]
        att, ix, m = lp["attention"], lp["indexer"], lp["moe"]
        layers.append({
            "ln1": w(lp["input_norm"]), "wq": w(att["q_proj"]),
            "wk": w(att["k_proj"]), "wv": w(att["v_proj"]),
            "q_gain": w(att["q_norm"]), "k_gain": w(att["k_norm"]),
            "wo": w(att["o_proj"]), "wqi": w(ix["q_proj"]),
            "wki": w(ix["k_proj"]), "ki_gain": w(ix["k_norm"]),
            "ki_bias": ix["k_norm"]["bias"], "ww": w(ix["w_proj"]),
            "ln2": w(lp["post_attention_norm"]),
            "router": w(m["router"]), "e_gate": m["experts"]["w_gate"],
            "e_up": m["experts"]["w_up"], "e_down": m["experts"]["w_down"]})
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
    low, select = CONTROLS[quant]
    keep = min(len(padded), -(-(first + rows) // CUT) * CUT)
    keep -= keep % keye_lm.ROW_BLOCK
    return keye_lm.logits(w, jnp.asarray(padded[:keep]), first, rows,
                          spec=keye_lm.spec_from_config(cfg), quant=low,
                          select=select)
