"""Binding of the program's ``hy4`` kind (``transformer/testing``'s
standalone Hy4-preview under ``InferenceEngine("hy4", paged)``): latent
attention over the positions a learned indexer picks, the picks of a full
layer reused by the shared layers after it, a sink a head, an elementwise
gate, four residual streams, held experts.  The same five functions as
``bindings/gpt.py``, and nothing of the loop.  The configuration file is
written in the published ``config.json``'s own keys; this file maps them to
the program's config.

``quant`` of ``reference_logits`` names the CONTROL the reference is run as
(``calibrate_hy4.py``): ``"fp8"`` is the precision below; ``"attend_all"``,
``"recent_topk"`` and ``"self_select"`` are WRONG SELECTIONS (every causal
position; the most recent ``topk``; every layer picking for itself) and
``"static_hc"`` the residual mixes without their input terms, each in full
precision.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import harness as H
from ..references import hy4_lm

#: ``quant`` -> the reference's (quant, select, static mixes)
CONTROLS = {None: (None, "learned", False),
            "fp8": ("fp8", "learned", False),
            "attend_all": (None, "all", False),
            "recent_topk": (None, "recent", False),
            "self_select": (None, "self", False),
            "static_hc": (None, "learned", True)}

#: a sequence handed to the reference is cut to a whole number of these
#: beyond its last judged row (causal: later rows change nothing)
CUT = 4096

#: query rows a prefill picks for at a time (the program's tiling of the
#: selection: it changes no result)
PICK_ROWS = 512


def _program_config(cfg):
    from apex_tpu.transformer.testing.standalone_hy4 import HY4Config

    spec = hy4_lm.spec_from_config(cfg)
    return HY4Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"], num_heads=spec.heads,
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=spec.kv_lora,
        qk_nope_head_dim=spec.nope, qk_rope_head_dim=spec.rope,
        v_head_dim=spec.v, rope_theta=spec.theta,
        index_heads=spec.index_heads, index_head_dim=spec.index_dim,
        index_topk=spec.topk,
        index_q_chunk=min(PICK_ROWS, spec.topk),
        indexer_types=spec.indexer_types,
        dense_layers=sum(spec.dense),
        ffn_hidden_size=cfg["intermediate_size"],
        moe_ffn_hidden_size=cfg["moe_intermediate_size"],
        shared_ffn_hidden_size=cfg["moe_intermediate_size"],
        num_experts=cfg["published"]["n_routed_experts"],
        held=(spec.held_first, cfg["n_routed_experts"]),
        experts_per_token=spec.top_k, routed_scale=spec.scale,
        swiglu_limit=spec.limit, hc_mult=spec.streams,
        hc_magnitude=spec.magnitude, hc_eps=spec.hc_eps,
        max_seq_length=cfg["max_position_embeddings"], rms_eps=spec.eps,
        params_dtype=jnp.bfloat16)


def check_supported(cfg) -> None:
    """Does this checkout's program serve the kind?  Asked before any
    weight is made, so that a commit without it exits in seconds."""
    try:
        from apex_tpu.inference.models import check_supported as serves
        serves("hy4", _program_config(cfg))
    except (ImportError, TypeError, ValueError) as e:
        raise H.Refused(f"this checkout's program does not serve the "
                        f"'hy4' kind as configured: {e}") from e


def model_of(cfg):
    """The program's model config and the tree of served shapes (from the
    program's own shape function: nothing is initialised)."""
    from apex_tpu.transformer.testing.standalone_hy4 import hy4_param_shapes

    hcfg = _program_config(cfg)

    def served(path, shape):
        # enable_lm_head_fp32: the head is held in float32
        head = getattr(path[0], "key", None) == "lm_head"
        return jax.ShapeDtypeStruct(shape, jnp.float32 if head
                                    else jnp.bfloat16)

    shapes = jax.tree_util.tree_map_with_path(
        served, hy4_param_shapes(hcfg),
        is_leaf=lambda s: isinstance(s, tuple))
    return hcfg, {"params": shapes}


def engine(cfg, hcfg, mix, params, seed: int):
    from apex_tpu.inference import InferenceEngine, SamplingConfig

    return InferenceEngine(
        "hy4", hcfg, params, slots=mix["slots"],
        max_seq=cfg["max_position_embeddings"],
        page_size=mix["page_size"], num_pages=mix["pool_pages"],
        dtype=jnp.bfloat16, sampling=SamplingConfig(),
        seed=seed & 0x7FFFFFFF)


def reference_weights(cfg, params) -> dict:
    """The benchmark's own weights, regrouped as ``hy4_lm`` names them —
    the served leaves themselves, in the type they are served in."""
    p = params["params"]

    def w(node):
        return node["weight"]

    def hc(node):
        return {"phi": node["phi"], "alpha": node["alpha"],
                "bias": node["bias"]}

    layers = []
    for i in range(cfg["num_hidden_layers"]):
        lp = p[f"layer_{i}"]
        att = lp["attention"]
        lw = {"ln1": w(lp["input_norm"]), "wdq": w(att["q_a_proj"]),
              "q_gain": w(att["q_a_norm"]), "wuq": w(att["q_b_proj"]),
              "wdkv": w(att["kv_a_proj"]), "kv_gain": w(att["kv_a_norm"]),
              "wukv": w(att["kv_b_proj"]), "wg": w(att["g_proj"]),
              "wo": w(att["o_proj"]), "sink": att["sink"],
              "ln2": w(lp["post_attention_norm"]),
              "hc_attn": hc(lp["hc_attention"]), "hc_ffn": hc(lp["hc_ffn"])}
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
            lw.update(router=w(m["router"]), e_gate=m["experts"]["w_gate"],
                      e_up=m["experts"]["w_up"],
                      e_down=m["experts"]["w_down"],
                      s_gate=w(m["shared"]["gate_proj"]),
                      s_up=w(m["shared"]["up_proj"]),
                      s_down=w(m["shared"]["down_proj"]))
        layers.append(lw)
    return {"embed": w(p["embed_tokens"]), "layers": layers,
            "hc_head": hc(p["hc_head"]), "final_norm": w(p["final_norm"]),
            "head": w(p["lm_head"])}


def reference_logits(cfg, w, padded, first: int, rows: int, quant=None):
    """The reference's float32 logits ``[rows, vocab]`` of the ``rows``
    positions from ``first`` on of the one sequence ``padded``: the judged
    rows alone go through the vocabulary projection, and the sequence is
    cut behind them (to a whole number of ``CUT`` positions)."""
    if quant not in CONTROLS:
        raise H.Refused(f"unknown control {quant!r}; there is "
                        f"{', '.join(map(str, CONTROLS))}")
    low, select, static = CONTROLS[quant]
    keep = min(len(padded), -(-(first + rows) // CUT) * CUT)
    keep -= keep % hy4_lm.ROW_BLOCK
    return hy4_lm.logits(w, jnp.asarray(padded[:keep]), first, rows,
                         spec=hy4_lm.spec_from_config(cfg), quant=low,
                         select=select, static=static)
