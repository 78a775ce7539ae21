"""Operations and bytes of a decoder with latent attention of two geometries
— the full layers' over the positions a learned indexer picks, the window
layers' over rings of latent rows of their own rank — a headwise gate and an
expert FFN that HOLDS a share of its experts (``model_type: dots3_note``),
from shapes alone, and the metrics built on them.  Beside ``counts_hy4.py``
(latent attention under an indexer) and ``counts_dsa.py``, whose selection
counts and trace rules for the index kernels are taken by import.

Every count is the LEAST any implementation must do for the work the window
completed, so that a later kernel is read against the same work:

* 2 FLOPs a parameter of the matrices a token is ACTIVE in — each layer's
  latent attention in its type's geometry with its gate, the indexer on a
  full layer, the router, the shared expert, a dense layer's FFN — plus
  ``2 x 3 x hidden x width`` an assignment that LANDED on a held expert
  (the program's own counter), plus one vocabulary row-block a sampled
  token;
* the indexer: ``2 x index_heads x index_dim`` FLOPs and one index key a
  position SCORED a full layer — a query whose context is over ``topk``;
* attention, the expanded form's count, the least of the two:
  ``2 x heads x (nope + rope + v)`` of the full geometry a position PICKED
  a full layer (``min(context, topk)`` a query) and of the window geometry
  a position IN THE WINDOW a window layer (``min(context, window)``);
* bytes: the resident weights once a step, the held experts HIT once a
  step, one latent row a full layer a position picked and one index key a
  full layer a position scored, one window row a window layer a position in
  the window; a prefill writes a latent row and an index key a full layer,
  and a window row a window layer, a position.

The kernels' own rooflines count what their form must do: the decode's
ring attention ``2 x swa_heads x (window row + latent)`` FLOPs and one
window row a position attended (``swa_attended``, the program's counter);
the windowed flash kernel of the prefill ``2 x swa_heads x (qk + v)`` a
(query, key in its window) pair and its q, k, v and output once.

A share above 100% means a count or a window is wrong.

``facts["moe"]`` and ``facts["dsa"]`` are ``drivers/dsa_swa_serve.py``'s:
per phase the expert counters (assignments that LANDED) and the
selection's — query rows x full layers that attended a selection, those
whose context exceeded ``topk``, positions attended — and the positions the
window layers attended (``swa_attended``).
"""
from __future__ import annotations

import re

from . import counts, counts_dsa, counts_moe, reduce, scopes
from . import trace as trace_mod

BF16 = counts.BF16

#: the kernels' own names in a trace (``pallas_call(name=...)``)
ATTEND_LATENT_KERNEL = r"^%apex_dsa_attend_latent"
INDEX_DECODE_KERNEL = counts_dsa.INDEX_DECODE_KERNEL
#: the window layers' attention, both phases, by the program's named scope
SWA_SCOPE = "apex_swa_attend"
FULL, SLIDING = "full_attention", "sliding_attention"


def is_ours(cfg: dict) -> bool:
    return cfg.get("model_type") == "dots3_note"


def model(cfg: dict) -> dict:
    """The numbers of a configuration file the counts need; ``held`` is
    ``n_routed_experts`` as run, ``experts`` the router's published width;
    ``full`` and ``swa`` the two geometries."""
    def geometry(prefix: str) -> dict:
        return dict(heads=cfg[prefix + "num_attention_heads"],
                    q_rank=cfg[prefix + "q_lora_rank"],
                    kv_rank=cfg[prefix + "kv_lora_rank"],
                    nope=cfg[prefix + "qk_nope_head_dim"],
                    rope=cfg[prefix + "qk_rope_head_dim"],
                    v_dim=cfg[prefix + "v_head_dim"])

    types = tuple(cfg["layer_types"])
    return dict(
        hidden=cfg["hidden_size"], full_geo=geometry(""),
        swa_geo=geometry("swa_"), types=types, layers=len(types),
        full=types.count(FULL), swa=types.count(SLIDING),
        window=cfg["sliding_window_size"],
        dense_layers=cfg["first_k_dense_replace"],
        dense_ffn=cfg["intermediate_size"],
        expert_ffn=cfg["moe_intermediate_size"],
        shared_ffn=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        experts=cfg["published"]["n_routed_experts"],
        held=cfg["n_routed_experts"], top_k=cfg["num_experts_per_tok"],
        index_heads=cfg["index_n_heads"], index_dim=cfg["index_head_dim"],
        topk=cfg["index_topk"], vocab=cfg["vocab_size"])


def expert_layers(m: dict) -> int:
    return m["layers"] - m["dense_layers"]


def row_bytes(g: dict) -> int:
    """One cached position of one layer of geometry ``g``: the latent
    beside the roped key channels."""
    return (g["kv_rank"] + g["rope"]) * BF16


def index_key_bytes(m: dict) -> int:
    return m["index_dim"] * BF16


def attention_params(m: dict, g: dict) -> int:
    """Latent attention's five matrices and the headwise gate, in the
    geometry ``g``."""
    h, heads = m["hidden"], g["heads"]
    return (h * g["q_rank"] + g["q_rank"] * heads * (g["nope"] + g["rope"])
            + h * (g["kv_rank"] + g["rope"])
            + g["kv_rank"] * heads * (g["nope"] + g["v_dim"])
            + heads * g["v_dim"] * h + h * heads)


def indexer_params(m: dict) -> int:
    """Index queries (from the full layer's query latent), the one index
    key, the head weights."""
    return (m["full_geo"]["q_rank"] * m["index_heads"] * m["index_dim"]
            + m["hidden"] * (m["index_dim"] + m["index_heads"]))


def expert_params(m: dict) -> int:
    """One routed expert: three matrices."""
    return 3 * m["hidden"] * m["expert_ffn"]


def layer_resident_params(m: dict, i: int) -> int:
    """What every token of layer ``i`` is multiplied by whatever it
    routes."""
    full = m["types"][i] == FULL
    base = (attention_params(m, m["full_geo"] if full else m["swa_geo"])
            + (indexer_params(m) if full else 0))
    if i < m["dense_layers"]:
        return base + 3 * m["hidden"] * m["dense_ffn"]
    return base + m["hidden"] * m["experts"] + 3 * m["hidden"] * m[
        "shared_ffn"]


def resident_params(m: dict) -> int:
    return sum(layer_resident_params(m, i) for i in range(m["layers"]))


def total_params(m: dict) -> int:
    """Everything this chip holds: the resident matrices, the held experts,
    embedding and head (norm gains, the index key's bias and the router's
    selection bias left out)."""
    return (resident_params(m)
            + expert_layers(m) * m["held"] * expert_params(m)
            + 2 * m["hidden"] * m["vocab"])


def held_bytes(m: dict) -> int:
    return BF16 * total_params(m)


def scored(context: int, m: dict) -> int:
    return counts_dsa.scored(context, m)


def picked(context: int, m: dict) -> int:
    return counts_dsa.picked(context, m)


def in_window(context: int, m: dict) -> int:
    """Positions ONE query at ``context`` attends in a window layer."""
    return min(context, m["window"])


def prompt_in_window(n: int, m: dict) -> int:
    """Summed over the ``n`` queries of a prompt."""
    w = min(n, m["window"])
    return w * (w + 1) // 2 + (n - w) * m["window"]


def index_flops(m: dict) -> int:
    return counts_dsa.index_flops(m)


def pair_flops(g: dict) -> int:
    """One query against one position it attends, all heads, expanded:
    scores over ``nope + rope`` channels and values of ``v_dim``."""
    return 2 * g["heads"] * (g["nope"] + g["rope"] + g["v_dim"])


def latent_flops(g: dict) -> int:
    """The absorbed form's own work a position, all heads: scores over the
    whole row, values its latent part."""
    return 2 * g["heads"] * (g["kv_rank"] + g["rope"] + g["kv_rank"])


def _token_flops(m: dict, landed: float) -> float:
    return 2 * resident_params(m) + 2 * expert_params(m) * landed


def prefill_flops(n: int, m: dict, landed: float = 0.0) -> float:
    """A prompt of ``n`` tokens of which ``landed`` (token, expert)
    assignments fell on a held expert."""
    return float(n * _token_flops(m, 0.0) + 2 * expert_params(m) * landed
                 + m["full"] * pair_flops(m["full_geo"])
                 * counts_dsa.prompt_picked(n, m)
                 + m["full"] * index_flops(m) * counts_dsa.prompt_scored(
                     n, m)
                 + m["swa"] * pair_flops(m["swa_geo"])
                 * prompt_in_window(n, m)
                 + 2 * m["hidden"] * m["vocab"])


def decode_flops(context: int, m: dict, landed: float = 0.0) -> float:
    """One generated token at ``context``."""
    return float(_token_flops(m, landed)
                 + m["full"] * pair_flops(m["full_geo"]) * picked(context, m)
                 + m["full"] * index_flops(m) * scored(context, m)
                 + m["swa"] * pair_flops(m["swa_geo"])
                 * in_window(context, m)
                 + 2 * m["hidden"] * m["vocab"])


def resident_weight_bytes(m: dict) -> float:
    """Bytes every step reads whatever it routes, the vocabulary
    projection among them (the embedding is a gather of rows)."""
    return float(BF16 * (resident_params(m) + m["hidden"] * m["vocab"]))


def cache_bytes_read(context: int, m: dict) -> int:
    """Index keys scored and latent rows picked (full layers), window rows
    attended (window layers), by one query."""
    return (m["full"] * (index_key_bytes(m) * scored(context, m)
                         + row_bytes(m["full_geo"]) * picked(context, m))
            + m["swa"] * row_bytes(m["swa_geo"]) * in_window(context, m))


def cache_bytes_written(n: int, m: dict) -> int:
    return n * (m["full"] * (row_bytes(m["full_geo"]) + index_key_bytes(m))
                + m["swa"] * row_bytes(m["swa_geo"]))


def flash_bytes(n: int, m: dict) -> int:
    """The windowed flash kernel's q, k, v and output of one window layer
    over a prompt of ``n``, once each."""
    g = m["swa_geo"]
    qk = g["nope"] + g["rope"]
    return n * g["heads"] * (2 * qk + 2 * g["v_dim"]) * BF16


# -- what the program counted ------------------------------------------------

def _counted(run, family: str, phase: str):
    c = run.facts.get(family)
    if not c or not is_ours(run.cell.config):
        return None
    return c.get(phase)


def _hit_per_pass(run, phase: str):
    """Mean held experts hit (summed over the expert layers) by a step."""
    c = _counted(run, "moe", phase)
    return c["experts_hit"] / c["passes"] if c and c["passes"] else None


def _tokens(run, phase: str) -> int:
    """Tokens of ``phase`` stamped over the window and its drain, what the
    counters are summed over."""
    stamped = [r for r in run.facts["requests"] if r["token_times"]]
    return (sum(r["prompt_len"] for r in stamped) if phase == "prefill"
            else sum(len(r["token_times"]) - 1 for r in stamped))


def _landed_per_token(run, phase: str):
    """Assignments that LANDED, a token of ``phase``, all expert layers."""
    c = _counted(run, "moe", phase)
    if c is None:
        return None
    tokens = _tokens(run, phase)
    return c["assignments"] / tokens if tokens else None


def _attended_per_decode(run):
    """Ring positions the window layers attended (the program's
    ``swa_attended``), a generated token, all window layers."""
    c = _counted(run, "dsa", "decode")
    if c is None or "swa_attended" not in c:
        return None
    tokens = _tokens(run, "decode")
    return c["swa_attended"] / tokens if tokens else None


# -- metrics -----------------------------------------------------------------

def serve_step_mfu(run):
    """The whole serving loop's share of the chip's bf16 peak over the work
    the window completed."""
    if not is_ours(run.cell.config):
        return None
    lo, hi = run.facts["window"]
    if run.facts.get("trace_started"):
        hi = min(hi, run.facts["trace_started"])
    prefills, decodes = reduce._tokens_between(run.facts, lo, hi)
    per_p = _landed_per_token(run, "prefill")
    per_d = _landed_per_token(run, "decode")
    if per_p is None or per_d is None or not (prefills or decodes):
        return None
    m = model(run.cell.config)
    flops = (sum(prefill_flops(n, m, per_p * n) for n in prefills)
             + sum(decode_flops(c, m, per_d) for c in decodes))
    return 100.0 * flops / (hi - lo) / (
        run.cell.chips * run.peaks["bf16_flops_per_s"])


def decode_roofline(run, pattern: str = r"^jit_decode"):
    """Least bytes of the decode steps traced (resident weights once a
    step, the held experts counted as hit once a step, the index keys each
    query scored, the latent rows it picked, the window rows it attended)
    over the device time of those programs."""
    if not is_ours(run.cell.config):
        return None
    seconds, steps = reduce._module_seconds(run, pattern)
    _, decodes = reduce._traced_tokens(run)
    hit = _hit_per_pass(run, "decode")
    if not seconds or not decodes or hit is None:
        return None
    m = model(run.cell.config)
    least = (steps * (resident_weight_bytes(m)
                      + hit * BF16 * expert_params(m))
             + sum(cache_bytes_read(c, m) for c in decodes)
             ) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds


def prefill_roofline(run, pattern: str = r"^jit_prefill"):
    """Per traced prefill the larger of FLOPs over peak and bytes over
    bandwidth (resident weights, held experts hit, the prompt's rows and
    index keys written), summed, over the device time of those programs;
    real prompt lengths, so bucket padding counts against it."""
    if not is_ours(run.cell.config):
        return None
    seconds, calls = reduce._module_seconds(run, pattern)
    prefills = counts_moe.traced_prefills(run.facts, calls) if calls \
        else None
    hit = _hit_per_pass(run, "prefill")
    per_p = _landed_per_token(run, "prefill")
    if not prefills or hit is None or per_p is None:
        return None
    m = model(run.cell.config)
    stream = resident_weight_bytes(m) + hit * BF16 * expert_params(m)
    least = sum(counts.roofline_seconds(
        prefill_flops(n, m, per_p * n), stream + cache_bytes_written(n, m),
        run.peaks) for n in prefills)
    return 100.0 * least / seconds


def _kernel_seconds(run, pattern: str):
    if run.trace is None or not run.trace.ops \
            or not is_ours(run.cell.config):
        return None
    chip = min(run.trace.ops)
    seconds, n = trace_mod.matching_seconds(run.trace.ops[chip], pattern)
    return seconds if n else None


def _scope_seconds(run, module: str):
    """Device seconds of the operations under ``apex_swa_attend`` in the
    executables whose name matches ``module`` (averaged over the chips),
    or ``None`` where there are none."""
    if not is_ours(run.cell.config):
        return None
    got = scopes.attribute(run.trace)
    if got is None:
        return None
    hit = [sec for (name, _), (chain, _, sec) in got["ops"].items()
           if SWA_SCOPE in chain and re.match(module, name)]
    return sum(hit) if hit else None


def swa_latent_ms_per_pass(run):
    """Device ms a traced pass under the window layers' attention scope
    (both phases, all window layers)."""
    if not is_ours(run.cell.config):
        return None
    return scopes.ms_per_pass(run, (SWA_SCOPE,))


def swa_latent_decode_roofline(run):
    """The decode's ring attention against the work of its form: per ring
    position a window layer attended (``swa_attended``) one window row and
    ``latent_flops`` of the window geometry, over the decode part of the
    scope's time."""
    seconds = _scope_seconds(run, r"^jit_decode")
    per = _attended_per_decode(run)
    _, decodes = reduce._traced_tokens(run)
    if not seconds or per is None or not decodes:
        return None
    m = model(run.cell.config)
    g = m["swa_geo"]
    n = per * len(decodes)
    least = counts.roofline_seconds(latent_flops(g) * n, row_bytes(g) * n,
                                    run.peaks)
    return 100.0 * least / seconds


def swa_flash_roofline(run):
    """The prefill's windowed flash kernel against its least time: the
    (query, key in its window) pairs of the traced prompts at their real
    lengths, ``pair_flops`` of the window geometry each, and q, k, v and
    the output once, every window layer; over the prefill part of the
    scope's time."""
    seconds = _scope_seconds(run, r"^jit_prefill")
    calls = reduce._module_seconds(run, r"^jit_prefill")[1]
    prefills = counts_moe.traced_prefills(run.facts, calls) if calls \
        else None
    if not seconds or not prefills:
        return None
    m = model(run.cell.config)
    least = sum(counts.roofline_seconds(
        m["swa"] * pair_flops(m["swa_geo"]) * prompt_in_window(n, m),
        m["swa"] * flash_bytes(n, m), run.peaks) for n in prefills)
    return 100.0 * least / seconds


def dsa_attend_latent_roofline(run):
    """The decode's latent attention of the full layers against the LEAST
    work — the picked positions only, one latent row each, ``latent_flops``
    a position a full layer — whichever way the kernel reaches them (it
    walks every live page, so it reads low by design)."""
    seconds = _kernel_seconds(run, ATTEND_LATENT_KERNEL)
    _, decodes = reduce._traced_tokens(run)
    if not seconds or not decodes:
        return None
    m = model(run.cell.config)
    g = m["full_geo"]
    n = m["full"] * sum(picked(c, m) for c in decodes)
    least = counts.roofline_seconds(latent_flops(g) * n, row_bytes(g) * n,
                                    run.peaks)
    return 100.0 * least / seconds


def dsa_index_roofline(run):
    """The decode's index kernel against its least time: one index key a
    position scored a full layer, ``index_flops`` each, the larger of the
    two times."""
    seconds = _kernel_seconds(run, INDEX_DECODE_KERNEL)
    _, decodes = reduce._traced_tokens(run)
    if not seconds or not decodes:
        return None
    m = model(run.cell.config)
    n = m["full"] * sum(scored(c, m) for c in decodes)
    least = counts.roofline_seconds(index_flops(m) * n,
                                    index_key_bytes(m) * n, run.peaks)
    return 100.0 * least / seconds

