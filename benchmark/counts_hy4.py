"""Operations and bytes of a decoder with latent attention over the positions
a learned indexer picks, the picks of a full layer reused by the shared
layers after it, an elementwise gate, a residual in several streams and an
expert FFN that HOLDS a share of its experts (``model_type: hy_v4``), from
shapes alone, and the metrics built on them.  Beside ``counts_mla.py`` (the
latent attention alone) and ``counts_dsa.py`` (a selection over per-head
K/V), whose trace rules for the index kernels are taken by import.

Every count is the LEAST any implementation must do for the work the window
completed, so that a later kernel is read against the same work:

* 2 FLOPs a parameter of the matrices a token is ACTIVE in — attention's
  five, the gate, the indexer on a full layer, the streams' mixes (``phi``),
  the router, the shared expert, a dense layer's FFN — plus ``2 x 3 x hidden
  x width`` an assignment that LANDED on a held expert (the program's own
  counter), plus one vocabulary row-block a sampled token;
* the streams: ``2 x (n + n^2 + n) x hidden`` a token a sublayer (read the
  sublayer's input, mix the streams, add its output), ``2 x n x hidden`` the
  head's collapse;
* the indexer: ``2 x index_heads x index_dim`` FLOPs and one index key a
  position SCORED a full layer — a query whose context is over ``topk``;
* attention: ``2 x heads x (nope + rope + v)`` a position PICKED a layer —
  ``min(context, topk)`` a query — the expanded form's count, the least of
  the two;
* bytes: the resident weights once a step (the head in float32), the held
  experts HIT once a step, one latent row a layer a position picked and one
  index key a full layer a position scored; a prefill writes one latent row
  a layer and one index key a full layer a position.

A share above 100% means a count or a window is wrong.

``facts["moe"]`` and ``facts["dsa"]`` are ``drivers/dsa_reuse_serve.py``'s:
per phase the expert counters (assignments that LANDED), and the
selection's — query rows x layers that attended a selection, those whose
context exceeded ``topk``, positions attended, and rows that attended a
carried pick set.
"""
from __future__ import annotations

from . import counts, counts_dsa, counts_moe, reduce, spans, scopes
from . import trace as trace_mod

BF16 = counts.BF16
F32 = 4

#: the kernels' own names in a trace (``pallas_call(name=...)``): the
#: decode's attention over the picked latent rows, and the index kernels
#: (both phases; the decode's alone) as ``counts_dsa`` finds them
ATTEND_LATENT_KERNEL = r"^%apex_dsa_attend_latent"
INDEX_KERNELS = counts_dsa.INDEX_KERNELS
INDEX_DECODE_KERNEL = counts_dsa.INDEX_DECODE_KERNEL
#: the streams' mixes, by the program's named scopes
HC_SCOPES = ("apex_hc_pre", "apex_hc_post", "apex_hc_head")


def is_ours(cfg: dict) -> bool:
    return "indexer_types" in cfg and "hc_mult" in cfg


def model(cfg: dict) -> dict:
    """The numbers of a configuration file the counts need; ``held`` is
    ``n_routed_experts`` as run, ``experts`` the router's published width."""
    return dict(
        hidden=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        q_rank=cfg["q_lora_rank"], kv_rank=cfg["kv_lora_rank"],
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
        v_dim=cfg["v_head_dim"], layers=cfg["num_hidden_layers"],
        full=cfg["indexer_types"].count("full"),
        indexer_full=tuple(t == "full" for t in cfg["indexer_types"]),
        dense_layers=cfg["mlp_layer_types"].count("dense"),
        dense_ffn=cfg["intermediate_size"],
        expert_ffn=cfg["moe_intermediate_size"],
        shared_ffn=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        experts=cfg["published"]["n_routed_experts"],
        held=cfg["n_routed_experts"], top_k=cfg["num_experts_per_tok"],
        index_heads=cfg["index_n_heads"], index_dim=cfg["index_head_dim"],
        topk=cfg["index_topk"], streams=cfg["hc_mult"],
        vocab=cfg["vocab_size"])


def expert_layers(m: dict) -> int:
    return m["layers"] - m["dense_layers"]


def row_bytes(m: dict) -> int:
    """One cached position of one layer: the latent beside the roped key
    channels."""
    return (m["kv_rank"] + m["rope"]) * BF16


def index_key_bytes(m: dict) -> int:
    return m["index_dim"] * BF16


def attention_params(m: dict) -> int:
    """MLA's five matrices and the gate."""
    h, heads = m["hidden"], m["heads"]
    return (h * m["q_rank"] + m["q_rank"] * heads * (m["nope"] + m["rope"])
            + h * (m["kv_rank"] + m["rope"])
            + m["kv_rank"] * heads * (m["nope"] + m["v_dim"])
            + 2 * heads * m["v_dim"] * h)


def indexer_params(m: dict) -> int:
    """Index queries (from the query latent), the one index key, the head
    weights."""
    return (m["q_rank"] * m["index_heads"] * m["index_dim"]
            + m["hidden"] * (m["index_dim"] + m["index_heads"]))


def mix_params(m: dict) -> int:
    """One sublayer's ``phi``: the pre, post and residual mixes."""
    n = m["streams"]
    return (2 * n + n * n) * n * m["hidden"]


def expert_params(m: dict) -> int:
    """One routed expert: three matrices."""
    return 3 * m["hidden"] * m["expert_ffn"]


def layer_resident_params(m: dict, i: int, full: bool) -> int:
    """What every token of layer ``i`` is multiplied by whatever it routes."""
    base = attention_params(m) + 2 * mix_params(m) \
        + (indexer_params(m) if full else 0)
    if i < m["dense_layers"]:
        return base + 3 * m["hidden"] * m["dense_ffn"]
    return base + m["hidden"] * m["experts"] + 3 * m["hidden"] * m[
        "shared_ffn"]


def resident_params(m: dict) -> int:
    return sum(layer_resident_params(m, i, full)
               for i, full in enumerate(m["indexer_full"]))


def head_mix_params(m: dict) -> int:
    return m["streams"] * m["streams"] * m["hidden"]


def total_params(m: dict) -> int:
    """Everything this chip holds: the resident matrices, the held experts,
    the head's mix, embedding and head (norm gains, sinks, the mixes' alpha
    and bias and the index key's bias left out)."""
    return (resident_params(m) + head_mix_params(m)
            + expert_layers(m) * m["held"] * expert_params(m)
            + 2 * m["hidden"] * m["vocab"])


def held_bytes(m: dict) -> int:
    """What ``total_params`` weighs as served: 2 B a parameter, the head
    4 B."""
    return BF16 * total_params(m) + (F32 - BF16) * m["hidden"] * m["vocab"]


def stream_flops(m: dict) -> int:
    """One token's mixing of the streams, all sublayers and the head."""
    n, h = m["streams"], m["hidden"]
    return 2 * m["layers"] * 2 * (2 * n + n * n) * h + 2 * n * h


def scored(context: int, m: dict) -> int:
    return counts_dsa.scored(context, m)


def picked(context: int, m: dict) -> int:
    return counts_dsa.picked(context, m)


def index_flops(m: dict) -> int:
    return counts_dsa.index_flops(m)


def pick_flops(m: dict) -> int:
    """One query against one picked position, all heads, expanded: scores
    over ``nope + rope`` channels and values of ``v_dim``."""
    return 2 * m["heads"] * (m["nope"] + m["rope"] + m["v_dim"])


def latent_flops(m: dict) -> int:
    """The decode kernel's own work a picked position, all heads: scores
    over the whole latent row, values its latent part."""
    return 2 * m["heads"] * (m["kv_rank"] + m["rope"] + m["kv_rank"])


def _token_flops(m: dict, landed: float) -> float:
    return (2 * (resident_params(m) + head_mix_params(m)) + stream_flops(m)
            + 2 * expert_params(m) * landed)


def prefill_flops(n: int, m: dict, landed: float = 0.0) -> float:
    """A prompt of ``n`` tokens of which ``landed`` (token, expert)
    assignments fell on a held expert."""
    return float(n * _token_flops(m, 0.0) + 2 * expert_params(m) * landed
                 + m["layers"] * pick_flops(m) * counts_dsa.prompt_picked(
                     n, m)
                 + m["full"] * index_flops(m) * counts_dsa.prompt_scored(
                     n, m)
                 + 2 * m["hidden"] * m["vocab"])


def decode_flops(context: int, m: dict, landed: float = 0.0) -> float:
    """One generated token at ``context``."""
    return float(_token_flops(m, landed)
                 + m["layers"] * pick_flops(m) * picked(context, m)
                 + m["full"] * index_flops(m) * scored(context, m)
                 + 2 * m["hidden"] * m["vocab"])


def resident_weight_bytes(m: dict) -> float:
    """Bytes every step reads whatever it routes, the float32 vocabulary
    projection among them (the embedding is a gather of rows)."""
    return float(BF16 * (resident_params(m) + head_mix_params(m))
                 + F32 * m["hidden"] * m["vocab"])


def cache_bytes_read(context: int, m: dict) -> int:
    """Index keys scored (full layers) and latent rows picked (every
    layer) by one query."""
    return (m["full"] * index_key_bytes(m) * scored(context, m)
            + m["layers"] * row_bytes(m) * picked(context, m))


def cache_bytes_written(n: int, m: dict) -> int:
    return n * (m["layers"] * row_bytes(m) + m["full"] * index_key_bytes(m))


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


def _landed_per_token(run, phase: str):
    """Assignments that LANDED, a token of ``phase``, all expert layers."""
    c = _counted(run, "moe", phase)
    if c is None:
        return None
    stamped = [r for r in run.facts["requests"] if r["token_times"]]
    tokens = (sum(r["prompt_len"] for r in stamped) if phase == "prefill"
              else sum(len(r["token_times"]) - 1 for r in stamped))
    return c["assignments"] / tokens if tokens else None


def dsa_rows_reused_share(run):
    """Of the query rows x layers that attended a selection (both phases),
    the share that attended a CARRIED pick set (the program's
    ``dsa_rows_reused``): the shared layers' share of the layers."""
    counted = [_counted(run, "dsa", ph) for ph in ("prefill", "decode")]
    if any(c is None or "rows_reused" not in c for c in counted):
        return None
    rows = sum(c["rows"] for c in counted)
    return 100.0 * sum(c["rows_reused"] for c in counted) / rows if rows \
        else None


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
    query scored and the latent rows it picked) over the device time of
    those programs."""
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
    bandwidth (resident weights, held experts hit, the prompt's latent rows
    and index keys written), summed, over the device time of those
    programs; real prompt lengths, so bucket padding counts against it."""
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


def _ms_per_pass(run, pattern: str):
    seconds = _kernel_seconds(run, pattern)
    passes = spans.traced_passes(run.trace)
    if seconds is None or not passes:
        return None
    return seconds * 1e3 / len(passes)


def hc_ms_per_pass(run):
    """Device ms a traced pass under the streams' scopes (both phases)."""
    if not is_ours(run.cell.config):
        return None
    return scopes.ms_per_pass(run, HC_SCOPES)


def dsa_attend_latent_ms_per_pass(run):
    """Device ms a traced pass of the decode's latent attention over the
    picked positions (all layers)."""
    return _ms_per_pass(run, ATTEND_LATENT_KERNEL)


def dsa_index_ms_per_pass(run):
    """Device ms a traced pass of the index kernels (both phases, the full
    layers)."""
    return _ms_per_pass(run, INDEX_KERNELS)


def dsa_attend_latent_roofline(run):
    """The decode's latent attention against the LEAST work — the picked
    positions only, one latent row each, ``latent_flops`` a position a
    layer — whichever way the kernel reaches them (it walks every live
    page, so it reads low by design)."""
    seconds = _kernel_seconds(run, ATTEND_LATENT_KERNEL)
    _, decodes = reduce._traced_tokens(run)
    if not seconds or not decodes:
        return None
    m = model(run.cell.config)
    n = m["layers"] * sum(picked(c, m) for c in decodes)
    least = counts.roofline_seconds(latent_flops(m) * n, row_bytes(m) * n,
                                    run.peaks)
    return 100.0 * least / seconds


def dsa_index_roofline(run):
    """The decode's index kernel against its least time: one index key a
    position scored a FULL layer, ``index_flops`` each, the larger of the
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
