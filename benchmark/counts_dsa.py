"""Operations and bytes of a decoder whose layers SELECT the cached positions
they attend by a learned index (``model_type: KeyeVL2``: grouped-query
attention, an indexer with one index key a position, an expert FFN in every
layer), from shapes alone, and the metrics built on them.  Beside
``counts.py`` (GPT's dense counts), ``counts_moe.py`` (Laguna's, whose trace
rule for the grouped products is taken by import) and ``counts_mla.py``.

Every count is the LEAST any implementation must do for the work the window
completed — PICKED rows only, a page's index keys once — so that a later
kernel is read against the same work, whichever form of the three stages the
program keeps:

* 2 FLOPs a parameter of the matrices a token is ACTIVE in — attention's
  four, the indexer's three, the router, ``top_k`` experts — plus one
  vocabulary row-block a sampled token;
* the indexer: ``2 x index_heads x index_dim`` FLOPs and one index key
  (``index_dim x 2 B``) a position SCORED — the context of a query whose
  context is over ``topk``; a query at or under ``topk`` attends everything
  and has nothing to score;
* attention: ``2 x heads x (d + d)`` FLOPs and one key and one value a KV
  head (``2 x kv_heads x d x 2 B``) a position PICKED — ``min(context,
  topk)`` a query;
* bytes: the resident weights once a step, the experts HIT once a step (the
  program's counter), and the index keys and K/V rows above; a prefill
  writes one K/V row and one index key a position a layer.

A share above 100% means a count or a window is wrong.

``facts["moe"]`` and ``facts["dsa"]`` are ``drivers/dsa_serve.py``'s: per
phase the expert counters, and the selection's — query rows x layers through
the indexer, those whose context exceeded ``topk``, positions attended.
"""
from __future__ import annotations

from . import counts, counts_moe, reduce, spans
from . import trace as trace_mod

BF16 = counts.BF16

#: the kernels' own names in a trace (``pallas_call(name=...)``): the index
#: kernels of both phases, the decode's alone, the decode's attention over
#: the picked rows
INDEX_KERNELS = r"^%apex_dsa_index"
INDEX_DECODE_KERNEL = r"^%apex_dsa_index(\.\d+)? ="
ATTEND_KERNEL = r"^%apex_dsa_attend"
#: the selection is XLA's (compare-and-count passes over an unsigned image
#: of the scores): its operations are the step's only ones that read or
#: write a RANK-2 uint32 array (the PRNG key is rank 1) — in the head of
#: their HLO text as result or operand.  The 32-pass loop itself is an
#: event too (``%while.N``, which carries the image) and spans the passes
#: inside it: it is left out, its body's operations are taken
SELECT_OPS = r"^(?!.* while\()(?=.*u32\[\d+,\d+\])"


def is_ours(cfg: dict) -> bool:
    return "sa_config" in cfg and "num_experts" in cfg


def model(cfg: dict) -> dict:
    """The numbers of a configuration file the counts need."""
    sa = cfg["sa_config"]
    return dict(
        hidden=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        layers=cfg["num_hidden_layers"],
        index_heads=sa["indexer_num_heads"], index_dim=sa["indexer_head_dim"],
        topk=sa["topk"], expert_ffn=cfg["moe_intermediate_size"],
        experts=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
        vocab=cfg["vocab_size"])


def attention_params(m: dict) -> int:
    """q, k, v and the output projection."""
    h, d = m["hidden"], m["head_dim"]
    return 2 * h * m["heads"] * d + 2 * h * m["kv_heads"] * d


def indexer_params(m: dict) -> int:
    """Index queries, the one index key, the head weights."""
    return m["hidden"] * (m["index_heads"] * m["index_dim"]
                          + m["index_dim"] + m["index_heads"])


def expert_params(m: dict) -> int:
    """One routed expert: three matrices."""
    return 3 * m["hidden"] * m["expert_ffn"]


def layer_resident_params(m: dict) -> int:
    """What every token of a layer is multiplied by whatever it routes."""
    return (attention_params(m) + indexer_params(m)
            + m["hidden"] * m["experts"])


def resident_params(m: dict) -> int:
    return m["layers"] * layer_resident_params(m)


def active_params(m: dict) -> int:
    """The matrices ONE token is multiplied by, all layers."""
    return resident_params(m) + m["layers"] * m["top_k"] * expert_params(m)


def total_params(m: dict) -> int:
    """Everything this chip holds: every layer's matrices with all its
    experts, embedding and head (norm gains and the one bias left out)."""
    return (resident_params(m)
            + m["layers"] * m["experts"] * expert_params(m)
            + 2 * m["hidden"] * m["vocab"])


def row_bytes(m: dict) -> int:
    """One cached position of one layer: a key and a value a KV head."""
    return 2 * m["kv_heads"] * m["head_dim"] * BF16


def index_key_bytes(m: dict) -> int:
    return m["index_dim"] * BF16


def scored(context: int, m: dict) -> int:
    """Positions ONE query at ``context`` has to score: all of them, or
    none where it attends everything anyway."""
    return context if context > m["topk"] else 0


def picked(context: int, m: dict) -> int:
    return min(context, m["topk"])


def prompt_scored(n: int, m: dict) -> int:
    """Summed over the ``n`` queries of a prompt (query ``t`` has ``t + 1``
    causal positions)."""
    k = min(n, m["topk"])
    return n * (n + 1) // 2 - k * (k + 1) // 2


def prompt_picked(n: int, m: dict) -> int:
    k = min(n, m["topk"])
    return k * (k + 1) // 2 + (n - k) * m["topk"]


def index_flops(m: dict) -> int:
    """One query against one index key: a product an index head."""
    return 2 * m["index_heads"] * m["index_dim"]


def attend_flops(m: dict) -> int:
    """One query against one picked position, all heads: scores and
    values."""
    return 2 * m["heads"] * 2 * m["head_dim"]


def prefill_flops(n: int, m: dict) -> float:
    return float(2 * n * active_params(m)
                 + m["layers"] * (index_flops(m) * prompt_scored(n, m)
                                  + attend_flops(m) * prompt_picked(n, m))
                 + 2 * m["hidden"] * m["vocab"])


def decode_flops(context: int, m: dict) -> float:
    return float(2 * active_params(m)
                 + m["layers"] * (index_flops(m) * scored(context, m)
                                  + attend_flops(m) * picked(context, m))
                 + 2 * m["hidden"] * m["vocab"])


def resident_weight_bytes(m: dict) -> float:
    """Bytes every step reads whatever it routes, the vocabulary
    projection among them (the embedding is a gather of rows)."""
    return float(BF16 * (resident_params(m) + m["hidden"] * m["vocab"]))


def cache_bytes_read(context: int, m: dict) -> int:
    """Index keys scored and K/V rows picked by one query, all layers."""
    return m["layers"] * (index_key_bytes(m) * scored(context, m)
                          + row_bytes(m) * picked(context, m))


def cache_bytes_written(n: int, m: dict) -> int:
    return m["layers"] * n * (row_bytes(m) + index_key_bytes(m))


# -- what the program counted ------------------------------------------------

def _counted(run, family: str, phase: str):
    c = run.facts.get(family)
    if not c or not is_ours(run.cell.config):
        return None
    return c.get(phase)


def _hit_per_pass(run, phase: str):
    """Mean experts hit (summed over the layers) by one step."""
    c = _counted(run, "moe", phase)
    return c["experts_hit"] / c["passes"] if c and c["passes"] else None


def moe_experts_hit_share(run):
    """Of the experts (all layers), those a decode step gave a token to,
    as a share: what ``decode_roofline.dsa`` counts as read."""
    hit = _hit_per_pass(run, "decode")
    if hit is None:
        return None
    m = model(run.cell.config)
    return 100.0 * hit / (m["experts"] * m["layers"])


def moe_load_max_over_mean(run):
    """Over the prefill passes: the busiest expert's tokens (largest of
    the pass's layers, summed over passes) over the mean expert's."""
    c = _counted(run, "moe", "prefill")
    if not c or not c["assignments"]:
        return None
    m = model(run.cell.config)
    return c["load_max"] / (c["assignments"] / (m["experts"] * m["layers"]))


def dsa_selected_share(run):
    """Of the positions the counted queries COULD have attended (their
    contexts, summed, all layers, both phases — from the stamps), the share
    they did attend (the program's ``dsa_selected``): how much of the cache
    the steps really read."""
    counted = [_counted(run, "dsa", ph) for ph in ("prefill", "decode")]
    if any(c is None for c in counted):
        return None
    could = 0
    for r in run.facts["requests"]:
        if r["token_times"]:
            n, later = r["prompt_len"], len(r["token_times"]) - 1
            could += n * (n + 1) // 2 + later * n + later * (later + 1) // 2
    if not could:
        return None
    m = model(run.cell.config)
    return 100.0 * sum(c["selected"] for c in counted) / (m["layers"] * could)


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
    if not (prefills or decodes):
        return None
    m = model(run.cell.config)
    flops = (sum(prefill_flops(n, m) for n in prefills)
             + sum(decode_flops(c, m) for c in decodes))
    return 100.0 * flops / (hi - lo) / (
        run.cell.chips * run.peaks["bf16_flops_per_s"])


def decode_roofline(run, pattern: str = r"^jit_decode"):
    """Least bytes of the decode steps traced (resident weights once a
    step, the experts the program counted as hit once a step, the index
    keys each query scored and the K/V rows it picked) over the device time
    of those programs."""
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
    bandwidth (resident weights, experts hit, the prompt's K/V rows and
    index keys written), summed, over the device time of those programs;
    real prompt lengths, so bucket padding counts against it."""
    if not is_ours(run.cell.config):
        return None
    seconds, calls = reduce._module_seconds(run, pattern)
    prefills = counts_moe.traced_prefills(run.facts, calls) if calls \
        else None
    hit = _hit_per_pass(run, "prefill")
    if not prefills or hit is None:
        return None
    m = model(run.cell.config)
    stream = resident_weight_bytes(m) + hit * BF16 * expert_params(m)
    least = sum(counts.roofline_seconds(
        prefill_flops(n, m), stream + cache_bytes_written(n, m), run.peaks)
        for n in prefills)
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


def dsa_index_ms_per_pass(run):
    """Device ms a traced pass of the index kernels (decode's over the
    paged index keys, prefill's over a block of rows; all layers)."""
    return _ms_per_pass(run, INDEX_KERNELS)


def dsa_select_ms_per_pass(run):
    """Device ms a traced pass of the selection (``SELECT_OPS``)."""
    return _ms_per_pass(run, SELECT_OPS)


def dsa_attend_ms_per_pass(run):
    """Device ms a traced pass of the decode's attention over the picked
    rows (all layers); a prefill attends through ``apex_flash_fwd``."""
    return _ms_per_pass(run, ATTEND_KERNEL)


def dsa_index_roofline(run):
    """The decode's index kernel against its least time: one index key a
    position scored a layer, ``index_flops`` each, the larger of the two
    times."""
    seconds = _kernel_seconds(run, INDEX_DECODE_KERNEL)
    _, decodes = reduce._traced_tokens(run)
    if not seconds or not decodes:
        return None
    m = model(run.cell.config)
    n = m["layers"] * sum(scored(c, m) for c in decodes)
    least = counts.roofline_seconds(index_flops(m) * n,
                                    index_key_bytes(m) * n, run.peaks)
    return 100.0 * least / seconds


def dsa_attend_roofline(run):
    """The decode's attention kernel against the LEAST work — the picked
    rows only, one key and one value a KV head each, ``attend_flops`` a
    position — whichever way the kernel reaches them."""
    seconds = _kernel_seconds(run, ATTEND_KERNEL)
    _, decodes = reduce._traced_tokens(run)
    if not seconds or not decodes:
        return None
    m = model(run.cell.config)
    n = m["layers"] * sum(picked(c, m) for c in decodes)
    least = counts.roofline_seconds(attend_flops(m) * n, row_bytes(m) * n,
                                    run.peaks)
    return 100.0 * least / seconds


def products_pattern(run) -> str:
    """XLA's ``%ragged-dot-*`` (``counts_moe.RAGGED``, by import) and the
    router.  The router's width (128 experts) is also a head's and a page's,
    so the rule is ``counts_mla``'s narrow one: a RANK-2 float32, int32 or
    bool result ``[tokens, experts]`` (a tuple's first member counts) — the
    router's float32 probabilities and its top-k.  Attention's and the
    cache's 128-wide arrays have a head or a page axis besides and are never
    found (a test holds that against both steps compiled for a v5e)."""
    m = model(run.cell.config)
    return r"%s|^%%\S+ = \(?(?:f32|s32|pred)\[\d+,%d\]" % (
        counts_moe.RAGGED, m["experts"])


def moe_products_ms_per_pass(run):
    """Device ms a traced pass of the experts' grouped products (with the
    fusions that read them) and the router."""
    if not is_ours(run.cell.config):
        return None
    return _ms_per_pass(run, products_pattern(run))


def moe_products_roofline(run):
    """Least time of the grouped products of the traced steps — the experts
    hit read once a step, each assignment's activations in and out, ``2 x 3
    x hidden x width`` FLOPs an assignment — over the device time
    ``moe_products_ms_per_pass.dsa`` reads."""
    if not is_ours(run.cell.config):
        return None
    seconds = _kernel_seconds(run, products_pattern(run))
    prefills, decodes = reduce._traced_tokens(run)
    _, steps = reduce._module_seconds(run, r"^jit_decode")
    hit_d, hit_p = _hit_per_pass(run, "decode"), _hit_per_pass(run, "prefill")
    if not seconds or hit_d is None or not (prefills or decodes):
        return None
    m = model(run.cell.config)
    assignments = (sum(prefills) + len(decodes)) * m["top_k"] * m["layers"]
    hits = steps * hit_d + len(prefills) * (hit_p or 0.0)
    least = counts.roofline_seconds(
        2.0 * expert_params(m) * assignments,
        BF16 * (hits * expert_params(m) + assignments * 2 * m["hidden"]),
        run.peaks)
    return 100.0 * least / seconds
