"""Operations and bytes of a decoder with an expert FFN, window layers and a
head count per layer (``model_type: laguna``), from shapes alone, and the
metrics built on them.  Beside ``counts.py``, whose dense counts stay GPT's.

Every count is the LEAST any implementation must do for the work the window
completed: only the matrices a token is ACTIVE in (its ``top_k`` experts,
the shared expert, the router), each layer's own head count, at most
``sliding_window`` keys in a window layer, the experts HIT read once a step
(from the program's own counter), one vocabulary projection per sampled
token.  A share above 100% means a count or a window is wrong.

``facts["moe"]`` is ``drivers/moe_serve.py``'s: per phase (``prefill``,
``decode``) the steps that ran an expert FFN, their (token, expert)
assignments, experts hit and busiest expert, over the window and its drain.
"""
from __future__ import annotations

from . import counts, reduce, spans
from . import trace as trace_mod

BF16 = counts.BF16

#: what of the expert FFN a trace lets a reader find.  An operation's event
#: is named by the head of its HLO text (``trace.NAME_CHARS``) and by nothing
#: else: on the v5e neither the event's name nor its statistics carry the
#: ``op_name`` a ``jax.named_scope`` sets (probed, PERF.md section 7.10), so
#: only two rules survive a rename of the program.  XLA names its
#: grouped-matmul custom calls ``%ragged-dot-*`` (the products and their tile
#: metadata), and a fusion that reads one names it among its operands; and
#: the router's operations (logits, soft-max, top-k) carry the EXPERT COUNT
#: as the last dimension of their result type — ``products_pattern`` builds
#: both.  The sort of the assignments, the gather of the sorted rows, the
#: un-sort and weighted combine and the shared expert have no such mark
#: (``[tokens * top_k, hidden]`` is also a longer prompt's ``[tokens,
#: hidden]``) and are NOT found: the metrics are named for what they read
RAGGED = r"ragged-dot"


def model(cfg: dict) -> dict:
    """The numbers of a configuration file the counts need."""
    return dict(
        hidden=cfg["hidden_size"], head_dim=cfg["head_dim"],
        kv_heads=cfg["num_key_value_heads"],
        heads=tuple(cfg["num_attention_heads_per_layer"]),
        sliding=tuple(t == "sliding_attention" for t in cfg["layer_types"]),
        sparse=tuple(t == "sparse" for t in cfg["mlp_layer_types"]),
        window=cfg["sliding_window"], dense_ffn=cfg["intermediate_size"],
        expert_ffn=cfg["moe_intermediate_size"],
        shared_ffn=cfg["shared_expert_intermediate_size"],
        experts=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
        vocab=cfg["vocab_size"])


def expert_layers(m: dict) -> int:
    return sum(m["sparse"])


def attention_params(m: dict, i: int) -> int:
    """q, k, v, the per-head gate and the output projection of layer i."""
    h, d = m["hidden"], m["head_dim"]
    return (2 * h * m["heads"][i] * d + 2 * h * m["kv_heads"] * d
            + h * m["heads"][i])


def expert_params(m: dict) -> int:
    """One routed expert: three matrices."""
    return 3 * m["hidden"] * m["expert_ffn"]


def ffn_active_params(m: dict, i: int) -> int:
    """The FFN matrices ONE token is multiplied by in layer i."""
    if not m["sparse"][i]:
        return 3 * m["hidden"] * m["dense_ffn"]
    return (m["hidden"] * m["experts"] + m["top_k"] * expert_params(m)
            + 3 * m["hidden"] * m["shared_ffn"])


def active_params(m: dict) -> int:
    return sum(attention_params(m, i) + ffn_active_params(m, i)
               for i in range(len(m["heads"])))


def keys_seen(n: int, window=None) -> int:
    """Keys the ``n`` tokens of a prompt attend, summed: causal, or the
    last ``window`` of them."""
    if window is None or n <= window:
        return n * (n + 1) // 2
    return window * (window + 1) // 2 + (n - window) * window


def prefill_flops(n: int, m: dict) -> float:
    """2 per active weight per token, scores and values over the keys each
    token may see (by layer type and head count), one vocabulary
    projection."""
    attn = sum(4 * m["head_dim"] * m["heads"][i]
               * keys_seen(n, m["window"] if m["sliding"][i] else None)
               for i in range(len(m["heads"])))
    return float(2 * n * active_params(m) + attn
                 + 2 * m["hidden"] * m["vocab"])


def decode_flops(context: int, m: dict) -> float:
    """One generated token attending ``context`` keys (a window layer at
    most ``window`` of them)."""
    attn = sum(4 * m["head_dim"] * m["heads"][i]
               * (min(context, m["window"]) if m["sliding"][i] else context)
               for i in range(len(m["heads"])))
    return float(2 * active_params(m) + attn
                 + 2 * m["hidden"] * m["vocab"])


def resident_weight_bytes(m: dict) -> float:
    """Bytes every step reads whatever it routes: attention, dense FFNs,
    routers, shared experts, the vocabulary projection (the embedding is a
    gather of rows, not a stream)."""
    n = sum(attention_params(m, i)
            + (m["hidden"] * m["experts"] + 3 * m["hidden"] * m["shared_ffn"]
               if m["sparse"][i] else 3 * m["hidden"] * m["dense_ffn"])
            for i in range(len(m["heads"])))
    return float(BF16 * (n + m["hidden"] * m["vocab"]))


def kv_bytes_attended(context: int, m: dict) -> int:
    """Bytes of cached keys and values one token at ``context`` reads."""
    per_key = 2 * m["kv_heads"] * m["head_dim"] * BF16
    return per_key * sum(min(context, m["window"]) if s else context
                         for s in m["sliding"])


def kv_bytes_written(n: int, m: dict) -> int:
    per_key = 2 * m["kv_heads"] * m["head_dim"] * BF16
    return per_key * n * len(m["heads"])


# -- metrics ------------------------------------------------------------------

def _moe(run, phase: str):
    """The driver's expert counters of a phase; None for a cell whose
    program or configuration has no expert FFN."""
    moe = run.facts.get("moe")
    if not moe or "num_experts" not in run.cell.config:
        return None
    return moe.get(phase)


def _hit_per_pass(run, phase: str):
    """Mean experts hit (summed over the expert layers) by one step."""
    c = _moe(run, phase)
    return c["experts_hit"] / c["passes"] if c and c["passes"] else None


def serve_step_mfu(run):
    """``reduce.serve_step_mfu`` with this kind's counts."""
    if "num_experts" not in run.cell.config:
        return None
    lo, hi = run.facts["window"]
    if run.facts.get("trace_started"):
        hi = min(hi, run.facts["trace_started"])
    prefills, decodes = reduce._tokens_between(run.facts, lo, hi)
    m = model(run.cell.config)
    flops = (sum(prefill_flops(n, m) for n in prefills)
             + sum(decode_flops(c, m) for c in decodes))
    if not flops:
        return None
    return 100.0 * flops / (hi - lo) / (
        run.cell.chips * run.peaks["bf16_flops_per_s"])


def decode_roofline(run, pattern: str = r"^jit_decode"):
    """Least bytes of the decode steps traced (resident weights once a
    step, the experts the program counted as hit once a step, the keys and
    values each token attended) over the device time of those programs."""
    seconds, steps = reduce._module_seconds(run, pattern)
    _, decodes = reduce._traced_tokens(run)
    hit = _hit_per_pass(run, "decode")
    if not seconds or not decodes or hit is None:
        return None
    m = model(run.cell.config)
    least = (steps * (resident_weight_bytes(m)
                      + hit * BF16 * expert_params(m))
             + sum(kv_bytes_attended(c, m) for c in decodes)
             ) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds


def traced_prefills(facts, calls: int, slack: int = 3):
    """``reduce.traced_prefills`` for a loop that prefills several prompts
    in one pass: all of a pass's first tokens are stamped when the pass
    returns, so at the trace's edges the device may have run up to a pass's
    worth of calls whose stamps fall after the stop (taken in, in stamp
    order), or not have run the first ones stamped (left out).  More than
    ``slack`` calls apart, nothing is read."""
    lo, hi = facts.get("trace_started"), facts.get("trace_stopped")
    if lo is None or hi is None:
        return None
    firsts = sorted((r["token_times"][0], r["prompt_len"])
                    for r in facts["requests"] if r["token_times"])
    inside = [n for t, n in firsts if lo <= t <= hi]
    after = [n for t, n in firsts if t > hi]
    extra = calls - len(inside)
    if 0 <= extra <= min(slack, len(after)):
        return inside + after[:extra]
    if -slack <= extra < 0:
        return inside[-extra:]
    return None


def prefill_roofline(run, pattern: str = r"^jit_prefill"):
    """Per traced prefill the larger of FLOPs over peak and bytes over
    bandwidth (resident weights, experts hit, keys and values written),
    summed, over the device time of those programs.  Prompt lengths from
    the first-token stamps, held to the device's count of calls."""
    seconds, calls = reduce._module_seconds(run, pattern)
    prefills = traced_prefills(run.facts, calls) if calls else None
    hit = _hit_per_pass(run, "prefill")
    if not prefills or hit is None:
        return None
    m = model(run.cell.config)
    stream = resident_weight_bytes(m) + hit * BF16 * expert_params(m)
    least = sum(counts.roofline_seconds(
        prefill_flops(n, m), stream + kv_bytes_written(n, m), run.peaks)
        for n in prefills)
    return 100.0 * least / seconds


def products_pattern(run) -> str:
    """See ``RAGGED``: the grouped products by XLA's own name for them, the
    router by the expert count as the LAST dimension of a result type —
    nothing else in the model is ``experts`` wide."""
    m = model(run.cell.config)
    return r"%s|^%%\S+ = \(?\w+\[(\d+,)*%d\]" % (RAGGED, m["experts"])


def _products_seconds(run):
    if run.trace is None or not run.trace.ops \
            or "num_experts" not in run.cell.config:
        return None
    chip = min(run.trace.ops)
    seconds, n = trace_mod.matching_seconds(run.trace.ops[chip],
                                            products_pattern(run))
    return seconds if n else None


def moe_products_ms_per_pass(run):
    """Device ms a traced pass of the expert FFN's grouped products (with
    the fusions that read them) and router."""
    seconds = _products_seconds(run)
    passes = spans.traced_passes(run.trace)
    if seconds is None or not passes:
        return None
    return seconds * 1e3 / len(passes)


def moe_products_roofline(run):
    """Least time of the grouped products of the traced steps — the experts
    hit read once a step, each assignment's activations in and out, ``2 * 3
    * hidden * width`` FLOPs an assignment — over the device time
    ``moe_products_ms_per_pass`` reads."""
    seconds = _products_seconds(run)
    prefills, decodes = reduce._traced_tokens(run)
    _, steps = reduce._module_seconds(run, r"^jit_decode")
    hit_d, hit_p = _hit_per_pass(run, "decode"), _hit_per_pass(run, "prefill")
    if not seconds or hit_d is None or not (prefills or decodes):
        return None
    m = model(run.cell.config)
    assignments = ((sum(prefills) + len(decodes)) * m["top_k"]
                   * expert_layers(m))
    hits = steps * hit_d + len(prefills) * (hit_p or 0.0)
    least = counts.roofline_seconds(
        2.0 * expert_params(m) * assignments,
        BF16 * (hits * expert_params(m) + assignments * 2 * m["hidden"]),
        run.peaks)
    return 100.0 * least / seconds


def moe_experts_hit_share(run):
    c = _moe(run, "decode")
    if not c or not c["passes"]:
        return None
    m = model(run.cell.config)
    return 100.0 * c["experts_hit"] / (
        m["experts"] * expert_layers(m) * c["passes"])


def moe_load_max_over_mean(run):
    """Over the prefill passes: the busiest expert's tokens (largest of the
    pass's expert layers, summed over passes) over the mean expert's."""
    c = _moe(run, "prefill")
    if not c or not c["assignments"]:
        return None
    m = model(run.cell.config)
    return c["load_max"] / (c["assignments"]
                            / (m["experts"] * expert_layers(m)))


def window_pages_live_peak(run):
    moe = run.facts.get("moe")
    return moe.get("window_pages_live_peak") if moe else None
