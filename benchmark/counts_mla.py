"""Operations and bytes of a decoder with latent attention (MLA) and an
expert FFN that HOLDS a share of its experts (``model_type: axk1``), from
shapes alone, and the metrics built on them.  Beside ``counts.py`` (GPT's
dense counts) and ``counts_moe.py`` (Laguna's), whose trace rules for the
grouped products are taken by import.

Every count is the LEAST any implementation must do for the work the window
completed, so that a later kernel is read against the same work:

* 2 FLOPs a parameter of the matrices a token is ACTIVE in — attention's
  five (``W_DQ``, ``W_UQ``, ``W_DKV``, ``W_UKV``, ``W_O``; the absorbed form
  applies ``W_UKV``'s two halves once each, the expanded form the whole),
  the router, the shared expert, a dense layer's FFN — plus ``2 x 3 x hidden
  x width`` an assignment that LANDED on a held expert (the program's own
  counter: assignments to the absent experts are nobody's work here), plus
  one vocabulary row-block a sampled token;
* attention over the contexts attended: a prefill in the EXPANDED form,
  ``2 (qk + v)`` a head a causal pair; a decode step in the ABSORBED form,
  which is what a cache of latent rows forces, ``2 (latent + rope +
  latent)`` a head a position;
* bytes: the resident weights once a step, the held experts HIT once a step
  (the program's counter), one latent row — ``(latent + rope) x 2 B`` — a
  layer a position attended or written.

A share above 100% means a count or a window is wrong.

``facts["moe"]`` is ``drivers/moe_serve.py``'s: per phase the steps that ran
an expert FFN, their assignments (for this kind: those that LANDED), experts
hit (of those held) and busiest expert, over the window and its drain.
"""
from __future__ import annotations

from . import counts, counts_moe, reduce, spans
from . import trace as trace_mod

BF16 = counts.BF16

#: the kernels' own names in a trace (``pallas_call(name=...)``)
LATENT_KERNEL = r"^%apex_paged_decode_latent"
FLASH_KERNEL = r"^%apex_flash_fwd"


def model(cfg: dict) -> dict:
    """The numbers of a configuration file the counts need; ``held`` is
    ``n_routed_experts`` as run, ``experts`` the router's published width."""
    return dict(
        hidden=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        q_rank=cfg["q_lora_rank"], kv_rank=cfg["kv_lora_rank"],
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
        v_dim=cfg["v_head_dim"], layers=cfg["num_hidden_layers"],
        dense_layers=cfg["first_k_dense_replace"],
        dense_ffn=cfg["intermediate_size"],
        expert_ffn=cfg["moe_intermediate_size"],
        shared_ffn=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        experts=cfg["published"]["n_routed_experts"],
        held=cfg["n_routed_experts"], top_k=cfg["num_experts_per_tok"],
        vocab=cfg["vocab_size"])


def is_ours(cfg: dict) -> bool:
    return "kv_lora_rank" in cfg and "n_routed_experts" in cfg.get(
        "published", {})


def expert_layers(m: dict) -> int:
    return m["layers"] - m["dense_layers"]


def row_bytes(m: dict) -> int:
    """One cached position of one layer: the latent beside the roped key
    channels, and nothing per head."""
    return (m["kv_rank"] + m["rope"]) * BF16


def attention_params(m: dict) -> int:
    h, heads = m["hidden"], m["heads"]
    return (h * m["q_rank"] + m["q_rank"] * heads * (m["nope"] + m["rope"])
            + h * (m["kv_rank"] + m["rope"])
            + m["kv_rank"] * heads * (m["nope"] + m["v_dim"])
            + heads * m["v_dim"] * h)


def expert_params(m: dict) -> int:
    """One routed expert: three matrices."""
    return 3 * m["hidden"] * m["expert_ffn"]


def layer_resident_params(m: dict, i: int) -> int:
    """What every token of layer ``i`` is multiplied by whatever it routes:
    attention, and the dense FFN or the router and the shared expert."""
    if i < m["dense_layers"]:
        return attention_params(m) + 3 * m["hidden"] * m["dense_ffn"]
    return (attention_params(m) + m["hidden"] * m["experts"]
            + 3 * m["hidden"] * m["shared_ffn"])


def resident_params(m: dict) -> int:
    return sum(layer_resident_params(m, i) for i in range(m["layers"]))


def total_params(m: dict) -> int:
    """Everything this chip holds: the resident matrices, the held experts,
    embedding and head (norm gains left out)."""
    return (resident_params(m)
            + expert_layers(m) * m["held"] * expert_params(m)
            + 2 * m["hidden"] * m["vocab"])


def pair_flops(m: dict) -> int:
    """Expanded form, one query against one key, all heads: scores over
    ``nope + rope`` channels and values of ``v_dim``."""
    return 2 * m["heads"] * (m["nope"] + m["rope"] + m["v_dim"])


def position_flops(m: dict) -> int:
    """Absorbed form, one query against one cached row, all heads: scores
    over the whole row, values its latent part — the kernel's own ops."""
    return 2 * m["heads"] * (m["kv_rank"] + m["rope"] + m["kv_rank"])


def prefill_flops(n: int, m: dict, landed: float = 0.0) -> float:
    """A prompt of ``n`` tokens of which ``landed`` (token, expert)
    assignments fell on a held expert."""
    return float(2 * n * resident_params(m) + 2 * expert_params(m) * landed
                 + m["layers"] * pair_flops(m) * (n * (n + 1) // 2)
                 + 2 * m["hidden"] * m["vocab"])


def decode_flops(context: int, m: dict, landed: float = 0.0) -> float:
    """One generated token attending ``context`` cached rows."""
    return float(2 * resident_params(m) + 2 * expert_params(m) * landed
                 + m["layers"] * position_flops(m) * context
                 + 2 * m["hidden"] * m["vocab"])


def resident_weight_bytes(m: dict) -> float:
    """Bytes every step reads whatever it routes, the vocabulary
    projection among them (the embedding is a gather of rows)."""
    return float(BF16 * (resident_params(m) + m["hidden"] * m["vocab"]))


def rows_bytes_attended(context: int, m: dict) -> int:
    return row_bytes(m) * context * m["layers"]


# -- what the program counted ------------------------------------------------

def _moe(run, phase: str):
    moe = run.facts.get("moe")
    if not moe or not is_ours(run.cell.config):
        return None
    return moe.get(phase)


def _hit_per_pass(run, phase: str):
    """Mean held experts hit (summed over the expert layers) by a step."""
    c = _moe(run, phase)
    return c["experts_hit"] / c["passes"] if c and c["passes"] else None


def _carried(facts) -> tuple:
    """Tokens the counted passes routed, ``(prefill, decode)``: a request's
    prompt with its first token, one token with each later one."""
    stamped = [r for r in facts["requests"] if r["token_times"]]
    return (sum(r["prompt_len"] for r in stamped),
            sum(len(r["token_times"]) - 1 for r in stamped))


def _landed_share(run, phase=None):
    """Of the (token, expert) assignments the counted passes made — ``top_k``
    a token an expert layer — the share that LANDED on a held expert, as a
    fraction; ``phase`` None: both phases."""
    if not is_ours(run.cell.config):
        return None
    phases = [phase] if phase else ["prefill", "decode"]
    counted = [_moe(run, ph) for ph in phases]
    if any(c is None for c in counted):
        return None
    pre, dec = _carried(run.facts)
    tokens = sum({"prefill": pre, "decode": dec}[ph] for ph in phases)
    m = model(run.cell.config)
    made = m["top_k"] * expert_layers(m) * tokens
    if not made:
        return None
    return sum(c["assignments"] for c in counted) / made


def moe_landed_share(run):
    share = _landed_share(run)
    return None if share is None else 100.0 * share


def moe_experts_hit_share(run):
    """Of the experts HELD (all expert layers), those a decode step gave a
    token to, as a share: what ``decode_roofline.mla`` counts as read."""
    hit = _hit_per_pass(run, "decode")
    if hit is None:
        return None
    m = model(run.cell.config)
    return 100.0 * hit / (m["held"] * expert_layers(m))


def moe_load_max_over_mean(run):
    """Over the prefill passes: the busiest held expert's tokens (largest
    of the pass's expert layers, summed over passes) over the mean held
    expert's — the assignments that LANDED over the experts held."""
    c = _moe(run, "prefill")
    if not c or not c["assignments"]:
        return None
    m = model(run.cell.config)
    return c["load_max"] / (c["assignments"]
                            / (m["held"] * expert_layers(m)))


def _landed_per_token(run, phase: str) -> float:
    """Assignments that landed, a token of ``phase``, summed over the
    expert layers — the phase's own mean."""
    share = _landed_share(run, phase)
    if share is None:
        return None
    m = model(run.cell.config)
    return share * m["top_k"] * expert_layers(m)


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
    step, the held experts the program counted as hit once a step, one
    latent row a layer a position attended) over the device time of those
    programs."""
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
             + sum(rows_bytes_attended(c, m) for c in decodes)
             ) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds


def prefill_roofline(run, pattern: str = r"^jit_prefill"):
    """Per traced prefill the larger of FLOPs over peak (expanded form) and
    bytes over bandwidth (resident weights, held experts hit, the prompt's
    latent rows written), summed, over the device time of those programs."""
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
        prefill_flops(n, m, per_p * n),
        stream + row_bytes(m) * n * m["layers"], run.peaks)
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


def mla_decode_ms_per_pass(run):
    """Device ms a traced pass of the latent decode kernel (all layers)."""
    return _ms_per_pass(run, LATENT_KERNEL)


def mla_decode_roofline(run):
    """The latent kernel's own least time — each position attended read
    once a layer, ``position_flops`` a position a layer, the larger of the
    two times — over its device time."""
    seconds = _kernel_seconds(run, LATENT_KERNEL)
    _, decodes = reduce._traced_tokens(run)
    if not seconds or not decodes:
        return None
    m = model(run.cell.config)
    positions = sum(decodes) * m["layers"]
    least = counts.roofline_seconds(position_flops(m) * positions,
                                    row_bytes(m) * positions, run.peaks)
    return 100.0 * least / seconds


def mla_flash_roofline(run):
    """The prefill attention kernel at 192-wide scores and 128-wide values:
    ``pair_flops`` a causal pair a layer (the causal half counted once), q,
    k, v read and the output written once, the larger of the two times,
    over the device time of ``apex_flash_fwd``."""
    seconds = _kernel_seconds(run, FLASH_KERNEL)
    _, calls = reduce._module_seconds(run, r"^jit_prefill")
    prefills = counts_moe.traced_prefills(run.facts, calls) if calls \
        else None
    if not seconds or not prefills:
        return None
    m = model(run.cell.config)
    per_row = BF16 * m["heads"] * (2 * (m["nope"] + m["rope"])
                                   + 2 * m["v_dim"])
    least = sum(counts.roofline_seconds(
        m["layers"] * pair_flops(m) * (n * (n + 1) // 2),
        m["layers"] * per_row * n, run.peaks) for n in prefills)
    return 100.0 * least / seconds


def products_pattern(run) -> str:
    """XLA's ``%ragged-dot-*`` (``counts_moe.RAGGED``, by import) and the
    router.  ``counts_moe.products_pattern`` finds Laguna's router by its
    width as the last dimension of ANY result; here that width (192
    experts) is also a query's and a key's (``qk_nope_head_dim +
    qk_rope_head_dim``), so the rule is narrower: a RANK-2 float32, int32
    or bool result ``[tokens, experts]`` (a tuple's first member counts) —
    the router's float32 scores, its group mask and its top-k's sort, as
    ``route_group_limited`` makes them whatever the activations' type.
    Attention's ``[.., heads, 192]`` assemblies have a head axis and are
    never found (a test holds that against the step compiled for a v5e)."""
    m = model(run.cell.config)
    return r"%s|^%%\S+ = \(?(?:f32|s32|pred)\[\d+,%d\]" % (
        counts_moe.RAGGED, m["experts"])


def moe_products_ms_per_pass(run):
    """Device ms a traced pass of the held experts' grouped products (with
    the fusions that read them) and the router."""
    if not is_ours(run.cell.config):
        return None
    return _ms_per_pass(run, products_pattern(run))


def moe_products_roofline(run):
    """Least time of the grouped products of the traced steps — the held
    experts hit read once a step, each LANDED assignment's activations in
    and out, ``2 x 3 x hidden x width`` FLOPs a landed assignment — over
    the device time ``moe_products_ms_per_pass.mla`` reads."""
    if not is_ours(run.cell.config):
        return None
    seconds = _kernel_seconds(run, products_pattern(run))
    prefills, decodes = reduce._traced_tokens(run)
    _, steps = reduce._module_seconds(run, r"^jit_decode")
    hit_d, hit_p = _hit_per_pass(run, "decode"), _hit_per_pass(run, "prefill")
    per_p = _landed_per_token(run, "prefill")
    per_d = _landed_per_token(run, "decode")
    if not seconds or hit_d is None or per_d is None \
            or not (prefills or decodes):
        return None
    m = model(run.cell.config)
    landed = (per_p or 0.0) * sum(prefills) + per_d * len(decodes)
    hits = steps * hit_d + len(prefills) * (hit_p or 0.0)
    least = counts.roofline_seconds(
        2.0 * expert_params(m) * landed,
        BF16 * (hits * expert_params(m) + landed * 2 * m["hidden"]),
        run.peaks)
    return 100.0 * least / seconds
