"""Capture hygiene: the physical-plausibility scrub for bench capture
payloads, shared by the bench orchestrator (``bench.py`` republishing
recorded history) and the perf-regression watch
(:mod:`apex_tpu.observability.watch` trending committed captures).

Extracted from ``bench.py`` (ISSUE 13) so package code can scrub
without importing the repo-root bench script: one copy of the rules,
two consumers — the no-second-copy discipline the chip-spec table
already follows.

ISSUE 19: the fleet bench leg's per-replica and policy-comparison
fields (``fleet_affinity_ttft_us`` / ``fleet_round_robin_ttft_us``,
``fleet_capacity_pred_ttft_us``) need NO new rules here — they ride
the existing ``*_us`` latency suffix scrub, and the capacity sim's
``fleet_capacity_drift_ratio`` is a unitless >= 1 agreement ratio the
watch already trends by its ``_drift_ratio`` suffix.
"""
from __future__ import annotations

import math

__all__ = ["MAX_PLAUSIBLE_SPEEDUP", "MAX_PLAUSIBLE_TOKENS_PER_S",
           "MAX_PLAUSIBLE_LATENCY_US", "MAX_PLAUSIBLE_MFU",
           "is_us_key", "is_tokens_per_s_key", "is_mfu_key",
           "is_acceptance_rate_key", "hbm_capacity_bound",
           "vmem_capacity_bound", "is_vmem_model_key",
           "MAX_PLAUSIBLE_HOST_TIER_BYTES", "is_host_tier_bytes_key",
           "scrub_capture_values"]

#: capture-hygiene bounds: a measured duration of exactly 0.0 µs means
#: the whole timing loop collapsed inside the subtracted dispatch
#: round trip's jitter (r5:
#: flash_attn_us 0.0, moe us_gather 0.0), and a kernel "speedup" beyond
#: 100x over an XLA baseline on the same chip is not physics either
#: (r5: flash_attn_speedup 89198634.0 — the ratio of a real baseline to
#: a collapsed ~0 measurement).  Such values are measurement artifacts
#: and must never reach the perf-regression watch.
MAX_PLAUSIBLE_SPEEDUP = 100.0

#: throughput sanity ceiling for ``*tokens_per_s`` capture fields.  The
#: same RTT-collapse that produced ``flash_attn_us: 0.0`` turns a
#: throughput field into tokens/(~0 s): a v5e streaming a transformer
#: at > 1e8 tokens/s is not physics (the flagship GPT measures ~1.1e5;
#: even the cheap MoE layer pass peaks ~2.3e6).  0 and negatives are
#: the us==0.0 artifact's other face (tokens / garbage-negative time).
MAX_PLAUSIBLE_TOKENS_PER_S = 1e8

#: latency sanity ceiling for ``*_us`` capture fields (ISSUE 8: the
#: telemetry TTFT / per-token decode latencies now ride in captures).
#: One HOUR for a single step/request latency is not physics — it is a
#: hung dispatch, a wedged profiler, or a unit bug (seconds stamped into
#: a ``_us`` field would read ~1e6x small, its inverse ~1e6x large);
#: negatives are clock-skew garbage, 0.0 the RTT-collapse artifact.
MAX_PLAUSIBLE_LATENCY_US = 3.6e9

#: MFU sanity ceiling (ISSUE 14: the measured-attribution stamps add
#: ``measured_mfu`` next to the model-derived ``mfu``/``mfu_compiled``).
#: A model-FLOP utilisation above 1.0 is not physics — it is a wrong
#: FLOP count, a wrong chip spec, or the us==0.0 RTT-collapse artifact
#: wearing its throughput face (flops / ~0 s); 0 and negatives are the
#: same artifact's other side.
MAX_PLAUSIBLE_MFU = 1.0

#: host-DRAM KV-tier budget ceiling (ISSUE 18: paged infer captures
#: stamp the effective ``APEX_TPU_HOST_KV_TIER_BYTES``).  The tier
#: lives in HOST RAM, not HBM, so the chip-selected HBM bound does not
#: apply — but a budget beyond ~2 TiB exceeds any TPU host's DRAM (a
#: v5e host tops out at 512 GiB) and reads as a units bug (pages or
#: GiB stamped into a bytes field).  0 is VALID here: it means the
#: tier is off, and captures must record that honestly.
MAX_PLAUSIBLE_HOST_TIER_BYTES = 1 << 41


def is_us_key(key: str) -> bool:
    return key == "us" or key.endswith("_us") or key.startswith("us_")


def is_tokens_per_s_key(key: str) -> bool:
    return key == "tokens_per_s" or key.endswith("_tokens_per_s")


def is_mfu_key(key: str) -> bool:
    return key == "mfu" or key.endswith("_mfu") or key.startswith("mfu_")


def is_acceptance_rate_key(key: str) -> bool:
    return key == "acceptance_rate" or key.endswith("_acceptance_rate")


def hbm_capacity_bound(obj: dict) -> int:
    """Physical ceiling for a ``compiled_peak_hbm_bytes`` field: the
    capture's own chip's HBM when the ``chip`` stamp matches the spec
    table, else the LARGEST capacity in the table (the permissive bound
    — an unknown chip must not scrub a valid value).

    A tensor-parallel serving capture (``infer_serve_tp`` > 1, ISSUE
    17) spans that many chips: its compiled peak may legitimately sum
    over the mesh, so the bound is PER-CHIP HBM x the capture's own tp
    stamp — a single-chip ceiling would scrub a valid multi-chip
    value, and an unsharded capture (tp absent or 1) keeps the strict
    one-chip bound."""
    from apex_tpu.chip_specs import CHIP_SPECS, match_spec
    spec = match_spec(str(obj.get("chip", "")))
    per_chip = (spec.hbm_bytes if spec is not None
                else max(s.hbm_bytes for s in CHIP_SPECS.values()))
    tp = obj.get("infer_serve_tp", 1)
    if isinstance(tp, bool) or not isinstance(tp, int) or tp < 1:
        tp = 1
    return per_chip * tp


def vmem_capacity_bound(obj: dict) -> int:
    """Physical ceiling for ``*vmem_model_bytes`` fields (ISSUE 16:
    the pallas_audit envelope stamp): the capture's own chip's VMEM
    when the ``chip`` stamp matches, else the largest in the table —
    the same miss policy as :func:`hbm_capacity_bound`."""
    from apex_tpu.chip_specs import CHIP_SPECS, match_spec
    spec = match_spec(str(obj.get("chip", "")))
    if spec is not None:
        return spec.vmem_bytes
    return max(s.vmem_bytes for s in CHIP_SPECS.values())


def is_vmem_model_key(key: str) -> bool:
    return (key == "vmem_model_bytes"
            or key.endswith("_vmem_model_bytes"))


def is_host_tier_bytes_key(key: str) -> bool:
    return (key == "host_tier_bytes"
            or key.endswith("_host_tier_bytes"))


def scrub_capture_values(obj):
    """Drop physically impossible values from a capture payload
    (recursively): NaN/Inf in ANY numeric field (NaN passes every
    range comparison below as False, so without this gate a poisoned
    measurement sails through checks written as rejections — ISSUE 11
    satellite), ``*_us``/``us_*`` latency fields that are
    non-positive (0.0 = the RTT-collapse artifact, negatives =
    clock-skew garbage) or beyond :data:`MAX_PLAUSIBLE_LATENCY_US`
    (covers the telemetry TTFT / decode-latency fields),
    ``*_speedup`` fields above :data:`MAX_PLAUSIBLE_SPEEDUP`,
    ``*tokens_per_s`` throughputs that are non-positive or beyond
    :data:`MAX_PLAUSIBLE_TOKENS_PER_S`, ``mfu``/``*_mfu``/``mfu_*``
    utilisations outside ``(0, 1]`` (ISSUE 14: covers the measured
    ``measured_mfu`` stamp — the ``*_us`` rule already bounds the
    measured attributed times at (0, 1 h]), and the ISSUE-10
    compiled-truth stamps — ``compiled_flops`` must be positive and
    ``compiled_peak_hbm_bytes`` must be positive and fit the chip's
    HBM (the ``chip`` field in the same dict selects the bound).
    ISSUE 15 speculation stats: ``*acceptance_rate`` outside
    ``(0, 1]`` is not physics (accepted drafts are a subset of
    drafted), and a ``*spec_effective_tokens_per_s`` BELOW its
    same-capture ``*spec_floor_tokens_per_s`` sibling (the 1-token-
    per-verify-step floor measured on the same clock) is a
    measurement artifact — every verify step emits at least the
    bonus token, so effective >= floor by construction.  ISSUE 16
    VMEM-model stamps: a ``*vmem_model_bytes`` field must be positive
    and fit the chip's VMEM capacity (same chip-selected bound policy
    as the HBM rule).  ISSUE 18 host-tier stamps: a
    ``*host_tier_bytes`` field is a HOST-RAM budget — 0 (tier off) is
    valid, but negatives and values beyond
    :data:`MAX_PLAUSIBLE_HOST_TIER_BYTES` (~2 TiB, above any TPU
    host's DRAM) are units bugs; the HBM rule deliberately does not
    see these keys (exact-key match), so a legitimate multi-hundred-GiB
    host budget never trips the chip's HBM ceiling.

    Returns a scrubbed copy; containers are preserved, only the
    corrupt scalar fields vanish."""
    if isinstance(obj, dict):
        out = {}
        hbm_bound = None
        for k, v in obj.items():
            if isinstance(v, (dict, list)):
                out[k] = scrub_capture_values(v)
                continue
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                if not math.isfinite(v):
                    continue
                if is_us_key(k) and \
                        not 0.0 < v <= MAX_PLAUSIBLE_LATENCY_US:
                    continue
                if (k == "speedup" or k.endswith("_speedup")) \
                        and v > MAX_PLAUSIBLE_SPEEDUP:
                    continue
                if is_tokens_per_s_key(k) \
                        and not 0.0 < v <= MAX_PLAUSIBLE_TOKENS_PER_S:
                    continue
                if is_mfu_key(k) and not 0.0 < v <= MAX_PLAUSIBLE_MFU:
                    continue
                if is_acceptance_rate_key(k) and not 0.0 < v <= 1.0:
                    continue
                if k.endswith("spec_effective_tokens_per_s"):
                    floor = obj.get(k.replace("effective", "floor"))
                    if isinstance(floor, (int, float)) \
                            and not isinstance(floor, bool) \
                            and math.isfinite(floor) and v < floor:
                        continue
                if k == "compiled_flops" and v <= 0:
                    continue
                if k == "compiled_peak_hbm_bytes":
                    if hbm_bound is None:
                        hbm_bound = hbm_capacity_bound(obj)
                    if not 0 < v <= hbm_bound:
                        continue
                if is_vmem_model_key(k) and \
                        not 0 < v <= vmem_capacity_bound(obj):
                    # a modeled VMEM envelope <= 0 or beyond the chip's
                    # VMEM is a wrong geometry / wrong chip stamp
                    continue
                if is_host_tier_bytes_key(k) and \
                        not 0 <= v <= MAX_PLAUSIBLE_HOST_TIER_BYTES:
                    # host-RAM budget, NOT an HBM quantity: 0 = tier
                    # off (valid); negative or beyond any TPU host's
                    # DRAM is a units bug
                    continue
            out[k] = v
        return out
    if isinstance(obj, list):
        return [scrub_capture_values(v) for v in obj]
    return obj
