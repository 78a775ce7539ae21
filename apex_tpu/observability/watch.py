"""Bench-capture perf-regression watch: the ratchet, applied to
measured performance.

``python -m apex_tpu.observability.watch bench_captures/`` loads the
committed capture history (``r<round>_*.json``), scrubs each payload
through the shared capture-hygiene rules
(:mod:`apex_tpu.observability.capture_hygiene`), and trends every
MEASUREMENT field of each group's newest capture against the **best
prior** capture of the *same backend, shape and knobs* — exiting
nonzero when a metric regressed beyond the slack factor.  The
budget-ledger pattern (``compare_budget``'s x1.05 drift ratchet, the
analysis baseline's new-findings-only gate) pointed at the bench
trajectory: an accidental slowdown must fail loudly instead of
becoming the new normal silently.

Mechanics:

* **measurement vs context** — a field is a measurement only if its
  name matches a known direction: lower-is-better (``*_us`` /
  ``us_*`` latencies, ``*sec_per_step``, and ``*_drift_ratio`` —
  the ISSUE 14 measured-vs-model exposed-comm drift, where a
  widening gap means the overlap model is losing touch with the
  hardware and must fail the watch like any latency regression; the
  ISSUE 19 ``fleet_capacity_drift_ratio`` — the capacity simulator's
  predicted-vs-measured TTFT agreement — rides the same suffix, so a
  simulator losing calibration fails the watch too.
  Lower-is-better is sound for this measured/model ratio because
  the model term is a pure function of the series' shape/knob
  context — constant WITHIN a comparability group — so the ratio
  trends measured exposure alone) or
  higher-is-better (``*tokens_per_s``/``*tokens_per_sec*``,
  ``*_gbps``, ``mfu*``/``*_mfu``, ``*_roofline``, ``*_speedup``,
  ``*_tflops``).  The measured-attribution stamps
  (``measured_window_us``/``measured_step_us``/
  ``measured_exposed_comm_us``/``measured_mfu``) trend through the
  same rules — the model-vs-measured drift table IS these rows.  Every
  other scalar (shapes, knob stamps like ``xent_chunk`` /
  ``infer_page_size``, element counts) is CONTEXT: two captures are
  comparable for metric ``m`` only when the context fields sharing
  ``m``'s leg prefix — plus the ``chip`` stamp — agree, so a shape or
  knob change starts a fresh series instead of reading as a
  regression.
* **best prior** — single captures swing run to run
  (PERF.md: ±3-15% in the 2026-07 captures), so the baseline is the BEST value among strictly
  earlier rounds, not the previous capture; ``--slack`` (default
  1.15) absorbs the residual noise.
* **ordering hygiene** (ISSUE 13 satellite): the per-capture scrubber
  cannot see ACROSS captures, so the watch enforces the one
  cross-capture invariant itself — ``captured_at`` stamps must be
  non-decreasing with the round index.  A capture stamped EARLIER
  than a lower round's stamp carries a lying clock (or a mislabeled
  round) and is rejected from trending, loudly.
"""
from __future__ import annotations

import argparse
import json
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from apex_tpu.observability.capture_hygiene import (is_tokens_per_s_key,
                                                    is_us_key,
                                                    scrub_capture_values)

__all__ = ["Capture", "load_captures", "validate_ordering",
           "metric_direction", "context_for", "analyze",
           "render_text", "main"]

_ROUND_RE = re.compile(r"^r(\d+)_.*\.json$")

#: non-metric bookkeeping fields never used as comparability context
_META_KEYS = frozenset({"captured_at", "backend", "chip", "_leg",
                        "_note", "error", "metric", "unit", "value",
                        "value_provenance", "vs_baseline",
                        "vs_baseline_tpu_best_recorded",
                        "value_tpu_best"})

DEFAULT_SLACK = 1.15


def metric_direction(key: str) -> Optional[str]:
    """``"lower"`` / ``"higher"`` for measurement fields, ``None`` for
    context (shapes, knob stamps, counts)."""
    base = key[:-len("_median")] if key.endswith("_median") else key
    if is_us_key(base) or base.endswith("sec_per_step") \
            or base.endswith("_drift_ratio") or base.endswith("_skew"):
        return "lower"
    if (is_tokens_per_s_key(base) or "tokens_per_s" in base
            or base.endswith("_gbps") or base == "mfu"
            or base.endswith("_mfu") or base.startswith("mfu_")
            or base.endswith("_roofline") or base.endswith("_speedup")
            or base.endswith("_tflops")
            # ISSUE 15: drafting quality is a measurement within a
            # comparability group (same leg shape + spec_k) — an
            # acceptance-rate drop is a drafter regression
            or base.endswith("_acceptance_rate")):
        return "higher"
    return None


@dataclass
class Capture:
    name: str                    # file name
    round: int                   # r<N>_ prefix
    backend: str
    stamp: str                   # captured_at ISO string ("" = none)
    fields: Dict[str, object] = field(default_factory=dict)
    rejected: Optional[str] = None   # ordering-rejection reason


def _flatten(payload: dict) -> Dict[str, object]:
    """Normalize the two committed capture shapes into one flat field
    dict: full orchestrator captures (``{"metric", "value",
    "extras": {...}}`` — the headline value lands under its metric
    name) and flat microbench leg captures (``{"_leg": ..., ...}``)."""
    extras = payload.get("extras")
    if isinstance(extras, dict):
        fields = dict(extras)
        metric = payload.get("metric")
        value = payload.get("value")
        if isinstance(metric, str) and isinstance(value, (int, float)):
            fields.setdefault(metric, value)
        return fields
    return dict(payload)


def load_captures(capdir: str) -> List[Capture]:
    """Eligible ``r<N>_*.json`` files, scrubbed and flattened.
    Non-JSON / non-object files are skipped (the captures dir also
    holds ``*.py`` experiment queues and README)."""
    out: List[Capture] = []
    for name in sorted(os.listdir(capdir)):
        m = _ROUND_RE.match(name)
        if m is None:
            continue
        path = os.path.join(capdir, name)
        try:
            with open(path, encoding="utf-8") as fh:
                payload = json.load(fh)
        except (OSError, json.JSONDecodeError):
            continue
        if not isinstance(payload, dict):
            continue
        fields = scrub_capture_values(_flatten(payload))
        # leg captures predate the backend stamp and were all on-chip
        backend = str(fields.get("backend", "tpu"))
        out.append(Capture(name=name, round=int(m.group(1)),
                           backend=backend,
                           stamp=str(fields.get("captured_at", "")),
                           fields=fields))
    return out


def validate_ordering(caps: List[Capture]) -> Tuple[List[Capture],
                                                    List[Capture]]:
    """Cross-capture wall-clock hygiene: walking rounds in ascending
    order, every stamped capture must not precede the latest stamp of
    any LOWER round (ISO-8601 stamps in one timezone format compare
    lexicographically — ours are always UTC ``isoformat``).  Returns
    ``(accepted, rejected)``; unstamped captures (the legacy r3 legs)
    are exempt — there is nothing to lie about."""
    accepted: List[Capture] = []
    rejected: List[Capture] = []
    prior_max = ""               # latest accepted stamp of lower rounds
    prior_max_src = ""
    by_round: Dict[int, List[Capture]] = {}
    for cap in caps:
        by_round.setdefault(cap.round, []).append(cap)
    for rnd in sorted(by_round):
        round_max, round_src = "", ""
        for cap in by_round[rnd]:
            if cap.stamp and prior_max and cap.stamp < prior_max:
                cap.rejected = (
                    f"captured_at {cap.stamp} precedes {prior_max} "
                    f"({prior_max_src}, a lower round) — stamped "
                    f"wall-clock order contradicts the round index")
                rejected.append(cap)
                continue
            accepted.append(cap)
            if cap.stamp and cap.stamp > round_max:
                round_max, round_src = cap.stamp, cap.name
        if round_max > prior_max:
            prior_max, prior_max_src = round_max, round_src
    return accepted, rejected


def context_for(fields: Dict[str, object], key: str) -> tuple:
    """The comparability signature for metric ``key``: every context
    field whose leg token appears in the metric's name (scalars, plus
    ``*_shape`` int lists), and the ``chip`` stamp.  Captures compare
    only within one signature — same shape, same knobs, same silicon.

    The match is token-wise, not first-prefix: ``fused_adam_us`` and
    ``unfused_adam_us`` carry the modifier up front but belong to the
    ``adam`` leg, so ``adam_nelem`` keys their context; a nelem/shape
    change forks the series instead of reading as a regression."""
    tokens = set(key.split("_"))
    ctx = {}
    for k, v in fields.items():
        if k == key or k in _META_KEYS or metric_direction(k) is not None:
            continue
        if k.split("_", 1)[0] not in tokens:
            continue
        if isinstance(v, (str, int, float, bool)):
            ctx[k] = v
        elif isinstance(v, list) and k.endswith("_shape"):
            ctx[k] = tuple(v)
    ctx["chip"] = fields.get("chip")
    return tuple(sorted((k, repr(v)) for k, v in ctx.items()))


def analyze(capdir: str, slack: float = DEFAULT_SLACK) -> dict:
    """The full pass: load -> ordering hygiene -> per-group trend.
    Returns ``{"rows": [...], "regressions": [...],
    "rejected": [...]}`` — one row per (backend, metric, context)
    series, its newest value vs the best strictly-prior round."""
    if slack < 1.0:
        raise ValueError(f"slack must be >= 1.0, got {slack}")
    caps, rejected = validate_ordering(load_captures(capdir))
    groups: Dict[tuple, List[Tuple[Capture, float]]] = {}
    for cap in caps:
        for k, v in cap.fields.items():
            if metric_direction(k) is None:
                continue
            if not isinstance(v, (int, float)) or isinstance(v, bool):
                continue
            gkey = (cap.backend, k, context_for(cap.fields, k))
            groups.setdefault(gkey, []).append((cap, float(v)))
    rows: List[dict] = []
    for (backend, metric, _ctx), entries in sorted(
            groups.items(), key=lambda kv: (kv[0][1], kv[0][0])):
        entries.sort(key=lambda cv: (cv[0].round, cv[0].stamp,
                                     cv[0].name))
        newest_cap, newest_val = entries[-1]
        prior = [(c, v) for c, v in entries
                 if c.round < newest_cap.round]
        direction = metric_direction(metric)
        row = {"metric": metric, "backend": backend,
               "direction": direction, "newest": newest_val,
               "newest_capture": newest_cap.name,
               "samples": len(entries)}
        if not prior:
            row.update(status="no-prior", best_prior=None,
                       best_prior_capture=None, ratio=None)
        else:
            pick = max if direction == "higher" else min
            best_cap, best_val = pick(prior, key=lambda cv: cv[1])
            ratio = (newest_val / best_val) if best_val else None
            if ratio is None:
                regressed = False
            elif direction == "lower":
                regressed = newest_val > best_val * slack
            else:
                regressed = newest_val < best_val / slack
            row.update(status="regressed" if regressed else "ok",
                       best_prior=best_val,
                       best_prior_capture=best_cap.name,
                       ratio=round(ratio, 4) if ratio is not None
                       else None)
        rows.append(row)
    return {
        "captures": len(caps),
        "slack": slack,
        "rows": rows,
        "regressions": [r for r in rows if r["status"] == "regressed"],
        "rejected": [{"capture": c.name, "reason": c.rejected}
                     for c in rejected],
    }


def render_text(result: dict) -> str:
    lines = [f"bench-capture watch: {result['captures']} capture(s), "
             f"slack x{result['slack']}"]
    for rej in result["rejected"]:
        lines.append(f"REJECTED {rej['capture']}: {rej['reason']}")
    for row in result["rows"]:
        if row["status"] == "no-prior":
            lines.append(
                f"  new      {row['metric']} [{row['backend']}] = "
                f"{row['newest']} ({row['newest_capture']}; no prior "
                f"round at this shape/knobs)")
            continue
        tag = "REGRESSED" if row["status"] == "regressed" else "  ok     "
        lines.append(
            f"{tag} {row['metric']} [{row['backend']}] = "
            f"{row['newest']} ({row['newest_capture']}) vs best prior "
            f"{row['best_prior']} ({row['best_prior_capture']}), "
            f"ratio {row['ratio']}")
    n = len(result["regressions"])
    lines.append(f"{n} regression(s) beyond slack"
                 if n else "no regressions beyond slack")
    return "\n".join(lines)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m apex_tpu.observability.watch",
        description="trend committed bench captures; exit nonzero on "
                    "perf regressions beyond the slack factor")
    p.add_argument("capdir", help="directory of r<N>_*.json captures "
                                  "(bench_captures/)")
    p.add_argument("--slack", type=float, default=DEFAULT_SLACK,
                   help=f"tolerated worst/best ratio before a trend "
                        f"delta counts as a regression (default "
                        f"{DEFAULT_SLACK}; run-to-run spread was ±3-15%%)")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="emit the analysis as JSON")
    args = p.parse_args(argv)
    if not os.path.isdir(args.capdir):
        p.error(f"capture dir not found: {args.capdir}")
    result = analyze(args.capdir, slack=args.slack)
    if args.as_json:
        print(json.dumps(result, indent=1, sort_keys=True))
    else:
        print(render_text(result))
    return 1 if result["regressions"] else 0


if __name__ == "__main__":  # pragma: no cover
    raise SystemExit(main())
