"""On-chip fleet front-door experiment queue for the next
on-chip session (r19, ISSUE 19): fleet-leg runs that land the
prefix_affinity vs round_robin A/B (``fleet_affinity_hit_rate`` /
``fleet_affinity_ttft_us`` against the ``fleet_round_robin_*``
control, equal aggregate HBM by construction) next to the capacity
simulator's calibration block (``fleet_capacity_pred_ttft_us`` /
``fleet_capacity_measured_ttft_us`` / ``fleet_capacity_drift_ratio``)
and the effective knob stamps (``fleet_replicas`` / ``fleet_policy``).

Same discipline as ``r18_host_tier_experiments.py``: every experiment
drives a REAL ``bench.py`` leg in its own subprocess, results are
rewritten after EVERY experiment, and re-runs resume.

What these answer:

1. Affinity vs striping at real prefill cost: the CPU dryrun already
   shows affinity winning both axes in interpret mode; on chips the
   gap is real prefill FLOPs saved vs pages re-materialized — the
   acceptance criterion's arithmetic, measured.
2. Scale in replicas: 2 -> 4 replicas with the SAME per-replica pool
   stresses the coprime prefix rotation harder (5 prefixes over 4
   replicas) — affinity's win should widen as round_robin duplicates
   each prefix across more pools.
3. Policy knob provenance: the SAME leg with the policy armed via
   APEX_TPU_FLEET_POLICY (stamped as ``fleet_policy``) and the
   replica count via APEX_TPU_FLEET_REPLICAS (stamped as
   ``fleet_replicas``) — env vs override precedence on chip.
4. Capacity drift at real service times: the queued-calibration
   drift ratio re-measured where prefill/decode latencies are real —
   the watch trends ``fleet_capacity_drift_ratio`` downward from
   whatever this window achieves (tolerance envelope 2.0).
5. Longer prefixes: seq=2048 multiplies pages per prefix, so
   affinity's page-reuse advantage and round_robin's duplication cost
   both scale up — the contrast at serving-realistic prefix sizes.

Usage:  python bench_captures/r19_fleet_experiments.py [--quick]
Writes: bench_captures/r19_fleet_experiments_out.json
"""
from __future__ import annotations

import json
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent
OUT = REPO / "bench_captures" / "r19_fleet_experiments_out.json"

# (key, bench.py args, timeout_s); --quick runs only the first row.
EXPERIMENTS = [
    # the tentpole A/B at the flagship shape: 2 replicas, default knobs
    ("fleet_default", ["--leg", "fleet"], 1800),
    # replica scale: 4 replicas x the same pool, 5 rotating prefixes
    ("fleet_replicas4", ["--leg", "fleet", "--override", "replicas=4"],
     2400),
    # env-knob provenance: the SAME leg armed via the env registry's
    # knobs (precedence: override > env > defaults)
    ("fleet_env_knobs", ["--leg", "fleet",
                         "env:APEX_TPU_FLEET_REPLICAS=2",
                         "env:APEX_TPU_FLEET_POLICY=prefix_affinity"],
     1800),
    # longer prefixes: more pages per prefix, bigger reuse stakes
    ("fleet_seq2048", ["--leg", "fleet", "--override", "seq=2048",
                       "--override", "prefix_len=1024"], 2400),
]


def last_json_line(text: str):
    for cand in reversed(text.strip().splitlines()):
        cand = cand.strip()
        if cand.startswith("{") and cand.endswith("}"):
            try:
                return json.loads(cand)
            except json.JSONDecodeError:
                continue
    return None


def run_experiment(key, args, timeout):
    import os
    env, cleaned = None, []
    for a in args:
        if a.startswith("env:"):
            env = dict(env or os.environ)
            name, _, val = a[4:].partition("=")
            env[name] = val
        else:
            cleaned.append(a)
    try:
        r = subprocess.run(
            [sys.executable, str(REPO / "bench.py"), "--inner", "tpu",
             *cleaned],
            capture_output=True, text=True, timeout=timeout,
            cwd=str(REPO), env=env)
    except subprocess.TimeoutExpired as e:
        payload = last_json_line((e.stdout or b"").decode()
                                 if isinstance(e.stdout, bytes)
                                 else (e.stdout or ""))
        return dict(payload, _timeout=True) if payload else {
            "_error": f"timeout after {timeout}s"}
    payload = last_json_line(r.stdout)
    if payload is None:
        return {"_error": f"rc={r.returncode}; no JSON; "
                          f"stderr tail: {r.stderr[-300:]}"}
    return payload


def main() -> None:
    quick = "--quick" in sys.argv
    results = {}
    if OUT.exists():              # resume: keep earlier window's answers
        try:
            results = json.loads(OUT.read_text())
        except json.JSONDecodeError:
            results = {}
    todo = EXPERIMENTS[:1] if quick else EXPERIMENTS
    for key, args, timeout in todo:
        prev = results.get(key)
        if prev and not ({"_error", "_timeout"} & set(prev)):
            print(f"{key}: already captured, skipping", flush=True)
            continue
        print(f"{key}: running bench.py {' '.join(args)}", flush=True)
        res = run_experiment(key, args, timeout)
        if prev and ({"_error", "_timeout"} & set(res)) and len(res) <= \
                len(prev):
            print(f"{key}: retry no better, keeping previous", flush=True)
            continue
        results[key] = res
        OUT.write_text(json.dumps(results, indent=1) + "\n")
        print(f"{key}: {'ERROR ' + res['_error'] if '_error' in res else 'ok'}",
              flush=True)
    print(f"results: {OUT}")


if __name__ == "__main__":
    main()
