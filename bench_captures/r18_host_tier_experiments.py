"""On-chip tiered-KV serving experiment queue for the next
on-chip session (r18, ISSUE 18): paged infer-leg runs that land the
hot-but-evicted TTFT (swap-in uploads from the host tier) next to the
cold-prefill and warm-hit TTFTs in the same capture as the effective
tier knobs (``infer_host_tier_bytes`` / ``infer_swap_batch_pages``)
and the swap traffic counters (``infer_swap_in_pages`` /
``infer_swap_out_pages`` / ``infer_prefix_host_hits``).

Same discipline as ``r17_tp_serve_experiments.py``: every experiment
drives a REAL ``bench.py`` leg in its own subprocess, results are
rewritten after EVERY experiment, and re-runs resume.

What these answer:

1. Swap-in vs recompute: the CPU dryrun already shows
   ``infer_prefix_hot_evicted_ttft_us`` under the cold TTFT in
   interpret mode; on chips the gap is the real PCIe-upload-vs-prefill
   race — the acceptance criterion's arithmetic, measured.  The
   warm-hit TTFT bounds it from below (HBM-resident pages cost no
   upload at all).
2. Batch sizing: the swap copy programs are fixed-width (one
   executable per direction), so ``APEX_TPU_SWAP_BATCH_PAGES`` trades
   dispatch count against padding waste — the 4/8/16 sweep finds the
   knee at real host-link bandwidth.
3. Sharded swap invariance: under tp=2 each rank offloads its own
   1/tp kv-head shard and the host books stay replicated — the tier
   stamps must match the tp=1 run page-for-page while
   ``measured_tp_rank_step_skew`` (profiler armed, deferred tp trace
   ingest) reports the measured straggler ratio next to APX217's
   HLO-analysis estimate (ROADMAP item 1 leftover).
4. Longer prefixes: seq=2048 multiplies pages per prefix, so the
   swap batch pipelining (uploads overlapped with chunked prefill of
   the tail) has real work to hide — the chunked-prefill knob rides
   the same leg.

Usage:  python bench_captures/r18_host_tier_experiments.py [--quick]
Writes: bench_captures/r18_host_tier_experiments_out.json
"""
from __future__ import annotations

import json
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent
OUT = REPO / "bench_captures" / "r18_host_tier_experiments_out.json"
PROF = REPO / "bench_captures" / "r18_profiles"

# (key, bench.py args, timeout_s); --quick runs only the first row.
EXPERIMENTS = [
    # the tentpole at the flagship paged shape: hot-but-evicted TTFT
    # vs cold prefill vs warm hit, default 64 MiB budget / batch 8
    ("infer_tier_default", ["--leg", "infer", "--override", "paged=1"],
     1200),
    # env-knob provenance: the SAME leg with the budget armed via
    # APEX_TPU_HOST_KV_TIER_BYTES (precedence: override > env > 64MiB)
    ("infer_tier_env_knob", ["--leg", "infer", "--override", "paged=1",
                             "env:APEX_TPU_HOST_KV_TIER_BYTES=134217728"],
     1200),
    # swap-batch sweep: dispatch count vs padding waste at real
    # host-link bandwidth (8 is the shipped default)
    ("infer_tier_batch4", ["--leg", "infer", "--override", "paged=1",
                           "env:APEX_TPU_SWAP_BATCH_PAGES=4"], 1200),
    ("infer_tier_batch16", ["--leg", "infer", "--override", "paged=1",
                            "env:APEX_TPU_SWAP_BATCH_PAGES=16"], 1200),
    # sharded swap invariance + the measured straggler skew: tp=2 with
    # the profiler armed — the deferred tp trace ingest stamps
    # measured_tp_rank_step_skew / measured_tp_step_us next to
    # exposed_comm_model_us in the same capture
    ("infer_tier_tp2_skew", ["--leg", "infer", "--override", "paged=1",
                             "--override", "tp=2",
                             f"env:APEX_TPU_PROFILE_DIR={PROF}"], 1800),
    # longer prefixes: more pages per swap, real overlap to hide
    ("infer_tier_seq2048", ["--leg", "infer", "--override", "paged=1",
                            "--override", "seq=2048"], 1800),
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
