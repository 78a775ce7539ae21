"""On-chip tensor-parallel serving experiment queue for the next
multi-chip on-chip session (r17, ISSUE 17): paged infer-leg runs
through the engine's tp-sharded shard_map executables that land the
sharded-vs-single-chip per-token decode latency next to the comm-model
stamps (``exposed_comm_model_us`` / ``overlap_step_time_model_us``) and
the per-rank HBM accounting (``infer_hbm_cache_bytes_tp``) in the same
capture as the knob provenance (``infer_serve_tp``).

Same discipline as ``r15_fused_spec_experiments.py``: every experiment
drives a REAL ``bench.py`` leg in its own subprocess, results are
rewritten after EVERY experiment, and re-runs resume.

What these answer:

1. Decode scaling: the CPU dryrun can only show the capture shape and
   the comm-model estimate (host-device collectives are loopback — the
   measured step there is meaningless); on chips,
   ``infer_decode_token_us_tp`` vs ``infer_decode_token_us`` is the
   real ~1/tp compute-scaling check, with ``exposed_comm_model_us``
   separating the modeled exposed-psum tax from the compute win.
2. HBM headroom: ``infer_hbm_cache_bytes_tp`` (per RANK) at the
   flagship shape vs one chip's HBM — the capacity case for serving a
   model that cannot fit a single chip (the acceptance criterion's
   arithmetic, measured).
3. Fusion under sharding: the fused-block A/B rides the same leg
   (``APEX_TPU_DECODE_FUSION=1``) with the 1/tp weight shard resident
   — the ``fused_vmem_model_bytes`` stamp prices the sharded envelope,
   so the fusion cap's predicted move UP under tp is checked against
   the observed win at hidden sizes the unsharded kernel cannot fuse.

Usage:  python bench_captures/r17_tp_serve_experiments.py [--quick]
Writes: bench_captures/r17_tp_serve_experiments_out.json
"""
from __future__ import annotations

import json
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent
OUT = REPO / "bench_captures" / "r17_tp_serve_experiments_out.json"

# (key, bench.py args, timeout_s); --quick runs only the first row.
EXPERIMENTS = [
    # single-chip baseline at the flagship paged shape, for the A-leg
    ("infer_paged_tp1", ["--leg", "infer", "--override", "paged=1"],
     1200),
    # the tentpole: sharded decode at tp=2 and tp=4 (same shape — the
    # infer_decode_token_us_tp vs baseline ratio is the scaling curve)
    ("infer_paged_tp2", ["--leg", "infer", "--override", "paged=1",
                         "--override", "tp=2"], 1500),
    ("infer_paged_tp4", ["--leg", "infer", "--override", "paged=1",
                         "--override", "tp=4"], 1500),
    # longer sequences: more pages per request => the sharded pool's
    # per-rank capacity win grows while decode stays page-streamed
    ("infer_tp2_seq2048", ["--leg", "infer", "--override", "paged=1",
                           "--override", "tp=2",
                           "--override", "seq=2048"], 1800),
    # fused-block decode under sharding: the 1/tp-resident kernel at a
    # hidden size near the unsharded fusion cap (PERF.md round-16's
    # ~2048 crossover — sharded, the static model says it fuses)
    ("infer_tp2_fused", ["--leg", "infer", "--override", "paged=1",
                         "--override", "tp=2",
                         "env:APEX_TPU_DECODE_FUSION=1"], 1500),
    # knob-path provenance: the SAME tp=2 leg armed via the env knob
    # instead of the override (serve_tp precedence: override > env)
    ("infer_tp2_env_knob", ["--leg", "infer", "--override", "paged=1",
                            "env:APEX_TPU_SERVE_TP=2"], 1500),
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
