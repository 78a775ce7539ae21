"""On-chip fused-block decode + speculative decoding experiment queue
for the next on-chip session (r15, ISSUE 15): paged infer-leg
A/Bs that land the fused-vs-unfused per-token decode latency and the
speculation rates (base / prompt-lookup / replay-ceiling, acceptance
rate, effective-vs-floor tokens/s) in the same capture as the knob
provenance stamps (``infer_decode_fusion`` / ``infer_fusion_min_pages``
/ ``infer_spec_k``).

Same discipline as ``r9_xent_fused_experiments.py``: every experiment
drives a REAL ``bench.py`` leg in its own subprocess, results are
rewritten after EVERY experiment, and re-runs resume.

What these answer:

1. Fused-block crossover: the CPU dryrun can only show the capture
   shape (interpret-mode Pallas is meaningless for wall time); on
   chip, the fused kernel's win should GROW with the virtual window
   (pages streamed once through one kernel with weights resident vs
   per-op dispatches re-reading weights per sublayer).  The seq sweep
   brackets where ``APEX_TPU_FUSION_MIN_PAGES`` should sit — today's
   8 is PROVISIONAL.
2. Speculation k sweep: effective tokens/s vs k at the flagship shape
   — more drafts amortize more dispatch but the verify slab's compute
   grows and acceptance decays with depth; the replay-ceiling stamp
   separates machinery overhead from draft quality.
3. The acceptance criterion: greedy speculation >= 1.5x effective
   tokens/s on the repeated-structure workload (the
   ``infer_spec_oracle_tokens_per_s`` vs ``infer_spec_base_tokens_
   per_s`` pair, with ``infer_spec_effective_tokens_per_s`` as the
   realistic prompt-lookup number).

Usage:  python bench_captures/r15_fused_spec_experiments.py [--quick]
Writes: bench_captures/r15_fused_spec_experiments_out.json
"""
from __future__ import annotations

import json
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent
OUT = REPO / "bench_captures" / "r15_fused_spec_experiments_out.json"

# (key, bench.py args, timeout_s); --quick runs only the first row.
EXPERIMENTS = [
    # the flagship paged shape: fused A/B + speculation legs ride the
    # standard infer leg (seq 1024 => 16 pages/slot, auto would fuse)
    ("infer_paged_base", ["--leg", "infer", "--override", "paged=1"],
     1200),
    # window sweep for the fusion crossover (pages/slot = seq/64)
    ("infer_seq512", ["--leg", "infer", "--override", "paged=1",
                      "--override", "seq=512"], 1200),
    ("infer_seq2048", ["--leg", "infer", "--override", "paged=1",
                       "--override", "seq=2048"], 1500),
    # speculation depth sweep at the flagship shape
    ("infer_spec_k2", ["--leg", "infer", "--override", "paged=1",
                       "--override", "spec_k=2"], 1200),
    ("infer_spec_k8", ["--leg", "infer", "--override", "paged=1",
                       "--override", "spec_k=8"], 1200),
    # fused decode UNDER the serve path too: the whole leg with the
    # engine-level knob armed (env: marker = environment variable for
    # the subprocess, not a bench override), so the serve TTFT/decode
    # stamps and the speculation wave all ride the fused executable
    ("infer_fusion_on", ["--leg", "infer", "--override", "paged=1",
                         "env:APEX_TPU_DECODE_FUSION=1"], 1200),
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
