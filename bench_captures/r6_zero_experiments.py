"""On-chip ZeRO experiment queue for the next on-chip session
(r6, ISSUE 3): the batch-48/64 BERT ZeRO captures plus zero-overhead
A/Bs on the flagship legs.

Same discipline as ``r5_experiments.py``: every experiment drives a
REAL ``bench.py`` leg in its own subprocess (``--inner tpu --leg X
--override k=v``) so the measured code is the shipped code, results
are rewritten after EVERY experiment (a wedge mid-batch keeps
everything already captured), and re-runs resume.

What these answer:

1. ``zero=1`` at the committed batch-32 BERT shape — the pure program-
   shape overhead of the zero step on ONE chip (dp=1: psum_scatter /
   all_gather are no-ops, so any delta is the restructured program,
   not communication).  This is the control for every later multi-chip
   number.
2. batch 48 (the largest no-remat HBM fit) and batch 64
   (+remat / +bf16-CE-residuals) under zero — the memory lever the
   north-star MFU push is gated on.  NOTE on one chip dp=1 ZeRO frees
   no memory (the shard IS the buffer); these rows pin the throughput
   side so the first multi-chip window (``--override zero_dp=N``) can
   read off the memory win against a known-speed baseline.
3. The same A/B on the GPT main leg and the llama leg.

Usage:  python bench_captures/r6_zero_experiments.py [--quick]
Writes: bench_captures/r6_zero_experiments_out.json
"""
from __future__ import annotations

import json
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent
OUT = REPO / "bench_captures" / "r6_zero_experiments_out.json"

# (key, bench.py args, timeout_s); --quick runs only the first row.
EXPERIMENTS = [
    # dp=1 zero-overhead control at the committed north-star shape
    ("bert_zero_b32", ["--leg", "bert", "--override", "zero=1"], 1200),
    ("bert_zero_b48", ["--leg", "bert", "--override", "zero=1",
                       "--override", "batch=48"], 1200),
    ("bert_zero_b64_remat", ["--leg", "bert", "--override", "zero=1",
                             "--override", "batch=64",
                             "--override", "remat=1"], 1200),
    ("bert_zero_b64_ce_half", ["--leg", "bert", "--override", "zero=1",
                               "--override", "batch=64",
                               "--override", "ce_half=1"], 1200),
    # non-zero twins for any shape not already in r5_experiments_out
    ("bert_b48", ["--leg", "bert", "--override", "batch=48"], 1200),
    ("gpt_zero_b8", ["--leg", "main", "--override", "zero=1"], 2400),
    ("llama_zero", ["--leg", "llama", "--override", "zero=1"], 1500),
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
    try:
        r = subprocess.run(
            [sys.executable, str(REPO / "bench.py"), "--inner", "tpu",
             *args],
            capture_output=True, text=True, timeout=timeout, cwd=str(REPO))
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
        print(f"{key}: {json.dumps(results[key])[:200]}", flush=True)
    clean = all(
        results.get(k) and not ({"_error", "_timeout"} & set(results[k]))
        for k, _, _ in EXPERIMENTS)
    if not quick and clean:
        print("ALL_COMPLETE", flush=True)


if __name__ == "__main__":
    main()
