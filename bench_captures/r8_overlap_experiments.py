"""On-chip comm/compute-overlap experiment queue for the next
on-chip session (r8, ISSUE 7): overlap=0|1 A/Bs on the zero and TP legs,
so every capture carries the measured step time NEXT TO the comm
model's ``overlap_step_time_model_us`` / ``sequential_step_time_model_us``
stamps (and ``zero_prefetch`` / ``tp_overlap_chunks`` provenance) —
the modeled win and the measured win land in the same artifact.

Same discipline as ``r6_zero_experiments.py``: every experiment drives
a REAL ``bench.py`` leg in its own subprocess, results are rewritten
after EVERY experiment, and re-runs resume.

What these answer:

1. dp=1 single-chip controls: the overlapped zero step's PROGRAM-SHAPE
   cost (per-span gathers are no-ops at dp=1 but the decomposed
   program still compiles differently) — any delta here is
   restructuring overhead, not communication, and bounds what a
   multi-chip window can attribute to overlap.
2. The first multi-chip window flips ``zero_dp=N`` on rows 1–4 and
   reads the overlap win directly: (zero@dp=N, overlap=0) vs
   (zero@dp=N, overlap=1) at identical comm bytes (APX215-pinned).
3. TP leg fused-vs-ring on a 2-chip tensor axis (skipped cleanly on a
   single-chip session — the leg stubs itself).

Usage:  python bench_captures/r8_overlap_experiments.py [--quick]
Writes: bench_captures/r8_overlap_experiments_out.json
"""
from __future__ import annotations

import json
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent
OUT = REPO / "bench_captures" / "r8_overlap_experiments_out.json"

# (key, bench.py args, timeout_s); --quick runs only the first row.
EXPERIMENTS = [
    # zero overlap A/B on the flagship GPT leg (dp defaults to the
    # session's device count: 1 on a single-chip host = shape
    # control, N on a multi-chip host = the real A/B)
    ("gpt_zero_seq", ["--leg", "main", "--override", "zero=1",
                      "--override", "overlap=0"], 2400),
    ("gpt_zero_overlap", ["--leg", "main", "--override", "zero=1",
                          "--override", "overlap=1"], 2400),
    # BERT north-star shape, same A/B (LAMB path: the per-leaf trust
    # ratios exercise the span-aware leaf machinery on chip)
    ("bert_zero_seq", ["--leg", "bert", "--override", "zero=1",
                       "--override", "overlap=0"], 1200),
    ("bert_zero_overlap", ["--leg", "bert", "--override", "zero=1",
                           "--override", "overlap=1"], 1200),
    # prefetch-depth sweep at the GPT shape (spans = 4 / 16 vs the
    # default 8): where does the per-span dispatch overhead cross the
    # hiding win
    ("gpt_zero_overlap_p4", ["--leg", "main", "--override", "zero=1",
                             "--override", "overlap=1",
                             "--override", "prefetch=4"], 2400),
    ("gpt_zero_overlap_p16", ["--leg", "main", "--override", "zero=1",
                              "--override", "overlap=1",
                              "--override", "prefetch=16"], 2400),
    # TP ring A/B (needs >= 2 devices; single-chip sessions record the
    # skip stub, costing seconds)
    ("tp_fused", ["--leg", "tp"], 900),
    ("tp_ring_c4", ["--leg", "tp", "--override", "overlap=1"], 900),
    ("tp_ring_c8", ["--leg", "tp", "--override", "overlap=1",
                    "--override", "overlap_chunks=8"], 900),
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
