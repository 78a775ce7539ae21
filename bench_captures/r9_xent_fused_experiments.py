"""On-chip chunked-fused-LM-head+CE experiment queue for the next
on-chip session (r9, ISSUE 9): fused-vs-unfused A/Bs on the
``xent_fused`` leg plus the flagship GPT train leg with the fused head
on, so every capture carries the measured wall time NEXT TO the APX215
peak-live model stamps (``xent_fused_peak_live_bytes`` /
``xent_unfused_peak_live_bytes``) and the knob provenance
(``xent_chunk`` / ``xent_vocab_chunk``) — the modeled memory win and
the measured recompute cost land in the same artifact.

Same discipline as ``r8_overlap_experiments.py``: every experiment
drives a REAL ``bench.py`` leg in its own subprocess, results are
rewritten after EVERY experiment, and re-runs resume.

What these answer:

1. Chunk sweep at the flagship head shape (8192 x 1024 x 51200, where
   the unfused bf16 logits alone are 800 MiB fwd + the softmax
   residual bwd): where does the per-chunk dispatch/recompute overhead
   cross the HBM-traffic win — on TPU the fused path should WIN wall
   time too once the unfused logits spill (the CPU dryrun can only
   show the memory model, its fused leg pays the scan overhead at toy
   shapes).
2. Vocab-chunked inner scan (online logsumexp) at chunk=512: does the
   [C, Vc] transient shrink cost measurable time vs the [C, V] one.
3. The end-to-end flagship: the GPT main leg at a seq/batch that the
   unfused head cannot fit (the config whose logits exceed the HBM
   budget) with ``xent_chunk=512`` — the capture that demonstrates
   training a config the dense path cannot reach.

Usage:  python bench_captures/r9_xent_fused_experiments.py [--quick]
Writes: bench_captures/r9_xent_fused_experiments_out.json
"""
from __future__ import annotations

import json
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent
OUT = REPO / "bench_captures" / "r9_xent_fused_experiments_out.json"

# (key, bench.py args, timeout_s); --quick runs only the first row.
EXPERIMENTS = [
    # chunk sweep on the dedicated A/B leg (each row re-times the
    # unfused twin so the pair shares a session)
    ("xent_c256", ["--leg", "xent_fused", "--override",
                   "xent_chunk=256"], 900),
    ("xent_c512", ["--leg", "xent_fused", "--override",
                   "xent_chunk=512"], 900),
    ("xent_c1024", ["--leg", "xent_fused", "--override",
                    "xent_chunk=1024"], 900),
    # vocab-chunked inner scan at the sweep's winner-so-far (6400
    # divides the leg's 51200 vocab — a power of two would not)
    ("xent_c512_vc6400", ["--leg", "xent_fused", "--override",
                          "xent_chunk=512", "--override",
                          "xent_vocab_chunk=6400"], 900),
    # end-to-end flagship GPT train leg, fused head on (the unfused
    # run of the same leg is every committed r1-r8 capture)
    ("gpt_fused_head", ["--leg", "main", "--override",
                        "xent_chunk=512"], 2400),
    # the memory-headline config: batch x seq pushed to where the
    # UNFUSED [tokens, vocab] logits alone exceed single-chip HBM
    # (16 x 2048 x 51200 fp32 logits = 6.4 GiB) — trains only fused
    ("gpt_fused_head_big", ["--leg", "main", "--override",
                            "xent_chunk=512", "--override", "batch=16",
                            "--override", "seq=2048"], 2400),
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
