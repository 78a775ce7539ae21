"""On-chip experiment runner for the next on-chip session (r5).

Every experiment drives a REAL ``bench.py`` leg in its own subprocess
(``--inner tpu --leg X --override k=v``), so the measured code is the
measured code — no templated model-setup duplicates that can drift from
the bench legs (r4 verdict weak #7; this file replaces
``r4_experiments.py``'s 5.8 kB of inline source snippets).

Open questions it answers, in priority order (a wedge mid-batch keeps
everything already written; the EXPERIMENTS table below is the
authoritative order):

1. ``--quick``: the BERT north-star leg alone (BASELINE north_star,
   >=50% MFU target) — first, so a brief window can't miss it.
2. The cheap bert-leg design A/Bs that set library defaults:
   split-state (tree fwd/bwd + flat master), embedding grad via
   matmul, batch 48.
3. GPT flagship main leg at batch 8/16/24, split-state, emb-matmul —
   bigger GEMM M dims vs the committed batch-8 number.
4. BERT batch 16 and batch 64 + remat.
5. Flash attention block 512 vs 1024 (the r3 block choice re-validated
   under base-2 softmax).
6. The MoE leg (its E-sweep + onehot/gather crossover is built in).

Usage:  python bench_captures/r5_experiments.py [--quick]
Writes: bench_captures/r5_experiments_out.json (one JSON object per
key), rewritten after EVERY experiment so a later wedge loses nothing.
"""
from __future__ import annotations

import json
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent
OUT = REPO / "bench_captures" / "r5_experiments_out.json"

# (key, bench.py args, timeout_s); --quick runs only the first row.
# Ordered by information-per-chip-second: the cheap bert-leg A/Bs that
# decide library defaults come before the 2400 s GPT sweeps, so a short
# session still answers the design questions.
EXPERIMENTS = [
    ("bert", ["--leg", "bert"], 1200),
    # two-buffer state (tree fwd/bwd + flat master) vs differentiating
    # through unravel — the leading candidate for the ~40 ms in-model
    # overhead (PERF.md round-5 §3)
    ("bert_split_state", ["--leg", "bert", "--override",
                          "split_state=1"], 900),
    # embedding-table grad: one-hot MXU matmul vs XLA scatter-add
    ("bert_emb_matmul_grad", ["--leg", "bert", "--override",
                              "emb_matmul_grad=1"], 900),
    # batch 48 projected ~13 GB — the largest no-remat fit
    ("bert_batch48", ["--leg", "bert", "--override", "batch=48"], 1200),
    ("gpt_batch8", ["--leg", "main"], 2400),
    ("gpt_split_state", ["--leg", "main", "--override",
                         "split_state=1"], 2400),
    ("gpt_batch16", ["--leg", "main", "--override", "batch=16"], 2400),
    ("gpt_batch24", ["--leg", "main", "--override", "batch=24"], 2400),
    ("gpt_emb_matmul_grad", ["--leg", "main", "--override",
                             "emb_matmul_grad=1"], 2400),
    ("bert_batch16", ["--leg", "bert", "--override", "batch=16"], 900),
    # batch 64 without remat OOMs (measured r5: 16.44 G vs 15.75 G HBM);
    # two ways to fit: bf16 CE residuals (~1 GB back, no recompute) or
    # remat (costs ~+fwd FLOPs — only wins if the bigger GEMMs beat the
    # recompute)
    ("bert_batch64_ce_half", ["--leg", "bert", "--override", "batch=64",
                              "--override", "ce_half=1"], 1200),
    ("bert_batch64_remat", ["--leg", "bert", "--override", "batch=64",
                            "--override", "remat=1"], 1200),
    # the beyond-parity llama decoder's measured MFU
    ("llama", ["--leg", "llama"], 1500),
    ("attn_block1024", ["--leg", "attn"], 900),
    ("attn_block512", ["--leg", "attn", "--override", "block_q=512",
                       "--override", "block_k=512"], 900),
    ("moe", ["--leg", "moe"], 1800),
]


def last_json_line(text: str):
    """Newest parseable JSON object line; skips unparseable lines (a
    timeout kill can truncate the final line mid-write — an earlier
    complete line, e.g. the moe leg's pre-sweep flush, still counts)."""
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
        # salvage any JSON the leg printed before wedging (the moe leg
        # flushes its base result before the sweep for exactly this)
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
        # partial salvage (_timeout) retries too: the whole point of
        # e.g. the moe experiment is the sweep a wedge cut short
        if prev and not ({"_error", "_timeout"} & set(prev)):
            print(f"{key}: already captured, skipping", flush=True)
            continue
        print(f"{key}: running bench.py {' '.join(args)}", flush=True)
        res = run_experiment(key, args, timeout)
        # never let a worse retry overwrite salvaged data
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
