"""Text generation demo: the inference engine end to end.

Builds a small standalone GPT or LLaMA (optionally trained for a few
quick steps on cyclic synthetic data so greedy decoding has structure to
reproduce), then serves a batch of prompts through the full stack —
prefill into cache slots, continuous-batching decode, greedy or
temperature/top-k sampling — and prints the generated token streams plus
prefill/decode throughput.

Runs anywhere::

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/generate.py --model llama --kv-heads 2

With ``--train-steps N`` the demo first trains next-token prediction on
cyclic sequences (tok[i+1] = (tok[i] + 1) % vocab), so the generated
continuations visibly count upward — a one-glance correctness check.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))                # repo root on sys.path

from apex_tpu import observability as obs
from apex_tpu.inference import InferenceEngine, SamplingConfig, \
    SlotScheduler
from apex_tpu.optimizers import functional
from apex_tpu import train_step
from apex_tpu.transformer import parallel_state
from apex_tpu.transformer.testing import (
    GPTConfig,
    LlamaConfig,
    gpt_model_provider,
    llama_model_provider,
)


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="apex_tpu generation demo")
    p.add_argument("--model", choices=("gpt", "llama"), default="gpt")
    p.add_argument("--vocab", type=int, default=64)
    p.add_argument("--hidden", type=int, default=128)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--kv-heads", type=int, default=None,
                   help="llama only: < heads for GQA, 1 for MQA")
    p.add_argument("--max-seq", type=int, default=128)
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--page-size", type=int, default=None,
                   help="serve from a paged KV pool with this page "
                        "size (tokens, power of two) instead of the "
                        "dense slot cache")
    p.add_argument("--num-pages", type=int, default=None,
                   help="paged pool size (default: dense-equivalent "
                        "slots * max_seq / page_size)")
    p.add_argument("--straggler-demo", action="store_true",
                   help="serve a straggler-shaped workload through the "
                        "slot cache and a paged pool of the SAME KV "
                        "HBM and report how many requests each admits "
                        "concurrently")
    p.add_argument("--spec-k", type=int, default=None,
                   help="speculative decoding: drafted tokens per "
                        "decode round via the n-gram prompt-lookup "
                        "drafter (None reads APEX_TPU_SPEC_K; 0 off)")
    p.add_argument("--decode-fusion", default=None,
                   help="fused transformer-block decode: 0/1/auto "
                        "(paged engines; None reads "
                        "APEX_TPU_DECODE_FUSION)")
    p.add_argument("--prompts", type=int, default=6)
    p.add_argument("--max-new-tokens", type=int, default=16)
    p.add_argument("--temperature", type=float, default=0.0,
                   help="0 = greedy")
    p.add_argument("--top-k", type=int, default=0)
    p.add_argument("--train-steps", type=int, default=150,
                   help="0 = serve random weights")
    p.add_argument("--seed", type=int, default=0)
    return p.parse_args(argv)


def build_model(args):
    if args.model == "gpt":
        cfg = GPTConfig(
            vocab_size=args.vocab, hidden_size=args.hidden,
            num_layers=args.layers, num_attention_heads=args.heads,
            max_seq_length=args.max_seq, hidden_dropout=0.0,
            attention_dropout=0.0)
        return cfg, gpt_model_provider(cfg)
    cfg = LlamaConfig(
        vocab_size=args.vocab, hidden_size=args.hidden,
        num_layers=args.layers, num_attention_heads=args.heads,
        num_kv_heads=args.kv_heads, max_seq_length=args.max_seq)
    return cfg, llama_model_provider(cfg)


def quick_train(model, params, args):
    """A few flat-native fused-Adam steps on cyclic next-token data."""
    rng = np.random.RandomState(args.seed)
    seq = 32

    def loss_fn(p, batch):
        return model.apply(p, batch["tokens"], batch["labels"])

    tx = functional.fused_adam(lr=1e-2)
    state = train_step.init_train_state(tx, params)
    run = train_step.train_loop(loss_fn, tx)
    starts = rng.randint(0, args.vocab, size=(args.train_steps, 8, 1))
    tokens = (starts + np.arange(seq)[None, None, :]) % args.vocab
    batches = {"tokens": jnp.asarray(tokens, jnp.int32),
               "labels": jnp.asarray(np.roll(tokens, -1, axis=2),
                                     jnp.int32)}
    state, losses = run(state, batches)
    print(f"trained {args.train_steps} steps: loss "
          f"{float(losses[0]):.3f} -> {float(losses[-1]):.3f}")
    # the checkpoint boundary the engine consumes: bf16 export off the
    # fp32 flat master
    return state


def straggler_demo(args, cfg, params, sampling):
    """Admission capacity at EQUAL KV HBM, slot cache vs paged pool.

    The workload one 128K-context user inflicts on a serving fleet,
    shrunk to demo scale: the dense cache must provision every slot for
    ``max_seq``, so a fixed HBM budget buys only ``budget_slots``
    concurrent requests no matter how short they are.  The paged engine
    spends the SAME bytes on a page pool and admits by free pages — the
    short requests each pin only their own few pages, so many more run
    concurrently (``SlotScheduler.peak_active`` is the observable)."""
    from apex_tpu.inference import SlotScheduler

    budget_slots = 2                  # dense slots the HBM budget buys
    page_size = args.page_size or 16
    rng = np.random.RandomState(args.seed + 2)
    n_req = args.prompts
    short = max(4, args.max_seq // 8)   # mean_seq << max_seq
    prompts = [list(rng.randint(0, args.vocab, size=rng.randint(2, short)))
               for _ in range(n_req)]
    new_toks = 4

    def run(engine):
        sched = SlotScheduler(engine)
        for p in prompts:
            sched.submit(p, max_new_tokens=new_toks)
        sched.run()
        return sched.peak_active, engine.cache_hbm_bytes()

    dense = InferenceEngine(args.model, cfg, params, slots=budget_slots,
                            max_seq=args.max_seq, dtype=jnp.bfloat16,
                            sampling=sampling, seed=args.seed)
    # same HBM: the pool gets exactly the dense cache's pages
    num_pages = budget_slots * args.max_seq // page_size - 1  # -1: trash
    paged = InferenceEngine(args.model, cfg, params, slots=n_req,
                            max_seq=args.max_seq, page_size=page_size,
                            num_pages=num_pages, dtype=jnp.bfloat16,
                            sampling=sampling, seed=args.seed)
    d_peak, d_bytes = run(dense)
    p_peak, p_bytes = run(paged)
    print(f"straggler demo ({n_req} short requests <= {short} tokens, "
          f"max_seq {args.max_seq}):")
    print(f"  slot cache: {d_bytes} B KV HBM -> {d_peak} concurrent "
          f"(capped by {budget_slots} max_seq-deep slots)")
    print(f"  paged pool: {p_bytes} B KV HBM -> {p_peak} concurrent "
          f"(admitted by free {page_size}-token pages)")
    assert p_peak > d_peak, "paged admission should beat the slot cache"


def main(argv=None):
    args = parse_args(argv)
    parallel_state.destroy_model_parallel()
    parallel_state.initialize_model_parallel(1)
    cfg, model = build_model(args)
    params = model.init(jax.random.PRNGKey(args.seed),
                        jnp.zeros((1, 8), jnp.int32))

    sampling = SamplingConfig(temperature=args.temperature,
                              top_k=args.top_k)
    if args.straggler_demo:
        straggler_demo(args, cfg, params, sampling)
        return
    paged_kw = {}
    if args.page_size is not None or args.num_pages is not None:
        paged_kw = dict(page_size=args.page_size,
                        num_pages=args.num_pages)
    if args.spec_k is not None:
        paged_kw["spec_k"] = args.spec_k
    if args.decode_fusion is not None:
        paged_kw["decode_fusion"] = args.decode_fusion
    if args.train_steps:
        state = quick_train(model, params, args)
        engine = InferenceEngine.from_train_state(
            args.model, cfg, state, slots=args.slots,
            max_seq=args.max_seq, sampling=sampling, seed=args.seed,
            **paged_kw)
    else:
        engine = InferenceEngine(args.model, cfg, params,
                                 slots=args.slots, max_seq=args.max_seq,
                                 dtype=jnp.bfloat16, sampling=sampling,
                                 seed=args.seed, **paged_kw)

    rng = np.random.RandomState(args.seed + 1)
    prompts = []
    for _ in range(args.prompts):
        start = rng.randint(0, args.vocab)
        n = rng.randint(4, 12)
        prompts.append([(start + i) % args.vocab for i in range(n)])

    # serve through the scheduler explicitly (what engine.generate
    # wraps) so its telemetry is in hand; APEX_TPU_PROFILE_DIR=<dir>
    # drops a jax.profiler trace of the serve, APEX_TPU_TELEMETRY=<dir>
    # writes the JSONL event log + Prometheus file alongside
    sched = SlotScheduler(engine)
    t0 = time.perf_counter()
    with obs.profile_capture(tag="generate",
                             registry=sched.telemetry.registry):
        uids = [sched.submit(p, max_new_tokens=args.max_new_tokens)
                for p in prompts]
        out = sched.run()
    dt = time.perf_counter() - t0
    outs = [out[u] for u in uids]
    n_new = sum(len(o) for o in outs)
    for p, o in zip(prompts, outs):
        print(f"  prompt {p} -> {o}")
    print(f"{n_new} tokens in {dt:.2f}s "
          f"({n_new / dt:.1f} tok/s incl. compile)")
    print(f"telemetry: {json.dumps(sched.telemetry.summary())}")
    # SLO accounting (ISSUE 13): armed by APEX_TPU_SLO_TTFT_US /
    # APEX_TPU_SLO_DECODE_US; the scheduler closed one window per wave
    if sched.slo.specs:
        print(f"slo: {json.dumps(sched.slo.summary())}")
    if sched.telemetry.tracer.enabled():
        print("traces: APEX_TPU_TRACE armed — render a waterfall with "
              "`python -m apex_tpu.observability.report <telemetry "
              f"dir> --trace <uid>` (uids 0..{len(uids) - 1})")
    if args.train_steps and args.temperature == 0.0:
        want = [[(p[-1] + 1 + i) % args.vocab
                 for i in range(len(o))] for p, o in zip(prompts, outs)]
        hits = sum(o == w for o, w in zip(outs, want))
        print(f"cyclic continuation reproduced on {hits}/{len(outs)} "
              f"prompts")


if __name__ == "__main__":
    from apex_tpu.utils.compile_cache import \
        enable_persistent_compile_cache
    enable_persistent_compile_cache()
    main()
