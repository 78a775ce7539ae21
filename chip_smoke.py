#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, three phases, through the entry points users call:

1. **trainer** — BERT-large phase 1 (hidden 1024, 24 layers, 16 heads, FFN
   4096, vocab 30592; seq 128, batch 32, bf16 parameters, fp32 LAMB master,
   dynamic loss scale) built by ``examples/bert/pretrain_bert.py :: build``
   and stepped with ``train_step.init_train_state`` +
   ``train_step.make_train_step`` + ``functional.fused_lamb``.
2. **server** — the ``gpt`` kind at the GPT-3 1.3B widths (hidden 2048, 16
   heads x 128, vocab 51200, max_seq 2048; depth printed), seeded random bf16
   weights, ``InferenceEngine`` with the paged cache (page 64) driven by
   ``SlotScheduler`` as ``examples/generate.py`` does: more requests than
   slots, prompts from tens to ~1,500 tokens, then a second wave that must
   compile nothing; decode logits are checked against the full forward.
3. **kernels** — every kernel in ``.analysis_kernel_budget.json`` compiled
   (``interpret=False`` on the chip) at the two models' shapes and compared
   with its reference under ``jax.default_matmul_precision("highest")``.

``--chips 4`` runs the trainer as ZeRO dp=4 under ``shard_map`` and the
server at ``tp=4`` with a KV pool larger than one chip's HBM, and compares
``tp=4`` logits with ``tp=1``'s.

The script never sets a platform.  The ``full`` preset (the default) is
refused unless JAX reports a TPU; the ``tiny`` preset — the same phases at
toy sizes, Pallas in interpret mode, for the test suite — is refused on one.
Any phase that raises ends the run non-zero.  The seconds printed are host
clock around ``block_until_ready``: evidence that the path ran, NOT a
benchmark.  The last two lines of stdout are JSON objects: the summary of
the run (it ends with ``"claim": null``), then the verdict, exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
with the device as JAX reports it.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import math
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

# (prompt tokens, max_new_tokens) per request.  Wave 1 has more requests
# than slots (4), so slots retire and re-admit; its prompts land in three
# prefill buckets (64 / 512 / 2048).  Wave 2 reuses those buckets with
# other lengths and must add zero compiles.
PRESETS = {
    "full": dict(
        bert=dict(hidden=1024, layers=24, heads=16, seq=128, vocab=30592,
                  batch=32, steps=8, lr=2e-3),
        gpt=dict(hidden=2048, layers=24, heads=16, vocab=51200,
                 max_seq=2048, slots=4, page_size=64,
                 wave1=[(24, 16), (1500, 10), (300, 12), (40, 20),
                        (350, 8), (60, 6)],
                 wave2=[(50, 8), (400, 6), (1300, 4)],
                 parity=(300, 4), tp_pool_bytes=24 * 1024 ** 3),
        kernels=dict(
            norm=[(4096, 1024), (2048, 2048)],
            flash_causal=(2, 16, 2048, 128), flash_masked=(2, 16, 512, 64),
            decode=(4, 16, 2048, 128, [2048, 1, 700, 0]),
            paged=(4, 16, 64, 128, 32, [2048, 1, 700, 65]),
            block=dict(hidden=2048, heads=16, vocab=51200, max_seq=2048,
                       page_size=64, prompt=200, steps=3),
            update_n=335_000_000 + 17),
    ),
    "tiny": dict(
        bert=dict(hidden=64, layers=2, heads=4, seq=32, vocab=512,
                  batch=8, steps=6, lr=5e-3),
        gpt=dict(hidden=64, layers=2, heads=4, vocab=128, max_seq=128,
                 slots=2, page_size=8,
                 wave1=[(5, 4), (70, 3), (20, 5), (9, 4)],
                 wave2=[(7, 3), (66, 2)],
                 parity=(20, 3), tp_pool_bytes=None),
        kernels=dict(
            norm=[(24, 128), (16, 256)],
            flash_causal=(1, 2, 256, 64), flash_masked=(1, 2, 128, 64),
            decode=(3, 4, 128, 64, [128, 1, 0]),
            paged=(3, 4, 8, 64, 4, [32, 1, 9]),
            block=dict(hidden=64, heads=4, vocab=128, max_seq=64,
                       page_size=8, prompt=11, steps=2),
            update_n=2 * 512 * 128 + 17),
    ),
}


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--preset", choices=sorted(PRESETS), default="full")
    p.add_argument("--chips", type=int, default=1,
                   help="devices to drive: 1, or N > 1 for ZeRO dp=N + "
                        "tp=N (fails if fewer are visible)")
    p.add_argument("--bert-layers", type=int, default=None,
                   help="cut BERT depth (width never changes)")
    p.add_argument("--gpt-layers", type=int, default=None,
                   help="cut GPT depth (width never changes)")
    p.add_argument("--phases", default=None,
                   help="comma list of trainer,server,kernels (default: "
                        "all on one chip; trainer,server on several)")
    p.add_argument("--seed", type=int, default=0)
    return p.parse_args(argv)


# --------------------------------------------------------------------------
# bookkeeping: compile events, device memory
# --------------------------------------------------------------------------

class CompileBook:
    """Counts of XLA compile requests, persistent-cache hits/misses and
    the seconds spent compiling (a miss) or loading the cached
    executable (a hit), from the public ``jax.monitoring`` stream."""

    def __init__(self):
        import jax
        self.requests = self.hits = self.misses = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._secs)

    def _event(self, name, **_):
        if name.endswith("/compile_requests_use_cache"):
            self.requests += 1
        elif name.endswith("/cache_hits"):
            self.hits += 1
        elif name.endswith("/cache_misses"):
            self.misses += 1

    def _secs(self, name, secs, **_):
        if name.endswith("/backend_compile_duration"):
            self.compile_s += secs

    def snapshot(self):
        return dict(requests=self.requests, hits=self.hits,
                    misses=self.misses, compile_s=round(self.compile_s, 2))

    def since(self, snap):
        now = self.snapshot()
        return {k: round(now[k] - snap[k], 2) for k in now}


def device_bytes(key: str):
    """``memory_stats()[key]`` per device, or None where the backend
    reports none (the CPU platform)."""
    import jax
    stats = [d.memory_stats() for d in jax.devices()]
    if any(s is None or key not in s for s in stats):
        return None
    return [int(s[key]) for s in stats]


def fmt_bytes(vals):
    if vals is None:
        return "n/a (backend reports no memory_stats)"
    return "[" + ", ".join(f"{v / 2**30:.2f}" for v in vals) + "] GiB"


def load_example(rel: str):
    path = os.path.join(REPO, "examples", rel)
    spec = importlib.util.spec_from_file_location(
        "chip_smoke_" + os.path.basename(rel)[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def rel_err(got, want) -> float:
    """max|got - want| / max|want| in fp32 — one number per tensor."""
    import jax.numpy as jnp
    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(want)), 1e-6)
    return float(jnp.max(jnp.abs(got - want)) / scale)


def reference(fn, *args):
    """``fn(*args)`` jitted under ``default_matmul_precision("highest")``
    — for the REFERENCE side only: the kernels pick their own MXU
    precision (bf16 operands, fp32 accumulation), and Mosaic rejects a
    bf16 dot traced under an fp32-precision context."""
    import jax
    with jax.default_matmul_precision("highest"):
        return jax.jit(fn)(*args)


def check(name: str, errs: dict, tol: float, why: str) -> dict:
    """Assert every normalized error is finite and within ``tol``."""
    worst = max(errs.values())
    say(f"  {name}: max rel err {worst:.3g} (tol {tol:g}: {why}) "
        + " ".join(f"{k}={v:.2g}" for k, v in errs.items()))
    if not all(math.isfinite(v) and v <= tol for v in errs.values()):
        raise AssertionError(
            f"{name}: errors {errs} exceed tolerance {tol} ({why})")
    return dict(name=name, max_rel_err=worst, tol=tol)


# --------------------------------------------------------------------------
# phase 1: trainer
# --------------------------------------------------------------------------

def phase_trainer(cfg, chips: int, seed: int, on_tpu: bool) -> dict:
    import jax
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from apex_tpu import train_step
    from apex_tpu.observability.timers import StepTimer

    ex = load_example("bert/pretrain_bert.py")
    args = ex.parse_args([
        "--hidden", str(cfg["hidden"]), "--layers", str(cfg["layers"]),
        "--heads", str(cfg["heads"]), "--seq", str(cfg["seq"]),
        "--vocab", str(cfg["vocab"]), "-b", str(cfg["batch"]),
        "--lr", str(cfg["lr"]), "--seed", str(seed)])
    b = ex.build(args)
    n_params = sum(int(x.size) for x in jax.tree.leaves(b.params))
    say(f"trainer: BERT hidden {cfg['hidden']} x {cfg['layers']} layers x "
        f"{cfg['heads']} heads, vocab {cfg['vocab']}, seq {cfg['seq']}, "
        f"batch {cfg['batch']}, {n_params / 1e6:.1f}M params, "
        + ("dense flat LAMB" if chips == 1 else f"ZeRO dp={chips}"))

    if chips == 1:
        state = train_step.init_train_state(b.tx, b.params,
                                            loss_scale=b.loss_scale)
        step = jax.jit(train_step.make_train_step(b.loss_fn, b.tx),
                       donate_argnums=(0,))
    else:
        if cfg["batch"] % chips:
            raise ValueError(f"batch {cfg['batch']} must divide over "
                             f"dp={chips}")
        state, specs = train_step.init_zero_train_state(
            b.tx, b.params, "data", chips, loss_scale=b.loss_scale)
        mesh = Mesh(np.array(jax.devices()[:chips]), ("data",))
        zstep = train_step.make_train_step(b.loss_fn, b.tx, zero=True)
        bspecs = {"tokens": P("data"), "labels": P("data")}
        step = jax.jit(
            jax.shard_map(zstep, mesh=mesh, in_specs=(specs, bspecs),
                          out_specs=(specs, P()), check_vma=False),
            donate_argnums=(0,))
    # the bf16 init tree is dead weight once the flat master exists
    b.params = None
    jax.block_until_ready(state)
    say(f"trainer: bytes_in_use per device after init "
        f"{fmt_bytes(device_bytes('bytes_in_use'))}")
    if chips > 1:
        # init_zero_train_state returns the GLOBAL view on the default
        # device (the line above is what it costs device 0).  Place it
        # by its spec tree before stepping from the host: the first
        # call would shard it anyway, and the second would then see new
        # input shardings and compile again.
        from jax.sharding import NamedSharding
        state = jax.device_put(state, jax.tree.map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda s: isinstance(s, P)))
        jax.block_until_ready(state)
        say(f"trainer: bytes_in_use per device after placing the state "
            f"on the mesh {fmt_bytes(device_bytes('bytes_in_use'))}")

    def batch():
        tokens, labels = ex.synthetic_mlm_batch(b.rng, args)
        return {"tokens": tokens, "labels": labels}

    timer = StepTimer()
    losses, scales, walls = [], [], []
    for i in range(cfg["steps"] + 1):
        data = batch()
        with timer.time_step():
            state, loss = step(state, data)
            jax.block_until_ready((state, loss))
        losses.append(float(loss))
        scales.append(float(state.scaler.loss_scale))
        walls.append(timer.last.seconds)
        if i == 0:
            say(f"trainer: step 0 (compile + run) {walls[0]:.1f} s; "
                f"bytes_in_use per device after it "
                f"{fmt_bytes(device_bytes('bytes_in_use'))}")
            after_first = device_bytes("bytes_in_use")
        elif timer.last.recompiled:
            raise AssertionError(f"trainer: step {i} recompiled")
    steady = sorted(walls[1:])[len(walls[1:]) // 2]
    say("trainer: loss " + " ".join(f"{x:.3f}" for x in losses))
    say("trainer: loss scale " + " ".join(f"{x:.0f}" for x in scales))
    say(f"trainer: steady steps (host clock around block_until_ready, "
        f"not a benchmark) median {steady * 1e3:.1f} ms, all "
        + " ".join(f"{w * 1e3:.1f}" for w in walls[1:]))

    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"trainer: non-finite loss {losses}")
    if not sum(losses[-2:]) < sum(losses[:2]):
        raise AssertionError(f"trainer: loss did not fall {losses}")
    # a skipped (overflowed) step backs the dynamic scale off; after the
    # first step it must only hold or grow, and stay far from the floor
    if any(b_ < a for a, b_ in zip(scales[1:], scales[2:])) \
            or scales[-1] < 2.0 ** 8:
        raise AssertionError(
            f"trainer: overflow-skipped step or collapsed scale {scales}")

    out = dict(params=n_params, cold_s=round(walls[0], 2),
               steady_ms=round(steady * 1e3, 2), loss_first=losses[0],
               loss_last=losses[-1], loss_scale=scales[-1])
    if on_tpu:
        # does block_until_ready bound a step?  The forward+backward
        # matmuls alone need 6*N FLOPs per token; a wall under that
        # floor at the chip's peak means the wait returned early.
        from apex_tpu.chip_specs import local_spec
        floor = (6 * n_params * cfg["batch"] * cfg["seq"]
                 / (local_spec().bf16_tflops * 1e12 * chips))
        # second opinion on the same step: sync by fetching the loss
        t0 = time.perf_counter()
        state, loss = step(state, batch())
        float(loss)
        fetched = time.perf_counter() - t0
        say(f"trainer: matmul-FLOP floor at peak {floor * 1e3:.1f} ms "
            f"<= block_until_ready wall {steady * 1e3:.1f} ms; the same "
            f"step synced by fetching the loss {fetched * 1e3:.1f} ms")
        if steady < floor:
            raise AssertionError(
                f"trainer: step wall {steady:.4f}s is under the FLOP "
                f"floor {floor:.4f}s — block_until_ready did not wait")
        out.update(flop_floor_ms=round(floor * 1e3, 2),
                   fetch_sync_ms=round(fetched * 1e3, 2))
    if chips > 1 and after_first is not None:
        # each rank's 1/dp of master + both moments, fp32
        share = 3 * 4 * state.opt.padded_numel // chips
        others = after_first[1:chips]
        say(f"trainer: ZeRO share per device {share / 2**30:.2f} GiB")
        if min(others) < share:
            raise AssertionError(
                f"trainer: a device holds less than its ZeRO share "
                f"{share}: {after_first}")
        if after_first[0] > max(others) + 256 * 2 ** 20:
            raise AssertionError(
                f"trainer: device 0 holds more than its share plus the "
                f"replicated scalars and batches: {after_first}")
    return out


# --------------------------------------------------------------------------
# phase 2: server
# --------------------------------------------------------------------------

def _gpt(cfg, seed: int):
    import jax
    import jax.numpy as jnp

    from apex_tpu.transformer import parallel_state
    from apex_tpu.transformer.testing import GPTConfig, gpt_model_provider

    parallel_state.destroy_model_parallel()
    parallel_state.initialize_model_parallel(1)
    gcfg = GPTConfig(
        vocab_size=cfg["vocab"], hidden_size=cfg["hidden"],
        num_layers=cfg["layers"], num_attention_heads=cfg["heads"],
        max_seq_length=cfg["max_seq"], hidden_dropout=0.0,
        attention_dropout=0.0, params_dtype=jnp.bfloat16)
    model = gpt_model_provider(gcfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed),
                                 jnp.zeros((1, 8), jnp.int32))
    return gcfg, model, params


def _teacher_forced_logits(engine, cache, tokens, n_prefill: int):
    """Prefill ``tokens[:n_prefill]`` into slot 0, then decode the rest
    one given token at a time.  Returns ``(cache, logits)`` with one
    fp32 row per position ``n_prefill - 1 .. len(tokens) - 1`` — the
    rows a full forward over ``tokens`` emits at the same positions.
    Feeding the given tokens (not the sampled ones) keeps two engines on
    one stream: with random weights the argmax flips on rounding."""
    import numpy as np

    alloc = engine.new_allocator()
    pages = alloc.acquire(alloc.pages_needed(len(tokens) + 1))
    cache, _, first = engine.prefill(cache, tokens[:n_prefill], 0,
                                     pages=pages)
    rows = [np.asarray(first, np.float32)]
    last = np.zeros((engine.slots,), np.int32)
    active = np.zeros((engine.slots,), bool)
    active[0] = True
    for tok in tokens[n_prefill:]:
        last[0] = tok
        cache, _, logits, _ = engine.decode(cache, last, active)
        rows.append(np.asarray(logits, np.float32)[0])
    return engine.evict_slot(cache, 0), np.stack(rows)


def phase_server(cfg, chips: int, seed: int, on_tpu: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.inference import InferenceEngine, SamplingConfig, \
        SlotScheduler
    from apex_tpu.observability.timers import compile_count

    gcfg, model, params = _gpt(cfg, seed)
    ps, mpps = cfg["page_size"], cfg["max_seq"] // cfg["page_size"]
    head_dim = cfg["hidden"] // cfg["heads"]
    token_kv = 2 * cfg["layers"] * cfg["heads"] * head_dim * 2   # bf16
    num_pages = cfg["slots"] * mpps
    if chips > 1 and cfg["tp_pool_bytes"]:
        # a pool no single chip can hold: only a sharded build fits
        num_pages = cfg["tp_pool_bytes"] // (ps * token_kv)
    say(f"server: gpt hidden {cfg['hidden']} x DEPTH {cfg['layers']} "
        f"layers x {cfg['heads']} heads x {head_dim}, vocab "
        f"{cfg['vocab']}, max_seq {cfg['max_seq']}, slots {cfg['slots']}, "
        f"page {ps}, pool {num_pages} pages = "
        f"{num_pages * ps * token_kv / 2**30:.2f} GiB, tp={chips}")

    def engine_for(tp, pages):
        return InferenceEngine(
            "gpt", gcfg, params, slots=cfg["slots"],
            max_seq=cfg["max_seq"], page_size=ps, num_pages=pages,
            dtype=jnp.bfloat16, sampling=SamplingConfig(), seed=seed,
            tp=tp)

    rng = np.random.RandomState(seed + 1)
    n_pre, n_dec = cfg["parity"]
    stream = [int(t) for t in rng.randint(0, cfg["vocab"],
                                          size=n_pre + n_dec)]

    t_cold = time.perf_counter()
    engine = engine_for(chips, num_pages)
    if chips > 1:
        # drop the unsharded source tree: what stays on device 0 is its
        # shard of the mirrors, like every other rank
        params = None
    cache = engine.init_cache()
    jax.block_until_ready(cache)
    after_init = device_bytes("bytes_in_use")
    say(f"server: bytes_in_use per device after init_cache "
        f"{fmt_bytes(after_init)}")
    cache, got = _teacher_forced_logits(engine, cache, stream, n_pre)
    after_first = device_bytes("bytes_in_use")
    say(f"server: bytes_in_use per device after the first steps "
        f"{fmt_bytes(after_first)}")

    if chips == 1:
        # reference: the training model's full forward over the stream
        full = jax.jit(model.apply)(params, jnp.asarray([stream]))
        want = np.asarray(full[n_pre - 1:, 0], np.float32)
        ref_name = "model.apply full forward"
    else:
        share = engine.cache_hbm_bytes()
        for name, vals in (("init_cache", after_init),
                           ("the first steps", after_first)):
            if vals is None:
                continue
            if min(vals[1:chips]) < share:
                raise AssertionError(
                    f"server: after {name} a device holds less than its "
                    f"pool share {share}: {vals}")
            if vals[0] > 1.05 * max(vals[1:chips]) + 64 * 2 ** 20:
                raise AssertionError(
                    f"server: after {name} device 0 holds more than its "
                    f"share plus the replicated tables: {vals}")
        gcfg, model, params = _gpt(cfg, seed)       # same seed, same bits
        ref = engine_for(1, 2 * mpps)
        _, want = _teacher_forced_logits(ref, ref.init_cache(), stream,
                                         n_pre)
        del ref
        ref_name = "the tp=1 engine on the same weights and tokens"
    # bf16 weights and activations through every layer, summed in
    # different orders by the two programs (flash blocks vs cached
    # decode, psum over tp ranks): ~2^-8 per rounding, growing with
    # depth; logits are compared to the reference's largest magnitude
    parity = check(f"server logits vs {ref_name}",
                   {f"pos{n_pre - 1 + i}": rel_err(got[i], want[i])
                    for i in range(len(got))},
                   tol=5e-2, why="bf16 end to end, different sum orders")

    def wave(sched, reqs):
        prompts = [[int(t) for t in rng.randint(0, cfg["vocab"], size=n)]
                   for n, _ in reqs]
        uids = [sched.submit(p, max_new_tokens=m)
                for p, (_, m) in zip(prompts, reqs)]
        out = sched.run(cache)
        for uid, (n, m) in zip(uids, reqs):
            reason = sched.finish_reasons[uid]
            if reason not in ("length", "eos") or len(out[uid]) != m:
                raise AssertionError(
                    f"server: request {uid} (prompt {n}, asked {m}) ended "
                    f"{reason!r} with {len(out[uid])} tokens")
        return sum(m for _, m in reqs)

    sched = SlotScheduler(engine)
    n1 = wave(sched, cfg["wave1"])
    cold_s = time.perf_counter() - t_cold
    cache = sched.cache
    c0 = compile_count()
    t0 = time.perf_counter()
    n2 = wave(sched, cfg["wave2"])
    steady_s = time.perf_counter() - t0
    added = compile_count() - c0
    summary = sched.telemetry.summary()
    say(f"server: wave 1 {len(cfg['wave1'])} requests / {n1} tokens on "
        f"{cfg['slots']} slots (peak active {sched.peak_active}), "
        f"{cold_s:.1f} s with compiles; wave 2 {len(cfg['wave2'])} "
        f"requests / {n2} tokens in {steady_s:.2f} s (host clock, not a "
        f"benchmark), compiles added {added}")
    say(f"server: telemetry {json.dumps(summary)}")
    if added or summary["recompiles"]:
        raise AssertionError(
            f"server: wave 2 compiled {added} programs; telemetry "
            f"recompiles {summary['recompiles']}")
    if len(cfg["wave1"]) <= cfg["slots"]:
        raise AssertionError("server: wave 1 must exceed the slot count")
    return dict(depth=cfg["layers"], cold_s=round(cold_s, 2),
                steady_s=round(steady_s, 3), tokens=n1 + n2,
                logits_max_rel_err=parity["max_rel_err"],
                pool_pages=int(num_pages))


# --------------------------------------------------------------------------
# phase 3: kernels
# --------------------------------------------------------------------------

def _normal(key, shape, dtype: str):
    import jax
    return jax.random.normal(key, shape, dtype)


def case_norms(k, seed):
    import jax

    from apex_tpu.ops import (layer_norm, layer_norm_reference, rms_norm,
                              rms_norm_reference)
    out = []
    for rows, hidden in k["norm"]:
        keys = jax.random.split(jax.random.PRNGKey(seed), 4)
        x = _normal(keys[0], (rows, hidden), "bfloat16")
        w = 1.0 + 0.1 * _normal(keys[1], (hidden,), "bfloat16")
        b = 0.1 * _normal(keys[2], (hidden,), "bfloat16")
        dy = _normal(keys[3], (rows, hidden), "bfloat16")
        for name, fn, ref, ops in (
                ("layer_norm", layer_norm, layer_norm_reference,
                 (x, w, b)),
                ("rms_norm", rms_norm, rms_norm_reference, (x, w))):
            def run(f, *a):
                y, vjp = jax.vjp(f, *a)
                return (y,) + vjp(dy)
            got = jax.jit(lambda *a: run(fn, *a))(*ops)
            want = reference(lambda *a: run(ref, *a), *ops)
            errs = {n: rel_err(g, r) for n, g, r in
                    zip(("y", "dx", "dw", "db"), got, want)}
            # bf16 outputs: both sides compute in fp32 and round once,
            # so they differ by at most one bf16 ulp (2^-8 relative)
            out.append(check(f"{name} fwd+bwd [{rows}, {hidden}] bf16",
                             errs, 1e-2, "one bf16 ulp of the output"))
    return out


def _flash_case(name, shape, causal, masked, seed):
    import jax
    import jax.numpy as jnp

    import apex_tpu.ops.attention as A
    b, h, s, d = shape
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, kk, v = (_normal(keys[i], shape, "bfloat16") for i in range(3))
    do = _normal(keys[3], shape, "bfloat16")
    mask = None
    if masked:
        # BERT-style key padding: each batch row keeps a prefix of keys
        lens = jnp.asarray([s - 37 * (i + 1) for i in range(b)])
        mask = (jnp.arange(s)[None, None, None, :]
                >= lens[:, None, None, None])

    def fwd_bwd(f):
        def g(q, kk, v):
            y, vjp = jax.vjp(lambda *a: f(*a, causal=causal, mask=mask),
                             q, kk, v)
            return (y,) + vjp(do)
        return g

    kern = lambda *a, **kw: A.flash_attention(*a, use_kernel=True, **kw)
    want = reference(fwd_bwd(A.mha_reference), q, kk, v)
    fused = jax.jit(fwd_bwd(kern))(q, kk, v)
    saved, A._FUSED_BWD_MAX_BYTES = A._FUSED_BWD_MAX_BYTES, 0
    try:
        # forces the two-kernel dq / dkv backward
        split = jax.jit(fwd_bwd(kern))(q, kk, v)
    finally:
        A._FUSED_BWD_MAX_BYTES = saved
    names = ("out", "dq", "dk", "dv")
    # the kernels feed the MXU bf16 probabilities / score grads with
    # fp32 accumulation; the fp32 oracle keeps them exact: ~2^-8 per
    # rounded operand, and grads pass through three rounded matmuls
    why = "bf16 p/ds into the MXU vs an fp32 oracle"
    return [
        check(f"{name} fwd + fused bwd {list(shape)}",
              {n: rel_err(g, r) for n, g, r in zip(names, fused, want)},
              2e-2, why),
        check(f"{name} split bwd {list(shape)}",
              {n: rel_err(g, r) for n, g, r in
               zip(names[1:], split[1:], want[1:])}, 2e-2, why),
    ]


def case_flash(k, seed):
    return (_flash_case("flash_attention causal", k["flash_causal"], True,
                        False, seed)
            + _flash_case("flash_attention masked", k["flash_masked"],
                          False, True, seed + 1))


def case_decode(k, seed):
    import jax
    import jax.numpy as jnp

    from apex_tpu.ops.attention import decode_attention, mha_reference
    slots, h, s, d, lengths = k["decode"]
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = _normal(keys[0], (slots, h, 1, d), "bfloat16")
    kk = _normal(keys[1], (slots, h, s, d), "bfloat16")
    v = _normal(keys[2], (slots, h, s, d), "bfloat16")
    ln = jnp.asarray(lengths, jnp.int32)
    got = jax.jit(lambda *a: decode_attention(*a, use_kernel=True))(
        q, kk, v, ln)
    mask = (jnp.arange(s)[None, None, None, :]
            >= ln[:, None, None, None])
    want = reference(lambda *a: mha_reference(*a, mask=mask), q, kk, v)
    return [check(f"decode_attention kernel q[{slots},{h},1,{d}] vs "
                  f"cache S={s} lengths {lengths}",
                  {"out": rel_err(got, want)}, 2e-2,
                  "bf16 p into the MXU vs an fp32 oracle")]


def case_paged(k, seed):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.ops.attention import decode_attention
    from apex_tpu.ops.paged_attention import paged_decode_attention
    slots, h, ps, d, mpps, lengths = k["paged"]
    n_pages = slots * mpps
    layers, layer = 2, 1
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = _normal(keys[0], (slots, h, d), "bfloat16")
    pk = _normal(keys[1], (n_pages + 1, layers, h, ps, d), "bfloat16")
    pv = _normal(keys[2], (n_pages + 1, layers, h, ps, d), "bfloat16")
    # a scrambled, non-contiguous page assignment
    pt = jnp.asarray(np.random.RandomState(seed).permutation(n_pages)
                     .reshape(slots, mpps), jnp.int32)
    ln = jnp.asarray(lengths, jnp.int32)
    got = jax.jit(lambda *a: paged_decode_attention(
        *a, layer=layer))(q, pk, pv, pt, ln)

    def gathered(q, pk, pv, pt, ln):
        # the slots' windows gathered dense, scored by the XLA chain
        def window(pool):
            g = jnp.take(pool[:, layer], pt, axis=0)
            return jnp.moveaxis(g, 2, 1).reshape(slots, h, mpps * ps, d)
        return decode_attention(q[:, :, None, :], window(pk), window(pv),
                                ln, use_kernel=False)[:, :, 0]

    want = reference(gathered, q, pk, pv, pt, ln)
    return [check(f"paged_decode_attention kernel MHA {h} x {d}, page "
                  f"{ps}, {mpps} pages/slot, layer {layer} of {layers}, "
                  f"lengths {lengths}",
                  {"out": rel_err(got, want)}, 2e-2,
                  "bf16 p into the MXU; the gathered windows through the "
                  "dense XLA decode at highest precision")]


def case_fused_block(k, seed):
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.inference import InferenceEngine, SamplingConfig
    c = k["block"]
    gcfg, _, params = _gpt(dict(c, layers=1), seed)
    mpps = c["max_seq"] // c["page_size"]

    def engine(fusion):
        return InferenceEngine(
            "gpt", gcfg, params, slots=2, max_seq=c["max_seq"],
            page_size=c["page_size"], num_pages=2 * mpps,
            dtype=jnp.bfloat16, sampling=SamplingConfig(), seed=seed,
            decode_fusion=fusion, tp=1)

    # a width whose layer does not fit the VMEM the compiler grants is
    # refused here, when the engine is built (resolve_decode_fusion),
    # with the limit in the message — not inside Mosaic on a decode
    name = (f"fused_block_decode gpt hidden {c['hidden']}, "
            f"{c['heads']} heads, page {c['page_size']}")
    stream = [int(t) for t in np.random.RandomState(seed).randint(
        0, c["vocab"], size=c["prompt"] + c["steps"])]
    rows = []
    for fusion in ("1", "0"):
        eng = engine(fusion)
        _, logits = _teacher_forced_logits(eng, eng.init_cache(), stream,
                                           c["prompt"])
        rows.append(logits[1:])            # the decode steps only
    return [check(name + " vs the per-op decode",
                  {f"step{i}": rel_err(rows[0][i], rows[1][i])
                   for i in range(len(rows[0]))}, 3e-2,
                  "fp32 residual chain in-kernel vs bf16 per-op rounding")]


def case_fused_update(k, seed):
    import jax
    import jax.numpy as jnp

    from apex_tpu.ops import fused_update as F
    n = k["update_n"]
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    p = _normal(keys[0], (n,), "float32")
    g = 0.1 * _normal(keys[1], (n,), "float32")
    m = 0.1 * _normal(keys[2], (n,), "float32")
    v = jnp.abs(0.1 * _normal(keys[3], (n,), "float32"))
    hp = dict(beta1=0.9, beta2=0.999, eps=1e-6, weight_decay=0.01)

    # every comparison reduces to scalars INSIDE one program, so the
    # reference's full-size outputs fuse into the reduction and never
    # occupy HBM next to the kernel's (fp32 elementwise math: the
    # ambient matmul precision touches nothing here)
    def cmp(kernel, ref, *ops):
        def f(*a):
            got, want = kernel(*a), ref(*a)
            got = got if isinstance(got, tuple) else (got,)
            want = want if isinstance(want, tuple) else (want,)
            return [jnp.max(jnp.abs(a_.astype(jnp.float32) - b_))
                    / jnp.maximum(jnp.max(jnp.abs(b_)), 1e-6)
                    for a_, b_ in zip(got, want)]
        return {f"o{i}": float(e)
                for i, e in enumerate(jax.jit(f)(*ops))}

    def lamb1_ref(p, g, m, v):
        m2 = 0.9 * m + 0.1 * g
        v2 = 0.999 * v + 0.001 * g * g
        u = (m2 / (1 - 0.9 ** 3)) / (jnp.sqrt(v2 / (1 - 0.999 ** 3))
                                     + 1e-6) + 0.01 * p
        return m2, v2, u

    def sgd_ref(p, g, buf):
        d = g + 0.01 * p
        buf2 = 0.9 * buf + d
        return p - 0.1 * buf2, buf2

    def adagrad_ref(p, g, h):
        g2 = g + 0.01 * p
        h2 = h + g2 * g2
        return p - 0.1 * g2 / (jnp.sqrt(h2) + 1e-10), h2

    cases = [
        ("fused_scale", lambda x: F.fused_scale(x, 0.5)[0],
         lambda x: x * 0.5, (g,)),
        ("fused_axpby", lambda x, y: F.fused_axpby(2.0, x, -0.5, y)[0],
         lambda x, y: 2.0 * x - 0.5 * y, (g, m)),
        ("fused_l2norm", F.fused_l2norm,
         lambda x: jnp.sqrt(jnp.sum(x * x)), (g,)),
        ("fused_l2norm_scale",
         lambda x: F.fused_l2norm_scale(x, 0.5)[:2],
         lambda x: (x * 0.5, jnp.sqrt(jnp.sum(x * x * 0.25))), (g,)),
        ("fused_adam_flat",
         lambda *a: F.fused_adam_flat(*a, lr=1e-3, step=3, **hp),
         lambda *a: F.adam_reference(*a, lr=1e-3, step=3, **hp),
         (p, g, m, v)),
        ("fused_adagrad_flat",
         lambda *a: F.fused_adagrad_flat(*a, lr=0.1, eps=1e-10,
                                         weight_decay=0.01),
         adagrad_ref, (p, g, v)),
        ("fused_sgd_flat",
         lambda *a: F.fused_sgd_flat(*a, lr=0.1, momentum=0.9,
                                     dampening=0.0, weight_decay=0.01),
         sgd_ref, (p, g, m)),
        ("fused_lamb_phase1_flat",
         lambda *a: F.fused_lamb_phase1_flat(*a, step=3, **hp),
         lamb1_ref, (p, g, m, v)),
    ]
    out = []
    for name, kernel, ref, ops in cases:
        # fp32 elementwise math on both sides; the norms sum n squares
        # in different orders (per-block partials vs one XLA reduce)
        out.append(check(f"{name} n={n}", cmp(kernel, ref, *ops), 1e-4,
                         "fp32 both sides; sum order for the norms"))
    return out


KERNEL_CASES = [("norms", case_norms), ("flash", case_flash),
                ("decode", case_decode), ("paged", case_paged),
                ("fused_block", case_fused_block),
                ("fused_update", case_fused_update)]


def phase_kernels(k, seed: int, on_tpu: bool) -> dict:
    from apex_tpu.utils import interpret_mode
    say(f"kernels: pallas interpret mode = {interpret_mode()} "
        f"(must be False on a TPU)")
    if on_tpu and interpret_mode():
        raise AssertionError("kernels: interpret mode on a TPU")
    rows = []
    t0 = time.perf_counter()
    for name, fn in KERNEL_CASES:
        t1 = time.perf_counter()
        rows += fn(k, seed)
        say(f"  ({name}: {time.perf_counter() - t1:.1f} s, compile "
            f"included)")
    return dict(cases=len(rows), cold_s=round(time.perf_counter() - t0, 2),
                worst=max(rows, key=lambda r: r["max_rel_err"] / r["tol"]))


# --------------------------------------------------------------------------
# driver
# --------------------------------------------------------------------------

def main(argv=None, *, persistent_cache: bool = False) -> int:
    """Run the phases; the exit code.  ``persistent_cache`` turns the
    compile cache on — the ``__main__`` entry point only, never a test
    calling ``main(argv)`` in a process whose compiles are counted."""
    args = parse_args(argv)
    import jax

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if args.preset == "full" and not on_tpu:
        print(f"chip_smoke: the full preset needs a TPU; JAX reports "
              f"platform {dev.platform!r} ({dev.device_kind})",
              file=sys.stderr)
        return 2
    if args.preset == "tiny" and dev.platform != "cpu":
        print(f"chip_smoke: the tiny preset is for the CPU test suite; "
              f"JAX reports platform {dev.platform!r}", file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips or args.chips < 1:
        print(f"chip_smoke: --chips {args.chips} but JAX reports "
              f"{len(jax.devices())} device(s)", file=sys.stderr)
        return 2

    import jaxlib
    from importlib import metadata

    from apex_tpu.utils.compile_cache import (CACHE_DIR_ENV,
                                              enable_persistent_compile_cache)
    cache_dir = None
    if persistent_cache:
        cache_dir = enable_persistent_compile_cache() \
            or os.environ.get(CACHE_DIR_ENV)
    book = CompileBook()
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    device = dict(platform=dev.platform, kind=dev.device_kind,
                  count=len(jax.devices()))
    versions = dict(jax=jax.__version__, jaxlib=jaxlib.__version__,
                    libtpu=libtpu)
    say(f"platform {dev.platform}, device_kind {dev.device_kind}, "
        f"{len(jax.devices())} device(s), driving {args.chips}; "
        + ", ".join(f"{k} {v}" for k, v in versions.items()))
    say(f"preset {args.preset}; compile cache at {cache_dir}")

    preset = PRESETS[args.preset]
    bert = dict(preset["bert"])
    gpt = dict(preset["gpt"])
    if args.bert_layers:
        bert["layers"] = args.bert_layers
    if args.gpt_layers:
        gpt["layers"] = args.gpt_layers
    default = "trainer,server,kernels" if args.chips == 1 \
        else "trainer,server"
    phases = (args.phases or default).split(",")
    unknown = set(phases) - {"trainer", "server", "kernels"}
    if unknown:
        print(f"chip_smoke: unknown phase(s) {sorted(unknown)}",
              file=sys.stderr)
        return 2

    runners = {
        "trainer": lambda: phase_trainer(bert, args.chips, args.seed,
                                         on_tpu),
        "server": lambda: phase_server(gpt, args.chips, args.seed, on_tpu),
        "kernels": lambda: phase_kernels(preset["kernels"], args.seed,
                                         on_tpu),
    }
    results = {}
    t_all = time.perf_counter()
    for name in ("trainer", "server", "kernels"):
        if name not in phases:
            continue
        snap, t0 = book.snapshot(), time.perf_counter()
        res = runners[name]()
        res["wall_s"] = round(time.perf_counter() - t0, 2)
        res["compile"] = book.since(snap)
        peak = device_bytes("peak_bytes_in_use")
        res["peak_bytes_in_use"] = peak
        say(f"{name}: done in {res['wall_s']} s; compile requests "
            f"{res['compile']['requests']}, persistent-cache hits "
            f"{res['compile']['hits']} / misses "
            f"{res['compile']['misses']}, compile-or-load "
            f"{res['compile']['compile_s']} s; peak_bytes_in_use per "
            f"device (process so far) {fmt_bytes(peak)}")
        results[name] = res
    total = book.snapshot()
    say(f"all phases passed in {time.perf_counter() - t_all:.1f} s; "
        f"compile requests {total['requests']}, persistent-cache hits "
        f"{total['hits']} / misses {total['misses']}, compile-or-load "
        f"{total['compile_s']} s")
    # the summary (second to last line), then the verdict the driver
    # parses: the last line holds "ok" and "device" and nothing else
    print(json.dumps(dict(
        ok=True, device=device, versions=versions, preset=args.preset,
        chips=args.chips, phases=results, compile_cache=total,
        note="seconds are host clock around block_until_ready on a "
             "smoke run: evidence the path ran, not a benchmark",
        claim=None)), flush=True)
    print(json.dumps(dict(ok=True, device=device)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(persistent_cache=True))
