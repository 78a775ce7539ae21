"""Decompose _lamb_step cost on-chip: phase1 kernel vs per-leaf norms vs
repeat broadcast.  Scratch diagnostic."""
import json
import time

import jax
import jax.numpy as jnp
import numpy as np


def rtt():
    triv = jax.jit(lambda x: x + 1.0)
    jax.device_get(triv(jnp.float32(0)))
    best = 1e9
    for _ in range(3):
        t0 = time.perf_counter()
        jax.device_get(triv(jnp.float32(1)))
        best = min(best, time.perf_counter() - t0)
    return best


def timed(loop, args, iters, r):
    jax.device_get(loop(*args))
    samples = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.device_get(loop(*args))
        samples.append(time.perf_counter() - t0)
    return (min(samples) - r) / iters


def main():
    from apex_tpu.ops.fused_update import fused_lamb_phase1_flat

    r = rtt()
    iters = 4
    n = 334_822_400
    # BERT-large-ish leaf structure: 297 leaves, one 31M embedding,
    # many 1M/4M matrices, many 1024 biases
    rng = np.random.default_rng(0)
    sizes = [31_254_528] + [1024 * 1024] * 96 + [4 * 1024 * 1024] * 48 + \
        [1024] * 151
    sizes.append(n - sum(sizes))
    assert sizes[-1] > 0
    sizes = tuple(sizes)
    offsets = tuple(int(o) for o in np.cumsum((0,) + sizes[:-1]))
    out = {"n_leaves": len(sizes)}

    p = jnp.ones((n,), jnp.float32)
    g = jnp.full((n,), 1e-4, jnp.float32)

    # 1. phase1 kernel alone (state carried)
    @jax.jit
    def ph1_loop(state, g):
        def body(state, _):
            p, m, v = state
            m2, v2, u = fused_lamb_phase1_flat(
                p, g, m, v, beta1=jnp.float32(0.9), beta2=jnp.float32(0.999),
                eps=jnp.float32(1e-6), weight_decay=jnp.float32(0.01),
                step=jnp.float32(1), bias_correction=True,
                grad_scale=jnp.float32(1.0), grad_averaging=True)
            return (p - 1e-9 * u, m2, v2), None
        state, _ = jax.lax.scan(body, state, None, length=iters)
        return jax.tree.map(lambda x: jnp.sum(x[:1]), state)
    st = (p, jnp.zeros_like(p), jnp.zeros_like(p))
    out["phase1_ms"] = round(timed(ph1_loop, (st, g), iters, r) * 1e3, 2)
    print("phase1", out["phase1_ms"], flush=True)

    # 2. per-leaf sq-norms via static slices (the suspect)
    def sq_norms_slices(flat):
        return jnp.stack([
            jnp.sum(jnp.square(jax.lax.dynamic_slice_in_dim(flat, o, s)))
            for o, s in zip(offsets, sizes)])

    @jax.jit
    def norms_loop(p):
        def body(c, _):
            nrm = sq_norms_slices(p + c * 1e-30)
            return c + jnp.sum(nrm[:1]), None
        c, _ = jax.lax.scan(body, jnp.float32(0), None, length=iters)
        return c
    out["norms_slices_ms"] = round(timed(norms_loop, (p,), iters, r) * 1e3, 2)
    print("norms_slices", out["norms_slices_ms"], flush=True)

    # 3. per-leaf sq-norms via segment_sum over a precomputed id vector
    # (seg_ids passed as an ARG — closure capture inlines 1.3 GB of HLO
    # constant)
    seg_ids = jnp.asarray(np.repeat(np.arange(len(sizes)), sizes), jnp.int32)

    @jax.jit
    def seg_loop(p, seg_ids):
        def body(c, _):
            nrm = jax.ops.segment_sum(jnp.square(p + c * 1e-30), seg_ids,
                                      num_segments=len(sizes))
            return c + jnp.sum(nrm[:1]), None
        c, _ = jax.lax.scan(body, jnp.float32(0), None, length=iters)
        return c
    out["norms_segsum_ms"] = round(
        timed(seg_loop, (p, seg_ids), iters, r) * 1e3, 2)
    print("norms_segsum", out["norms_segsum_ms"], flush=True)

    # 4. repeat broadcast alone
    ratio = jnp.ones((len(sizes),), jnp.float32)
    sz = jnp.asarray(sizes)

    @jax.jit
    def rep_loop(ratio):
        def body(c, _):
            scale = jnp.repeat(ratio + c * 1e-30, sz, total_repeat_length=n)
            return c + jnp.sum(scale[:1]), None
        c, _ = jax.lax.scan(body, jnp.float32(0), None, length=iters)
        return c
    out["repeat_ms"] = round(timed(rep_loop, (ratio,), iters, r) * 1e3, 2)
    print("repeat", out["repeat_ms"], flush=True)

    # 5. gather broadcast: scale = ratio[seg_ids]
    @jax.jit
    def gat_loop(ratio, seg_ids):
        def body(c, _):
            scale = (ratio + c * 1e-30)[seg_ids]
            return c + jnp.sum(scale[:1]), None
        c, _ = jax.lax.scan(body, jnp.float32(0), None, length=iters)
        return c
    out["gather_ms"] = round(
        timed(gat_loop, (ratio, seg_ids), iters, r) * 1e3, 2)
    print("gather", out["gather_ms"], flush=True)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
