"""Driver of the training cells: the program's own jitted train step, driven
from the host as a training job drives it: batches made in the loop, the
mix's ``ahead_seconds`` of steps dispatched ahead of the one whose loss is
waited for, each loss read that late.  So the chip stays fed while the host
stands still, and a stall of the shared host weighs on the rate only where
it outlasts what was dispatched.  When the window's time is up nothing more
is sent, all that was sent is waited for, and the clock is read after that
wait: every step sent counts, over all of that time.

Set-up builds ONE object — the compiled step with its state — drives it
through its first ``CHECK_STEPS`` steps by the window's own call and feed
(keeping what ``correct`` compares), and hands that same object to the
window.  Once the window has closed and the peak memory is read, the
program's state is freed and the plain reference follows the same first
steps from the same weights and batches.
"""
from __future__ import annotations

import collections
import gc
import importlib.util
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import harness as H
from .. import traffic, weights
from ..references import bert_mlm
from ..references.transformer import LAYER_KEYS

CHECK_STEPS = 3

#: program leaf (under a layer) -> reference key
LAYER_LEAVES = {
    ("input_layernorm", "weight"): "ln1_g",
    ("input_layernorm", "bias"): "ln1_b",
    ("self_attention", "query_key_value", "weight"): "w_qkv",
    ("self_attention", "query_key_value", "bias"): "b_qkv",
    ("self_attention", "dense", "weight"): "w_o",
    ("self_attention", "dense", "bias"): "b_o",
    ("post_attention_layernorm", "weight"): "ln2_g",
    ("post_attention_layernorm", "bias"): "ln2_b",
    ("mlp", "dense_h_to_4h", "weight"): "w_fc",
    ("mlp", "dense_h_to_4h", "bias"): "b_fc",
    ("mlp", "dense_4h_to_h", "weight"): "w_proj",
    ("mlp", "dense_4h_to_h", "bias"): "b_proj",
}
_TOP_LEAVES = {
    ("word_embeddings", "weight"): "wte",
    ("position_embeddings",): "wpe",
    ("final_layernorm", "weight"): "lnf_g",
    ("final_layernorm", "bias"): "lnf_b",
    ("lm_head_dense", "kernel"): "head_w",      # stored [in, out]
    ("lm_head_dense", "bias"): "head_b",
    ("lm_head_layernorm", "weight"): "head_ln_g",
    ("lm_head_layernorm", "bias"): "head_ln_b",
}


def leaf_names(params) -> list:
    """For each leaf of the program's tree, in its own order, the
    reference's ``(key, layer or None)``."""
    out = []
    for path, _ in jax.tree_util.tree_leaves_with_path(params):
        names = tuple(str(p.key) for p in path)[1:]      # drop 'params'
        if names[0].startswith("layer_"):
            out.append((LAYER_LEAVES[names[1:]], int(names[0][6:])))
        else:
            out.append((_TOP_LEAVES[names], None))
    return out


def reference_weights(params, n_layers: int) -> dict:
    """The benchmark's own weights, regrouped as the reference names them,
    float32 (the program's fp32 master holds the same numbers)."""
    p = params["params"]
    f32 = lambda x: jnp.asarray(x, jnp.float32)         # noqa: E731
    out = {ref: f32(dig(p, prog)) for prog, ref in _TOP_LEAVES.items()}
    out["head_w"] = out["head_w"].T
    out["layers"] = {
        ref: jnp.stack([f32(dig(p[f"layer_{i}"], prog))
                        for i in range(n_layers)])
        for prog, ref in LAYER_LEAVES.items()}
    assert set(out["layers"]) == set(LAYER_KEYS)
    return out


def dig(tree, names):
    for n in names:
        tree = tree[n]
    return tree


def in_leaf_order(norms: dict, names: list) -> np.ndarray:
    """The reference's per-tensor norms as a vector in the program's leaf
    order."""
    return np.asarray([
        float(norms["layers"][k][i]) if i is not None else float(norms[k])
        for k, i in names], np.float64)


def worst_leaf_gap(got: np.ndarray, want: np.ndarray,
                   keep=None) -> float:
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger."""
    floor = float(np.median(want))
    gap = np.abs(got - want) / np.maximum(want, floor)
    if keep is not None:
        gap = gap[keep]
    return float(np.max(gap))


def compare(got: dict, want: dict, limits: dict) -> list:
    """Every number compared, beside its limit."""
    checks = [{"name": f"loss{i + 1}_rel", "value": abs(g - w) / abs(w),
               "limit": limits["loss_rel"][i]}
              for i, (g, w) in enumerate(zip(got["losses"],
                                             want["losses"]))]
    checks.append({"name": "grad1_worst_leaf",
                   "value": worst_leaf_gap(got["grad1"], want["grad1"]),
                   "limit": limits["grad1_worst_leaf"]})
    # a leaf whose gradient is nought to rounding moves by round-off alone
    moved = want["grad1"] >= 1e-3 * float(np.median(want["grad1"]))
    checks.append({"name": "delta_worst_leaf",
                   "value": worst_leaf_gap(got["delta"], want["delta"],
                                           moved),
                   "limit": limits["delta_worst_leaf"]})
    for c in checks:
        if not np.isfinite(c["value"]):
            c["value"] = float("inf")
    return checks


def load_example(root, rel: str):
    path = root / rel
    if not path.exists():
        raise H.Refused(f"the program's entry {rel} is not in this "
                        f"checkout")
    spec = importlib.util.spec_from_file_location("bench_entry_example",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def build(cell, seed: int):
    """The program's objects for this cell and the benchmark's weights."""
    from apex_tpu import train_step

    cfg, mix = cell.config, cell.mix
    ex = load_example(cell.root, cfg["entry"]["file"])
    args = ex.parse_args([
        "--hidden", str(cfg["hidden_size"]),
        "--layers", str(cfg["num_hidden_layers"]),
        "--heads", str(cfg["num_attention_heads"]),
        "--seq", str(mix["seq"]), "--vocab", str(cfg["vocab_size"]),
        "-b", str(mix["batch"]), "--lr", str(cfg["optimizer"]["lr"]),
        "--seed", "0"])
    b = ex.build(args)
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), b.params)
    b.params = None
    params = weights.make(shapes, seed)
    state = train_step.init_train_state(b.tx, params,
                                        loss_scale=b.loss_scale)
    step = jax.jit(train_step.make_train_step(b.loss_fn, b.tx),
                   donate_argnums=(0,))
    return state, step, shapes


def leaf_norms_of(flat, sizes):
    """Per-leaf norms of a flat buffer laid out leaf after leaf."""
    def norms(x):
        out, off = [], 0
        for size in sizes:
            out.append(jnp.sqrt(jnp.sum(jnp.square(
                jax.lax.dynamic_slice_in_dim(x, off, size)))))
            off += size
        return jnp.stack(out)
    return jax.jit(norms)(flat)


def first_steps(cell, seed: int, state, shapes, one_step, note=None):
    """Drive ``state`` through its first ``CHECK_STEPS`` steps by
    ``one_step`` (the window's own call and feed) and keep what ``correct``
    compares: each loss, per leaf the norm of the first gradient as LAMB
    gets it (its first moment after one step over 1 - beta1) and the norm
    of the parameters' change after all the steps.  Returns ``(state,
    readings, batches)``."""
    sizes = state.opt.sizes
    beta1 = cell.config["optimizer"]["betas"][0]
    got, batches = {"losses": []}, []
    for i in range(CHECK_STEPS):
        state, loss, batch = one_step(state)
        got["losses"].append(float(loss))
        batches.append(batch)
        if note:
            note(f"step {i + 1} done")
        if i == 0:
            got["grad1"] = np.asarray(leaf_norms_of(
                state.opt.slots["exp_avg"], sizes), np.float64) / (
                    1.0 - beta1)
    start = jnp.concatenate([
        jnp.ravel(x).astype(jnp.float32)
        for x in jax.tree.leaves(weights.make(shapes, seed))])
    got["delta"] = np.asarray(leaf_norms_of(
        state.opt.master[:int(sum(sizes))] - start, sizes), np.float64)
    if note:
        note("first steps' norms read")
    return state, got, batches


def run(*, cell, devices, seed, seconds, profiler, t_process) -> dict:
    from apex_tpu.observability.timers import compile_count

    cfg, mix = cell.config, cell.mix
    feed = traffic.TrainBatches(mix, seed, cfg["token_ids"])
    H.note(t_process, "imports done, building the state and the step")
    state, step, shapes = build(cell, seed)
    H.note(t_process, "state built")
    n_params = int(sum(state.opt.sizes))

    def send(state):
        """One step made and dispatched; nothing is waited for."""
        with H.span("make_batch"):
            batch = feed.next()
        with H.span("step"):
            state, loss = step(state, batch)
        return state, loss, batch

    def one_step(state):
        """The first steps: the window's own call and feed, read at once."""
        t = time.perf_counter()
        state, loss, batch = send(state)
        with H.span("sync"):
            jax.block_until_ready((state, loss))
        warm_s.append(time.perf_counter() - t)
        return state, loss, batch

    warm_s = []
    state, got, first_batches = first_steps(
        cell, seed, state, shapes, one_step,
        lambda what: H.note(t_process, what))
    state, loss, _ = one_step(state)        # one more, warm and unrecorded
    # the depth in steps, from the quickest of the steps that set-up drove
    # after the one that compiled
    step_s = min(warm_s[1:])
    ahead = steps_ahead(mix["ahead_seconds"], step_s)
    H.note(t_process, f"a step takes {step_s * 1e3:.1f} ms: "
                      f"up to {ahead} steps dispatched ahead")

    # -- the window ---------------------------------------------------------
    trace_s = mix["trace_seconds"]
    compiles0 = compile_count()
    starts, ends = [], []
    flying = collections.deque()            # losses sent and not yet read

    def wait_until(left: int):
        """Read the oldest losses until ``left`` are in flight; each stamp
        is the host's first sight of a finished step."""
        nonlocal loss
        while len(flying) > left:
            loss = flying.popleft()
            with H.span("sync"):
                jax.block_until_ready(loss)
            ends.append(time.perf_counter())

    tracing, slowest_send = False, 0.0
    # a traced run stops sending early enough for the wait that closes its
    # untraced part, so that the traced tail still ends with the window
    t_end = seconds
    t_trace = None if profiler is None else max(
        0.0, seconds - trace_s - mix["ahead_seconds"])
    setup_s = time.perf_counter() - t_process
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter()
        if t_trace is not None and not tracing and now - t0 >= t_trace:
            # the untraced part closes as a window does: all that was sent
            # is waited for; the traced tail then runs shallow for
            # ``trace_seconds``, so that the trace holds whole steps only
            wait_until(0)
            ahead = steps_ahead(mix["trace_ahead_seconds"], step_s)
            profiler.start()
            tracing = True
            now = time.perf_counter()
            t_end = now - t0 + trace_s
        if now - t0 >= t_end:
            break
        starts.append(now)
        state, sent_loss, _ = send(state)
        slowest_send = max(slowest_send, time.perf_counter() - now)
        flying.append(sent_loss)
        wait_until(ahead)
    t_close = time.perf_counter()
    wait_until(0)                           # nothing more is sent
    jax.block_until_ready(state)
    if tracing:
        profiler.stop()
    H.note(t_process, f"window closed: {len(starts)} steps sent, the "
                      f"slowest send {slowest_send * 1e3:.1f} ms, the last "
                      f"wait {ends[-1] - t_close:.2f} s")
    compiles = compile_count() - compiles0
    last_loss = float(loss)
    peak = devices.memory_peak_bytes()

    # -- free the program's state, then the reference -----------------------
    del state, loss
    free_device(devices.platform)
    want = reference_readings(cell, shapes, seed, first_batches)
    checks = compare(got, want, cfg["correct"]["limits"])
    checks.append({"name": "last_loss_finite",
                   "value": 0.0 if np.isfinite(last_loss) else 1.0,
                   "limit": 0.0})
    traced = [i for i, s in enumerate(starts)
              if profiler is not None and profiler.started is not None
              and s >= profiler.started]
    facts = {
        "step_starts": starts, "step_ends": ends,
        "tokens_per_step": feed.tokens_per_step,
        "labels_per_row": feed.labels_per_row,
        "compiles_in_window": compiles, "n_params": n_params,
        "traced_steps": len(traced), "memory_peak_bytes": peak,
    }
    return {"facts": facts, "setup_s": setup_s, "memory_peak_bytes": peak,
            "correct": all(c["value"] <= c["limit"] for c in checks),
            "attempted": len(starts), "failed": 0, "checks": checks}


def steps_ahead(seconds: float, step_s: float) -> int:
    """How many steps make up ``seconds`` of work for the chip: at least
    one, so that the host always makes the next batch while a step runs."""
    return max(1, int(round(seconds / step_s)))


def free_device(platform: str) -> None:
    """Drop every array the process holds on the chip (the program's state
    is dead by now), so that the reference fits."""
    gc.collect()
    if platform == "tpu":
        for a in jax.live_arrays():
            a.delete()
        jax.clear_caches()


def reference_readings(cell, shapes, seed: int, batches, *, quant=None,
                       rows=None) -> dict:
    """The plain reference's losses and per-leaf norms (in the program's
    leaf order) over the first steps, from the benchmark's weights for
    ``seed``.  ``quant`` makes it the control, ``rows`` the planted
    half-batch fault."""
    cfg = cell.config
    opt = cfg["optimizer"]
    hp = {"lr": opt["lr"], "beta1": opt["betas"][0],
          "beta2": opt["betas"][1], "eps": opt["eps"],
          "weight_decay": opt["weight_decay"],
          "max_grad_norm": opt["max_grad_norm"]}
    w = reference_weights(weights.make(shapes, seed),
                          cfg["num_hidden_layers"])
    r = bert_mlm.first_steps(
        w, batches, hp, heads=cfg["num_attention_heads"],
        block_rows=cfg["correct"]["reference_block_rows"], quant=quant,
        rows=rows)
    names = leaf_names(shapes)
    return {"losses": r["losses"],
            "grad1": in_leaf_order(r["grad1"], names),
            "delta": in_leaf_order(r["delta"], names)}
