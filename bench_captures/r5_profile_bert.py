"""Capture a device trace of the BERT north-star step and print the top
ops by self time.  Run on the chip:

  python bench_captures/r5_profile_bert.py [--leg gpt]

Writes the raw xplane under bench_captures/profile/ and prints a
ranked op table (via tensorboard_plugin_profile's converter when it can
parse the trace; falls back to listing the xplane event names).
"""
import glob
import json
import os
import sys

import jax
import jax.flatten_util
import jax.numpy as jnp
import numpy as np

PROFDIR = os.path.join(os.path.dirname(__file__), "profile")


def build_bert_step():
    from apex_tpu.optimizers.fused_lamb import _lamb_step
    from apex_tpu.transformer import parallel_state
    from apex_tpu.transformer.testing import BertConfig, bert_model_provider

    parallel_state.destroy_model_parallel()
    parallel_state.initialize_model_parallel(1)
    cfg = BertConfig(max_seq_length=128, hidden_dropout=0.0,
                     attention_dropout=0.0, params_dtype=jnp.bfloat16)
    batch, seq = 32, 128
    model = bert_model_provider(cfg, add_binary_head=False)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (batch, seq), 0,
                                cfg.vocab_size)
    types = jnp.zeros((batch, seq), jnp.int32)
    labels = jax.random.randint(jax.random.PRNGKey(2), (batch, seq), 0,
                                cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(1), tokens, types,
                        lm_labels=labels)
    flat, unravel = jax.flatten_util.ravel_pytree(params)
    flat = flat.astype(jnp.float32)
    sizes = tuple(int(np.prod(l.shape)) if l.ndim else 1
                  for l in jax.tree.leaves(params))
    offsets = tuple(int(o) for o in np.cumsum((0,) + sizes[:-1]))

    @jax.jit
    def step(state):
        fp, m, v = state

        def loss_fn(fp):
            loss, _ = model.apply(unravel(fp), tokens, types,
                                  lm_labels=labels)
            return loss

        _, g = jax.value_and_grad(loss_fn)(fp)
        return _lamb_step(
            fp, m, v, g, jnp.float32(1), jnp.float32(1e-4),
            jnp.float32(0.9), jnp.float32(0.999), jnp.float32(1e-6),
            jnp.float32(0.01), jnp.float32(1.0), jnp.float32(0),
            jnp.float32(1.0), bias_correction=True, offsets=offsets,
            sizes=sizes, use_nvlamb=False)

    state = (flat, jnp.zeros_like(flat), jnp.zeros_like(flat))
    return step, state


def main():
    os.makedirs(PROFDIR, exist_ok=True)
    step, state = build_bert_step()
    # warm/compile outside the trace
    state = step(state)
    jax.block_until_ready(state)
    with jax.profiler.trace(PROFDIR):
        for _ in range(3):
            state = step(state)
        jax.block_until_ready(state)
    print("trace captured under", PROFDIR, flush=True)

    pbs = sorted(glob.glob(os.path.join(
        PROFDIR, "**", "*.xplane.pb"), recursive=True))
    if not pbs:
        print("no xplane.pb found — device tracing unsupported?")
        return
    latest = pbs[-1]
    print("xplane:", latest, flush=True)
    try:
        from tensorboard_plugin_profile.convert import raw_to_tool_data
        data, _ = raw_to_tool_data.xspace_to_tool_data(
            [latest], "framework_op_stats", params={})
        out = os.path.join(PROFDIR, "op_stats.json")
        with open(out, "w") as f:
            f.write(data if isinstance(data, str) else data.decode())
        print("op stats written to", out)
        try:
            rows = json.loads(data if isinstance(data, str)
                              else data.decode())
            print(json.dumps(rows[:2], indent=1)[:2000])
        except Exception:  # noqa: BLE001 — format varies by version
            pass
    except Exception as e:  # noqa: BLE001
        print(f"converter failed ({type(e).__name__}: {e}); raw xplane "
              f"kept for manual inspection")


if __name__ == "__main__":
    main()
