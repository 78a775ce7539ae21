"""LLaMA-family pretraining over a tp x dp mesh (beyond-parity model:
``apex_tpu.models.LlamaModel`` — RMSNorm + RoPE + GQA + SwiGLU on the
same TP layers the GPT flagship uses).

The loop shows the decoder recipe composed with the parallel stack:
  * tensor parallelism inside attention (GQA kv shards) and SwiGLU,
  * data parallelism with psum gradient reduction,
  * flat-native fused Adam (``optimizers.functional``): the fp32 flat
    master is the differentiation variable, so autodiff produces flat
    grads and the step has no pytree repacking.

Synthetic data is next-token-predictable (cyclic sequences), so the
loss falls fast and the smoke test can assert learning.  Runs anywhere
(``--platform cpu`` is ``JAX_PLATFORMS=cpu`` spelled as a flag):

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python pretrain_llama.py --tp 2 --dp 2 --platform cpu
"""
from __future__ import annotations

import argparse
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

# abspath first: with a relative __main__.__file__ (plain
# `python pretrain_llama.py`) slicing path components off the raw value
# would compute a bogus repo root
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))               # repo root on sys.path

from apex_tpu import train_step
from apex_tpu.optimizers import functional
from apex_tpu.transformer import parallel_state
from apex_tpu.transformer.testing import LlamaConfig, llama_model_provider


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="mesh LLaMA pretrain")
    p.add_argument("--tp", type=int, default=2)
    p.add_argument("--dp", type=int, default=2)
    p.add_argument("--hidden", type=int, default=64)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--kv-heads", type=int, default=2)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--seq", type=int, default=16)
    p.add_argument("--vocab", type=int, default=64)
    p.add_argument("--batch", type=int, default=4, help="per-dp-rank")
    p.add_argument("--iters", type=int, default=30)
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--xent-chunk", type=int, default=None,
                   help="token-chunk size for the fused LM-head+CE "
                        "(no [tokens, vocab/tp] logits transient). "
                        "Default reads APEX_TPU_XENT_CHUNK; 0 = unfused")
    p.add_argument("--zero", action="store_true",
                   help="ZeRO: shard the fused-Adam master/moments 1/dp "
                        "over the data axis (reduce-scatter grads, "
                        "all-gather params; numerics match the dense "
                        "run)")
    p.add_argument("--platform", type=str, default=None,
                   help="force a jax platform (e.g. cpu); same as the "
                        "JAX_PLATFORMS environment variable, which is "
                        "honoured")
    return p.parse_args(argv)


def cyclic_batch(rng, args, dp):
    """[dp, batch, seq] sequences with t[i+1] = t[i]+1 mod V."""
    starts = rng.integers(0, args.vocab, size=(dp, args.batch, 1))
    toks = (starts + np.arange(args.seq)[None, None, :]) % args.vocab
    return jnp.asarray(toks, jnp.int32)


def main(argv=None):
    args = parse_args(argv)
    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    parallel_state.destroy_model_parallel()
    if args.tp * args.dp > len(jax.devices()):
        # a short mesh would shrink the data axis under the ZeRO step's
        # /dp mean (and the TP shards) — refuse rather than train wrong
        raise SystemExit(
            f"tp={args.tp} x dp={args.dp} needs {args.tp * args.dp} "
            f"devices, have {len(jax.devices())}")
    # dp is inferred as n_devices // tp — restrict the mesh to tp*dp
    parallel_state.initialize_model_parallel(
        tensor_model_parallel_size_=args.tp,
        devices=jax.devices()[:args.tp * args.dp])
    mesh = parallel_state.get_mesh()
    cfg = LlamaConfig(
        vocab_size=args.vocab, hidden_size=args.hidden,
        num_layers=args.layers, num_attention_heads=args.heads,
        num_kv_heads=args.kv_heads, max_seq_length=args.seq,
        # None falls through to APEX_TPU_XENT_CHUNK inside the model
        fused_head_xent=args.xent_chunk)
    model = llama_model_provider(cfg)
    tx = functional.fused_adam(lr=args.lr, betas=(0.9, 0.999), eps=1e-8,
                               weight_decay=0.0)
    rng = np.random.default_rng(args.seed)
    # replicated-kv (MQA/GQA with kv_heads % tp != 0): each rank
    # backpropagates only its OWN q-heads' contribution to the shared
    # kv_proj weights — the true grad is the psum over the tensor axis
    # (same contract as ``standalone_llama.reduce_llama_grads``, applied
    # here to flat-grad slices so the step stays re-ravel-free)
    need_kv_psum = args.tp > 1 and cfg.kv_heads % args.tp != 0
    if args.zero and need_kv_psum:
        # the kv fixup indexes FULL-grad offsets; under ZeRO the grads
        # arrive pre-scattered as shards, so those offsets don't apply
        raise SystemExit(
            "--zero requires kv_heads % tp == 0 (the replicated-kv "
            "psum fixup operates on full-grad offsets, which do not "
            "exist in the reduce-scattered shard)")

    def train(stream):
        """One rank's whole run: init, then a scan over the iteration
        stream (my dp shard of it).  Per-rank state — the sharded param
        tree flattened into one functional fused-Adam FlatState — never
        crosses the shard_map boundary, so no per-leaf specs are needed.
        The fp32 flat master is the differentiation variable: autodiff
        produces flat grads, no per-step grad re-ravel exists."""
        params = model.init(jax.random.PRNGKey(args.seed + 1),
                            stream[0, 0])
        if args.zero:
            # ZeRO: the fp32 master SHARD is the differentiation
            # variable — the zero step all-gathers params into the
            # forward and autodiff's transpose reduce-scatters the flat
            # grads; per-rank optimizer state is 1/dp of the dense run
            zstep = train_step.make_train_step(
                lambda tree, tokens: model.apply(
                    tree, tokens[0], jnp.roll(tokens[0], -1, axis=1)),
                tx, zero=True)
            st0 = train_step.init_train_state(
                tx, params, shard=(parallel_state.DATA_AXIS, args.dp))
            _, losses = jax.lax.scan(zstep, st0, stream)
            return losses
        st0 = tx.init(params)
        kv_slices = [(off, size) for key, (off, size, _)
                     in train_step.leaf_offsets(params).items()
                     if "kv_proj" in key]

        def body(st, tokens):
            def flat_loss(flat):
                tree = st.unravel(flat.astype(st.flat_dtype))
                labels = jnp.roll(tokens[0], -1, axis=1)
                return model.apply(tree, tokens[0], labels)

            loss, g = jax.value_and_grad(flat_loss)(st.master)
            if need_kv_psum:
                for off, size in kv_slices:
                    leaf = jax.lax.dynamic_slice_in_dim(g, off, size)
                    leaf = jax.lax.psum(leaf, parallel_state.TENSOR_AXIS)
                    g = jax.lax.dynamic_update_slice_in_dim(
                        g, leaf, off, 0)
            g = jax.lax.pmean(g, parallel_state.DATA_AXIS)
            loss = jax.lax.pmean(loss, parallel_state.DATA_AXIS)
            return tx.update(st, g), loss

        _, losses = jax.lax.scan(body, st0, stream)
        return losses

    stream = jnp.stack([cyclic_batch(rng, args, args.dp)
                        for _ in range(args.iters)])   # [it, dp, b, s]
    losses = jax.jit(functools.partial(jax.shard_map, check_vma=False)(
        train, mesh=mesh,
        in_specs=(P(None, parallel_state.DATA_AXIS),),
        out_specs=P()))(stream)
    losses = np.asarray(losses)
    for i in range(0, args.iters, max(1, args.iters // 4)):
        print(f"iter {i:3d}  loss {losses[i]:.4f}", flush=True)
    first, last = float(losses[0]), float(losses[-1])
    print(f"loss {first:.4f} -> {last:.4f}")
    return first, last


if __name__ == "__main__":
    from apex_tpu.utils.compile_cache import \
        enable_persistent_compile_cache
    enable_persistent_compile_cache()
    main()
