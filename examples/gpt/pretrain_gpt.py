"""GPT pretraining over a tp x pp x dp device mesh — the flagship
`apex.transformer`-style driver (reference: the Megatron driver pattern
the reference's transformer README documents: ``initialize_model_parallel``
-> ``setup_microbatch_calculator`` -> ``get_forward_backward_func`` ->
schedule + grad reductions + optimizer).

Everything the parallel stack offers in one loop:
  * tensor parallelism inside each transformer layer (TP matmul shards),
  * 1F1B pipeline parallelism over the layer stack (bounded activations),
  * data parallelism with bucketed psum gradient reduction,
  * TIED input/output embeddings across the first/last stage with the
    masked-psum embedding-group reduction,
  * one flat-native fused Adam update (``optimizers.functional``) over
    the per-rank FlatState carried through the scan.

Synthetic data is next-token-predictable (cyclic sequences), so the loss
falls fast and the smoke test can assert learning.  Runs anywhere:

  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python pretrain_gpt.py --tp 2 --pp 2
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

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))               # repo root on sys.path

from apex_tpu.ops.fused_lm_xent import (fused_lm_head_cross_entropy,
                                        xent_chunk_default)
from apex_tpu.optimizers import functional
from apex_tpu.parallel.distributed import flat_allreduce
from apex_tpu.transformer import parallel_state
from apex_tpu.transformer.pipeline_parallel import (
    embedding_grads_all_reduce,
    get_forward_backward_func,
    get_num_microbatches,
)
from apex_tpu.transformer.pipeline_parallel.utils import (
    _reconfigure_microbatch_calculator,
)
from apex_tpu.transformer.testing import GPTConfig
from apex_tpu.transformer.testing.standalone_gpt import (
    ParallelTransformerLayer,
)
from apex_tpu.utils import tree_ravel


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="mesh GPT pretrain (apex_tpu)")
    p.add_argument("--tp", type=int, default=2)
    p.add_argument("--pp", type=int, default=2)
    p.add_argument("--vpp", type=int, default=1,
                   help="virtual pipeline chunks per rank (interleaved "
                        "1F1B when > 1)")
    p.add_argument("--hidden", type=int, default=32)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--seq", type=int, default=16)
    p.add_argument("--vocab", type=int, default=64)
    p.add_argument("--micro-batch-size", type=int, default=2)
    p.add_argument("--global-batch-size", type=int, default=16)
    p.add_argument("--iters", type=int, default=30)
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--dropout", type=float, default=0.0,
                   help="hidden + attention dropout through the pipeline "
                        "(per-microbatch keys ride the batch pytree; the "
                        "attention part runs IN-KERNEL on the softmax "
                        "probabilities). Toy default 0 so the smoke run "
                        "converges fast")
    p.add_argument("--xent-chunk", type=int, default=None,
                   help="token-chunk size for the fused LM-head+CE "
                        "(the [tokens, vocab] logits never materialize; "
                        "backward re-projects per chunk). Default reads "
                        "APEX_TPU_XENT_CHUNK; 0 = unfused dense logits")
    p.add_argument("--zero", action="store_true",
                   help="ZeRO over the data axis: the flat fused-Adam "
                        "master/moments shard 1/dp per rank; the dp "
                        "grad all-reduce becomes reduce-scatter and "
                        "the per-step params materialize via "
                        "all-gather (numerics match the dense run)")
    p.add_argument("--platform", type=str, default=None,
                   help="force a jax platform (e.g. cpu); same as the "
                        "JAX_PLATFORMS environment variable, which is "
                        "honoured")
    return p.parse_args(argv)


def cyclic_batch(rng, args, n_micro, dp):
    """[n_micro, dp*micro_bs, seq] sequences with t[i+1] = t[i]+1 mod V —
    next-token prediction a 1-layer-per-stage model learns in a few
    dozen steps."""
    starts = rng.randint(0, args.vocab,
                         size=(n_micro, dp * args.micro_batch_size, 1))
    ramp = np.arange(args.seq)[None, None, :]
    tokens = (starts + ramp) % args.vocab
    labels = (tokens + 1) % args.vocab
    return jnp.asarray(tokens), jnp.asarray(labels)


def main(argv=None):
    args = parse_args(argv)
    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    n_dev = len(jax.devices())
    dp = n_dev // (args.tp * args.pp)
    assert dp >= 1, f"need tp*pp <= {n_dev} devices"
    if args.vpp > 1 and args.pp <= 1:
        raise SystemExit(
            "--vpp > 1 requires --pp > 1 (virtual chunks interleave "
            "across pipeline ranks; with one rank there is nothing to "
            "interleave)")

    parallel_state.destroy_model_parallel()
    parallel_state.initialize_model_parallel(
        tensor_model_parallel_size_=args.tp,
        pipeline_model_parallel_size_=args.pp)
    mesh = parallel_state.get_mesh()
    # _reconfigure_* (vs setup_*) so repeated runs in one process work —
    # same helper the reference's tests use
    _reconfigure_microbatch_calculator(
        rank=0, rampup_batch_size=None,
        global_batch_size=args.global_batch_size,
        micro_batch_size=args.micro_batch_size,
        data_parallel_size=dp)
    n_micro = get_num_microbatches()
    fwd_bwd = get_forward_backward_func(
        virtual_pipeline_model_parallel_size=args.vpp,
        pipeline_model_parallel_size=args.pp)
    print(f"mesh: tp={args.tp} pp={args.pp} dp={dp} vpp={args.vpp} "
          f"micro-batches/step={n_micro} executor={fwd_bwd.__name__}")

    train_mode = args.dropout > 0.0
    cfg = GPTConfig(
        vocab_size=args.vocab, hidden_size=args.hidden,
        num_layers=args.pp * args.vpp,
        num_attention_heads=args.heads, max_seq_length=args.seq,
        hidden_dropout=args.dropout, attention_dropout=args.dropout)
    layer = ParallelTransformerLayer(cfg, causal=True)
    tx = functional.fused_adam(lr=args.lr, betas=(0.9, 0.999), eps=1e-8,
                               weight_decay=0.0)

    def stage_fn(params, x, mb):
        # injection at VIRTUAL stage 0 only: rank 0 AND chunk 0 (the
        # chunk identity is a param leaf precisely so the interleaved
        # executor's per-chunk param slicing selects it)
        stage = jax.lax.axis_index("pipe") if args.pp > 1 else 0
        emb = jnp.take(params["embed"], mb["tokens"], axis=0)  # [b,s,h]
        emb = emb.transpose(1, 0, 2)                           # [s,b,h]
        inject = (stage == 0) & (params["chunk_id"] < 0.5)
        x = jnp.where(inject, emb, x)
        if not train_mode:
            return layer.apply(params["layer"], x, None, True)
        # dropout under pipelining (schedules.py contract): the
        # per-microbatch key rides the batch, the (stage, chunk) fold
        # decorrelates virtual stages, and the layer itself folds the
        # TP rank for its in-kernel attention dropout
        key = jax.random.fold_in(
            jax.random.fold_in(mb["key"], stage),
            params["chunk_id"].astype(jnp.int32))
        return layer.apply(params["layer"], x, None, False,
                           rngs={"dropout": key})

    xent_chunk = (args.xent_chunk if args.xent_chunk is not None
                  else xent_chunk_default())

    def loss_fn(y, mb, params):
        # TIED head: logits through the same embedding table (3-arg loss
        # contract so the head weight gets gradients)
        if xent_chunk and xent_chunk > 0:
            # fused chunked head+CE: the [s*b, vocab] logits never
            # materialize (forward scans token chunks; backward
            # re-projects each chunk and accumulates d_embed in the
            # scan carry)
            return fused_lm_head_cross_entropy(
                y, params["embed"], mb["labels"].T,
                token_chunk=xent_chunk).mean()
        logits = jnp.einsum("sbh,vh->sbv", y, params["embed"])
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -jnp.mean(jnp.take_along_axis(
            logp, mb["labels"].T[..., None], axis=-1))

    def input_fn(mb):
        return jnp.zeros((args.seq, args.micro_batch_size, args.hidden))

    def body(all_batches):
        """Whole training run inside ONE shard_map: per-rank TP-sharded
        layer init (axis_index-folded keys), then lax.scan over steps —
        the sharded optimizer state never crosses the jit boundary."""
        x0 = jnp.zeros((args.seq, args.micro_batch_size, args.hidden),
                       dtype=jnp.float32)
        pipe_rank = jax.lax.axis_index("pipe") if args.pp > 1 else 0
        embed0 = jax.random.normal(            # replicated tied embedding
            jax.random.PRNGKey(args.seed + 1),
            (args.vocab, args.hidden)) * 0.02

        def chunk_params(chunk):
            key = jax.random.fold_in(jax.random.fold_in(
                jax.random.PRNGKey(args.seed), pipe_rank), chunk)
            return {
                "embed": embed0,
                "layer": layer.init(key, x0, None, True),
                "chunk_id": jnp.float32(chunk),
            }

        if args.vpp > 1:
            # leading [v] chunk dim; chunk c on rank r = virtual stage
            # c*pp + r (the interleaved executor's layout)
            params = jax.tree.map(lambda *xs: jnp.stack(xs),
                                  *[chunk_params(c)
                                    for c in range(args.vpp)])
        else:
            params = chunk_params(0)
        # flat-native functional Adam: ONE ravel at init; the scan
        # carries the FlatState, params rematerialize per step as
        # unravel slices that fuse into the forward.  Under --zero the
        # state is the local 1/dp shard and st.params() all-gathers.
        opt0 = tx.init(params,
                       shard=("data", dp) if args.zero else None)

        def one_step(carry, xs):
            st = carry
            step, batch = xs
            params = st.params()
            loss, grads = fwd_bwd(
                stage_fn, loss_fn, params, batch,
                num_microbatches=n_micro, input_fn=input_fn,
                virtual_pipeline_model_parallel_size=args.vpp)
            # tied-embedding reconciliation (first+last stage group
            # psum); with vpp the chunk contributions (lookup in chunk 0,
            # head in chunk v-1) sum first, and every replica receives
            # the reconciled total so they update in lockstep
            g_embed = grads["embed"]
            if args.vpp > 1:
                total = embedding_grads_all_reduce(g_embed.sum(axis=0))
                g_embed = jnp.broadcast_to(total, g_embed.shape)
            else:
                g_embed = embedding_grads_all_reduce(g_embed)
            grads["embed"] = g_embed
            if args.zero:
                # ZeRO-2: the dp all-reduce becomes ONE reduce-scatter
                # into my master shard's window (+ the dp mean)
                flat_g, _ = tree_ravel(grads)
                return tx.update(
                    st, functional.shard_flat_grads(flat_g, st)), loss
            if dp > 1:
                grads = flat_allreduce(grads, axis_name="data")
                grads = jax.tree.map(lambda g: g / dp, grads)
            # the pipeline executor produces grads per-leaf, so ONE
            # ravel per step remains here; the params side needs none
            flat_g, _ = tree_ravel(grads)
            return tx.update(st, flat_g), loss

        steps = jnp.arange(args.iters)
        _, losses = jax.lax.scan(
            one_step, opt0, (steps, all_batches))
        # fwd_bwd psums the loss over 'pipe' only; average the dp shards
        # so the reported metric is the GLOBAL-batch loss (and the P()
        # out-spec's replication claim actually holds)
        return jax.lax.pmean(losses, "data")

    batch_specs = {"tokens": P(None, None, "data"),
                   "labels": P(None, None, "data")}
    if train_mode:
        batch_specs["key"] = P()         # keys are replicated, not sharded
    run = jax.jit(functools.partial(jax.shard_map, check_vma=False)(
        body, mesh=mesh,
        in_specs=(batch_specs,),
        out_specs=P()))

    rng = np.random.RandomState(args.seed)
    toks, labs = zip(*[cyclic_batch(rng, args, n_micro, dp)
                       for _ in range(args.iters)])
    all_batches = {"tokens": jnp.stack(toks), "labels": jnp.stack(labs)}
    if train_mode:
        # one key per (step, microbatch), sliced by the executors like
        # any other batch leaf
        all_batches["key"] = jax.vmap(jax.vmap(jax.random.PRNGKey))(
            (args.seed + jnp.arange(args.iters * n_micro,
                                    dtype=jnp.uint32))
            .reshape(args.iters, n_micro))
    losses = [float(l) for l in np.asarray(run(all_batches))]
    for it in range(0, args.iters, 5):
        print(f"iter {it:3d} loss {losses[it]:.4f}")
    print(f"final loss {losses[-1]:.4f} (first {losses[0]:.4f})")
    return losses


if __name__ == "__main__":
    from apex_tpu.utils.compile_cache import \
        enable_persistent_compile_cache
    enable_persistent_compile_cache()
    main()
