"""BERT MLM pretraining loop: standalone BERT + flat-native FusedLAMB +
dynamic loss scaling (BASELINE config 2's model/optimizer pairing — the
reference's BERT-large phase-1 recipe is amp O2 + FusedLAMB; here bf16
params with fp32 LAMB masters and the jit-carried scaler play that role).

Flat-native structure (matching the gpt example's one-program shape):
the whole run is ONE jitted ``lax.scan`` over pre-staged batches, built
by :func:`apex_tpu.train_step.train_loop` — the fp32 flat LAMB master is
the differentiation variable, so autodiff produces flat grads (no
per-step grad re-ravel), and the scaler's ``found_inf`` feeds the update
kernel's ``noop_flag`` in-program (no host sync anywhere in the step).

Synthetic MLM data (recoverable signal: masked positions' labels are a
deterministic function of their neighbors) so the smoke path needs no
corpus.  Scale the config up and shard the batch over a mesh for the real
thing; the model supports TP/SP via ``parallel_state``.

Run:  python pretrain_bert.py --iters 20
"""
from __future__ import annotations

import argparse
import os
import types

import jax
import jax.numpy as jnp
import numpy as np

import sys
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))               # repo root on sys.path

from apex_tpu import train_step
from apex_tpu.optimizers import functional
from apex_tpu.transformer import parallel_state
from apex_tpu.transformer.testing import BertConfig, bert_model_provider


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="BERT MLM pretrain (apex_tpu)")
    p.add_argument("--hidden", type=int, default=64)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--seq", type=int, default=64)
    p.add_argument("--vocab", type=int, default=1024)
    p.add_argument("-b", "--batch-size", type=int, default=8)
    p.add_argument("--iters", type=int, default=40)
    p.add_argument("--lr", type=float, default=5e-3)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--dropout", type=float, default=0.0,
                   help="hidden + attention dropout (the reference BERT "
                        "recipe uses 0.1; attention dropout runs "
                        "IN-KERNEL on the softmax probabilities). The "
                        "toy default stays 0 so the smoke run converges "
                        "in tens of steps")
    p.add_argument("--loss-scale", type=str, default="dynamic")
    p.add_argument("--zero", action="store_true",
                   help="ZeRO dp-sharded optimizer state over a 'data' "
                        "mesh: the fp32 LAMB master + moments shard "
                        "1/dp per device, grads reduce-scatter, params "
                        "all-gather — same numerics as the dense run "
                        "(the dryrun 'zero' leg asserts it)")
    p.add_argument("--dp", type=int, default=None,
                   help="data-parallel width for --zero (default: all "
                        "local devices)")
    p.add_argument("--numerics", action="store_true",
                   help="drive the run host-side through "
                        "instrumented_train_loop(numerics=True): the "
                        "step gains in-program grad/param-norm + "
                        "update-ratio probes and the overflow autopsy "
                        "names any parameter leaf whose grads go "
                        "nonfinite (same ONE donated executable; "
                        "APEX_TPU_TELEMETRY=<dir> writes the JSONL + "
                        "Prometheus artifacts). Not combinable with "
                        "--zero here (the scanned zero run stays one "
                        "opaque executable)")
    p.add_argument("--platform", type=str, default=None,
                   help="force a jax platform (e.g. cpu); same as the "
                        "JAX_PLATFORMS environment variable, which is "
                        "honoured")
    args = p.parse_args(argv)
    if args.zero and args.numerics:
        p.error("--numerics drives a host-side step loop; the --zero "
                "run here is one scanned executable — run them "
                "separately")
    return args


def synthetic_mlm_batch(rng, args):
    """Masked-LM batches with a position-determined target (masked
    position ``p``'s label is ``(7*p + 13) % vocab``): solvable from the
    position embeddings alone, so the smoke run converges in tens of
    steps at toy scale, and every batch is FRESH — a falling loss means
    the model generalizes, not memorizes.  Swap in a real tokenized
    corpus (15% random masking, labels = original tokens) to pretrain for
    real; the training loop is identical."""
    tokens = rng.randint(4, args.vocab, size=(args.batch_size, args.seq))
    labels = np.full_like(tokens, -100)           # ignored positions
    n_mask = max(1, int(0.15 * args.seq))
    for i in range(args.batch_size):
        pos = rng.choice(np.arange(1, args.seq), size=n_mask,
                         replace=False)
        labels[i, pos] = (7 * pos + 13) % args.vocab
        tokens[i, pos] = 3                         # [MASK] id
    return jnp.asarray(tokens), jnp.asarray(labels)


def build(args):
    """The objects a run trains with — model, seeded params, loss and
    the flat-native FusedLAMB — for ``args``.  ``main`` below trains
    them; ``chip_smoke.py`` builds the same ones at BERT-large width."""
    parallel_state.destroy_model_parallel()
    parallel_state.initialize_model_parallel(1)
    cfg = BertConfig(
        vocab_size=args.vocab, hidden_size=args.hidden,
        num_layers=args.layers, num_attention_heads=args.heads,
        max_seq_length=args.seq, hidden_dropout=args.dropout,
        attention_dropout=args.dropout, params_dtype=jnp.bfloat16)
    model = bert_model_provider(cfg, add_binary_head=False)

    rng = np.random.RandomState(args.seed)
    tokens0, labels0 = synthetic_mlm_batch(rng, args)
    # jitted: only the initializers survive DCE, so a BERT-large init is
    # one small program instead of an eager 24-layer forward
    params = jax.jit(model.init)(jax.random.PRNGKey(args.seed), tokens0,
                                 lm_labels=labels0)

    # vocab_parallel_cross_entropy has no ignore_index: weight the loss
    # to the masked positions via loss_mask (attention stays FULL — the
    # model must see the unmasked neighbors to solve the task)
    train_mode = args.dropout > 0.0

    def masked_lm_loss(params, tokens, labels, **apply_kw):
        valid = labels >= 0
        safe = jnp.where(valid, labels, 0)
        loss, _ = model.apply(params, tokens, lm_labels=safe,
                              loss_mask=valid.astype(jnp.int32),
                              **apply_kw)
        return loss

    def loss_fn(params, batch):
        apply_kw = (dict(deterministic=False,
                         rngs={"dropout": batch["key"]})
                    if train_mode else {})
        return masked_lm_loss(params, batch["tokens"], batch["labels"],
                              **apply_kw)

    # flat-native FusedLAMB: fp32 flat master of the bf16 params (the O2
    # regime) IS the differentiation variable; loss scaling, overflow
    # detection, and the noop-predicated update all run in-program
    tx = functional.fused_lamb(lr=args.lr, weight_decay=0.01,
                               max_grad_norm=1.0)
    loss_scale = (args.loss_scale if args.loss_scale == "dynamic"
                  else float(args.loss_scale))
    return types.SimpleNamespace(
        model=model, params=params, rng=rng, train_mode=train_mode,
        masked_lm_loss=masked_lm_loss, loss_fn=loss_fn, tx=tx,
        loss_scale=loss_scale)


def main(argv=None):
    args = parse_args(argv)
    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    b = build(args)
    params, rng, train_mode = b.params, b.rng, b.train_mode
    masked_lm_loss, loss_fn = b.masked_lm_loss, b.loss_fn
    tx, loss_scale = b.tx, b.loss_scale
    dp = args.dp or len(jax.devices())
    if args.zero:
        if dp > len(jax.devices()):
            # a short mesh would psum_scatter over fewer ranks than the
            # /dp mean assumes — silently wrong gradients, so refuse
            raise SystemExit(f"--zero: --dp {dp} exceeds the "
                             f"{len(jax.devices())} available devices")
        if args.batch_size % dp:
            raise SystemExit(f"--zero: batch size {args.batch_size} "
                             f"must divide over dp={dp}")
        # GLOBAL-view sharded state built outside; shard_map slices each
        # rank's 1/dp window via the returned spec tree
        state, state_specs = train_step.init_zero_train_state(
            tx, params, "data", dp, loss_scale=loss_scale)
    else:
        state = train_step.init_train_state(tx, params,
                                            loss_scale=loss_scale)

    heldout = synthetic_mlm_batch(rng, args)   # never trained on
    # all batches staged on-device up front: the whole run is one jitted
    # lax.scan (the gpt example's structure), so there is no per-step
    # host round-trip for a prefetcher to hide.  NOTE memory is
    # O(iters): for corpus-scale runs, chunk the stream and call the
    # jitted loop once per chunk (the carried TrainState composes)
    toks, labs = zip(*[synthetic_mlm_batch(rng, args)
                       for _ in range(args.iters)])
    batches = {"tokens": jnp.stack(toks), "labels": jnp.stack(labs)}
    if train_mode:
        dropout_root = jax.random.PRNGKey(args.seed + 1)
        batches["key"] = jax.vmap(
            lambda i: jax.random.fold_in(dropout_root, i))(
                jnp.arange(args.iters))
    if args.zero:
        # ZeRO run: the scan body is the zero step (psum_scatter'd bf16
        # grads -> local fused LAMB on the master shard -> all-gather'd
        # bf16 params into the next forward), the whole run still ONE
        # donated executable; the batch shards over the mesh's data
        # axis, so this IS data-parallel training, with optimizer state
        # 1/dp per device
        import functools
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(np.array(jax.devices()[:dp]), ("data",))
        zstep = train_step.make_train_step(loss_fn, tx, zero=True)
        batch_specs = {"tokens": P(None, "data"),
                       "labels": P(None, "data")}
        if train_mode:
            batch_specs["key"] = P()
        run = jax.jit(functools.partial(jax.shard_map, check_vma=False)(
            lambda st, bs: jax.lax.scan(zstep, st, bs), mesh=mesh,
            in_specs=(state_specs, batch_specs),
            out_specs=(state_specs, P())), donate_argnums=(0,))
    elif args.numerics:
        # ISSUE 11: host-driven loop so the numerics probes have
        # somewhere to land between steps — same step math (parity
        # pinned by tests/L1/test_numerics_train_step.py), grad/param
        # norms + overflow autopsy resolved one step late
        run = train_step.instrumented_train_loop(
            loss_fn, tx, tokens_per_batch=args.batch_size * args.seq,
            numerics=True)
    else:
        run = train_step.train_loop(loss_fn, tx)
    state, losses = run(state, batches)
    losses = [float(l) for l in np.asarray(losses)]
    for it in range(0, args.iters, 5):
        print(f"iter {it:3d} loss {losses[it]:.4f}")
    # held-out eval is ALWAYS deterministic (dropout off), so the number
    # is comparable across dropout settings; one eager call on the
    # materialized params (the checkpoint/eval boundary) — a second jit
    # compile would never amortize
    final_params = state.params()
    heldout_loss = float(masked_lm_loss(final_params, heldout[0],
                                        heldout[1]))
    print(f"final loss {losses[-1]:.4f} (first {losses[0]:.4f}) "
          f"held-out {heldout_loss:.4f} "
          f"scale {float(state.scaler.loss_scale):.0f}")
    if args.numerics:                  # parse_args forbids it with --zero
        acc = run.telemetry.numerics
        fmt = lambda v: "—" if v is None else f"{v:.4g}"  # noqa: E731
        print(f"numerics: grad_norm {fmt(acc.grad_norm.value())} "
              f"param_norm {fmt(acc.param_norm.value())} "
              f"update_ratio {fmt(acc.update_ratio.value())} "
              f"backoffs {int(acc.backoffs.total())} "
              f"nonfinite_elems {int(acc.nonfinite_elems.total())}")
    return losses, heldout_loss


if __name__ == "__main__":
    from apex_tpu.utils.compile_cache import \
        enable_persistent_compile_cache
    enable_persistent_compile_cache()
    main()
