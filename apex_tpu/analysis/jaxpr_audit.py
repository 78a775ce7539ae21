"""Jaxpr precision/transfer auditor for the public fused ops.

Each op in :data:`OPS` is traced (``jax.make_jaxpr`` — abstract, zero
FLOPs, runs in milliseconds on CPU) under the declared precision policy
(bf16 activations; optimizer math on fp32 master params; losses
reduce in fp32) and the whole jaxpr — including pallas kernel bodies,
``custom_vjp`` branches and nested ``pjit``/``cond`` jaxprs — is walked
to assert three invariants:

* **APX201 — upcast discipline.** Every ``convert_element_type``
  bf16→fp32 must either feed an accumulating primitive (reductions,
  ``dot_general``) or be one of the op's *declared* entry upcasts
  (``upcast_budget`` — e.g. LayerNorm applies γ/β in fp32 by design).
  A NEW unexplained upcast — someone dropping an fp32 constant into a
  bf16 kernel — fails the audit.
* **APX202 — transfer/callback discipline.** No host callbacks,
  ``device_put`` or infeed/outfeed anywhere in a kernel body.
* **APX203 — output dtype policy.** Outputs match the declared dtypes
  (bf16 in → bf16 out for kernels; losses and optimizer states fp32).

Trace failures surface as APX200 so a refactor that breaks an op's
public signature cannot silently drop it from the audit.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional, Sequence

from apex_tpu.analysis.finding import Finding

__all__ = ["OpSpec", "OPS", "run_jaxpr_audit", "POLICY"]

POLICY = ("bf16 activations / fp32 accumulators and losses / "
          "fp32 optimizer master state")

# Primitives whose consumption of an fp32 value justifies the upcast:
# the whole point of fp32 inside a bf16 kernel is accumulation.
ACCUM_PRIMS = {
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
    "dot_general", "add_any", "cumsum", "cumprod", "cumlogsumexp",
    "logsumexp",
}

# Host-transfer / callback primitives that must never appear in a fused
# op's body (they serialise the TPU pipeline or break AOT compilation).
FORBIDDEN_PRIMS = {
    "pure_callback", "io_callback", "callback", "debug_callback",
    "debug_print",          # what jax.debug.print binds since jax 0.5
    "outside_call", "device_put", "infeed", "outfeed",
    "copy_to_host_async",
}


@dataclass
class OpSpec:
    """One audited op: how to trace it + its declared invariants."""
    name: str
    path: str                           # module the finding anchors to
    build: Callable[[], tuple]          # () -> (fn, args tuple)
    out_dtypes: Optional[tuple] = None  # expected output dtypes, None = skip
    # bf16->fp32 converts allowed beyond accumulator feeds (declared
    # entry upcasts, e.g. applying affine params in fp32)
    upcast_budget: Optional[int] = 0    # None = skip the upcast check


def _builders():
    """Specs are built lazily so importing this module stays jax-free
    until an audit actually runs."""
    import jax
    import jax.numpy as jnp

    bf16 = jnp.bfloat16
    f32 = jnp.float32

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    def layer_norm():
        from apex_tpu.ops import layer_norm as op
        return (lambda x, w, b: op(x, w, b),
                (s((8, 256), bf16), s((256,), bf16), s((256,), bf16)))

    def rms_norm():
        from apex_tpu.ops import rms_norm as op
        return (lambda x, w: op(x, w), (s((8, 256), bf16), s((256,), bf16)))

    def flash_attention():
        from apex_tpu.ops import flash_attention as op
        qkv = s((1, 2, 128, 64), bf16)
        return (lambda q, k, v: op(q, k, v, causal=True), (qkv, qkv, qkv))

    def ring_attention():
        from apex_tpu.ops import ring_attention as op
        qkv = s((1, 2, 128, 64), bf16)
        # axis_name=None exercises the single-shard entry path without a
        # mesh; the collective path shares the same kernels
        return (lambda q, k, v: op(q, k, v, causal=True, axis_name=None),
                (qkv, qkv, qkv))

    def ulysses_attention():
        from apex_tpu.ops import ulysses_attention as op
        qkv = s((1, 2, 128, 64), bf16)
        # axis_name=None exercises the single-shard entry path without a
        # mesh (same contract as the ring entry); the cp>1 all_to_all
        # path is audited with a bound mesh by the SPMD auditor's
        # ulysses_attention_cp executable
        return (lambda q, k, v: op(q, k, v, causal=True, axis_name=None),
                (qkv, qkv, qkv))

    def xentropy():
        from apex_tpu.ops import softmax_cross_entropy_loss as op
        return (lambda l, y: op(l, y),
                (s((8, 128), bf16), s((8,), jnp.int32)))

    def fused_lm_xent():
        from apex_tpu.ops import fused_lm_head_cross_entropy as op
        # traced fused (chunked) so the scan + custom_vjp bodies are
        # walked; the chunk=0 lowering is the already-audited xentropy
        # op plus a matmul
        return (lambda h, w, y: op(h, w, y, token_chunk=32,
                                   vocab_chunk=0),
                (s((96, 64), bf16), s((512, 64), bf16),
                 s((96,), jnp.int32)))

    def fused_adam():
        from apex_tpu.ops import fused_adam_flat as op
        p = s((256,), f32)
        return (lambda p_, g, m, v: op(
            p_, g, m, v, lr=1e-3, beta1=0.9, beta2=0.999, eps=1e-8,
            weight_decay=0.0, step=1), (p, p, p, p))

    def moe_layer():
        import flax  # noqa: F401 — optional dep; ImportError skips the op
        from apex_tpu.transformer.moe.layer import MoELayer
        layer = MoELayer(num_experts=4, hidden_size=64,
                         ffn_hidden_size=128, top_k=2)
        key = jax.random.PRNGKey(0)
        x = s((16, 64), bf16)
        variables = jax.eval_shape(layer.init, key, x)
        return (lambda v, x_: layer.apply(v, x_), (variables, x))

    def decode_attention():
        from apex_tpu.ops import decode_attention as op
        q = s((2, 4, 1, 64), bf16)
        kv = s((2, 2, 128, 64), bf16)
        return (lambda q_, k_, v_, n: op(q_, k_, v_, n),
                (q, kv, kv, s((2,), jnp.int32)))

    def _engine_audit_pieces():
        """Shared tiny-GPT engine fixture for the inference entries:
        abstract params (eval_shape — no FLOPs) + an abstract cache."""
        import flax  # noqa: F401 — optional dep; ImportError skips
        from apex_tpu.inference import kv_cache
        from apex_tpu.inference.sampling import SamplingConfig
        from apex_tpu.transformer import parallel_state
        from apex_tpu.transformer.testing import (GPTConfig,
                                                  gpt_model_provider)
        # the TP layers' tp=1 identity fast path reads parallel_state;
        # tracing outside a test harness needs it initialized (same
        # single-rank init every consumer of these models performs)
        if not parallel_state.model_parallel_is_initialized():
            parallel_state.initialize_model_parallel(1)
        cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                        num_attention_heads=4, max_seq_length=64,
                        hidden_dropout=0.0, attention_dropout=0.0,
                        params_dtype=bf16)
        model = gpt_model_provider(cfg)
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                                s((1, 8), jnp.int32))
        cache = jax.eval_shape(
            lambda: kv_cache.init_cache(2, cfg.num_layers,
                                        cfg.num_attention_heads, 64,
                                        64 // cfg.num_attention_heads))
        key = s((2,), jnp.uint32)
        return cfg, SamplingConfig(), params, cache, key

    def inference_prefill():
        from apex_tpu.inference.engine import make_prefill_fn
        cfg, sampling, params, cache, key = _engine_audit_pieces()
        fn = make_prefill_fn("gpt", cfg, sampling)
        return (fn, (cache, params, s((16,), jnp.int32),
                     s((), jnp.int32), s((), jnp.int32), key,
                     s((), jnp.int32)))

    def inference_decode():
        from apex_tpu.inference.engine import make_decode_fn
        cfg, sampling, params, cache, key = _engine_audit_pieces()
        fn = make_decode_fn("gpt", cfg, sampling)
        return (fn, (cache, params, s((2,), jnp.int32), s((2,), bool),
                     key, s((), jnp.int32)))

    def _paged_engine_audit_pieces():
        """Straggler-shaped paged fixture (ISSUE 6): slots x max_seq
        would be 4 x 256 = 1024 cached tokens dense, but the pool holds
        only 20 pages x 16 = 320 (mean_seq << max_seq sizing) — the
        geometry the APX215 peak-live comparison test measures the
        paged win on: decode reads the pool through the page table
        with NO materialized gather window."""
        import flax  # noqa: F401 — optional dep; ImportError skips
        from apex_tpu.inference import kv_cache
        from apex_tpu.inference.sampling import SamplingConfig
        from apex_tpu.transformer import parallel_state
        from apex_tpu.transformer.testing import (GPTConfig,
                                                  gpt_model_provider)
        if not parallel_state.model_parallel_is_initialized():
            parallel_state.initialize_model_parallel(1)
        cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                        num_attention_heads=4, max_seq_length=256,
                        hidden_dropout=0.0, attention_dropout=0.0,
                        params_dtype=bf16)
        model = gpt_model_provider(cfg)
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                                s((1, 8), jnp.int32))
        cache = jax.eval_shape(
            lambda: kv_cache.init_paged_cache(
                20, cfg.num_layers, cfg.num_attention_heads, 16,
                64 // cfg.num_attention_heads, slots=4,
                max_pages_per_slot=16))
        key = s((2,), jnp.uint32)
        return cfg, SamplingConfig(), params, cache, key

    def inference_prefill_paged():
        # operand order: cache, params, tokens, slot, length, row,
        # prefill_from (ISSUE 12: the suffix-prefill position — 0 for
        # a cold prefill; the cond'd prefix-window path is part of the
        # ONE audited executable), key, step
        from apex_tpu.inference.engine import make_prefill_fn
        cfg, sampling, params, cache, key = _paged_engine_audit_pieces()
        fn = make_prefill_fn("gpt", cfg, sampling, paged=True)
        return (fn, (cache, params, s((64,), jnp.int32),
                     s((), jnp.int32), s((), jnp.int32),
                     s((16,), jnp.int32), s((), jnp.int32), key,
                     s((), jnp.int32)))

    def inference_decode_paged():
        from apex_tpu.inference.engine import make_decode_fn
        cfg, sampling, params, cache, key = _paged_engine_audit_pieces()
        fn = make_decode_fn("gpt", cfg, sampling)
        return (fn, (cache, params, s((4,), jnp.int32), s((4,), bool),
                     key, s((), jnp.int32)))

    def inference_decode_latent():
        # ISSUE 34: the decode step of a kind with LATENT attention — one
        # pool with no KV-head axis and no value array, the absorbed
        # query through apex_paged_decode_latent, the held experts' loop
        from apex_tpu.inference import kv_cache
        from apex_tpu.inference.engine import make_decode_fn
        from apex_tpu.inference.sampling import SamplingConfig
        from apex_tpu.transformer.testing import standalone_axk1 as SA
        cfg = SA.AXK1Config(params_dtype=bf16, held=(4, 8))
        params = {"params": jax.tree.map(
            lambda shape: s(shape, bf16), SA.axk1_param_shapes(cfg),
            is_leaf=lambda x: isinstance(x, tuple))}
        cache = jax.eval_shape(
            lambda: kv_cache.init_paged_cache(
                20, cfg.num_layers, 0, 16, 0, slots=4,
                max_pages_per_slot=16, latent=cfg.latent_dim))
        fn = make_decode_fn("axk1", cfg, SamplingConfig())
        return (fn, (cache, params, s((4,), jnp.int32), s((4,), bool),
                     s((2,), jnp.uint32), s((), jnp.int32)))

    def inference_decode_select():
        # ISSUE 36: the decode step of a kind whose layers SELECT — an
        # index-key pool beside k and v, index scores along the work list
        # (apex_dsa_index), the picked set, attention over the picked
        # rows (apex_dsa_attend), an expert FFN in every layer
        from apex_tpu.inference import kv_cache
        from apex_tpu.inference.engine import make_decode_fn
        from apex_tpu.inference.sampling import SamplingConfig
        from apex_tpu.transformer.testing import standalone_keye as SK
        cfg = SK.KeyeConfig(params_dtype=bf16)
        params = {"params": jax.tree.map(
            lambda shape: s(shape, bf16), SK.keye_param_shapes(cfg),
            is_leaf=lambda x: isinstance(x, tuple))}
        cache = jax.eval_shape(
            lambda: kv_cache.init_paged_cache(
                20, cfg.num_layers, cfg.num_kv_heads, 16, cfg.head_dim,
                slots=4, max_pages_per_slot=16,
                index=cfg.index_head_dim))
        fn = make_decode_fn("keye", cfg, SamplingConfig())
        return (fn, (cache, params, s((4,), jnp.int32), s((4,), bool),
                     s((2,), jnp.uint32), s((), jnp.int32)))

    def fused_block_decode_op():
        # the ISSUE 15 fused transformer-block decode kernel at an
        # op-level GPT-shaped fixture (LN + qkv + paged attention incl.
        # the current token + out proj + MLP in ONE pallas_call): the
        # kernel body's precision discipline is audited directly, the
        # whole-executable twin below covers the engine lowering
        from apex_tpu.ops.paged_attention import fused_block_decode as op
        hidden, heads, d, ps, mpps, slots = 64, 4, 16, 16, 4, 2
        hd = heads * d
        blk = {
            "ln1_w": s((1, hidden), bf16), "ln1_b": s((1, hidden), bf16),
            "wq": s((hidden, hd), bf16), "bq": s((1, hd), bf16),
            "wk": s((hidden, hd), bf16), "bk": s((1, hd), bf16),
            "wv": s((hidden, hd), bf16), "bv": s((1, hd), bf16),
            "wo": s((hd, hidden), bf16), "bo": s((1, hidden), bf16),
            "ln2_w": s((1, hidden), bf16), "ln2_b": s((1, hidden), bf16),
            "wu": s((hidden, 4 * hidden), bf16),
            "bu": s((1, 4 * hidden), bf16),
            "wd": s((4 * hidden, hidden), bf16),
            "bd": s((1, hidden), bf16),
        }
        pages = s((9, heads, ps, d), bf16)
        return (lambda x, b, kp, vp, pt, ln: op(
                    x, b, kp, vp, pt, ln, kind="gpt", eps=1e-5),
                (s((slots, hidden), bf16), blk, pages, pages,
                 s((slots, mpps), jnp.int32), s((slots,), jnp.int32)))

    def inference_decode_fused_paged():
        # the fused-block decode EXECUTABLE (APEX_TPU_DECODE_FUSION=1
        # lowering of the one donated decode step): same signature and
        # output pins as the per-op twin, params operand = (tree,
        # fused layout)
        from apex_tpu.inference import models
        from apex_tpu.inference.engine import make_decode_fn
        cfg, sampling, params, cache, key = _paged_engine_audit_pieces()
        fused = jax.eval_shape(
            lambda p: models.fused_layer_params("gpt", cfg, p), params)
        fn = make_decode_fn("gpt", cfg, sampling, fused=True)
        return (fn, (cache, (params, fused), s((4,), jnp.int32),
                     s((4,), bool), key, s((), jnp.int32)))

    def inference_verify_paged():
        # the speculative verify step (ISSUE 15): k drafts + bonus
        # scored in one batched executable, lengths advanced by the
        # accepted count in-program (the rollback)
        from apex_tpu.inference.engine import make_verify_fn
        cfg, sampling, params, cache, key = _paged_engine_audit_pieces()
        fn = make_verify_fn("gpt", cfg, sampling, k=4)
        return (fn, (cache, params, s((4, 5), jnp.int32),
                     s((4,), bool), key, s((), jnp.int32)))

    def inference_cow_page():
        # the ISSUE 12 copy-on-write barrier: one page duplicated
        # inside the donated pool — audited for precision/transfer
        # discipline like every serving program (it moves exactly one
        # page and adds no collectives, so it carries no budget entry)
        from apex_tpu.inference import kv_cache as kvc
        _, _, _, cache, _ = _paged_engine_audit_pieces()
        return (kvc.cow_page, (cache, s((), jnp.int32),
                               s((), jnp.int32)))

    def inference_evict_slot():
        # retirement's device half (ISSUE 35): the slot's length,
        # capacity and page-table row reset inside the donated cache —
        # like the COW copy it moves no pool data, adds no collective
        # and carries no budget entry
        from apex_tpu.inference import kv_cache as kvc
        _, _, _, cache, _ = _paged_engine_audit_pieces()
        return (kvc.evict, (cache, s((), jnp.int32)))

    def inference_swap_out_paged():
        # the ISSUE 18 host-tier offload gather: one fixed-width batch
        # of page slabs read out of the pool (D2H happens at the
        # dispatch boundary via device_get — the program itself must
        # stay free of host callbacks/transfers, which is exactly what
        # this audit pins)
        from apex_tpu.inference import kv_cache as kvc
        _, _, _, cache, _ = _paged_engine_audit_pieces()
        return (kvc.extract_pages, (cache, s((8,), jnp.int32)))

    def inference_swap_in_paged():
        # the ISSUE 18 swap-back scatter: one fixed-width batch of host
        # slabs written into the (donated) pool at their new page ids;
        # padding lanes carry an out-of-bounds id and drop
        from apex_tpu.inference import kv_cache as kvc
        _, _, _, cache, _ = _paged_engine_audit_pieces()
        slab = s((8, 2, 4, 16, 16), bf16)
        return (kvc.restore_pages, (cache, s((8,), jnp.int32),
                                    slab, slab))

    return {
        # budgets are the measured entry upcasts (γ/β applied in fp32 by
        # design — see the kernel docstrings); any increase fails
        "layer_norm": (layer_norm, "apex_tpu/ops/layer_norm.py",
                       ("bfloat16",), 2),
        "rms_norm": (rms_norm, "apex_tpu/ops/layer_norm.py",
                     ("bfloat16",), 3),
        "flash_attention": (flash_attention, "apex_tpu/ops/attention.py",
                            ("bfloat16",), 0),
        "ring_attention": (ring_attention, "apex_tpu/ops/ring_attention.py",
                           ("bfloat16",), 0),
        "ulysses_attention": (ulysses_attention,
                              "apex_tpu/ops/ulysses_attention.py",
                              ("bfloat16",), 0),
        "xentropy": (xentropy, "apex_tpu/ops/xentropy.py",
                     ("float32",), 0),
        "fused_lm_xent": (fused_lm_xent, "apex_tpu/ops/fused_lm_xent.py",
                          ("float32",), 0),
        "fused_adam": (fused_adam, "apex_tpu/ops/fused_update.py",
                       ("float32", "float32", "float32"), 0),
        # flax module: dtype promotion is the router's business — audit
        # transfer discipline only
        "moe_layer": (moe_layer, "apex_tpu/transformer/moe/layer.py",
                      None, None),
        # the inference subsystem's device programs (ISSUE 4/6): the
        # decode core holds the full bf16 policy; the whole prefill/
        # decode executables pin output dtypes (cache bf16 / page
        # table + lengths + capacity + sampled tokens int32 / logits
        # fp32 / truncated flags bool) and transfer discipline — a host
        # callback sneaking into the serving hot loop fails the audit.
        # Per-layer LN entry upcasts make a whole-model upcast budget
        # churn with depth, so the engine entries skip that one check
        # (decode_attention carries it).
        "decode_attention": (decode_attention,
                             "apex_tpu/ops/attention.py",
                             ("bfloat16",), 0),
        "inference_prefill": (inference_prefill,
                              "apex_tpu/inference/engine.py",
                              ("bfloat16", "bfloat16", "int32", "int32",
                               "int32", "float32"), None),
        "inference_decode": (inference_decode,
                             "apex_tpu/inference/engine.py",
                             ("bfloat16", "bfloat16", "int32", "int32",
                              "int32", "float32", "bool"), None),
        "inference_prefill_paged": (inference_prefill_paged,
                                    "apex_tpu/inference/engine.py",
                                    ("bfloat16", "bfloat16", "int32",
                                     "int32", "int32", "int32", "int32",
                                     "float32"), None),
        "inference_decode_paged": (inference_decode_paged,
                                   "apex_tpu/inference/engine.py",
                                   ("bfloat16", "bfloat16", "int32",
                                    "int32", "int32", "int32", "int32",
                                    "float32", "bool"), None),
        # ISSUE 34: the cache is ONE bf16 pool (then table, lengths,
        # capacity, last_tokens), the tokens carry the four counters
        "inference_decode_latent": (inference_decode_latent,
                                    "apex_tpu/inference/engine.py",
                                    ("bfloat16", "int32", "int32",
                                     "int32", "int32", "int32", "float32",
                                     "bool"),
                                    None),
        # ISSUE 36: k, v, table, lengths, capacity, last_tokens and the
        # index-key pool, then the tokens with the seven counters
        "inference_decode_select": (inference_decode_select,
                                    "apex_tpu/inference/engine.py",
                                    ("bfloat16", "bfloat16", "int32",
                                     "int32", "int32", "int32", "bfloat16",
                                     "int32", "float32", "bool"), None),
        # ISSUE 15: the fused-block kernel (op-level; measured entry
        # upcasts = 11: the norm gains/biases and the projection/MLP
        # biases applied in fp32 by design — layer_norm's budget-2
        # pattern across the whole block — plus the fp32 residual
        # carry of x) + the two new serving executables.  The fused decode pins the SAME outputs as the
        # unfused paged decode (one signature, two lowerings behind
        # APEX_TPU_DECODE_FUSION); the verify step swaps logits for
        # the emitted token slab + accepted counts.
        "fused_block_decode": (fused_block_decode_op,
                               "apex_tpu/ops/paged_attention.py",
                               ("bfloat16", "bfloat16", "bfloat16"),
                               11),
        "inference_decode_fused_paged": (inference_decode_fused_paged,
                                         "apex_tpu/inference/engine.py",
                                         ("bfloat16", "bfloat16",
                                          "int32", "int32", "int32",
                                          "int32", "int32", "float32",
                                          "bool"),
                                         None),
        "inference_verify_paged": (inference_verify_paged,
                                   "apex_tpu/inference/engine.py",
                                   ("bfloat16", "bfloat16", "int32",
                                    "int32", "int32", "int32", "int32",
                                    "int32", "bool"), None),
        "inference_cow_page": (inference_cow_page,
                               "apex_tpu/inference/kv_cache.py",
                               ("bfloat16", "bfloat16", "int32",
                                "int32", "int32", "int32"), 0),
        "inference_evict_slot": (inference_evict_slot,
                                 "apex_tpu/inference/kv_cache.py",
                                 ("bfloat16", "bfloat16", "int32",
                                  "int32", "int32", "int32"), 0),
        # ISSUE 18: the two host-tier copy programs — pure gathers/
        # scatters over the pool (no collectives, no host callbacks,
        # no entry upcasts); the swap-in returns the whole cache (cow's
        # output pins), the swap-out returns the two page slabs
        "inference_swap_out_paged": (inference_swap_out_paged,
                                     "apex_tpu/inference/kv_cache.py",
                                     ("bfloat16", "bfloat16"), 0),
        "inference_swap_in_paged": (inference_swap_in_paged,
                                    "apex_tpu/inference/kv_cache.py",
                                    ("bfloat16", "bfloat16", "int32",
                                     "int32", "int32", "int32"), 0),
    }


def op_specs() -> list:
    return [OpSpec(name, path, build, out, budget)
            for name, (build, path, out, budget) in _builders().items()]


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------

def _subjaxprs(params: dict):
    import jax.extend.core as jex_core
    for v in params.values():
        items = v if isinstance(v, (list, tuple)) else [v]
        for item in items:
            if isinstance(item, jex_core.ClosedJaxpr):
                yield item.jaxpr
            elif isinstance(item, jex_core.Jaxpr):
                yield item


def _iter_jaxprs(jaxpr):
    yield jaxpr
    for eqn in jaxpr.eqns:
        for sub in _subjaxprs(eqn.params):
            yield from _iter_jaxprs(sub)


def _audit_jaxpr(closed) -> tuple:
    """-> (unexplained_upcast_count, forbidden_prim_names)"""
    import jax
    import jax.extend.core as jex_core
    import jax.numpy as jnp
    unexplained = 0
    forbidden: list = []
    for jaxpr in _iter_jaxprs(closed.jaxpr):
        consumers: dict = {}
        for eqn in jaxpr.eqns:
            for var in eqn.invars:
                if not isinstance(var, jex_core.Literal):
                    consumers.setdefault(var, []).append(eqn.primitive.name)
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name in FORBIDDEN_PRIMS:
                forbidden.append(name)
            if name == "convert_element_type" and \
                    eqn.params.get("new_dtype") == jnp.float32 and \
                    getattr(eqn.invars[0], "aval", None) is not None and \
                    eqn.invars[0].aval.dtype == jnp.bfloat16:
                outs = consumers.get(eqn.outvars[0], [])
                # escaping the subjaxpr (no local consumer) means the
                # fp32 value is an output/residual — a declared boundary
                if outs and not any(c in ACCUM_PRIMS for c in outs):
                    unexplained += 1
    return unexplained, forbidden


def audit_op(spec: OpSpec) -> list:
    """Audit one op; returns findings (empty = all invariants hold)."""
    import jax

    findings: list = []

    def finding(rule, msg):
        # line_text feeds the baseline fingerprint — keep it to the
        # stable (op, rule) identity; msg carries the volatile details
        # (exception strings, counts) that must not churn the ratchet
        return Finding(rule, spec.path, 0, 0, msg,
                       line_text=f"{spec.name}:{rule}")

    try:
        fn, args = spec.build()
    except ImportError:
        return []  # optional dependency absent — op not in this build
    try:
        closed = jax.make_jaxpr(fn)(*args)
    except Exception as e:  # noqa: BLE001 — any trace failure is a finding
        return [finding("APX200",
                        f"tracing {spec.name} under the precision policy "
                        f"failed: {type(e).__name__}: {e}")]

    unexplained, forbidden = _audit_jaxpr(closed)
    if forbidden:
        findings.append(finding(
            "APX202",
            f"{spec.name} jaxpr contains host-transfer/callback "
            f"primitive(s) {sorted(set(forbidden))} — fused op bodies "
            f"must stay on-device"))
    if spec.upcast_budget is not None and unexplained > spec.upcast_budget:
        findings.append(finding(
            "APX201",
            f"{spec.name} has {unexplained} bf16→fp32 upcast(s) that feed "
            f"no accumulator (budget {spec.upcast_budget}) — an fp32 "
            f"constant/operand is silently promoting the bf16 kernel "
            f"body"))
    if spec.out_dtypes is not None:
        got = tuple(str(v.aval.dtype) for v in closed.jaxpr.outvars)
        if got != tuple(spec.out_dtypes):
            findings.append(finding(
                "APX203",
                f"{spec.name} output dtypes {got} violate the declared "
                f"policy {tuple(spec.out_dtypes)}"))
    return findings


def run_jaxpr_audit(ops: Optional[Sequence[str]] = None) -> list:
    """Audit every (or the named) public fused op under the bf16 policy."""
    specs = op_specs()
    if ops:
        wanted = set(ops)
        missing = wanted - {s.name for s in specs}
        if missing:
            raise ValueError(f"unknown op(s): {sorted(missing)}")
        specs = [s for s in specs if s.name in wanted]
    out: list = []
    for spec in specs:
        out.extend(audit_op(spec))
    return out
