"""SPMD soundness auditor over the registered multi-device executables.

The jaxpr precision auditor (:mod:`apex_tpu.analysis.jaxpr_audit`)
checks single-device properties — dtype policy, host-transfer
discipline.  This engine walks the *distributed* executables the repo
actually ships — dense and ZeRO train steps, DDP bucketed allreduce,
TP column/row layers, pipeline 1F1B, ring/Ulysses attention, MoE
expert dispatch, inference prefill/decode — and machine-checks the
invariants PRs 3–4 proved by hand, per registered executable:

* **APX211 — collective axis soundness.**  Every ``psum`` /
  ``all_gather`` / ``psum_scatter`` / ``ppermute`` / ``all_to_all`` /
  ``pmax`` names an axis the executable's mesh binds AND that belongs
  to ``parallel_state``'s canonical topology (``pipe/data/expert/
  context/tensor``).  A collective over a foreign axis is dead comm at
  best, a shape bug at worst.
* **APX212 — branch collective parity.**  All branches of a
  ``lax.cond``/``switch`` carry the SAME multiset of (collective,
  axes).  A collective in only one branch is the classic SPMD
  deadlock/divergence shape: ranks disagreeing on the predicate stall
  each other inside the collective.
* **APX213 — replica-uniform control values.**  A dataflow pass tracks
  which values VARY across mesh axes (sharded inputs, ``axis_index``,
  ``psum_scatter``/``ppermute``/``all_to_all`` outputs) and which are
  replica-uniform (replicated inputs, constants, reducing-collective
  outputs).  Predicates of conds whose branches contain collectives
  must be uniform, and so must the small hyperparameter/flag operands
  of the fused update kernels (``noop_flag`` — the exact invariant
  ZeRO's overflow skip rests on: drop the ``pmax`` on ``found_inf``
  and this fires).
* **APX214 — donation verification.**  The lowered executable's
  ``tf.aliasing_output`` attributes actually cover every large leaf of
  the declared donated arguments (FlatState slots, KV cache buffers);
  for step-shaped executables, a large UNdonated input whose aval
  exactly matches an output is flagged — XLA could have reused the
  buffer and silently is not.
* **APX215/APX216 — comm/HBM budget ledger.**  Per-executable
  analytic collective bytes + peak-live-buffer estimate
  (:mod:`~apex_tpu.analysis.comm_model`), ratcheted against the
  committed ``.analysis_budget.json``: growth (or an unbudgeted
  executable) exits nonzero, shrinkage is silent until re-pinned.
  APX216 machine-checks PERF.md round-6's ZeRO accounting on the zero
  step's own jaxpr: all-gather bytes == reduce-scatter bytes, i.e.
  RS + AG == the ring all-reduce of the same flat buffer.
* **APX218 — compiled-truth attribution + drift ratchet.**  Every
  registered executable's budget entry carries XLA's OWN numbers —
  ``lower().compile()``'s ``cost_analysis()`` FLOPs/bytes and
  ``memory_analysis()`` buffer sizes (via
  :mod:`apex_tpu.observability.xla_stats`, provenance-marked when a
  backend degrades) — next to the analytic estimates, plus the
  estimate/compiled drift ratios (APX215's linear-scan peak-live vs
  compiled peak bytes; ``comm_model``'s dot-FLOPs vs compiled FLOPs).
  :func:`compare_budget` ratchets the drift: an executable whose
  ratio moved further from 1 than the committed band (x
  :data:`DRIFT_RATCHET_SLACK`), lost its attribution, or was never
  pinned with one, fails the run — the estimates can no longer drift
  silently away from what XLA actually builds.
* **APX217 — comm/compute overlap (async scheduling).**  For
  executables restructured for overlap (ISSUE 7: the layered-prefetch
  zero step, the chunked TP ring), the COMPILED executable — the same
  lowered-HLO route APX214 takes for donation, one step further — must
  actually expose the overlap: on backends that schedule async
  collectives, a strict majority of ``*-start``/``*-done`` pairs with
  a compute op scheduled between start and done; on backends that
  lower collectives synchronously (the CPU host devices this audit
  runs on), the dependency-graph equivalent — a strict majority of the
  DOMINANT collectives must each have substantial compute that is
  mutually independent of them (exactly what a latency-hiding
  scheduler would run between that start and its done; a decomposed
  pipeline exposes only its boundary collectives, while a monolithic
  gather gates every consumer and a fused matmul+psum hides at most
  its wgrad half).  The pre-overlap lowerings fire this check — the
  seeded-violation tests keep it honest.

Everything is trace-only (``jax.make_jaxpr`` + ``jit(...).lower``) —
zero FLOPs, runs on the 8 forced host devices in seconds — except
APX217, which compiles its (two) flagged executables for the host.
"""
from __future__ import annotations

import os
import re
from dataclasses import dataclass, field
from typing import Callable, Dict, FrozenSet, List, Optional, Sequence

from apex_tpu.analysis.comm_model import (COLLECTIVE_PRIMS, collective_axes,
                                          comm_report, jaxpr_dot_flops,
                                          peak_live_bytes)
from apex_tpu.analysis.finding import Finding
from apex_tpu.analysis.pallas_audit import kernel_function_name

__all__ = ["ExecSpec", "exec_specs", "run_spmd_audit", "compare_budget",
           "ensure_devices", "CANONICAL_AXES", "DONATION_FLOOR_BYTES",
           "BUDGET_NAME", "DRIFT_RATCHET_SLACK"]

BUDGET_NAME = ".analysis_budget.json"

#: APX218 drift ratchet slack: the estimate/compiled ratio's distance
#: from 1 may grow by at most this factor over the committed band
#: before the audit fails (identical backends reproduce the ratios
#: bit-for-bit; the slack only absorbs compiler-version scheduling
#: jitter, never a real new temporary).
DRIFT_RATCHET_SLACK = 1.05

#: parallel_state's mesh axis names — the only axes a registered
#: executable's collectives may ride (APX211).
CANONICAL_AXES = frozenset({"pipe", "data", "expert", "context", "tensor"})

#: donated/aliasable leaves smaller than this are noise (scalar step
#: counters, PRNG keys) — the donation checks ignore them.
DONATION_FLOOR_BYTES = 1024

# Fused optimizer/scaler kernel names whose small (<=16-element 1-D)
# operands — lr/beta/noop_flag hyperparameter vectors — must be
# replica-uniform: a rank-varying noop_flag silently diverges the
# masters (PR 3's hand-proved invariant, now enforced).
_UPDATE_KERNEL_MARKS = ("_adam_kernel", "_adagrad_kernel", "_sgd_kernel",
                        "_lamb1_kernel", "_scale_kernel",
                        "_l2norm_scale_kernel")
_UPDATE_OPERAND_MAX_ELEMS = 16

# Collectives that make their output replica-uniform over the reduced/
# gathered axes (every rank holds the identical result)...
_UNIFORMING = {"psum", "pmax", "pmin", "all_gather"}
# ...and collectives whose output stays (or becomes) rank-varying.
_VARYING = {"reduce_scatter", "psum_scatter", "ppermute", "all_to_all"}


def ensure_devices(n: int = 8) -> int:
    """Force ``n`` host devices BEFORE the backend initializes (the
    same ``xla_force_host_platform_device_count`` route the test
    conftest uses); returns the live device count.  A backend already
    pinned to fewer devices is left alone — callers decide whether
    that is fatal."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}").strip()
    import jax
    return len(jax.devices())


# ---------------------------------------------------------------------------
# executable registry
# ---------------------------------------------------------------------------

@dataclass
class ExecSpec:
    """One registered multi-device executable and its declared contract."""
    name: str
    path: str                        # module findings anchor to
    build: Callable[[], tuple]       # () -> (fn, args, axis_sizes)
    donate_argnums: tuple = ()       # declared donated args (jit-level)
    flag_undonated: bool = False     # step-shaped: flag alias-able args
    check_update_uniformity: bool = False
    rs_ag_identity: bool = False     # machine-check RS+AG==AR (PERF r6)
    check_overlap: bool = False      # APX217: comm/compute overlap


def _builders():
    """Lazy spec builders (importing this module stays jax-free).

    Each builder OWNS its ``parallel_state`` topology —
    :func:`run_spmd_audit` snapshots and restores the global mesh
    around the whole run so the audit composes with test harnesses.
    """
    import functools

    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from apex_tpu.transformer import parallel_state as ps

    shard_map = functools.partial(jax.shard_map, check_vma=False)

    def _mlp_params(n_layers=8, d=8):
        out = {}
        for i in range(n_layers):
            base = np.linspace(-0.3, 0.3, d * d, dtype=np.float32)
            out[f"w{i}"] = jnp.asarray(np.roll(base, i).reshape(d, d))
            out[f"b{i}"] = jnp.asarray(
                np.linspace(-0.01, 0.01, d, dtype=np.float32))
        return out

    def _mlp_loss(params, batch):
        h = batch["x"]
        for i in range(sum(1 for k in params if k.startswith("w"))):
            h = jnp.tanh(h @ params[f"w{i}"] + params[f"b{i}"])
        return jnp.mean((h - batch["y"]) ** 2)

    def _mlp_batch(n=16, d=8):
        x = np.linspace(-1.0, 1.0, n * d, dtype=np.float32).reshape(n, d)
        return {"x": jnp.asarray(x), "y": jnp.asarray(np.tanh(x @ np.full(
            (d, d), 0.1, np.float32)))}

    def train_step_dense():
        from apex_tpu import train_step
        from apex_tpu.optimizers import functional
        tx = functional.fused_adam(lr=1e-2)
        state = train_step.init_train_state(tx, _mlp_params(),
                                            loss_scale="dynamic")
        step = train_step.make_train_step(_mlp_loss, tx)
        return step, (state, _mlp_batch()), {}

    def train_step_zero(prefetch=8, numerics=False):
        from apex_tpu import train_step
        from apex_tpu.optimizers import functional
        tx = functional.fused_adam(lr=1e-2)
        mesh = Mesh(np.array(jax.devices()[:2]), (ps.DATA_AXIS,))
        # layered prefetch ON (one gather span per layer): the param
        # all-gather decomposes into 8 independent per-span gathers the
        # scheduler can hide under the consuming layers (APX217), at
        # bytes identical to the monolithic gather (APX215 pins it).
        # The prefetch=0 twin (train_step_zero_mono) keeps the
        # production default — APEX_TPU_ZERO_PREFETCH=0, monolithic
        # gather — under APX211-APX216.  The numerics=True twin
        # (train_step_zero_numerics, ISSUE 11) pins that the numerics
        # probes add exactly one scalar-vector psum of comm and keep
        # donation + replica-uniformity intact.
        state, specs = train_step.init_zero_train_state(
            tx, _mlp_params(), ps.DATA_AXIS, 2, loss_scale="dynamic",
            prefetch=prefetch)
        step = train_step.make_train_step(_mlp_loss, tx, zero=True,
                                          numerics=numerics)
        fn = shard_map(step, mesh=mesh, in_specs=(specs, P()),
                       out_specs=(specs, P()))
        return fn, (state, _mlp_batch()), dict(mesh.shape)

    # --- chunked fused LM-head + CE twins (ISSUE 9) --------------------
    # A train-step fixture where the [tokens, vocab] logits DOMINATE
    # memory: tokens=512 x vocab=4096 fp32 logits are 8 MiB, while the
    # model/optimizer state is ~0.6 MiB.  The env-knob-selected lowering
    # ships as TWO registered executables — the fused scan (chunk=64,
    # peak-live O(chunk x vocab)) and its unfused twin (chunk=0, full
    # logits forward AND softmax-residual backward) — so the APX215
    # ledger pins the peak-live drop and a regression in either lowering
    # is caught (the tier-1 twin guard in
    # tests/L1/test_fused_lm_xent_budget.py asserts fused < unfused and
    # that the unfused logits alone exceed the fused twin's entire
    # peak).
    _LM_TOKENS, _LM_HID, _LM_VOCAB, _LM_CHUNK = 512, 32, 4096, 64

    def _lm_head_params():
        base = np.linspace(-0.05, 0.05, _LM_VOCAB * _LM_HID,
                           dtype=np.float32)
        proj = np.linspace(-0.3, 0.3, _LM_HID * _LM_HID,
                           dtype=np.float32)
        return {"head_w": jnp.asarray(base.reshape(_LM_VOCAB, _LM_HID)),
                "proj": jnp.asarray(proj.reshape(_LM_HID, _LM_HID))}

    def _lm_head_batch():
        x = np.linspace(-1.0, 1.0, _LM_TOKENS * _LM_HID,
                        dtype=np.float32).reshape(_LM_TOKENS, _LM_HID)
        y = (np.arange(_LM_TOKENS) * 37) % _LM_VOCAB
        return {"x": jnp.asarray(x),
                "y": jnp.asarray(y, dtype=jnp.int32)}

    def _lm_head_loss(chunk):
        from apex_tpu.ops.fused_lm_xent import fused_lm_head_cross_entropy

        def loss(params, batch):
            h = jnp.tanh(batch["x"] @ params["proj"])
            return fused_lm_head_cross_entropy(
                h, params["head_w"], batch["y"], smoothing=0.1,
                token_chunk=chunk, vocab_chunk=0).mean()
        return loss

    def lm_xent_step(chunk):
        from apex_tpu import train_step
        from apex_tpu.optimizers import functional
        tx = functional.fused_adam(lr=1e-2)
        state = train_step.init_train_state(tx, _lm_head_params(),
                                            loss_scale="dynamic")
        step = train_step.make_train_step(_lm_head_loss(chunk), tx)
        return step, (state, _lm_head_batch()), {}

    def tp_fused_lm_xent():
        from apex_tpu.ops.fused_lm_xent import (
            fused_lm_head_vocab_parallel_cross_entropy)
        ps.destroy_model_parallel()
        ps.initialize_model_parallel(tensor_model_parallel_size_=2)
        mesh = ps.get_mesh()
        tokens, hid, vocab, chunk = 64, 16, 256, 16

        def body(h, w, y):
            def loss(h, w):
                return fused_lm_head_vocab_parallel_cross_entropy(
                    h, w, y, smoothing=0.1, token_chunk=chunk,
                    grad_input_psum=True).mean()
            return jax.value_and_grad(loss, argnums=(0, 1))(h, w)

        fn = shard_map(body, mesh=mesh,
                       in_specs=(P(), P(ps.TENSOR_AXIS, None), P()),
                       out_specs=(P(), (P(), P(ps.TENSOR_AXIS, None))))
        h = jnp.asarray(np.linspace(-1, 1, tokens * hid,
                                    dtype=np.float32).reshape(tokens, hid))
        w = jnp.asarray(np.linspace(-0.2, 0.2, vocab * hid,
                                    dtype=np.float32).reshape(vocab, hid))
        y = jnp.asarray((np.arange(tokens) * 7) % vocab, dtype=jnp.int32)
        return fn, (h, w, y), dict(mesh.shape)

    def ddp_bucketed_allreduce():
        from apex_tpu.parallel.distributed import DistributedDataParallel
        mesh = Mesh(np.array(jax.devices()[:2]), (ps.DATA_AXIS,))
        # small message_size forces the bucketed multi-psum path
        ddp = DistributedDataParallel(axis_name=ps.DATA_AXIS,
                                      message_size=4096)
        grads = _mlp_params(n_layers=6, d=16)

        def body(grads):
            return ddp.reduce_gradients(grads)

        fn = shard_map(body, mesh=mesh, in_specs=(P(),), out_specs=P())
        return fn, (grads,), dict(mesh.shape)

    def tp_column_row(chunks=4):
        from apex_tpu.transformer import tensor_parallel
        ps.destroy_model_parallel()
        ps.initialize_model_parallel(tensor_model_parallel_size_=2)
        mesh = ps.get_mesh()
        # chunked overlap ON: the row matmul+psum becomes a 4-chunk
        # matmul/ppermute ring (+ all-gather) and the column backward
        # psum the matching ring pipeline — same ring bytes as the
        # fused psums (APX215), chunk GEMMs schedulable under the hops
        # (APX217).  Tokens 4 (was 3) so the ring chunks divide; 4
        # chunks (not 2) because at tp=2 a 2-chunk ring is ONE hop —
        # boundary-dominated at this fixture size, so only half its
        # collectives can overlap and APX217's majority bar
        # (correctly) treats that as not pipelined.  The chunks=1 twin
        # (tp_column_row_fused) keeps the production default —
        # APEX_TPU_TP_OVERLAP_CHUNKS=1, fused psums — under
        # APX211-APX216.
        col = tensor_parallel.ColumnParallelLinear(8, 16,
                                                   gather_output=False,
                                                   bias=False,
                                                   overlap_chunks=chunks)
        row = tensor_parallel.RowParallelLinear(16, 8,
                                                input_is_parallel=True,
                                                bias=False,
                                                overlap_chunks=chunks)

        def body(x):
            pc = col.init(jax.random.key(0), x)
            h, _ = col.apply(pc, x)
            pr = row.init(jax.random.key(1), h)

            def loss(x):
                h, _ = col.apply(pc, x)
                y, _ = row.apply(pr, h)
                return jnp.mean(y ** 2)

            return jax.value_and_grad(loss)(x)

        fn = shard_map(body, mesh=mesh, in_specs=(P(),),
                       out_specs=(P(), P()))
        x = jnp.asarray(np.linspace(-1, 1, 4 * 8,
                                    dtype=np.float32).reshape(4, 8))
        return fn, (x,), dict(mesh.shape)

    def pipeline_1f1b():
        from apex_tpu.transformer.pipeline_parallel.schedules import (
            forward_backward_pipelining_without_interleaving)
        ps.destroy_model_parallel()
        ps.initialize_model_parallel(pipeline_model_parallel_size_=2)
        mesh = ps.get_mesh()
        HID, N_MICRO, MB = 8, 2, 2
        params = {"w": jnp.stack([jnp.eye(HID) * 0.5] * 2),
                  "b": jnp.zeros((2, HID))}
        batch = {"x": jnp.asarray(np.linspace(
                     -1, 1, N_MICRO * MB * HID,
                     dtype=np.float32).reshape(N_MICRO, MB, HID)),
                 "target": jnp.full((N_MICRO, MB, HID), 0.1)}

        def stage_fn(p, x, mb):
            return jax.nn.gelu(x @ p["w"] + p["b"])

        def loss_fn(y, mb):
            return jnp.mean((y - mb["target"]) ** 2)

        def body(params, batch):
            local = jax.tree.map(lambda p: p[0], params)
            loss, grads = forward_backward_pipelining_without_interleaving(
                stage_fn, loss_fn, local, batch,
                num_microbatches=N_MICRO, input_fn=lambda mb: mb["x"])
            return loss, jax.tree.map(lambda g: g[None], grads)

        fn = shard_map(body, mesh=mesh,
                       in_specs=(P(ps.PIPE_AXIS), P()),
                       out_specs=(P(), P(ps.PIPE_AXIS)))
        return fn, (params, batch), dict(mesh.shape)

    def _cp_qkv():
        s = jax.ShapeDtypeStruct
        q = s((1, 2, 256, 64), jnp.bfloat16)
        return q, q, q

    def ring_attention_cp():
        from apex_tpu.ops import ring_attention as op
        ps.destroy_model_parallel()
        ps.initialize_model_parallel(context_parallel_size_=2)
        mesh = ps.get_mesh()
        fn = shard_map(lambda q, k, v: op(q, k, v, causal=True),
                       mesh=mesh,
                       in_specs=(P(None, None, ps.CONTEXT_AXIS, None),) * 3,
                       out_specs=P(None, None, ps.CONTEXT_AXIS, None))
        return fn, _cp_qkv(), dict(mesh.shape)

    def ulysses_attention_cp():
        from apex_tpu.ops import ulysses_attention as op
        ps.destroy_model_parallel()
        ps.initialize_model_parallel(context_parallel_size_=2)
        mesh = ps.get_mesh()
        fn = shard_map(lambda q, k, v: op(q, k, v, causal=True),
                       mesh=mesh,
                       in_specs=(P(None, None, ps.CONTEXT_AXIS, None),) * 3,
                       out_specs=P(None, None, ps.CONTEXT_AXIS, None))
        return fn, _cp_qkv(), dict(mesh.shape)

    def moe_dispatch():
        import flax  # noqa: F401 — optional dep; ImportError skips
        from apex_tpu.transformer.moe.layer import MoELayer
        ps.destroy_model_parallel()
        ps.initialize_model_parallel(expert_model_parallel_size_=2)
        mesh = ps.get_mesh()
        layer = MoELayer(num_experts=4, hidden_size=16, ffn_hidden_size=32,
                         top_k=1, capacity=4, expert_parallel_size=2)

        def body(x):
            params = layer.init(jax.random.key(3), x)
            y, _ = layer.apply(params, x)
            return y

        dp = mesh.shape[ps.DATA_AXIS]
        spec = P((ps.DATA_AXIS, ps.EXPERT_AXIS))
        fn = shard_map(body, mesh=mesh, in_specs=(spec,), out_specs=spec)
        x = jax.ShapeDtypeStruct((dp * 2 * 4, 16), jnp.float32)
        return fn, (x,), dict(mesh.shape)

    def _inference(which):
        from apex_tpu.analysis import jaxpr_audit
        ps.destroy_model_parallel()
        fn, args = jaxpr_audit._builders()[which][0]()
        return fn, args, {}

    def _inference_tp2(which):
        """Tensor-parallel serving executables (ISSUE 17): build a REAL
        ``InferenceEngine(tp=2)`` on forced host devices and audit its
        own ``_*_raw`` shard_map step bodies with its own placed
        operands — the audited mesh program IS the one the engine
        dispatches, not a re-derived fixture.  GPT at the jaxpr-audit
        paged fixture geometry; the fused-decode entry compiles the
        sharded Pallas block (partial_out) + the out-of-kernel psum
        tail, the verify entry the k=4 sharded slab scoring."""
        from apex_tpu.inference import kv_cache
        from apex_tpu.inference.engine import InferenceEngine
        from apex_tpu.inference.sampling import SamplingConfig
        from apex_tpu.transformer.testing.standalone_gpt import (
            GPTConfig, gpt_model_provider)
        ps.destroy_model_parallel()
        ps.initialize_model_parallel(1)     # model.init's tp=1 world
        cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                        num_attention_heads=4, max_seq_length=256,
                        hidden_dropout=0.0, attention_dropout=0.0,
                        params_dtype=jnp.bfloat16)
        model = gpt_model_provider(cfg)
        params = model.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
        eng = InferenceEngine(
            "gpt", cfg, params, slots=4, paged=True, page_size=16,
            num_pages=20, sampling=SamplingConfig(),
            decode_fusion="1" if which == "decode_fused" else "0",
            spec_k=4 if which == "verify" else 0, tp=2)
        cache = eng.init_cache()
        key, step = eng._key, np.int32(0)
        shape = dict(eng.mesh.shape)
        if which == "prefill":
            row = kv_cache.page_row(list(range(4)),
                                    eng.max_pages_per_slot,
                                    eng.num_pages)
            return eng._prefill_raw, (
                cache, eng.params, np.zeros((64,), np.int32),
                np.int32(0), np.int32(10), row, np.int32(0), key,
                step), shape
        if which == "decode_fused":
            return eng._decode_raw, (
                cache, (eng.params, eng._fused_layers),
                np.zeros((4,), np.int32), np.ones((4,), bool), key,
                step), shape
        return eng._verify_raw, (
            cache, eng.params, np.zeros((4, 5), np.int32),
            np.ones((4,), bool), key, step), shape

    return {
        # name: (builder, path, donate, flag_undonated, update_unif,
        #        rs_ag, overlap)
        "train_step_dense": (train_step_dense, "apex_tpu/train_step.py",
                             (0,), True, True, False, False),
        "train_step_zero": (train_step_zero, "apex_tpu/train_step.py",
                            (0,), True, True, True, True),
        # production default (APEX_TPU_ZERO_PREFETCH=0): the monolithic
        # gather stays machine-checked even though the overlapped
        # fixture above is what APX217 verifies
        "train_step_zero_mono": (functools.partial(train_step_zero,
                                                   prefetch=0),
                                 "apex_tpu/train_step.py",
                                 (0,), True, True, True, False),
        # the numerics-probed zero step (ISSUE 11): same lowering as
        # train_step_zero plus compute_probes' single packed psum —
        # its APX215 ledger entry minus train_step_zero's IS the
        # mode's entire comm cost (the tier-1 twin guard asserts it),
        # and APX213/214 pin that the probes stay replica-uniform and
        # donation-intact
        "train_step_zero_numerics": (functools.partial(train_step_zero,
                                                       numerics=True),
                                     "apex_tpu/observability/"
                                     "numerics.py",
                                     (0,), True, True, True, False),
        # the fused/unfused LM-head+CE twins (ISSUE 9): the env-knob
        # (APEX_TPU_XENT_CHUNK) selects between these two lowerings, so
        # BOTH are budgeted — the twin guard compares their APX215
        # peak-live entries
        "lm_xent_fused": (functools.partial(lm_xent_step, _LM_CHUNK),
                          "apex_tpu/ops/fused_lm_xent.py",
                          (0,), True, True, False, False),
        "lm_xent_unfused": (functools.partial(lm_xent_step, 0),
                            "apex_tpu/ops/fused_lm_xent.py",
                            (0,), True, True, False, False),
        "tp_fused_lm_xent": (tp_fused_lm_xent,
                             "apex_tpu/ops/fused_lm_xent.py",
                             (), False, False, False, False),
        "ddp_allreduce": (ddp_bucketed_allreduce,
                          "apex_tpu/parallel/distributed.py",
                          (), False, False, False, False),
        "tp_column_row": (tp_column_row,
                          "apex_tpu/transformer/tensor_parallel/layers.py",
                          (), False, False, False, True),
        # production default (APEX_TPU_TP_OVERLAP_CHUNKS=1): the fused
        # psum lowering stays machine-checked alongside the ring twin
        "tp_column_row_fused": (functools.partial(tp_column_row,
                                                  chunks=1),
                                "apex_tpu/transformer/tensor_parallel/"
                                "layers.py",
                                (), False, False, False, False),
        "pipeline_1f1b": (pipeline_1f1b,
                          "apex_tpu/transformer/pipeline_parallel/"
                          "schedules.py",
                          (), False, False, False, False),
        "ring_attention_cp": (ring_attention_cp,
                              "apex_tpu/ops/ring_attention.py",
                              (), False, False, False, False),
        "ulysses_attention_cp": (ulysses_attention_cp,
                                 "apex_tpu/ops/ulysses_attention.py",
                                 (), False, False, False, False),
        "moe_dispatch": (moe_dispatch,
                         "apex_tpu/transformer/moe/layer.py",
                         (), False, False, False, False),
        "inference_prefill": (lambda: _inference("inference_prefill"),
                              "apex_tpu/inference/engine.py",
                              (0,), True, False, False, False),
        "inference_decode": (lambda: _inference("inference_decode"),
                             "apex_tpu/inference/engine.py",
                             (0,), True, False, False, False),
        # the paged serving memory model (ISSUE 6), registered at a
        # straggler-shaped fixture: the pool (+page table) is donated
        # like the dense cache, and its APX215 peak-live entry is the
        # number the paged-vs-dense HBM comparison test ratchets
        "inference_prefill_paged": (
            lambda: _inference("inference_prefill_paged"),
            "apex_tpu/inference/engine.py", (0,), True, False, False,
            False),
        "inference_decode_paged": (
            lambda: _inference("inference_decode_paged"),
            "apex_tpu/inference/engine.py", (0,), True, False, False,
            False),
        # ISSUE 34: the latent-attention kind's decode step — one pool,
        # donated like the others
        "inference_decode_latent": (
            lambda: _inference("inference_decode_latent"),
            "apex_tpu/inference/engine.py", (0,), True, False, False,
            False),
        # ISSUE 36: the selecting kind's decode step — three pools under
        # one table, donated like the others
        "inference_decode_select": (
            lambda: _inference("inference_decode_select"),
            "apex_tpu/inference/engine.py", (0,), True, False, False,
            False),
        # ISSUE 15: the fused-block decode lowering
        # (APEX_TPU_DECODE_FUSION=1 twin of inference_decode_paged —
        # same signature, same donation, one Pallas kernel per layer)
        # and the speculative verify step (k=4 slab; lengths advance
        # by the accepted count in-program = the rollback), both
        # budgeted from day one like every serving executable
        "inference_decode_fused_paged": (
            lambda: _inference("inference_decode_fused_paged"),
            "apex_tpu/inference/engine.py", (0,), True, False, False,
            False),
        "inference_verify_paged": (
            lambda: _inference("inference_verify_paged"),
            "apex_tpu/inference/engine.py", (0,), True, False, False,
            False),
        # ISSUE 18: the host-tier copy programs.  The swap-out gather
        # deliberately does NOT donate (and must not be flagged for
        # it): the pool stays live — the evicted pages' contents are
        # read out while other pages keep serving.  The swap-in
        # scatter donates the pool like every mutating serving
        # program; its slab operands are small fixed-width staging
        # buffers, not aliasable state.
        "inference_swap_out_paged": (
            lambda: _inference("inference_swap_out_paged"),
            "apex_tpu/inference/kv_cache.py", (), False, False, False,
            False),
        "inference_swap_in_paged": (
            lambda: _inference("inference_swap_in_paged"),
            "apex_tpu/inference/kv_cache.py", (0,), True, False, False,
            False),
        # ISSUE 17: the tensor-parallel serving executables — the
        # engine's own shard_map mesh programs at tp=2, donated pool
        # and all; APX217 overlap verified on the sharded fused decode
        # (per-layer row psums vs the independent pool appends)
        "inference_prefill_paged_tp2": (
            lambda: _inference_tp2("prefill"),
            "apex_tpu/inference/engine.py", (0,), True, False, False,
            False),
        "inference_decode_fused_paged_tp2": (
            lambda: _inference_tp2("decode_fused"),
            "apex_tpu/inference/engine.py", (0,), True, False, False,
            True),
        "inference_verify_paged_tp2": (
            lambda: _inference_tp2("verify"),
            "apex_tpu/inference/engine.py", (0,), True, False, False,
            False),
    }


def exec_specs() -> List[ExecSpec]:
    return [ExecSpec(name, path, build, donate, undon, unif, rs_ag, ovl)
            for name, (build, path, donate, undon, unif, rs_ag, ovl)
            in _builders().items()]


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------

def _iter_jaxprs(jaxpr):
    from apex_tpu.analysis.jaxpr_audit import _iter_jaxprs as it
    return it(jaxpr)


def _collective_multiset(jaxpr) -> dict:
    """{(prim, axes): count} over a jaxpr INCLUDING nested jaxprs; scan
    bodies multiply by length (two psums == one psum scanned twice)."""
    import jax.extend.core as jex_core

    out: Dict[tuple, int] = {}

    def walk(j, mult):
        j = getattr(j, "jaxpr", j)
        for eqn in j.eqns:
            name = eqn.primitive.name
            if name in COLLECTIVE_PRIMS:
                key = (name, collective_axes(eqn))
                out[key] = out.get(key, 0) + mult
            m = mult
            if name == "scan":
                m = mult * int(eqn.params.get("length", 1))
            for v in eqn.params.values():
                items = v if isinstance(v, (list, tuple)) else [v]
                for item in items:
                    if isinstance(item, (jex_core.Jaxpr,
                                         jex_core.ClosedJaxpr)):
                        walk(item, m)

    walk(jaxpr, 1)
    return out


# ---------------------------------------------------------------------------
# replica-uniformity dataflow
# ---------------------------------------------------------------------------

class _Uniformity:
    """Per-executable varying-axes dataflow + the checks riding on it.

    ``vary[var]`` is the frozenset of mesh axes the value differs
    across; empty/absent means replica-uniform.  Conservative: unknown
    primitives union their inputs; unmappable subjaxprs seed every
    inner input with the union of the outer inputs.
    """

    def __init__(self, spec: ExecSpec, emit):
        self.spec = spec
        self.emit = emit            # (rule, message) -> None
        self._reported: set = set()

    # -- eqn transfer functions -----------------------------------------

    def run(self, jaxpr, seed: List[FrozenSet], checks: bool) -> list:
        import jax.extend.core as jex_core

        vary: dict = {}
        open_j = getattr(jaxpr, "jaxpr", jaxpr)
        for v, s in zip(open_j.invars, seed):
            vary[v] = s
        for v in open_j.constvars:
            vary[v] = frozenset()

        def vof(v):
            if isinstance(v, jex_core.Literal):
                return frozenset()
            return vary.get(v, frozenset())

        for eqn in open_j.eqns:
            name = eqn.primitive.name
            invary = frozenset().union(*[vof(v) for v in eqn.invars]) \
                if eqn.invars else frozenset()
            axes = set(collective_axes(eqn))
            if name in _UNIFORMING and \
                    eqn.params.get("axis_index_groups") is None:
                out = [invary - axes] * len(eqn.outvars)
            elif name in _VARYING:
                out = [invary | axes] * len(eqn.outvars)
            elif name == "axis_index":
                out = [frozenset(axes)] * len(eqn.outvars)
            elif name == "cond":
                out = self._cond(eqn, vof, checks)
            elif name == "scan":
                out = self._scan(eqn, vof, checks)
            elif name == "while":
                out = self._while(eqn, vof, checks)
            elif name == "pjit":
                sub = eqn.params["jaxpr"]
                out = self.run(sub, [vof(v) for v in eqn.invars], checks)
            elif name == "pallas_call":
                if checks:
                    self._pallas(eqn, vof)
                out = [invary] * len(eqn.outvars)
            else:
                out = [invary] * len(eqn.outvars)
                out = self._generic_subjaxprs(eqn, invary, out, checks)
            for v, s in zip(eqn.outvars, out):
                vary[v] = s
        return [vof(v) for v in open_j.outvars]

    def _cond(self, eqn, vof, checks) -> list:
        pred = vof(eqn.invars[0])
        branches = eqn.params.get("branches", ())
        seed = [vof(v) for v in eqn.invars[1:]]
        outs = None
        multisets = []
        for br in branches:
            sub_out = self.run(br, seed, checks)
            multisets.append(_collective_multiset(br))
            outs = sub_out if outs is None else [
                a | b for a, b in zip(outs, sub_out)]
        if checks and multisets:
            base = multisets[0]
            if any(m != base for m in multisets[1:]):
                self._emit_once(
                    "APX212",
                    "lax.cond/switch branches carry different collective "
                    f"multisets {[sorted(f'{p}@{a}' for (p, a) in m) for m in multisets]}"
                    " — ranks disagreeing on the predicate deadlock or "
                    "diverge inside the missing collective")
            if pred and any(multisets):
                self._emit_once(
                    "APX213",
                    f"cond predicate varies over mesh axes "
                    f"{sorted(pred)} while its branches contain "
                    f"collectives — rank-divergent collective entry is "
                    f"the SPMD deadlock shape; derive the predicate "
                    f"through a reducing collective (psum/pmax) or a "
                    f"constant")
        outs = outs or []
        return [o | pred for o in outs]

    def _scan(self, eqn, vof, checks) -> list:
        num_consts = eqn.params["num_consts"]
        num_carry = eqn.params["num_carry"]
        sub = eqn.params["jaxpr"]
        consts = [vof(v) for v in eqn.invars[:num_consts]]
        carry = [vof(v) for v in
                 eqn.invars[num_consts:num_consts + num_carry]]
        xs = [vof(v) for v in eqn.invars[num_consts + num_carry:]]
        for _ in range(8):  # fixpoint over the carried varying sets
            out = self.run(sub, consts + carry + xs, False)
            new_carry = [a | b for a, b in zip(carry, out[:num_carry])]
            if new_carry == carry:
                break
            carry = new_carry
        out = self.run(sub, consts + carry + xs, checks)
        return [a | b for a, b in zip(carry, out[:num_carry])] \
            + out[num_carry:]

    def _while(self, eqn, vof, checks) -> list:
        cn = eqn.params["cond_nconsts"]
        bn = eqn.params["body_nconsts"]
        cond_j = eqn.params["cond_jaxpr"]
        body_j = eqn.params["body_jaxpr"]
        cconsts = [vof(v) for v in eqn.invars[:cn]]
        bconsts = [vof(v) for v in eqn.invars[cn:cn + bn]]
        carry = [vof(v) for v in eqn.invars[cn + bn:]]
        for _ in range(8):
            out = self.run(body_j, bconsts + carry, False)
            new_carry = [a | b for a, b in zip(carry, out)]
            if new_carry == carry:
                break
            carry = new_carry
        out = self.run(body_j, bconsts + carry, checks)
        pred = self.run(cond_j, cconsts + carry, False)
        if checks and pred and pred[0] and _collective_multiset(body_j):
            self._emit_once(
                "APX213",
                f"while_loop predicate varies over mesh axes "
                f"{sorted(pred[0])} while the body contains collectives "
                f"— rank-divergent trip counts deadlock the collective")
        return [a | b for a, b in zip(carry, out)]

    def _pallas(self, eqn, vof) -> None:
        label = kernel_function_name(eqn)
        if not any(mark in label for mark in _UPDATE_KERNEL_MARKS):
            return
        if not self.spec.check_update_uniformity:
            return
        for v in eqn.invars:
            aval = getattr(v, "aval", None)
            if aval is None or aval.ndim > 1:
                continue
            size = 1
            for d in aval.shape:
                size *= int(d)
            if size > _UPDATE_OPERAND_MAX_ELEMS:
                continue
            axes = vof(v)
            if axes:
                self._emit_once(
                    "APX213",
                    f"update kernel {label.split(' at ')[0]!r} consumes a "
                    f"hyperparameter/flag operand (shape "
                    f"{tuple(aval.shape)}) that varies over mesh axes "
                    f"{sorted(axes)} — a rank-local noop_flag/lr silently "
                    f"diverges the sharded masters; reduce it "
                    f"replica-uniform first (pmax/psum over the axis)")

    def _generic_subjaxprs(self, eqn, invary, out, checks) -> list:
        """custom_vjp/jvp, remat, closed_call, ...: recurse for the
        CHECKS with conservative seeding; outputs stay the input
        union (already set by the caller)."""
        import jax.extend.core as jex_core

        for v in eqn.params.values():
            items = v if isinstance(v, (list, tuple)) else [v]
            for item in items:
                if isinstance(item, (jex_core.Jaxpr, jex_core.ClosedJaxpr)):
                    open_j = getattr(item, "jaxpr", item)
                    self.run(item, [invary] * len(open_j.invars), checks)
        return out

    def _emit_once(self, rule: str, message: str) -> None:
        key = (rule, message)
        if key not in self._reported:
            self._reported.add(key)
            self.emit(rule, message)


# ---------------------------------------------------------------------------
# donation verification
# ---------------------------------------------------------------------------

_MLIR_DT = {"float32": "f32", "bfloat16": "bf16", "float16": "f16",
            "float64": "f64", "int8": "i8", "int16": "i16",
            "int32": "i32", "int64": "i64", "uint8": "ui8",
            "uint16": "ui16", "uint32": "ui32", "uint64": "ui64",
            "bool": "i1"}

# the attr dict may carry quoted values containing '}' (e.g.
# mhlo.sharding = "{devices=[2]<=[2]}") — match quoted spans atomically
_ARG_RE = re.compile(
    r"%arg\d+:\s*(tensor<[^>]*>)\s*(\{(?:[^{}\"]|\"[^\"]*\")*\})?")


def _mlir_type(aval) -> str:
    dims = "x".join(str(int(d)) for d in aval.shape)
    dt = _MLIR_DT.get(str(aval.dtype), str(aval.dtype))
    return f"tensor<{dims}x{dt}>" if dims else f"tensor<{dt}>"


def _aval_bytes(aval) -> int:
    size = 1
    for d in aval.shape:
        size *= int(d)
    return size * aval.dtype.itemsize


def _parse_main_args(text: str) -> list:
    """[(mlir type, donated?)] for @main's arguments, from the lowered
    StableHLO text.  Single-device lowerings mark donated-and-usable
    inputs ``tf.aliasing_output``; multi-device (mesh) lowerings defer
    the alias decision to XLA and mark ``jax.buffer_donor`` — either
    attribute proves the declared donation reached the executable."""
    start = text.index("@main(")
    depth, i = 0, start + len("@main")
    for i in range(start + len("@main"), len(text)):
        if text[i] == "(":
            depth += 1
        elif text[i] == ")":
            depth -= 1
            if depth == 0:
                break
    sig = text[start:i + 1]
    return [(m.group(1),
             any(mark in (m.group(2) or "")
                 for mark in ("tf.aliasing_output", "jax.buffer_donor")))
            for m in _ARG_RE.finditer(sig)]


def _check_donation(spec: ExecSpec, fn, args, emit) -> None:
    import jax

    jitted = jax.jit(fn, donate_argnums=spec.donate_argnums or ())
    try:
        text = jitted.lower(*args).as_text()
    except Exception as e:  # noqa: BLE001 — surfaced as a finding
        emit("APX210", f"lowering {spec.name} for donation verification "
                       f"failed: {type(e).__name__}: {e}")
        return
    sig = _parse_main_args(text)

    donated, undonated = [], []
    for i, a in enumerate(args):
        leaves = jax.tree.leaves(a)
        (donated if i in (spec.donate_argnums or ()) else
         undonated).extend(leaves)

    def aval_of(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)

    out_types: Dict[str, int] = {}
    for o in jax.tree.leaves(jax.eval_shape(fn, *args)):
        t = _mlir_type(o)
        out_types[t] = out_types.get(t, 0) + 1

    # (a) every large declared-donated leaf (1) reached the lowered
    # executable as a donor/alias and (2) has a matching output XLA can
    # actually alias it to
    donor_pool: Dict[str, int] = {}
    for t, al in sig:
        if al:
            donor_pool[t] = donor_pool.get(t, 0) + 1
    alias_pool = dict(out_types)
    for leaf in donated:
        aval = aval_of(leaf)
        if _aval_bytes(aval) < DONATION_FLOOR_BYTES:
            continue
        t = _mlir_type(aval)
        has_donor = donor_pool.get(t, 0) > 0
        if has_donor:
            donor_pool[t] -= 1
        has_target = alias_pool.get(t, 0) > 0
        if has_target:
            alias_pool[t] -= 1
        if not has_target:
            emit("APX214",
                 f"{spec.name}: donated input {t} matches NO output aval "
                 f"— XLA cannot alias it, so the old buffer stays live "
                 f"across the step (a dtype/shape change between the "
                 f"donated input and its updated output defeats "
                 f"donation)")
        elif not has_donor:
            emit("APX214",
                 f"{spec.name}: declared-donated input {t} carries no "
                 f"donor/alias attribute in the lowered executable — the "
                 f"donation never reached XLA (wrong donate_argnums, or "
                 f"the arg was pruned)")

    # (b) step-shaped executables: a large undonated input whose aval
    # matches an output could have been reused and is not
    if spec.flag_undonated:
        spare = dict(out_types)
        for leaf in donated:
            t = _mlir_type(aval_of(leaf))
            if spare.get(t, 0) > 0:
                spare[t] -= 1
        for leaf in undonated:
            aval = aval_of(leaf)
            if _aval_bytes(aval) < DONATION_FLOOR_BYTES:
                continue
            t = _mlir_type(aval)
            if spare.get(t, 0) > 0:
                spare[t] -= 1
                emit("APX214",
                     f"{spec.name}: large undonated input {t} exactly "
                     f"matches an output — donate it so XLA reuses the "
                     f"buffer in place instead of holding both copies "
                     f"live")


# ---------------------------------------------------------------------------
# APX217 — comm/compute overlap verification on the COMPILED executable
# ---------------------------------------------------------------------------

#: collective HLO opcodes whose scheduling the overlap check reasons
#: about (the sync spellings; async backends suffix -start/-done).
_OVERLAP_COLL_OPS = frozenset({
    "all-gather", "all-reduce", "collective-permute", "reduce-scatter",
    "all-to-all", "collective-broadcast"})

#: HLO opcodes that count as REAL compute for "compute scheduled
#: between start and done" — data movement (bitcast/copy/slice/concat/
#: broadcast/transpose/tuple) deliberately does not.
_HLO_COMPUTE_OPS = frozenset({
    "fusion", "dot", "convolution", "reduce", "reduce-window", "add",
    "subtract", "multiply", "divide", "tanh", "exponential", "log",
    "rsqrt", "sqrt", "power", "negate", "maximum", "minimum", "select",
    "compare", "map", "sort", "scatter", "custom-call"})

_HLO_INSTR_RE = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_HLO_OP_RE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_HLO_REF_RE = re.compile(r"%([\w.\-]+)")
_HLO_SHAPE_RE = re.compile(r"([a-z]+[0-9]*)\[([0-9,]*)\]")
_HLO_CALLS_RE = re.compile(r"(?:calls|to_apply)=%?([\w.\-]+)")

_HLO_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
                 "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4,
                 "s64": 8, "u64": 8, "f64": 8}


def _hlo_type_bytes(type_seg: str) -> int:
    total = 0
    for dt, dims in _HLO_SHAPE_RE.findall(type_seg):
        size = 1
        for d in dims.split(","):
            if d:
                size *= int(d)
        total += size * _HLO_ITEMSIZE.get(dt, 4)
    return total


def _parse_entry_instructions(text: str) -> list:
    """``[(name, opcode, operand_names, result_bytes)]`` for the
    compiled module's ENTRY computation, in schedule (program) order.
    Operand refs that don't name an earlier entry instruction
    (computation names in ``calls=``/``to_apply=``, metadata) drop out
    when the dependency graph resolves names.  Handles both HLO text
    spellings: ``%``-sigiled names, and the sigil-less dump (operand
    names are then the identifier tokens in the opcode's argument
    list)."""
    out = []
    in_entry = False
    for line in text.splitlines():
        if line.lstrip().startswith("ENTRY"):
            in_entry = True
            continue
        if not in_entry:
            continue
        if line.strip() == "}":
            break
        m = _HLO_INSTR_RE.match(line)
        if m is None:
            continue
        name, rest = m.group(2), m.group(3)
        om = _HLO_OP_RE.search(" " + rest)
        if om is None:
            continue
        type_seg = (" " + rest)[:om.start(1)]
        refs = _HLO_REF_RE.findall(rest)
        if not refs:
            seg = (" " + rest)[om.end(1):]
            seg = seg[:seg.index(")")] if ")" in seg else seg
            seg = re.sub(r"[a-z]+[0-9]*\[[0-9,]*\]\S*", " ", seg)
            refs = re.findall(r"[A-Za-z_][\w.\-]*", seg)
        cm = _HLO_CALLS_RE.search(rest)
        if cm and cm.group(1) not in refs:
            refs.append(cm.group(1))
        out.append((name, om.group(1), refs, _hlo_type_bytes(type_seg)))
    return out


def _computation_collectives(text: str) -> dict:
    """Non-ENTRY computation name -> set of collective opcodes in its
    body.  Resolves GENERIC ``async-start(...), calls=...`` wrappers —
    the spelling XLA uses to asyncify collectives without a dedicated
    fused opcode (e.g. reduce-scatter / all-to-all on TPU) — back to
    the collective they wrap."""
    out: dict = {}
    cur = None
    for line in text.splitlines():
        st = line.strip()
        if st.endswith("{") and "=" not in st:
            if st.startswith("ENTRY"):
                cur = None
                continue
            m = re.match(r"%?([\w.\-]+)", st)
            cur = m.group(1) if m else None
            if cur is not None:
                out[cur] = set()
            continue
        if st == "}":
            cur = None
            continue
        if cur is None:
            continue
        m = _HLO_INSTR_RE.match(line)
        if m is not None:
            om = _HLO_OP_RE.search(" " + m.group(3))
            if om is not None and om.group(1) in _OVERLAP_COLL_OPS:
                out[cur].add(om.group(1))
    return out


def _emit_compile_failed(emit, name: str, err) -> None:
    emit("APX210", f"compiling {name} for overlap verification "
                   f"failed: {type(err).__name__}: {err}")


def _compile_executable(spec: "ExecSpec", fn, args) -> tuple:
    """ONE XLA compile per executable, shared by APX217 (schedule
    inspection) and APX218 (cost/memory attribution) — compilation
    dominates audit wall time, so it must never run twice for the same
    spec.  Returns ``(Compiled or None, error or None)``."""
    import jax

    try:
        return (jax.jit(fn, donate_argnums=spec.donate_argnums or ())
                .lower(*args).compile(), None)
    except Exception as e:  # noqa: BLE001 — callers surface it
        return None, e


def _check_async_overlap(spec: "ExecSpec", fn, args, emit,
                         compiled=None) -> None:
    """APX217: the compiled executable of an overlap-restructured hot
    path must expose comm/compute overlap to the scheduler.
    ``compiled`` lets :func:`_audit_exec` share its one compile; when
    absent (direct callers, tests) this compiles itself.

    Async backends (TPU latency-hiding scheduler): find
    ``*-start``/``*-done`` collective pairs — dedicated fused opcodes
    AND generic ``async-start`` wrappers resolved through their
    ``calls=`` computation (XLA's spelling for reduce-scatter /
    all-to-all), following ``async-update`` chains to the done — and
    require a strict majority with at least one compute op scheduled
    between start and done.  Synchronous backends (the forced CPU host devices this audit
    runs on): the dependency-graph equivalent — a dominant collective
    counts as OVERLAPPED when some substantial compute op is mutually
    independent of it (exactly the op an async scheduler would place
    between its start and done), and a strict majority of the dominant
    collectives must be overlapped.  The majority bar is the pipeline
    bound: a K-way decomposition exposes only its schedule-boundary
    collectives (first gather, last scatter — < half for any K >= 2),
    while a monolithic gather gates every consumer and a fused
    matmul+psum hides at most its wgrad half (exactly half).  Two
    floors keep trivia out: collectives below 1/8 of the largest
    collective's payload (scalar loss pmeans, found_inf pmax) are not
    dominant, and witness compute below 1/8 of the collective's payload
    (scaler bookkeeping) does not count as hiding it."""
    if compiled is None:
        compiled, err = _compile_executable(spec, fn, args)
        if compiled is None:
            _emit_compile_failed(emit, spec.name, err)
            return
    _overlap_findings_from_hlo(spec.name, compiled.as_text(), emit)


def _overlap_findings_from_hlo(name: str, text: str, emit) -> None:
    """APX217 over already-compiled HLO text (split from
    :func:`_check_async_overlap` so the async route — which only real
    TPU lowerings produce — is testable from canned module text)."""
    instrs = _parse_entry_instructions(text)
    index = {name: i for i, (name, _, _, _) in enumerate(instrs)}

    def dominant(idxs):
        if not idxs:
            return idxs
        floor = max(instrs[i][3] for i in idxs) / 8
        return [i for i in idxs if instrs[i][3] >= floor]

    # -- async route: explicit start/done pairs in the schedule --------
    # two async spellings: dedicated fused opcodes (all-gather-start,
    # collective-permute-start, ...) and the generic async-start whose
    # calls= computation wraps the collective (reduce-scatter /
    # all-to-all on TPU)
    comp_colls = _computation_collectives(text)

    def async_coll(i):
        _, op, refs, _ = instrs[i]
        if op.endswith("-start") and op[:-6] in _OVERLAP_COLL_OPS:
            return op[:-6]
        if op == "async-start":
            for r in refs:
                if comp_colls.get(r):
                    return sorted(comp_colls[r])[0]
        return None

    start_coll = {i: c for i in range(len(instrs))
                  if (c := async_coll(i)) is not None}
    starts = dominant(list(start_coll))
    if starts:
        overlapped = 0
        for i in starts:
            done_ops = {start_coll[i] + "-done", "async-done"}
            # follow the start's async value through any async-update
            # links to its done
            aliases = {instrs[i][0]}
            done = None
            for j in range(i + 1, len(instrs)):
                nm, op, refs, _ = instrs[j]
                if op == "async-update" and aliases & set(refs):
                    aliases.add(nm)
                elif op in done_ops and aliases & set(refs):
                    done = j
                    break
            if done is None:
                continue
            # same witness floor as the sync route: scalar bookkeeping
            # scheduled between start and done is not hiding the comm
            wfloor = max(instrs[i][3] // 8, 16)
            if any(instrs[k][1] in _HLO_COMPUTE_OPS
                   and instrs[k][3] >= wfloor
                   for k in range(i + 1, done)):
                overlapped += 1
        if 2 * overlapped <= len(starts):
            emit("APX217",
                 f"{name}: only {overlapped}/{len(starts)} async "
                 f"collective pair(s) in the compiled schedule have a "
                 f"compute op between start and done — the comm is "
                 f"async in name only and still serializes the critical "
                 f"path")
        return

    # -- sync route: dependency-graph schedulability -------------------
    colls = dominant([i for i, (_, op, _, _) in enumerate(instrs)
                      if op in _OVERLAP_COLL_OPS])
    if len(colls) < 2:
        emit("APX217",
             f"{name}: the compiled executable carries "
             f"{len(colls)} dominant collective(s) — the overlap "
             f"restructuring (per-span gathers / ring chunks) did not "
             f"survive lowering, so there is nothing a scheduler could "
             f"overlap")
        return
    # ancestors as bitsets over instruction indices (defs precede uses)
    anc = [0] * len(instrs)
    for i, (_, _, refs, _) in enumerate(instrs):
        a = 0
        for rname in refs:
            j = index.get(rname)
            if j is not None and j < i:
                a |= anc[j] | (1 << j)
        anc[i] = a
    compute = [i for i, (_, op, _, _) in enumerate(instrs)
               if op in _HLO_COMPUTE_OPS]
    overlapped = 0
    for c in colls:
        wfloor = max(instrs[c][3] // 8, 16)
        if any(instrs[f][3] >= wfloor
               and not (anc[f] & (1 << c)) and not (anc[c] & (1 << f))
               for f in compute):
            overlapped += 1
    if 2 * overlapped <= len(colls):
        emit("APX217",
             f"{name}: only {overlapped}/{len(colls)} dominant "
             f"collective(s) in the compiled executable have substantial "
             f"compute a scheduler could run between their start and "
             f"done (the rest each gate — or hang off — every compute "
             f"op); decompose the collective along the consumption "
             f"order (per-span gathers, ring chunks) so comm hides "
             f"under compute")


# ---------------------------------------------------------------------------
# audit driver
# ---------------------------------------------------------------------------

def _audit_exec(spec: ExecSpec) -> tuple:
    """-> (findings, budget_entry or None)"""
    import jax

    findings: list = []

    def emit(rule, msg):
        findings.append(Finding(rule, spec.path, 0, 0, msg,
                                line_text=f"{spec.name}:{rule}"))

    try:
        fn, args, axis_sizes = spec.build()
    except ImportError:
        return [], None  # optional dependency absent
    except Exception as e:  # noqa: BLE001 — a broken builder is a finding
        emit("APX210", f"building {spec.name} failed: "
                       f"{type(e).__name__}: {e}")
        return findings, None
    try:
        closed = jax.make_jaxpr(fn)(*args)
    except Exception as e:  # noqa: BLE001 — any trace failure is a finding
        emit("APX210", f"tracing {spec.name} failed: "
                       f"{type(e).__name__}: {e}")
        return findings, None

    # APX211 — axis soundness over the whole program
    bound = set(axis_sizes)
    for j in _iter_jaxprs(closed.jaxpr):
        for eqn in j.eqns:
            if eqn.primitive.name in COLLECTIVE_PRIMS or \
                    eqn.primitive.name == "axis_index":
                for ax in collective_axes(eqn):
                    if ax not in CANONICAL_AXES:
                        emit("APX211",
                             f"{spec.name}: {eqn.primitive.name} rides "
                             f"axis {ax!r}, which is not one of "
                             f"parallel_state's mesh axes "
                             f"{sorted(CANONICAL_AXES)}")
                    elif bound and ax not in bound:
                        emit("APX211",
                             f"{spec.name}: {eqn.primitive.name} names "
                             f"axis {ax!r} but the executable's mesh "
                             f"binds only {sorted(bound)}")

    # APX212/APX213 — branch parity + replica-uniformity dataflow,
    # seeded from each shard_map eqn's in_specs (one PartitionSpec per
    # operand; an entry is None, an axis name or a tuple of them)
    uni = _Uniformity(spec, emit)
    for eqn in closed.jaxpr.eqns:
        if eqn.primitive.name != "shard_map":
            continue
        seed = []
        for pspec in eqn.params["in_specs"]:
            seed.append(frozenset(
                ax for entry in pspec if entry is not None
                for ax in (entry if isinstance(entry, tuple)
                           else (entry,))))
        uni.run(eqn.params["jaxpr"], seed, checks=True)

    # APX214 — donation verification on the lowered executable
    if spec.donate_argnums or spec.flag_undonated:
        _check_donation(spec, fn, args, emit)

    # ONE compile per executable: APX217 reads its schedule, APX218
    # its cost/memory numbers
    compiled, compile_err = _compile_executable(spec, fn, args)

    # APX217 — comm/compute overlap on the COMPILED executable
    if spec.check_overlap:
        if compiled is None:
            _emit_compile_failed(emit, spec.name, compile_err)
        else:
            _check_async_overlap(spec, fn, args, emit, compiled=compiled)

    # comm/HBM ledger entry
    sizes = dict(axis_sizes)
    report = comm_report(closed, sizes)
    entry = {
        "comm_bytes": int(report["total_bytes"]),
        "by_collective": {k: int(v)
                          for k, v in sorted(report["by_collective"].items())},
        "collective_counts": {k: int(v)
                              for k, v in sorted(report["counts"].items())},
        "peak_live_bytes": int(peak_live_bytes(closed.jaxpr)),
        "axes": {k: int(v) for k, v in sorted(sizes.items())},
    }

    # APX218 — compiled-truth attribution from the SAME compile the
    # overlap check read.  XLA's cost/memory numbers (or an explicit
    # degradation marker — never a silent zero) ride the entry, with
    # the estimate/compiled drift ratios the budget ratchet watches.
    from apex_tpu.observability.xla_stats import (
        CompiledStats, PROVENANCE_UNAVAILABLE_PREFIX,
        stats_from_compiled)
    if compiled is None:
        stats = CompiledStats(
            provenance=PROVENANCE_UNAVAILABLE_PREFIX
            + f"compile-failed:{type(compile_err).__name__}")
    else:
        stats = stats_from_compiled(compiled)
    compiled_entry = stats.asdict()
    est_flops = int(jaxpr_dot_flops(closed))
    compiled_entry["dot_flops_estimate"] = est_flops
    if stats.flops and est_flops > 0:
        compiled_entry["dot_flops_drift"] = round(
            est_flops / stats.flops, 4)
    if stats.peak_hbm_bytes:
        compiled_entry["peak_live_drift"] = round(
            entry["peak_live_bytes"] / stats.peak_hbm_bytes, 4)
    entry["compiled"] = compiled_entry

    # APX216 — the PERF.md round-6 identity on the zero step's own
    # jaxpr: params all-gather bytes == grad reduce-scatter bytes
    # (i.e. RS + AG == ring all-reduce of the same flat buffer)
    if spec.rs_ag_identity:
        by = entry["by_collective"]
        ag = sum(v for k, v in by.items() if k.startswith("all_gather@"))
        rs = sum(v for k, v in by.items()
                 if k.startswith(("reduce_scatter@", "psum_scatter@")))
        entry["rs_ag_equals_ar"] = bool(ag > 0 and ag == rs)
        if not entry["rs_ag_equals_ar"]:
            emit("APX216",
                 f"{spec.name}: ZeRO comm identity broken — all_gather "
                 f"moves {ag} B/chip vs reduce_scatter {rs} B/chip; "
                 f"RS+AG must equal the dense all-reduce (PERF.md "
                 f"round-6 accounting, machine-checked)")
    return findings, entry


def run_spmd_audit(execs: Optional[Sequence[str]] = None) -> tuple:
    """Audit every (or the named) registered multi-device executable.

    Returns ``(findings, report)`` where ``report`` is the budget
    ledger shape committed as ``.analysis_budget.json``:
    ``{"version": 1, "executables": {name: {comm_bytes, by_collective,
    collective_counts, peak_live_bytes, axes[, rs_ag_equals_ar]}}}``.
    """
    n = ensure_devices()
    if n < 2:
        raise RuntimeError(
            f"the SPMD audit needs >=2 host devices to bind mesh axes "
            f"(got {n}); the jax backend initialized before the audit "
            f"could request them — set XLA_FLAGS="
            f"--xla_force_host_platform_device_count=8")

    specs = exec_specs()
    if execs:
        wanted = set(execs)
        missing = wanted - {s.name for s in specs}
        if missing:
            raise ValueError(f"unknown executable(s): {sorted(missing)}")
        specs = [s for s in specs if s.name in wanted]

    from apex_tpu.transformer import parallel_state as ps
    saved_mesh = ps._MESH
    saved_vpp_rank = ps._VIRTUAL_PIPELINE_MODEL_PARALLEL_RANK
    saved_vpp_world = ps._VIRTUAL_PIPELINE_MODEL_PARALLEL_WORLD_SIZE
    findings: list = []
    executables: dict = {}
    try:
        for spec in specs:
            f, entry = _audit_exec(spec)
            findings.extend(f)
            if entry is not None:
                executables[spec.name] = entry
    finally:
        # the builders destroy/reinit topology freely; hand the caller
        # back EVERYTHING parallel_state tracks, not just the mesh
        ps._MESH = saved_mesh
        ps._VIRTUAL_PIPELINE_MODEL_PARALLEL_RANK = saved_vpp_rank
        ps._VIRTUAL_PIPELINE_MODEL_PARALLEL_WORLD_SIZE = saved_vpp_world
    return findings, {"version": 1, "executables": executables}


def _drift_distance(ratio: float) -> float:
    """Symmetric distance of an estimate/compiled ratio from 1 (2x over
    and 2x under are equally far); non-positive ratios are maximally
    wrong."""
    if ratio <= 0:
        return float("inf")
    return max(ratio, 1.0 / ratio)


def _compare_compiled(name: str, path: str, entry: dict, pinned: dict,
                      emit218) -> None:
    """APX218 half of the ratchet: compiled-truth attribution must
    exist (stats or an explicit degradation marker), must not silently
    degrade, and its drift ratios must stay inside the committed band."""
    comp = entry.get("compiled")
    if not isinstance(comp, dict) or "provenance" not in comp:
        emit218(name, path,
                f"{name}: budget entry carries no compiled-stats "
                f"attribution (neither XLA cost/memory numbers nor an "
                f"explicit degradation marker) — the auditor must "
                f"always attribute or mark, never skip silently")
        return
    pinned_comp = pinned.get("compiled")
    if not isinstance(pinned_comp, dict):
        emit218(name, path,
                f"{name}: executable has no committed compiled-stats "
                f"entry — run apex-tpu-analyze --spmd --write-budget "
                f"to pin its APX218 drift ledger")
        return
    # full > cost-only > unavailable: ANY slide down the provenance
    # ladder is a degradation (a full->cost-only slide silently
    # disables the peak-live drift ratchet, not just the cliff to
    # unavailable)
    from apex_tpu.observability.xla_stats import provenance_rank
    prov = comp["provenance"]
    pinned_prov = pinned_comp.get("provenance", "")
    if provenance_rank(prov) < provenance_rank(pinned_prov):
        emit218(name, path,
                f"{name}: compiled-stats attribution DEGRADED "
                f"({pinned_prov!r} -> {prov!r}) — the executable "
                f"stopped reporting stats it used to on this backend")
        return
    for key, est_name, truth_name in (
            ("peak_live_drift", "APX215 peak-live estimate",
             "compiled peak bytes"),
            ("dot_flops_drift", "comm_model dot-FLOPs",
             "compiled cost_analysis FLOPs")):
        cur, pin = comp.get(key), pinned_comp.get(key)
        if pin is not None and cur is None:
            emit218(name, path,
                    f"{name}: the {est_name} drift ratio vanished from "
                    f"the fresh entry (pinned {pin}) — the analytic "
                    f"estimate degenerated (e.g. to zero) and the "
                    f"ratchet lost its input; fix the model or re-pin "
                    f"consciously with --write-budget")
            continue
        if cur is None or pin is None:
            continue
        if _drift_distance(cur) > \
                _drift_distance(pin) * DRIFT_RATCHET_SLACK:
            emit218(name, path,
                    f"{name}: {est_name} drifted further from the "
                    f"{truth_name} ({pin} -> {cur}; band "
                    f"{_drift_distance(pin):.4f} x "
                    f"{DRIFT_RATCHET_SLACK}) — the analytic model and "
                    f"the compiled executable disagree more than they "
                    f"used to; fix the model or justify and re-pin "
                    f"with --write-budget")


def compare_budget(report: dict, committed: Optional[dict]) -> list:
    """Ratchet: findings for every executable whose comm bytes or peak
    estimate GREW vs the committed budget (or that the budget has never
    seen), APX215-coded; plus the APX218 compiled-truth checks — every
    entry must carry compiled stats (or an explicit degradation
    marker), and the estimate-vs-compiled drift ratios must stay inside
    the committed band.  Shrinkage is silent — re-pin with
    ``--write-budget``."""
    findings: list = []

    def emit(name, path, msg, rule="APX215"):
        findings.append(Finding(rule, path, 0, 0, msg,
                                line_text=f"{name}:{rule}"))

    def emit218(name, path, msg):
        emit(name, path, msg, rule="APX218")

    paths = {s.name: s.path for s in exec_specs()}
    base = (committed or {}).get("executables", {})
    for name, entry in report.get("executables", {}).items():
        path = paths.get(name, "<spmd_audit>")
        pinned = base.get(name)
        if pinned is None:
            emit(name, path,
                 f"{name}: executable has no committed budget entry — "
                 f"run apex-tpu-analyze --spmd --write-budget to pin "
                 f"its comm/HBM ledger")
            continue
        if entry["comm_bytes"] > pinned.get("comm_bytes", 0):
            emit(name, path,
                 f"{name}: collective bytes grew "
                 f"{pinned.get('comm_bytes', 0)} -> "
                 f"{entry['comm_bytes']} B/chip/step "
                 f"({entry['by_collective']}) — justify and re-pin with "
                 f"--write-budget, or remove the new collective")
        if entry["peak_live_bytes"] > pinned.get("peak_live_bytes", 0):
            emit(name, path,
                 f"{name}: peak-live-buffer estimate grew "
                 f"{pinned.get('peak_live_bytes', 0)} -> "
                 f"{entry['peak_live_bytes']} B — a new full-size "
                 f"temporary entered the executable")
        _compare_compiled(name, path, entry, pinned, emit218)
    return findings
