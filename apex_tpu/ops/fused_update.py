"""The multi-tensor engine — fused optimizer/scaling kernels over flat buffers.

TPU-native rebuild of the reference's ``amp_C`` extension
(``csrc/multi_tensor_apply.cuh`` chunked tensor-list launcher plus the functor
kernels ``multi_tensor_scale_kernel.cu``, ``multi_tensor_axpby_kernel.cu``,
``multi_tensor_l2norm_kernel.cu``, ``multi_tensor_adam.cu``,
``multi_tensor_adagrad.cu``, ``multi_tensor_sgd_kernel.cu``,
``multi_tensor_lamb.cu``), driven from Python by
``apex/multi_tensor_apply/multi_tensor_apply.py :: MultiTensorApply``.

Design (TPU-first, not a translation):

* The CUDA engine exists to amortize kernel-launch overhead across a *list* of
  small tensors by packing chunk metadata into kernel arguments.  On TPU the
  idiomatic equivalent is stronger: ravel the whole parameter pytree into ONE
  flat buffer (``jax.flatten_util.ravel_pytree``) and run ONE Pallas kernel
  over it per step.  Chunking becomes the Pallas grid; "tensor boundaries"
  only matter for per-tensor reductions (LAMB trust ratios), which are
  computed per-leaf by XLA and applied through a precomputed per-element
  segment-id gather.
* The reference's ``noop_flag`` (device-side overflow guard that turns the
  whole launch into a no-op) maps to a traced scalar in SMEM: the kernel
  computes the update and predicates the write with ``jnp.where`` — no host
  sync, jit-safe, exactly the semantics amp needs for skip-on-overflow.
  Every state-writing kernel (Adam, Adagrad, SGD, LAMB stage 1) takes it:
  the state buffers are aliased to the outputs, so a select spelled
  OUTSIDE the kernel would read the old buffer after the kernel has
  overwritten it and force XLA to copy each buffer first.  LAMB stage 1
  predicates its two moments; its ``u`` output and the parameter apply
  (stage 2, per-tensor trust ratios) stay with the optimizer.
* Hyperparameters (lr, betas, bias corrections, the noop flag) travel in a
  single small fp32 vector placed in SMEM, so changing the learning rate does
  NOT recompile the kernel.
* Every kernel has a pure-jnp oracle twin (``*_reference``) used as the test
  oracle and as the fallback for shapes the kernel does not accept.

Flat buffers are processed as 1-D arrays in blocks of ``_BLOCK`` elements;
Pallas masks the partial tail block, so buffers of ANY length run with zero
padding copies — the perf property of the reference's chunked launcher
(``multi_tensor_apply.cuh`` chunks at arbitrary offsets).  Empty (length-0)
buffers are handled at the wrapper level (the grid would be empty and the
SMEM flag/accumulator initializers would never run).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.utils import cdiv, interpret_mode

__all__ = [
    "fused_scale",
    "fused_axpby",
    "fused_l2norm",
    "fused_l2norm_scale",
    "fused_adam_flat",
    "fused_adagrad_flat",
    "fused_sgd_flat",
    "fused_lamb_phase1_flat",
    "adam_reference",
    "ADAM_MODE_L2",
    "ADAM_MODE_ADAMW",
]

_LANES = 128
_BLOCK = 512 * 128  # 1-D block: 256 KiB fp32 per operand tile

#: pallas_audit registration (analysis hook only, no behavior change):
#: flat arrays are padded up to the lane-aligned block, so the block
#: intentionally exceeds short operands — the tail is masked in-kernel
#: via the n scalar (APX303 masked_tail); _l2norm's sum-of-squares
#: accumulates in fp32 scratch (APX302).
PALLAS_AUDIT = {
    "_scale_kernel": {"masked_tail": True},
    "_axpby_kernel": {"masked_tail": True},
    "_l2norm_kernel": {"reduction": True, "masked_tail": True},
    "_l2norm_scale_kernel": {"reduction": True, "masked_tail": True},
    "_adam_kernel": {"masked_tail": True},
    "_adagrad_kernel": {"masked_tail": True},
    "_sgd_kernel": {"masked_tail": True},
    "_lamb1_kernel": {"masked_tail": True},
}

ADAM_MODE_L2 = 0  # classic Adam: weight decay folded into the gradient
ADAM_MODE_ADAMW = 1  # decoupled weight decay


def _grid(x: jax.Array) -> int:
    return cdiv(x.shape[0], _BLOCK)


def _vspec():
    return pl.BlockSpec((_BLOCK,), lambda i: (i,))


def _sspec():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _tail_mask(i, n: int, x, fill):
    """Zero/neutralize out-of-bounds lanes of the final partial block.
    Elementwise kernels don't need this (OOB writes are dropped); reduction
    and flag kernels must not read OOB garbage."""
    if n % _BLOCK == 0:
        return x
    idx = i * _BLOCK + jax.lax.broadcasted_iota(jnp.int32, (_BLOCK,), 0)
    return jnp.where(idx < n, x, fill)


# the elementwise kernels' grids are parallel (Megacore splits them
# freely).  The flag / accumulator kernels write PER-BLOCK partials into
# a (grid,)-shaped SMEM output (each step owns its own slot) that the
# wrapper reduces with one tiny XLA max/sum — no scalar state is carried
# across grid steps (parity: ``amp_C.multi_tensor_scale``'s chunked
# launcher with a global flag buffer).  Their grid is "arbitrary": the
# slots travel in shared 1024-slot output blocks (see :func:`_bspec`),
# which consecutive steps must revisit in order.
_PAR = pltpu.CompilerParams(dimension_semantics=("parallel",))
_SEQ = pltpu.CompilerParams(dimension_semantics=("arbitrary",))

#: slots per SMEM partials block.  A (1,) block per step, which jax 0.4
#: accepted, no longer lowers: Mosaic requires a rank-1 block to be the
#: whole array or a multiple of 128 32-bit words, and to match XLA's own
#: tiling of the operand — T(1024) for a long fp32 vector (observed on
#: "TPU v5 lite", PR 21: a 128-slot block fails layout verification)
_SLOTS = 1024


def _bspec():
    """SMEM partials block: grid step ``i`` owns slot ``i % _SLOTS`` of
    block ``i // _SLOTS``.  Only one 4 KiB block is staged in SMEM at
    a time (the assembled array lives in HBM), so SMEM pressure is O(1)
    in buffer size; SMEM is the right home for a scalar store (Mosaic
    vector stores want lane-shaped VMEM tiles)."""
    return pl.BlockSpec((_SLOTS,), lambda i: (i // _SLOTS,),
                        memory_space=pltpu.SMEM)


def _bshape(grid: int):
    """The partials array: ``grid`` slots rounded up to whole blocks;
    wrappers reduce ``[:grid]`` (the tail slots are never written)."""
    return jax.ShapeDtypeStruct((cdiv(grid, _SLOTS) * _SLOTS,),
                                jnp.float32)


# ---------------------------------------------------------------------------
# scale / axpby (the amp unscale path) with non-finite detection
# ---------------------------------------------------------------------------

def _scale_kernel(n, x_ref, hp_ref, o_ref, flag_ref):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)
    y = x * hp_ref[0]
    flag_ref[i % _SLOTS] = jnp.any(~jnp.isfinite(_tail_mask(i, n, y, 0.0))
                                   ).astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


def fused_scale(flat: jax.Array, scale, out_dtype=None):
    """``out = flat * scale`` with fused non-finite detection.

    Parity: ``amp_C.multi_tensor_scale`` (csrc/multi_tensor_scale_kernel.cu) —
    the overflow buffer becomes a returned fp32 flag (0.0 clean, 1.0 inf/nan).
    """
    out_dtype = out_dtype or flat.dtype
    n = flat.shape[0]
    if n == 0:   # empty grid would leave the SMEM flag uninitialized
        return flat.astype(out_dtype), jnp.float32(0.0)
    hp = jnp.asarray([scale], jnp.float32)
    out, flags = pl.pallas_call(
        functools.partial(_scale_kernel, n),
        grid=(_grid(flat),),
        in_specs=[_vspec(), _sspec()],
        out_specs=[_vspec(), _bspec()],
        out_shape=[
            jax.ShapeDtypeStruct(flat.shape, out_dtype),
            _bshape(_grid(flat)),
        ],
        compiler_params=_SEQ,
        interpret=interpret_mode(),
        name="apex_amp_unscale",
    )(flat, hp)
    return out, jnp.max(flags[:_grid(flat)])


def _axpby_kernel(n, x_ref, y_ref, hp_ref, o_ref, flag_ref):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)
    y = y_ref[...].astype(jnp.float32)
    o = hp_ref[0] * x + hp_ref[1] * y
    flag_ref[i % _SLOTS] = jnp.any(~jnp.isfinite(_tail_mask(i, n, o, 0.0))
                                   ).astype(jnp.float32)
    o_ref[...] = o.astype(o_ref.dtype)


def fused_axpby(a, x: jax.Array, b, y: jax.Array, out_dtype=None):
    """``out = a*x + b*y`` with non-finite detection.

    Parity: ``amp_C.multi_tensor_axpby`` (csrc/multi_tensor_axpby_kernel.cu).
    """
    out_dtype = out_dtype or x.dtype
    n = x.shape[0]
    if n == 0:   # empty grid would leave the SMEM flag uninitialized
        return x.astype(out_dtype), jnp.float32(0.0)
    hp = jnp.asarray([a, b], jnp.float32)
    out, flags = pl.pallas_call(
        functools.partial(_axpby_kernel, n),
        grid=(_grid(x),),
        in_specs=[_vspec(), _vspec(), _sspec()],
        out_specs=[_vspec(), _bspec()],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, out_dtype),
            _bshape(_grid(x)),
        ],
        compiler_params=_SEQ,
        interpret=interpret_mode(),
        name="apex_axpby",
    )(x, y, hp)
    return out, jnp.max(flags[:_grid(x)])


# ---------------------------------------------------------------------------
# L2 norm (grad clipping, LAMB global norm)
# ---------------------------------------------------------------------------

def _l2norm_kernel(n, x_ref, acc_ref):
    i = pl.program_id(0)
    x = _tail_mask(i, n, x_ref[...].astype(jnp.float32), 0.0)
    acc_ref[i % _SLOTS] = jnp.sum(x * x)


def fused_l2norm(flat: jax.Array) -> jax.Array:
    """L2 norm of a flat buffer in one fused pass.

    Parity: ``amp_C.multi_tensor_l2norm`` (csrc/multi_tensor_l2norm_kernel.cu).
    """
    n = flat.shape[0]
    if n == 0:   # empty grid would leave the SMEM accumulator uninitialized
        return jnp.float32(0.0)
    acc = pl.pallas_call(
        functools.partial(_l2norm_kernel, n),
        grid=(_grid(flat),),
        in_specs=[_vspec()],
        out_specs=_bspec(),
        out_shape=_bshape(_grid(flat)),
        compiler_params=_SEQ,
        interpret=interpret_mode(),
        name="apex_l2norm",
    )(flat)
    return jnp.sqrt(jnp.sum(acc[:_grid(flat)]))


def _l2norm_scale_kernel(n, x_ref, hp_ref, o_ref, acc_ref, flag_ref):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32) * hp_ref[0]
    xm = _tail_mask(i, n, x, 0.0)
    acc_ref[i % _SLOTS] = jnp.sum(xm * xm)
    flag_ref[i % _SLOTS] = jnp.any(~jnp.isfinite(xm)).astype(jnp.float32)
    o_ref[...] = x.astype(o_ref.dtype)


def fused_l2norm_scale(flat: jax.Array, scale, out_dtype=None):
    """``out = flat * scale`` AND the L2 norm of the scaled buffer, in one
    pass (parity: ``amp_C.multi_tensor_l2norm_scale`` — the reference
    fuses gradient unscaling with the norm the clipper needs, halving
    the HBM traffic of scale-then-norm).  Returns ``(out, norm,
    found_inf)`` — the non-finite flag keeps the unscale path's
    skip-on-overflow contract (same as :func:`fused_scale`).
    """
    out_dtype = out_dtype or flat.dtype
    n = flat.shape[0]
    if n == 0:
        return flat.astype(out_dtype), jnp.float32(0.0), jnp.float32(0.0)
    hp = jnp.asarray([scale], jnp.float32)
    out, acc, flags = pl.pallas_call(
        functools.partial(_l2norm_scale_kernel, n),
        grid=(_grid(flat),),
        in_specs=[_vspec(), _sspec()],
        out_specs=[_vspec(), _bspec(), _bspec()],
        out_shape=[
            jax.ShapeDtypeStruct(flat.shape, out_dtype),
            _bshape(_grid(flat)),
            _bshape(_grid(flat)),
        ],
        compiler_params=_SEQ,
        interpret=interpret_mode(),
        name="apex_l2norm_scale",
    )(flat, hp)
    g = _grid(flat)
    return out, jnp.sqrt(jnp.sum(acc[:g])), jnp.max(flags[:g])


# ---------------------------------------------------------------------------
# Adam / AdamW
# ---------------------------------------------------------------------------

def _adam_kernel(adam_w, p_ref, g_ref, m_ref, v_ref, hp_ref,
                 po_ref, mo_ref, vo_ref):
    lr, b1, b2, eps, wd = (hp_ref[0], hp_ref[1], hp_ref[2], hp_ref[3],
                           hp_ref[4])
    inv_bc1, inv_sqrt_bc2, noop, gscale = (hp_ref[5], hp_ref[6], hp_ref[7],
                                           hp_ref[8])
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32) * gscale
    m = m_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)

    if not adam_w:
        g = g + wd * p
    m_new = b1 * m + (1.0 - b1) * g
    v_new = b2 * v + (1.0 - b2) * g * g
    denom = jnp.sqrt(v_new) * inv_sqrt_bc2 + eps
    update = (m_new * inv_bc1) / denom
    if adam_w:
        update = update + wd * p
    p_new = p - lr * update

    skip = noop > 0.0
    po_ref[...] = jnp.where(skip, p, p_new).astype(po_ref.dtype)
    mo_ref[...] = jnp.where(skip, m, m_new).astype(mo_ref.dtype)
    vo_ref[...] = jnp.where(skip, v, v_new).astype(vo_ref.dtype)


def fused_adam_flat(p, g, m, v, *, lr, beta1, beta2, eps, weight_decay,
                    step, adam_w_mode=True, bias_correction=True,
                    noop_flag=0.0, grad_scale=1.0):
    """One fused Adam(W) step over flat fp32 state.

    Parity: ``amp_C.multi_tensor_adam`` (csrc/multi_tensor_adam.cu ::
    AdamFunctor) as driven by ``apex/optimizers/fused_adam.py :: FusedAdam``.
    ``noop_flag`` > 0 turns the whole step into a no-op (overflow skip);
    ``grad_scale`` folds gradient unscaling into the same kernel.
    Returns (p, m, v) updated.
    """
    if bias_correction:
        t = jnp.asarray(step, jnp.float32)
        inv_bc1 = 1.0 / (1.0 - jnp.power(jnp.float32(beta1), t))
        inv_sqrt_bc2 = jax.lax.rsqrt(1.0 - jnp.power(jnp.float32(beta2), t))
    else:
        inv_bc1 = jnp.float32(1.0)
        inv_sqrt_bc2 = jnp.float32(1.0)
    hp = jnp.stack([
        jnp.asarray(lr, jnp.float32),
        jnp.asarray(beta1, jnp.float32),
        jnp.asarray(beta2, jnp.float32),
        jnp.asarray(eps, jnp.float32),
        jnp.asarray(weight_decay, jnp.float32),
        jnp.asarray(inv_bc1, jnp.float32),
        jnp.asarray(inv_sqrt_bc2, jnp.float32),
        jnp.asarray(noop_flag, jnp.float32),
        jnp.asarray(grad_scale, jnp.float32),
    ])
    p2, n = p, p.shape[0]
    g2 = g
    m2 = m
    v2 = v
    po, mo, vo = pl.pallas_call(
        functools.partial(_adam_kernel, bool(adam_w_mode)),
        grid=(_grid(p2),),
        in_specs=[_vspec(), _vspec(), _vspec(), _vspec(), _sspec()],
        out_specs=[_vspec(), _vspec(), _vspec()],
        out_shape=[
            jax.ShapeDtypeStruct(p2.shape, p2.dtype),
            jax.ShapeDtypeStruct(m2.shape, m2.dtype),
            jax.ShapeDtypeStruct(v2.shape, v2.dtype),
        ],
        input_output_aliases={0: 0, 2: 1, 3: 2},
        compiler_params=_PAR,
        interpret=interpret_mode(),
        name="apex_adam_update",
    )(p2, g2, m2, v2, hp)
    return (po, mo, vo)


def adam_reference(p, g, m, v, *, lr, beta1, beta2, eps, weight_decay, step,
                   adam_w_mode=True, bias_correction=True, grad_scale=1.0):
    """Pure-jnp oracle for :func:`fused_adam_flat` (mirrors torch.optim.AdamW)."""
    p = p.astype(jnp.float32)
    g = g.astype(jnp.float32) * grad_scale
    if not adam_w_mode:
        g = g + weight_decay * p
    m = beta1 * m + (1 - beta1) * g
    v = beta2 * v + (1 - beta2) * g * g
    if bias_correction:
        bc1 = 1 - beta1 ** step
        bc2 = 1 - beta2 ** step
    else:
        bc1 = bc2 = 1.0
    update = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
    if adam_w_mode:
        update = update + weight_decay * p
    return p - lr * update, m, v


# ---------------------------------------------------------------------------
# Adagrad
# ---------------------------------------------------------------------------

def _adagrad_kernel(w_mode, p_ref, g_ref, h_ref, hp_ref, po_ref, ho_ref):
    lr, eps, wd, noop, gscale = (hp_ref[0], hp_ref[1], hp_ref[2], hp_ref[3],
                                 hp_ref[4])
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32) * gscale
    h = h_ref[...].astype(jnp.float32)
    if not w_mode:
        g = g + wd * p
    h_new = h + g * g
    update = g / (jnp.sqrt(h_new) + eps)
    if w_mode:
        update = update + wd * p
    p_new = p - lr * update
    skip = noop > 0.0
    po_ref[...] = jnp.where(skip, p, p_new).astype(po_ref.dtype)
    ho_ref[...] = jnp.where(skip, h, h_new).astype(ho_ref.dtype)


def fused_adagrad_flat(p, g, h, *, lr, eps, weight_decay, w_mode=False,
                       noop_flag=0.0, grad_scale=1.0):
    """Fused Adagrad step (parity: ``amp_C.multi_tensor_adagrad``; ``w_mode``
    is the reference's ADAGRAD_MODE for decoupled weight decay)."""
    hp = jnp.stack([
        jnp.asarray(lr, jnp.float32),
        jnp.asarray(eps, jnp.float32),
        jnp.asarray(weight_decay, jnp.float32),
        jnp.asarray(noop_flag, jnp.float32),
        jnp.asarray(grad_scale, jnp.float32),
    ])
    p2, n = p, p.shape[0]
    g2 = g
    h2 = h
    po, ho = pl.pallas_call(
        functools.partial(_adagrad_kernel, bool(w_mode)),
        grid=(_grid(p2),),
        in_specs=[_vspec(), _vspec(), _vspec(), _sspec()],
        out_specs=[_vspec(), _vspec()],
        out_shape=[
            jax.ShapeDtypeStruct(p2.shape, p2.dtype),
            jax.ShapeDtypeStruct(h2.shape, h2.dtype),
        ],
        input_output_aliases={0: 0, 2: 1},
        compiler_params=_PAR,
        interpret=interpret_mode(),
        name="apex_adagrad_update",
    )(p2, g2, h2, hp)
    return po, ho


# ---------------------------------------------------------------------------
# SGD (momentum, nesterov)
# ---------------------------------------------------------------------------

def _sgd_kernel(nesterov, wd_after, p_ref, g_ref, b_ref, hp_ref, po_ref,
                bo_ref):
    lr, mom, damp, wd = hp_ref[0], hp_ref[1], hp_ref[2], hp_ref[3]
    first, noop, gscale = hp_ref[4], hp_ref[5], hp_ref[6]
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32) * gscale
    buf = b_ref[...].astype(jnp.float32)
    # wd_after_momentum (reference multi_tensor_sgd flag): decay joins
    # AFTER the momentum update instead of inside the momentum input
    d = g if wd_after else g + wd * p
    buf_new = jnp.where(first > 0.0, d, mom * buf + (1.0 - damp) * d)
    if nesterov:
        step_dir = d + mom * buf_new
    else:
        step_dir = buf_new
    step_dir = jnp.where(mom == 0.0, d, step_dir)
    if wd_after:
        step_dir = step_dir + wd * p
    p_new = p - lr * step_dir
    skip = noop > 0.0
    po_ref[...] = jnp.where(skip, p, p_new).astype(po_ref.dtype)
    bo_ref[...] = jnp.where(skip, buf, buf_new).astype(bo_ref.dtype)


def fused_sgd_flat(p, g, buf, *, lr, momentum, dampening, weight_decay,
                   nesterov=False, wd_after_momentum=False,
                   first_run=False, noop_flag=0.0, grad_scale=1.0):
    """Fused SGD step, torch-SGD semantics.

    Parity: ``amp_C.multi_tensor_sgd`` (csrc/multi_tensor_sgd_kernel.cu) as
    driven by ``apex/optimizers/fused_sgd.py :: FusedSGD``, including the
    ``wd_after_momentum`` decay-placement flag.
    """
    hp = jnp.stack([
        jnp.asarray(lr, jnp.float32),
        jnp.asarray(momentum, jnp.float32),
        jnp.asarray(dampening, jnp.float32),
        jnp.asarray(weight_decay, jnp.float32),
        jnp.asarray(1.0 if first_run else 0.0, jnp.float32)
        if isinstance(first_run, bool)
        else jnp.asarray(first_run, jnp.float32),
        jnp.asarray(noop_flag, jnp.float32),
        jnp.asarray(grad_scale, jnp.float32),
    ])
    p2, n = p, p.shape[0]
    g2 = g
    b2 = buf
    po, bo = pl.pallas_call(
        functools.partial(_sgd_kernel, bool(nesterov),
                          bool(wd_after_momentum)),
        grid=(_grid(p2),),
        in_specs=[_vspec(), _vspec(), _vspec(), _sspec()],
        out_specs=[_vspec(), _vspec()],
        out_shape=[
            jax.ShapeDtypeStruct(p2.shape, p2.dtype),
            jax.ShapeDtypeStruct(b2.shape, b2.dtype),
        ],
        input_output_aliases={0: 0, 2: 1},
        compiler_params=_PAR,
        interpret=interpret_mode(),
        name="apex_sgd_update",
    )(p2, g2, b2, hp)
    return po, bo


# ---------------------------------------------------------------------------
# LAMB phase 1 (elementwise Adam-style direction; trust ratio applied later)
# ---------------------------------------------------------------------------

def _lamb1_kernel(p_ref, g_ref, m_ref, v_ref, hp_ref, mo_ref, vo_ref, u_ref):
    b1, b2, eps, wd = hp_ref[0], hp_ref[1], hp_ref[2], hp_ref[3]
    inv_bc1, inv_sqrt_bc2, gscale = hp_ref[4], hp_ref[5], hp_ref[6]
    beta3 = hp_ref[7]      # 1-b1 normally; 1.0 when grad_averaging=False
    noop = hp_ref[8]
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32) * gscale
    m = m_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    m_new = b1 * m + beta3 * g
    v_new = b2 * v + (1.0 - b2) * g * g
    u = (m_new * inv_bc1) / (jnp.sqrt(v_new) * inv_sqrt_bc2 + eps) + wd * p
    # u is written unpredicated: its value on a skipped step is never used
    skip = noop > 0.0
    mo_ref[...] = jnp.where(skip, m, m_new).astype(mo_ref.dtype)
    vo_ref[...] = jnp.where(skip, v, v_new).astype(vo_ref.dtype)
    u_ref[...] = u.astype(u_ref.dtype)


def fused_lamb_phase1_flat(p, g, m, v, *, beta1, beta2, eps, weight_decay,
                           step, bias_correction=True, grad_scale=1.0,
                           grad_averaging=True, noop_flag=0.0):
    """LAMB stage 1: moments + raw update direction ``u``.

    Parity: ``amp_C.multi_tensor_lamb_stage_1`` / the fused
    ``multi_tensor_lamb.cu``; stage 2 (per-tensor trust ratio apply) happens
    at the optimizer level where tensor boundaries are known.
    ``noop_flag`` > 0 (overflow skip) returns ``m`` and ``v`` unchanged,
    in place: the moment buffers are aliased to the outputs and the
    predicate sits inside the kernel, so a caller must NOT select between
    old and new moments afterwards (a late read of the old buffer costs a
    whole-buffer copy of each).  ``u`` is not predicated — on a skipped
    step it may hold inf/nan and the caller selects the parameter.
    Returns (m, v, u).
    """
    if bias_correction:
        t = jnp.asarray(step, jnp.float32)
        inv_bc1 = 1.0 / (1.0 - jnp.power(jnp.float32(beta1), t))
        inv_sqrt_bc2 = jax.lax.rsqrt(1.0 - jnp.power(jnp.float32(beta2), t))
    else:
        inv_bc1 = jnp.float32(1.0)
        inv_sqrt_bc2 = jnp.float32(1.0)
    hp = jnp.stack([
        jnp.asarray(beta1, jnp.float32),
        jnp.asarray(beta2, jnp.float32),
        jnp.asarray(eps, jnp.float32),
        jnp.asarray(weight_decay, jnp.float32),
        jnp.asarray(inv_bc1, jnp.float32),
        jnp.asarray(inv_sqrt_bc2, jnp.float32),
        jnp.asarray(grad_scale, jnp.float32),
        jnp.asarray((1.0 - beta1) if grad_averaging else 1.0, jnp.float32),
        jnp.asarray(noop_flag, jnp.float32),
    ])
    p2, n = p, p.shape[0]
    g2 = g
    m2 = m
    v2 = v
    mo, vo, u = pl.pallas_call(
        _lamb1_kernel,
        grid=(_grid(p2),),
        in_specs=[_vspec(), _vspec(), _vspec(), _vspec(), _sspec()],
        out_specs=[_vspec(), _vspec(), _vspec()],
        out_shape=[
            jax.ShapeDtypeStruct(m2.shape, m2.dtype),
            jax.ShapeDtypeStruct(v2.shape, v2.dtype),
            jax.ShapeDtypeStruct(p2.shape, jnp.float32),
        ],
        input_output_aliases={2: 0, 3: 1},
        compiler_params=_PAR,
        interpret=interpret_mode(),
        name="apex_lamb_stage1",
    )(p2, g2, m2, v2, hp)
    return (mo, vo, u)
