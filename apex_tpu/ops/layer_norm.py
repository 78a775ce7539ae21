"""Fused LayerNorm / RMSNorm — Pallas TPU kernels with pure-jnp oracles.

TPU-native rebuild of the reference's layer-norm CUDA extension
(``csrc/layer_norm_cuda.cpp`` dispatch + ``csrc/layer_norm_cuda_kernel.cu ::
cuApplyLayerNorm / cuComputeGradInput / cuComputePartGradGammaBeta`` and the
RMSNorm variants), surfaced in Python by
``apex/normalization/fused_layer_norm.py :: FusedLayerNormAffineFunction``.

Design notes (TPU-first, not a translation):

* Rows live in VMEM one block at a time; statistics are computed in fp32
  registers in a single pass over the block (the CUDA Welford machinery exists
  to cooperate across threads — unnecessary here, the VPU reduces a whole
  (block_rows, hidden) tile at once).
* The backward kernel *recomputes* mean/rstd from the saved input instead of
  saving them forward (the reference's ``memory_efficient=True`` mode) — on
  TPU this trades a tiny amount of VPU math for not writing two fp32 vectors
  per row to HBM, a win since LayerNorm is bandwidth-bound.
* dγ/dβ are accumulated across the sequential TPU grid into a single (1, H)
  fp32 output (the CUDA version needs a two-stage partial-sum reduction across
  thread blocks; the TPU grid is sequential so a running accumulate works).
* Hidden sizes that are not lane-aligned (H % 128 != 0) dispatch to the jnp
  reference path — mirroring the reference's CPU fallback behavior
  (``FusedLayerNorm`` falls back to ``F.layer_norm`` off-GPU).
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.utils import interpret_mode, pad_rows, round_up

__all__ = [
    "layer_norm",
    "rms_norm",
    "layer_norm_reference",
    "rms_norm_reference",
]

#: pallas_audit registration (analysis hook only, no behavior change):
#: both kernels reduce over the hidden dim — mean/var (fwd) and dw/db
#: partials (bwd) must accumulate in fp32 (APX302).
PALLAS_AUDIT = {
    "_ln_fwd_kernel": {"reduction": True},
    "_ln_bwd_kernel": {"reduction": True},
}

_MAX_BLOCK_ROWS = 512
_VMEM_BUDGET_BYTES = 3 * 1024 * 1024  # per fp32 operand tile


def _block_rows(hidden: int) -> int:
    br = _VMEM_BUDGET_BYTES // (hidden * 4)
    return min(_MAX_BLOCK_ROWS, (br // 8) * 8)


def _pallas_ok(hidden: int) -> bool:
    # Need at least one (8, hidden) fp32 tile inside the per-operand budget;
    # otherwise fall back to the jnp path rather than overflow VMEM.
    return hidden % 128 == 0 and _block_rows(hidden) >= 8


# ---------------------------------------------------------------------------
# jnp oracles (the "eager fallback" twins; also the test oracle)
# ---------------------------------------------------------------------------

def layer_norm_reference(x, weight=None, bias=None, eps: float = 1e-5):
    """Pure-jnp LayerNorm over the last axis (oracle for the Pallas kernel)."""
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    xc = x32 - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    y = xc * jax.lax.rsqrt(var + eps)
    if weight is not None:
        y = y * weight.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def rms_norm_reference(x, weight=None, eps: float = 1e-5):
    """Pure-jnp RMSNorm over the last axis (oracle for the Pallas kernel)."""
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(ms + eps)
    if weight is not None:
        y = y * weight.astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------

def _ln_fwd_kernel(eps, rms, x_ref, w_ref, b_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    if rms:
        ms = jnp.mean(x * x, axis=-1, keepdims=True)
        xhat = x * jax.lax.rsqrt(ms + eps)
    else:
        mean = jnp.mean(x, axis=-1, keepdims=True)
        xc = x - mean
        var = jnp.mean(xc * xc, axis=-1, keepdims=True)
        xhat = xc * jax.lax.rsqrt(var + eps)
    y = xhat * w_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


def _ln_bwd_kernel(eps, rms, x_ref, w_ref, dy_ref, dx_ref, dw_ref, db_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    x = x_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    if rms:
        ms = jnp.mean(x * x, axis=-1, keepdims=True)
        rstd = jax.lax.rsqrt(ms + eps)
        xhat = x * rstd
        wdy = dy * w
        c2 = jnp.mean(wdy * xhat, axis=-1, keepdims=True)
        dx = (wdy - xhat * c2) * rstd
    else:
        mean = jnp.mean(x, axis=-1, keepdims=True)
        xc = x - mean
        var = jnp.mean(xc * xc, axis=-1, keepdims=True)
        rstd = jax.lax.rsqrt(var + eps)
        xhat = xc * rstd
        wdy = dy * w
        c1 = jnp.mean(wdy, axis=-1, keepdims=True)
        c2 = jnp.mean(wdy * xhat, axis=-1, keepdims=True)
        dx = (wdy - c1 - xhat * c2) * rstd
    dx_ref[...] = dx.astype(dx_ref.dtype)
    dw_ref[...] += jnp.sum(dy * xhat, axis=0, keepdims=True)
    db_ref[...] += jnp.sum(dy, axis=0, keepdims=True)


def _fwd_2d(x2, w, b, eps, rms):
    rows, hidden = x2.shape
    br = _block_rows(hidden)
    x2p, orig = pad_rows(x2, br)
    grid = x2p.shape[0] // br
    w2 = w.reshape(1, hidden)
    b2 = b.reshape(1, hidden)
    out = pl.pallas_call(
        functools.partial(_ln_fwd_kernel, eps, rms),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((br, hidden), lambda i: (i, 0)),
            pl.BlockSpec((1, hidden), lambda i: (0, 0)),
            pl.BlockSpec((1, hidden), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, hidden), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2p.shape, x2.dtype),
        interpret=interpret_mode(),
        name="apex_layer_norm_fwd",
    )(x2p, w2, b2)
    return out[:orig]


def _bwd_2d(x2, w, dy2, eps, rms):
    rows, hidden = x2.shape
    br = _block_rows(hidden)
    x2p, orig = pad_rows(x2, br)
    dy2p, _ = pad_rows(dy2, br)
    grid = x2p.shape[0] // br
    w2 = w.reshape(1, hidden)
    dx, dw, db = pl.pallas_call(
        functools.partial(_ln_bwd_kernel, eps, rms),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((br, hidden), lambda i: (i, 0)),
            pl.BlockSpec((1, hidden), lambda i: (0, 0)),
            pl.BlockSpec((br, hidden), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, hidden), lambda i: (i, 0)),
            pl.BlockSpec((1, hidden), lambda i: (0, 0)),
            pl.BlockSpec((1, hidden), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x2p.shape, x2.dtype),
            jax.ShapeDtypeStruct((1, hidden), jnp.float32),
            jax.ShapeDtypeStruct((1, hidden), jnp.float32),
        ],
        interpret=interpret_mode(),
        name="apex_layer_norm_bwd",
    )(x2p, w2, dy2p)
    return dx[:orig], dw.reshape(hidden), db.reshape(hidden)


# ---------------------------------------------------------------------------
# custom_vjp public entry points
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _layer_norm_affine(x2, w, b, eps):
    if _pallas_ok(x2.shape[-1]):
        return _fwd_2d(x2, w, b, eps, rms=False)
    return layer_norm_reference(x2, w, b, eps)


def _layer_norm_affine_fwd(x2, w, b, eps):
    return _layer_norm_affine(x2, w, b, eps), (x2, w)


def _layer_norm_affine_bwd(eps, res, dy2):
    x2, w = res
    if _pallas_ok(x2.shape[-1]):
        dx, dw, db = _bwd_2d(x2, w, dy2, eps, rms=False)
    else:
        _, vjp = jax.vjp(lambda x, w_, b_: layer_norm_reference(x, w_, b_, eps),
                         x2, w, jnp.zeros_like(w))
        dx, dw, db = vjp(dy2)
    return dx, dw.astype(w.dtype), db.astype(w.dtype)


_layer_norm_affine.defvjp(_layer_norm_affine_fwd, _layer_norm_affine_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rms_norm_affine(x2, w, eps):
    if _pallas_ok(x2.shape[-1]):
        zeros = jnp.zeros_like(w)
        return _fwd_2d(x2, w, zeros, eps, rms=True)
    return rms_norm_reference(x2, w, eps)


def _rms_norm_affine_fwd(x2, w, eps):
    return _rms_norm_affine(x2, w, eps), (x2, w)


def _rms_norm_affine_bwd(eps, res, dy2):
    x2, w = res
    if _pallas_ok(x2.shape[-1]):
        dx, dw, _ = _bwd_2d(x2, w, dy2, eps, rms=True)
    else:
        _, vjp = jax.vjp(lambda x, w_: rms_norm_reference(x, w_, eps), x2, w)
        dx, dw = vjp(dy2)
    return dx, dw.astype(w.dtype)


_rms_norm_affine.defvjp(_rms_norm_affine_fwd, _rms_norm_affine_bwd)


def _flatten_normalized(x, normalized_shape):
    if isinstance(normalized_shape, int):
        normalized_shape = (normalized_shape,)
    normalized_shape = tuple(normalized_shape)
    n_norm = 1
    for d in normalized_shape:
        n_norm *= d
    if tuple(x.shape[-len(normalized_shape):]) != normalized_shape:
        raise ValueError(
            f"normalized_shape {normalized_shape} does not match input trailing "
            f"dims {x.shape}")
    lead = x.shape[: x.ndim - len(normalized_shape)]
    return x.reshape(-1, n_norm), lead, normalized_shape, n_norm


def layer_norm(x, weight=None, bias=None, *, normalized_shape=None,
               eps: float = 1e-5):
    """Fused LayerNorm over ``normalized_shape`` (defaults to the last axis).

    API parity: ``apex.normalization.fused_layer_norm :: fused_layer_norm`` /
    ``FusedLayerNormAffineFunction.apply``.  Differentiable (custom_vjp with a
    fused backward kernel).
    """
    if normalized_shape is None:
        normalized_shape = (x.shape[-1],)
    x2, lead, nshape, n = _flatten_normalized(x, normalized_shape)
    w = (weight.reshape(n) if weight is not None
         else jnp.ones((n,), jnp.float32))
    b = (bias.reshape(n) if bias is not None
         else jnp.zeros((n,), jnp.float32))
    out = _layer_norm_affine(x2, w, b, float(eps))
    return out.reshape(*lead, *nshape)


def rms_norm(x, weight=None, *, normalized_shape=None, eps: float = 1e-5):
    """Fused RMSNorm (parity: ``fused_rms_norm`` / ``FusedRMSNormAffineFunction``)."""
    if normalized_shape is None:
        normalized_shape = (x.shape[-1],)
    x2, lead, nshape, n = _flatten_normalized(x, normalized_shape)
    w = (weight.reshape(n) if weight is not None
         else jnp.ones((n,), jnp.float32))
    out = _rms_norm_affine(x2, w, float(eps))
    return out.reshape(*lead, *nshape)
