"""Shared helpers for apex_tpu.

Pallas kernels compile natively on TPU and run in interpret mode on the
CPU platform (the test suite), mirroring the reference's "fused kernel vs
eager fallback" dispatch (e.g. ``apex/normalization/fused_layer_norm.py ::
FusedLayerNorm`` falls back to ``F.layer_norm`` on CPU tensors).
"""
from __future__ import annotations

import jax
import jax.flatten_util
import jax.numpy as jnp


def interpret_mode() -> bool:
    """Whether ``pallas_call`` runs in interpret mode: True on platform
    ``cpu``, False on ``tpu``.  Any other platform — and a backend that
    fails to come up, which ``jax.default_backend()`` raises for — is an
    error, never a quiet switch to the interpreter: a kernel that
    interprets on a machine with a chip hides the chip."""
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"apex_tpu Pallas kernels run compiled on 'tpu' and interpreted "
        f"on 'cpu'; the default JAX backend is {platform!r}")


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(x: int, multiple: int) -> int:
    return cdiv(x, multiple) * multiple


def pad_rows(x: jax.Array, multiple: int) -> tuple[jax.Array, int]:
    """Zero-pad the leading dim of 2D ``x`` to a multiple; returns (padded, orig_rows)."""
    rows = x.shape[0]
    padded = round_up(max(rows, 1), multiple)
    if padded != rows:
        x = jnp.pad(x, ((0, padded - rows), (0, 0)))
    return x, rows


def tree_ravel(tree):
    """Flatten a pytree of arrays into one 1-D buffer plus an unravel fn.

    TPU-native analog of the reference's flat-buffer pack/unpack
    (``csrc/flatten_unflatten.cpp :: apex_C.flatten/unflatten``).
    """
    return jax.flatten_util.ravel_pytree(tree)
