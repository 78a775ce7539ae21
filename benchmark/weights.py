"""Seeded weights, made on the device in one jitted call.

The benchmark makes the weights — not the program's ``init`` — so that the
plain reference can be handed the very same numbers without taking anything
the program has made.  Every matrix, embedding and bias is normal(0, 0.02);
a norm's scale (a rank-1 leaf named ``weight`` under a ``*norm``: LayerNorm
or RMSNorm) is 1 + normal(0, 0.02), so every leaf has a non-zero norm and
gradient path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

STD = 0.02


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**63: the low 31 bits seed
    the key and the rest is folded in (a bare PRNGKey overflows int32)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _is_norm_scale(path, leaf) -> bool:
    names = [str(getattr(p, "key", p)) for p in path]
    return (names[-1] == "weight" and leaf.ndim == 1
            and names[-2].endswith("norm"))


def make(shapes, seed: int):
    """A tree of arrays shaped and typed like ``shapes`` (a tree of
    ``ShapeDtypeStruct``), from ``seed``, in one compiled program."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        out = []
        for i, (path, leaf) in enumerate(flat):
            x = STD * jax.random.normal(jax.random.fold_in(key, i),
                                        leaf.shape, jnp.float32)
            if _is_norm_scale(path, leaf):
                x = 1.0 + x
            out.append(x.astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(seed_key(seed))
