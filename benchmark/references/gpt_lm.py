"""Plain reference of the ``gpt3-1.3b-serve`` configuration: the decoder of
GPT-2/GPT-3 (Radford et al. 2019; Brown et al. 2020, section 2.1) — learned
token and position embeddings, a causal pre-LN transformer stack, a final
LayerNorm and logits through the transposed token embedding.  One full
forward pass over a whole sequence; no cache, no paging, no batching.

Weights: ``{"wte" [vocab, h], "wpe" [positions, h], "lnf_g", "lnf_b",
"layers": {<LAYER_KEYS>: [L, ...]}}``, float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import transformer as T


@functools.partial(jax.jit, static_argnames=("heads", "quant"))
def logits(weights, tokens, *, heads: int, quant=None):
    """Float32 logits [seq, vocab] for one sequence ``tokens`` [seq]."""
    s = tokens.shape[0]
    x = weights["wte"][tokens] + weights["wpe"][:s]
    x = T.stack(x[None], weights["layers"], heads, True, quant)[0]
    x = T.layer_norm(x, weights["lnf_g"], weights["lnf_b"])
    return T.matmul(x, weights["wte"], quant)
