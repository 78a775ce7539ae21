"""apex_tpu.ops — Pallas TPU kernels + pure-jnp oracle twins.

This is the rebuild of the reference's native kernel layer (``csrc/`` and
``apex/contrib/csrc/``).  Every fused kernel ships with a jnp reference
implementation (the "oracle"); tests assert kernel ≡ oracle, mirroring the
reference's fused-vs-eager test pattern.
"""
from .layer_norm import (
    layer_norm,
    rms_norm,
    layer_norm_reference,
    rms_norm_reference,
)
from .fused_update import (
    fused_scale,
    fused_axpby,
    fused_l2norm,
    fused_adam_flat,
    fused_adagrad_flat,
    fused_sgd_flat,
    fused_lamb_phase1_flat,
    adam_reference,
)
from .attention import decode_attention, flash_attention, mha_reference
from .paged_attention import paged_decode_attention, paged_work_list
from .ring_attention import ring_attention, ring_attention_reference
from .ulysses_attention import ulysses_attention
from .xentropy import softmax_cross_entropy_loss, xentropy_reference
from .fused_lm_xent import (
    fused_lm_head_cross_entropy,
    fused_lm_head_vocab_parallel_cross_entropy,
    lm_head_xentropy_reference,
)

__all__ = [
    "ring_attention",
    "ring_attention_reference",
    "ulysses_attention",
    "layer_norm",
    "rms_norm",
    "layer_norm_reference",
    "rms_norm_reference",
    "fused_scale",
    "fused_axpby",
    "fused_l2norm",
    "fused_adam_flat",
    "fused_adagrad_flat",
    "fused_sgd_flat",
    "fused_lamb_phase1_flat",
    "adam_reference",
    "flash_attention",
    "decode_attention",
    "paged_decode_attention",
    "paged_work_list",
    "mha_reference",
    "softmax_cross_entropy_loss",
    "xentropy_reference",
    "fused_lm_head_cross_entropy",
    "fused_lm_head_vocab_parallel_cross_entropy",
    "lm_head_xentropy_reference",
]
