"""apex_tpu — a TPU-native framework with the capabilities of NVIDIA Apex.

Reference: shawnwang18/apex (fork of NVIDIA/apex).  Layer map (see SURVEY.md):

* ``apex_tpu.ops``            — L0': Pallas TPU kernels + pure-jnp oracle twins
  (replaces ``csrc/`` CUDA: fused LayerNorm/RMSNorm, multi-tensor optimizer
  functors, scaled-masked softmax, RoPE, fused attention, xentropy).
* ``apex_tpu.multi_tensor_apply`` — ``MultiTensorApply`` parity shim.
* ``apex_tpu.optimizers``     — FusedAdam / FusedLAMB / FusedSGD / FusedNovoGrad
  / FusedAdagrad over the fused-update kernel (reference: ``apex/optimizers``),
  plus ``optimizers.functional`` — the flat-native pure init/update core.
* ``apex_tpu.train_step``     — flat-native train-step builder: forward,
  backward, loss scaling, and the fused update as ONE donated XLA program.
* ``apex_tpu.normalization``  — FusedLayerNorm / FusedRMSNorm modules
  (reference: ``apex/normalization/fused_layer_norm.py``).
* ``apex_tpu.amp``            — opt-level O0–O3 mixed precision with functional
  dynamic loss scaling (reference: ``apex/amp``).
* ``apex_tpu.fp16_utils``     — legacy manual mixed-precision helpers.
* ``apex_tpu.parallel``       — DistributedDataParallel (bucketed psum),
  SyncBatchNorm (psum Welford), LARC (reference: ``apex/parallel``).
* ``apex_tpu.transformer``    — Megatron-style TP/PP/SP toolkit on
  jax.sharding meshes (reference: ``apex/transformer``).
* ``apex_tpu.contrib``        — DistributedFusedAdam (ZeRO), clip_grad,
  xentropy, fmha/flash attention, groupnorm, focal loss, ...
* ``apex_tpu.models``         — flagship model zoo (GPT, BERT) built on the
  transformer toolkit (reference: ``apex/transformer/testing/standalone_*``).

Subpackages are imported lazily to keep ``import apex_tpu`` cheap.
"""

import importlib

__version__ = "0.1.0"

_SUBMODULES = (
    "ops",
    "multi_tensor_apply",
    "optimizers",
    "normalization",
    "amp",
    "fp16_utils",
    "parallel",
    "transformer",
    "contrib",
    "models",
    "train_step",
    "utils",
)


def __getattr__(name):
    if name in _SUBMODULES:
        try:
            mod = importlib.import_module(f"{__name__}.{name}")
        except ModuleNotFoundError as e:
            raise AttributeError(
                f"module {__name__!r} has no attribute {name!r}") from e
        globals()[name] = mod
        return mod
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(list(globals()) + list(_SUBMODULES))
