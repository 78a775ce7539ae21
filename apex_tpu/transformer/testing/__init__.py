"""Test-only model zoo + harness (reference: ``apex/transformer/testing/``).

The reference ships minimal Megatron GPT/BERT models
(``standalone_gpt.py``/``standalone_bert.py``) built on the real TP/PP
layers so distributed tests exercise a genuine tiny transformer, not mocks.
Same here: :mod:`standalone_gpt` / :mod:`standalone_bert` are flax models
over ``apex_tpu.transformer.tensor_parallel`` layers and the Pallas flash
attention kernel, runnable on a CPU mesh or real TPU.
"""
from .commons import IdentityLayer, initialize_distributed, set_random_seed
from .standalone_gpt import GPTConfig, GPTModel, gpt_model_provider
from .standalone_bert import BertConfig, BertModel, bert_model_provider
from .standalone_llama import LlamaConfig, LlamaModel, llama_model_provider
from .standalone_laguna import (LagunaConfig, LagunaModel,
                                laguna_model_provider)
from .batch_sampler import (
    MegatronPretrainingSampler,
    MegatronPretrainingRandomSampler,
)

__all__ = [
    "IdentityLayer",
    "initialize_distributed",
    "set_random_seed",
    "GPTConfig",
    "GPTModel",
    "gpt_model_provider",
    "BertConfig",
    "BertModel",
    "bert_model_provider",
    "LlamaConfig",
    "LlamaModel",
    "llama_model_provider",
    "LagunaConfig",
    "LagunaModel",
    "laguna_model_provider",
    "MegatronPretrainingSampler",
    "MegatronPretrainingRandomSampler",
]
