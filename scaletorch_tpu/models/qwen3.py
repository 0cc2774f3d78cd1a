"""Qwen3 dense model — Llama variant with QK-norm and tied embeddings.

Parity with reference scaletorch/models/model_qwen3.py:139-350: explicit
``head_dim`` from config (:148), per-head q/k RMSNorm before RoPE
(:179-180, 209-210), ``tie_word_embeddings`` (:297-298), rope_theta
default 1e6-class values. The decoder body is shared with Llama
(models/llama.py) via the ``qk_norm`` config flag — one implementation to
optimise, two model identities for API/checkpoint parity.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax

from scaletorch_tpu.models import llama as _llama
from scaletorch_tpu.models.llama import LlamaConfig, Params


@dataclass(frozen=True)
class Qwen3Config(LlamaConfig):
    # Qwen3-0.6B-ish defaults; override from HF config in practice.
    vocab_size: int = 151936
    hidden_size: int = 1024
    intermediate_size: int = 3072
    num_hidden_layers: int = 28
    num_attention_heads: int = 16
    num_key_value_heads: int = 8
    head_dim: int = 128  # explicit, != hidden // heads (model_qwen3.py:148)
    rope_theta: float = 1000000.0
    tie_word_embeddings: bool = True
    qk_norm: bool = True


def init_params(key: jax.Array, cfg: Qwen3Config) -> Params:
    # a function of THIS module: the benchmark harness takes a config's
    # initialiser from the module of its class and holds the two to one
    return _llama.init_params(key, cfg)


# the Llama forwards themselves (qk_norm rides the config flag): a step
# built for either family traces one function
forward = _llama.forward
forward_cached = _llama.forward_cached


def config_from_args(args, common: dict) -> Qwen3Config:
    return Qwen3Config(qk_norm=True, **common)


def config_from_hf(args, hf_config, overrides: dict) -> Qwen3Config:
    return Qwen3Config.from_hf(hf_config, **overrides)


class Qwen3(_llama.Llama):
    config_cls = Qwen3Config
