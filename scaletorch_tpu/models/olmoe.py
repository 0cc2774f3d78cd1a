"""OLMoE — a Qwen3-MoE-shaped sparse decoder with three differences.

HF ``OlmoeForCausalLM`` (allenai/OLMoE-1B-7B-0125-Instruct): the block,
the layer stack, ``forward`` and ``forward_cached`` are
``models/qwen3_moe.py``'s; what the configuration states differently:

  * q/k RMSNorm over the WHOLE projection width, before the split into
    heads (``qk_norm_scope="projection"``: gains ``[heads*head_dim]`` and
    ``[kv_heads*head_dim]``; Qwen3 norms each head's 128);
  * the top-k router weights are kept as the softmax gave them
    (``norm_topk_prob=False``);
  * routing is dropless (``dropless=True``): OLMoE was trained without a
    capacity and the HF forward has none, so this is a property of the
    architecture, not a capacity factor for the user to guess.

``config.json`` names: ``intermediate_size`` is the expert width (there
is no dense MLP), ``num_experts``, ``num_experts_per_tok``,
``norm_topk_prob``, ``router_aux_loss_coef``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

from scaletorch_tpu.models import qwen3_moe as _moe
from scaletorch_tpu.models.qwen3_moe import Qwen3MoEConfig


@dataclass(frozen=True)
class OlmoeConfig(Qwen3MoEConfig):
    # OLMoE-1B-7B defaults (the published config.json)
    vocab_size: int = 50304
    hidden_size: int = 2048
    intermediate_size: int = 1024          # = moe_intermediate_size
    num_hidden_layers: int = 16
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    head_dim: Optional[int] = None         # hidden // heads = 128
    max_position_embeddings: int = 4096
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    qk_norm: bool = True
    qk_norm_scope: str = "projection"
    num_experts: int = 64
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 1024
    norm_topk_prob: bool = False
    aux_loss_coef: float = 0.01
    dropless: bool = True

    @classmethod
    def from_hf(cls, hf_config, **overrides) -> "OlmoeConfig":
        kw = dict(
            moe_intermediate_size=hf_config.intermediate_size,
            num_experts=hf_config.num_experts,
            num_experts_per_tok=hf_config.num_experts_per_tok,
            norm_topk_prob=getattr(hf_config, "norm_topk_prob", False),
            aux_loss_coef=getattr(hf_config, "router_aux_loss_coef", 0.01),
        )
        kw.update(overrides)
        return super().from_hf(hf_config, **kw)


# the Qwen3-MoE functions themselves: the differences ride the config
init_params = _moe.init_params
forward = _moe.forward
forward_cached = _moe.forward_cached


def config_from_args(args, common: dict) -> OlmoeConfig:
    """The published config.json names: ``intermediate_size`` is the
    expert width (OLMoE has no dense MLP)."""
    return OlmoeConfig(
        moe_intermediate_size=common["intermediate_size"],
        num_experts=args.num_experts,
        num_experts_per_tok=args.num_experts_per_tok,
        aux_loss_coef=args.router_aux_loss_coef,
        z_loss_coef=args.router_z_loss_coef,
        **_moe.expert_share_from_args(args),
        **common,
    )


def config_from_hf(args, hf_config, overrides: dict) -> OlmoeConfig:
    return OlmoeConfig.from_hf(
        hf_config, z_loss_coef=args.router_z_loss_coef,
        **_moe.expert_share_from_args(args), **overrides)


class Olmoe(_moe.Qwen3MoE):
    config_cls = OlmoeConfig
