"""What a ``model_type`` is: one row per family.

A family is one module of this package: its config class,
``config_from_args(args, common)`` (the class filled from the launch
arguments, with the family's own refusals), ``init_params``, ``forward``
and ``forward_cached``. The launch arguments (``build_model_config``),
the trainer, the step programs (``inference.decode``), the server and
the tests' oracle ask here. Nothing of ``trainer``, ``inference`` or
``config`` is imported: every arrow points down to this module.
"""

from __future__ import annotations

import dataclasses
from types import ModuleType
from typing import Dict, Optional

import jax.numpy as jnp

from scaletorch_tpu.models import (
    afmoe,
    gpt_moe,
    granite_moe_hybrid,
    jamba,
    kimi_linear,
    llama,
    mimo_v2_flash,
    olmo_hybrid,
    olmoe,
    pangu_ultra_moe,
    qwen3,
    qwen3_moe,
    qwen3_next,
)

_STATE_CARRYING = (
    "its state-carrying layers have no sharding rules (tp / cp / pp / "
    "ep), no loss wiring and no HF weight loading; the family is served "
    "(scripts/serve.py --preset ...)")


@dataclasses.dataclass(frozen=True)
class Family:
    module: ModuleType
    config_cls: type
    # the cached forward counts what it routes (``return_routing``); in
    # the trainer, the expert-parallel wiring
    counts_routing: bool = False
    # why the trainer has no step for it (None: it trains)
    untrained: Optional[str] = None
    # HF auto-fill (the module's ``config_from_hf``) and weight loading
    # are written for it
    loads_hf: bool = False
    # a row of a prefill call names its slot (``slot_ids``): a call's
    # rows are its admitted prompts and the family's one prefill program
    # is one row (``inference.decode.SlotRows``). Written and
    # parity-tested for the two delta-rule families, for kimi_linear
    # (whose row also writes latent rows at its pages) and for
    # mimo_v2_flash (``RingKVIO``'s table from slot ids: its row writes
    # the pool at its pages and the rings at its slot) and for
    # granitemoehybrid (its Mamba-2 state and tail). The same write
    # would serve jamba's state, and the two other by-slot shapes are a
    # forward each (afmoe: ``RingKVIO``'s table from slot ids;
    # pangu_ultra_moe: a page-addressed long-prompt shape), but their
    # cells' cost functions charge ``max_slots`` rows to every prefill
    # call they find, so a one-row call would read 237-348 % of a
    # roofline there: the ``benchmark`` PR goes first (ROADMAP B1 (r)),
    # then each is a flip, and the column goes with ROADMAP S2 (e)
    rows_name_slots: bool = False


FAMILIES: Dict[str, Family] = {
    "llama": Family(llama, llama.LlamaConfig, loads_hf=True),
    "qwen3": Family(qwen3, qwen3.Qwen3Config, loads_hf=True),
    "qwen3_moe": Family(qwen3_moe, qwen3_moe.Qwen3MoEConfig,
                        counts_routing=True, loads_hf=True),
    "olmoe": Family(olmoe, olmoe.OlmoeConfig, counts_routing=True,
                    loads_hf=True),
    "olmo_hybrid": Family(olmo_hybrid, olmo_hybrid.OlmoHybridConfig,
                          untrained=_STATE_CARRYING, rows_name_slots=True),
    "qwen3_next": Family(qwen3_next, qwen3_next.Qwen3NextConfig,
                         counts_routing=True, untrained=_STATE_CARRYING,
                         rows_name_slots=True),
    "afmoe": Family(
        afmoe, afmoe.AfmoeConfig, counts_routing=True,
        untrained=(
            "its window layers and its sigmoid router have no sharding "
            "rules (tp / cp / pp / ep), load_balance_coeff names a loss "
            "and a bias update whose equations its config.json does not "
            "give, and there is no HF weight loading; the family is "
            "served (scripts/serve.py --preset trinity-mini)")),
    "jamba": Family(
        jamba, jamba.JambaConfig,
        untrained=(
            "its selective scan has no backward (the Mosaic kernel is "
            "forward only, and the chunked XLA form under jax.grad keeps "
            "every chunk's state), its Mamba layers have no sharding "
            "rules (tp / cp / pp), and there is no loss wiring and no HF "
            "weight loading; the family is served (scripts/serve.py "
            "--preset jamba2-3b)")),
    "pangu_ultra_moe": Family(
        pangu_ultra_moe, pangu_ultra_moe.PanguUltraMoEConfig,
        counts_routing=True,
        untrained=(
            "its latent projections (q_a / q_b / kv_a / kv_b) have no "
            "sharding rules (tp / cp / pp), its experts no exchange "
            "(ep), num_nextn_predict_layers names a multi-token "
            "prediction module and loss that are not built, and there "
            "is no HF weight loading; the family is served "
            "(scripts/serve.py --preset openpangu-ultra-moe-718b)")),
    "kimi_linear": Family(
        kimi_linear, kimi_linear.KimiLinearConfig, counts_routing=True,
        rows_name_slots=True,
        untrained=(
            "its per-channel delta-rule scan has no backward kernel and "
            "no loss wiring, its KDA and latent layers have no sharding "
            "rules (tp / cp / pp), its experts no exchange (ep), and "
            "there is no HF weight loading; the family is served "
            "(scripts/serve.py --preset kimi-linear-48b-a3b)")),
    "mimo_v2_flash": Family(
        mimo_v2_flash, mimo_v2_flash.MimoV2FlashConfig, counts_routing=True,
        rows_name_slots=True,
        untrained=(
            "the flash backward knows neither a window nor a sink nor a "
            "value narrower than its key, its two kinds of layer have no "
            "sharding rules (tp / cp / pp), its experts no exchange (ep), "
            "its multi-token-prediction layers and their loss are not "
            "built, and there is no HF weight loading; the family is "
            "served (scripts/serve.py --preset mimo-v2-flash)")),
    "granitemoehybrid": Family(
        granite_moe_hybrid, granite_moe_hybrid.GraniteMoeHybridConfig,
        counts_routing=True, rows_name_slots=True,
        untrained=(
            "its Mamba-2 scan has no backward (the chunked form under "
            "jax.grad keeps every chunk's decay matrices), its Mamba-2 "
            "layers have no sharding rules (tp / cp / pp), its experts no "
            "exchange (ep), and there is no loss wiring and no HF weight "
            "loading; the family is served (scripts/serve.py --preset "
            "granite-4.0-h-small)")),
    # served and tested through its config class; trains via its example
    "gpt_moe": Family(gpt_moe, gpt_moe.GPTMoEConfig),
}
_BY_CLASS = {row.config_cls: row for row in FAMILIES.values()}
_DTYPE = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
          "float16": jnp.float16}


def family_of(cfg) -> Family:
    """The row of a config OBJECT, by its exact class: every
    ``model_type`` has a class of its own, so no subclass is taken for
    its base whatever the order of the rows."""
    try:
        return _BY_CLASS[type(cfg)]
    except KeyError:
        raise TypeError(
            f"no family known for config {type(cfg).__name__} "
            "(models/families.py)") from None


def build_model_config(args):
    """A model config from the launch arguments
    (``ScaleTorchTPUArguments``): the keys every family reads, HF
    AutoConfig auto-fill when ``model_name_or_path`` is set, and the
    family's own ``config_from_args``."""
    row = FAMILIES.get(args.model_type)
    if row is None:
        if args.model_type in ("lenet", "mingpt"):  # the examples' tier
            gpt_moe.config_from_args(args, {})
        raise ValueError(f"unknown model_type {args.model_type!r}")
    overrides = dict(dtype=_DTYPE[args.dtype],
                     param_dtype=_DTYPE[args.param_dtype])
    for name in ("embed_init_std", "routed_expert_init_scale",
                 "query_init_scale", "sink_init_mean",
                 "ssm_decay_init_scale"):
        # properties of random weights: a family whose initialiser reads
        # one has the field
        if getattr(args, name) is None:
            continue
        if name not in row.config_cls.__dataclass_fields__:
            raise NotImplementedError(
                f"--{name} with model_type {args.model_type!r}: "
                "its config class has no such field (no initialiser of "
                "the family reads it)")
        overrides[name] = getattr(args, name)
    if args.model_name_or_path:
        if not row.loads_hf:
            raise NotImplementedError(
                f"{args.model_type} from --model_name_or_path: HF config "
                "auto-fill and weight loading are not written for this "
                "family; give its sizes by their config.json names "
                "(models/presets.py)")
        from transformers import AutoConfig

        return row.module.config_from_hf(
            args, AutoConfig.from_pretrained(args.model_name_or_path),
            overrides)
    return row.module.config_from_args(args, dict(
        vocab_size=args.vocab_size,
        hidden_size=args.hidden_size,
        intermediate_size=args.intermediate_size or 4 * args.hidden_size,
        num_hidden_layers=args.num_hidden_layers,
        num_attention_heads=args.num_attention_heads,
        num_key_value_heads=(args.num_key_value_heads
                             or args.num_attention_heads),
        head_dim=args.head_dim,
        max_position_embeddings=args.max_position_embeddings,
        rope_theta=args.rope_theta,
        rms_norm_eps=args.rms_norm_eps,
        tie_word_embeddings=args.tie_word_embeddings,
        **overrides,
    ))
