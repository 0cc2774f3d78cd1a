"""Named architecture presets for benchmarks and tools.

The reference benchmarks against local HF checkout dirs
(scripts/benchmark_comprehensive.py:24 MODEL_ROOT + Qwen3-* names); the
TPU build runs hermetic synthetic-data benchmarks, so the architectures
are declared here directly (field values match the published HF configs
for Qwen/Qwen3-*; MoE matches Qwen/Qwen3-30B-A3B).

Each preset is a kwargs dict for ``ScaleTorchTPUArguments`` — pass
``**preset("qwen3-0.6b")`` plus run-shape fields.
"""

from __future__ import annotations

from typing import Any, Dict

_QWEN3_COMMON = dict(
    model_type="qwen3",
    vocab_size=151936,
    num_key_value_heads=8,
    head_dim=128,
    rope_theta=1e6,
    rms_norm_eps=1e-6,
    max_position_embeddings=40960,
)

MODEL_PRESETS: Dict[str, Dict[str, Any]] = {
    "qwen3-0.6b": dict(
        _QWEN3_COMMON,
        hidden_size=1024,
        intermediate_size=3072,
        num_hidden_layers=28,
        num_attention_heads=16,
        tie_word_embeddings=True,
    ),
    "qwen3-1.7b": dict(
        _QWEN3_COMMON,
        hidden_size=2048,
        intermediate_size=6144,
        num_hidden_layers=28,
        num_attention_heads=16,
        tie_word_embeddings=True,
    ),
    "qwen3-4b": dict(
        _QWEN3_COMMON,
        hidden_size=2560,
        intermediate_size=9728,
        num_hidden_layers=36,
        num_attention_heads=32,
        tie_word_embeddings=True,
    ),
    "qwen3-8b": dict(
        _QWEN3_COMMON,
        hidden_size=4096,
        intermediate_size=12288,
        num_hidden_layers=36,
        num_attention_heads=32,
        tie_word_embeddings=False,
    ),
    "qwen3-14b": dict(
        _QWEN3_COMMON,
        hidden_size=5120,
        intermediate_size=17408,
        num_hidden_layers=40,
        num_attention_heads=40,
        tie_word_embeddings=False,
    ),
    "qwen3-32b": dict(
        _QWEN3_COMMON,
        hidden_size=5120,
        intermediate_size=25600,
        num_hidden_layers=64,
        num_attention_heads=64,
        tie_word_embeddings=False,
    ),
    # Qwen3-30B-A3B: 128 experts, top-8, 3.3B active of 30.5B total.
    "qwen3-30b-a3b": dict(
        model_type="qwen3_moe",
        vocab_size=151936,
        hidden_size=2048,
        intermediate_size=6144,
        moe_intermediate_size=768,
        num_hidden_layers=48,
        num_attention_heads=32,
        num_key_value_heads=4,
        head_dim=128,
        rope_theta=1e6,
        rms_norm_eps=1e-6,
        max_position_embeddings=40960,
        tie_word_embeddings=False,
        num_experts=128,
        num_experts_per_tok=8,
    ),
    # Single-v5e-chip MoE (same shape family as qwen3-30b-a3b, scaled to
    # fit 16 GB with bf16 master weights): E=64/top-8 keeps the
    # large-expert-count dispatch regime where the index form wins
    # (tools/bench_moe_dispatch.py measures it on-chip).
    "moe-mid": dict(
        model_type="qwen3_moe",
        vocab_size=32768,
        hidden_size=1024,
        intermediate_size=3072,
        moe_intermediate_size=384,
        num_hidden_layers=12,
        num_attention_heads=16,
        num_key_value_heads=4,
        head_dim=64,
        rope_theta=1e6,
        rms_norm_eps=1e-6,
        max_position_embeddings=40960,
        tie_word_embeddings=False,
        num_experts=64,
        num_experts_per_tok=8,
    ),
    # Downscaled MoE for 8-chip correctness/system sweeps (same shape
    # family as qwen3-30b-a3b; fits a CPU-device mesh).
    "moe-tiny": dict(
        model_type="qwen3_moe",
        vocab_size=4096,
        hidden_size=256,
        intermediate_size=512,
        moe_intermediate_size=192,
        num_hidden_layers=4,
        num_attention_heads=8,
        num_key_value_heads=4,
        head_dim=32,
        rope_theta=1e6,
        max_position_embeddings=8192,
        tie_word_embeddings=True,
        num_experts=8,
        num_experts_per_tok=2,
    ),
    # OLMoE-1B-7B (allenai/OLMoE-1B-7B-0125-Instruct config.json): 64
    # experts of width 1024 (``intermediate_size`` IS the expert width),
    # top 8 unnormalised, dropless, q/k norm over the whole projection;
    # 6.92B parameters, 1.28B active.
    "olmoe-1b-7b": dict(
        model_type="olmoe",
        vocab_size=50304,
        hidden_size=2048,
        intermediate_size=1024,
        num_hidden_layers=16,
        num_attention_heads=16,
        num_key_value_heads=16,
        rope_theta=10000.0,
        rms_norm_eps=1e-5,
        max_position_embeddings=4096,
        tie_word_embeddings=False,
        num_experts=64,
        num_experts_per_tok=8,
        norm_topk_prob=False,
        router_aux_loss_coef=0.01,
    ),
    # The same family at a size the CPU tests serve.
    "olmoe-tiny": dict(
        model_type="olmoe",
        vocab_size=128,
        hidden_size=64,
        intermediate_size=32,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=4,
        rope_theta=10000.0,
        rms_norm_eps=1e-5,
        max_position_embeddings=4096,
        tie_word_embeddings=False,
        num_experts=8,
        num_experts_per_tok=2,
        norm_topk_prob=False,
        router_aux_loss_coef=0.01,
    ),
    # Olmo-Hybrid-7B (allenai/Olmo-Hybrid-7B config.json): gated
    # delta-rule layers, three in four, between full-attention layers
    # without rotary embedding; 7.43B parameters at the published 32
    # layers, 14.9 GB in bf16, so one v5e chip serves a stage of a
    # two-chip pipeline: the first 16 layers (four whole periods) with
    # the embedding and the head, 4.10B parameters. ``layer_types``
    # omitted: three linear_attention, one full_attention, repeated.
    "olmo-hybrid-7b": dict(
        model_type="olmo_hybrid",
        vocab_size=100352,
        hidden_size=3840,
        intermediate_size=11008,
        num_hidden_layers=16,
        num_attention_heads=30,
        num_key_value_heads=30,
        rms_norm_eps=1e-6,
        max_position_embeddings=65536,
        tie_word_embeddings=False,
        linear_num_key_heads=30,
        linear_num_value_heads=30,
        linear_key_head_dim=96,
        linear_value_head_dim=192,
        linear_conv_kernel_dim=4,
        linear_allow_neg_eigval=True,
        rope_parameters={"rope_theta": None},
    ),
    # The same family at a size the CPU tests serve: two periods.
    "olmo-hybrid-tiny": dict(
        model_type="olmo_hybrid",
        vocab_size=128,
        hidden_size=64,
        intermediate_size=96,
        num_hidden_layers=8,
        num_attention_heads=4,
        num_key_value_heads=4,
        rms_norm_eps=1e-6,
        max_position_embeddings=4096,
        tie_word_embeddings=False,
        linear_num_key_heads=4,
        linear_num_value_heads=4,
        linear_key_head_dim=8,
        linear_value_head_dim=16,
        linear_conv_kernel_dim=4,
        linear_allow_neg_eigval=True,
        rope_parameters={"rope_theta": None},
    ),
    # Qwen3-Next-80B-A3B-Instruct (Qwen/Qwen3-Next-80B-A3B-Instruct
    # config.json), as published: 48 layers (three gated delta-rule
    # layers, one gated full-attention layer, repeated), each with 512
    # routed experts of width 512 (top 10) and a gated shared expert;
    # 80 B parameters = 160 GB in bf16, sixteen v5e chips at the least.
    # One chip serves a share (benchmarks/configs/
    # qwen3-next-80b-a3b-serve.json): --num_hidden_layers 12,
    # --num_experts 128 --num_routed_experts 512, a quarter of the
    # vocabulary.
    "qwen3-next-80b-a3b": dict(
        model_type="qwen3_next",
        vocab_size=151936,
        hidden_size=2048,
        intermediate_size=5120,
        num_hidden_layers=48,
        num_attention_heads=16,
        num_key_value_heads=2,
        head_dim=256,
        rope_theta=1e7,
        partial_rotary_factor=0.25,
        full_attention_interval=4,
        rms_norm_eps=1e-6,
        max_position_embeddings=262144,
        tie_word_embeddings=False,
        linear_num_key_heads=16,
        linear_num_value_heads=32,
        linear_key_head_dim=128,
        linear_value_head_dim=128,
        linear_conv_kernel_dim=4,
        num_experts=512,
        num_experts_per_tok=10,
        moe_intermediate_size=512,
        shared_expert_intermediate_size=512,
        norm_topk_prob=True,
    ),
    # The same family at a size the CPU tests serve: two periods, 2 key
    # heads over 4 value heads, a quarter-rotary head, and a SHARE of
    # the experts: 8 of 16 routed ones held here, from id 4.
    "qwen3-next-tiny": dict(
        model_type="qwen3_next",
        vocab_size=128,
        hidden_size=64,
        intermediate_size=96,
        num_hidden_layers=8,
        num_attention_heads=4,
        num_key_value_heads=2,
        head_dim=32,
        rope_theta=1e4,
        partial_rotary_factor=0.25,
        full_attention_interval=4,
        rms_norm_eps=1e-6,
        max_position_embeddings=4096,
        tie_word_embeddings=False,
        linear_num_key_heads=2,
        linear_num_value_heads=4,
        linear_key_head_dim=8,
        linear_value_head_dim=16,
        linear_conv_kernel_dim=4,
        num_experts=8,
        num_routed_experts=16,
        first_expert_id=4,
        num_experts_per_tok=3,
        moe_intermediate_size=32,
        shared_expert_intermediate_size=48,
        norm_topk_prob=True,
    ),
    # Trinity-Mini (arcee-ai/Trinity-Mini config.json, model_type
    # afmoe), as published: 32 layers, three sliding_attention (window
    # 2048, rotary) to one full_attention (no rotary), 2 leading dense
    # layers, then 128 sigmoid-routed experts of width 1024 (top 8) and
    # an ungated shared expert; 26 B parameters = 52 GB in bf16, eight
    # v5e chips at the least. One chip serves a share
    # (benchmarks/configs/trinity-mini-serve.json): --num_hidden_layers
    # 16, --num_experts 32 --num_routed_experts 128, a quarter of the
    # vocabulary.
    "trinity-mini": dict(
        model_type="afmoe",
        vocab_size=200192,
        hidden_size=2048,
        intermediate_size=6144,
        num_hidden_layers=32,
        num_attention_heads=32,
        num_key_value_heads=4,
        head_dim=128,
        rope_theta=10000.0,
        rms_norm_eps=1e-5,
        max_position_embeddings=131072,
        tie_word_embeddings=False,
        global_attn_every_n_layers=4,
        sliding_window_size=2048,
        num_dense_layers=2,
        num_experts=128,
        num_experts_per_tok=8,
        moe_intermediate_size=1024,
        num_shared_experts=1,
        score_func="sigmoid",
        route_norm=True,
        route_scale=2.826,
        mup_enabled=True,
    ),
    # The same family at a size the CPU tests serve: two periods, a
    # window of 24 tokens (a ring of 4 pages of 8), 2 leading dense
    # layers, and a SHARE of the experts: 8 of 16 routed ones held
    # here, from id 4.
    "afmoe-tiny": dict(
        model_type="afmoe",
        vocab_size=128,
        hidden_size=64,
        intermediate_size=96,
        num_hidden_layers=8,
        num_attention_heads=4,
        num_key_value_heads=2,
        head_dim=32,
        rope_theta=1e4,
        rms_norm_eps=1e-5,
        max_position_embeddings=4096,
        tie_word_embeddings=False,
        global_attn_every_n_layers=4,
        sliding_window_size=24,
        num_dense_layers=2,
        num_experts=8,
        num_routed_experts=16,
        first_expert_id=4,
        num_experts_per_tok=3,
        moe_intermediate_size=32,
        num_shared_experts=1,
        score_func="sigmoid",
        route_norm=True,
        route_scale=2.826,
        mup_enabled=True,
    ),
    # AI21-Jamba2-3B (ai21labs/AI21-Jamba2-3B config.json, model_type
    # jamba), as published: 28 layers, Mamba-1 layers with an attention
    # layer at 7 and 21 (one in 14), 20 query heads on ONE K/V head,
    # no rotary embedding, a dense SwiGLU MLP in every layer
    # (num_experts 1), tied embedding. 3.03 B parameters, 6.06 GB in
    # bf16: one v5e chip serves it whole.
    "jamba2-3b": dict(
        model_type="jamba",
        vocab_size=65536,
        hidden_size=2560,
        intermediate_size=8192,
        num_hidden_layers=28,
        num_attention_heads=20,
        num_key_value_heads=1,
        rms_norm_eps=1e-6,
        max_position_embeddings=262144,
        tie_word_embeddings=True,
        attn_layer_period=14,
        attn_layer_offset=7,
        num_experts=1,
        num_experts_per_tok=1,
        mamba_d_state=16,
        mamba_d_conv=4,
        mamba_expand=2,
        mamba_dt_rank=160,
        mamba_conv_bias=True,
        mamba_proj_bias=False,
    ),
    # The same family at a size the CPU tests serve: two periods of
    # (mamba, attention, mamba, mamba), 128 channels of 8 states.
    "jamba-tiny": dict(
        model_type="jamba",
        vocab_size=128,
        hidden_size=64,
        intermediate_size=96,
        num_hidden_layers=8,
        num_attention_heads=4,
        num_key_value_heads=1,
        rms_norm_eps=1e-6,
        max_position_embeddings=4096,
        tie_word_embeddings=True,
        attn_layer_period=4,
        attn_layer_offset=1,
        num_experts=1,
        num_experts_per_tok=1,
        mamba_d_state=8,
        mamba_d_conv=4,
        mamba_expand=2,
        mamba_dt_rank=8,
        mamba_conv_bias=True,
        mamba_proj_bias=False,
    ),
    # openPangu-Ultra-MoE-718B (FreedomIntelligence/openPangu-Ultra-MoE-
    # 718B config.json, model_type pangu_ultra_moe), as published: 61
    # layers of latent attention (128 heads over a 512 + 64 latent row)
    # under four norms, 3 leading dense layers, then 256 sigmoid-routed
    # experts of width 2048 (top 8) and an ungated shared expert; 718 B
    # parameters = 1.44 TB in bf16. One chip serves a share
    # (benchmarks/configs/openpangu-ultra-moe-718b-serve.json):
    # --num_hidden_layers 6 --first_k_dense_replace 1,
    # --n_routed_experts 8 --num_routed_experts 256, an eighth of the
    # vocabulary.
    "openpangu-ultra-moe-718b": dict(
        model_type="pangu_ultra_moe",
        vocab_size=153600,
        hidden_size=7680,
        intermediate_size=18432,
        num_hidden_layers=61,
        num_attention_heads=128,
        num_key_value_heads=128,
        rope_theta=25600000.0,
        rms_norm_eps=1e-5,
        max_position_embeddings=131072,
        tie_word_embeddings=False,
        q_lora_rank=1536,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        first_k_dense_replace=3,
        n_routed_experts=256,
        num_experts_per_tok=8,
        moe_intermediate_size=2048,
        n_shared_experts=1,
        norm_topk_prob=True,
        routed_scaling_factor=2.5,
        sandwich_norm=True,
        num_nextn_predict_layers=1,
    ),
    # The same family at a size the CPU tests serve: one dense layer and
    # three sparse ones, 4 heads over a 32 + 8 latent row (stored 128
    # wide), and a SHARE of the experts: 4 of 16 routed ones held here,
    # from id 4.
    "pangu-tiny": dict(
        model_type="pangu_ultra_moe",
        vocab_size=128,
        hidden_size=64,
        intermediate_size=96,
        num_hidden_layers=4,
        num_attention_heads=4,
        num_key_value_heads=4,
        rope_theta=1e4,
        rms_norm_eps=1e-5,
        max_position_embeddings=4096,
        tie_word_embeddings=False,
        q_lora_rank=48,
        kv_lora_rank=32,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=16,
        first_k_dense_replace=1,
        n_routed_experts=4,
        num_routed_experts=16,
        first_expert_id=4,
        num_experts_per_tok=3,
        moe_intermediate_size=32,
        n_shared_experts=1,
        norm_topk_prob=True,
        routed_scaling_factor=2.5,
        sandwich_norm=True,
        num_nextn_predict_layers=1,
    ),
    # Kimi-Linear-48B-A3B-Instruct (moonshotai config.json, model_type
    # kimi_linear), as published: 27 layers, three Kimi Delta Attention
    # layers (32 heads x 128, a decay per key channel) to one rope-free
    # latent-attention layer (32 heads on a 512 + 64 row), one leading
    # dense layer, then 256 sigmoid-routed experts of width 1024 (top 8)
    # and an ungated shared expert; 48 B parameters = 96 GB in bf16. One
    # chip serves a share (benchmarks/configs/
    # kimi-linear-48b-a3b-serve.json): --num_hidden_layers 8 with the
    # first eight entries of linear_attn_config, --num_experts 64
    # --num_routed_experts 256, a quarter of the vocabulary.
    "kimi-linear-48b-a3b": dict(
        model_type="kimi_linear",
        vocab_size=163840,
        hidden_size=2304,
        intermediate_size=9216,
        num_hidden_layers=27,
        num_attention_heads=32,
        num_key_value_heads=32,
        rms_norm_eps=1e-5,
        max_position_embeddings=1048576,
        tie_word_embeddings=False,
        linear_attn_config=dict(
            kda_layers=[1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18,
                        19, 21, 22, 23, 25, 26],
            full_attn_layers=[4, 8, 12, 16, 20, 24, 27],
            head_dim=128, num_heads=32, short_conv_kernel_size=4),
        q_lora_rank=None,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        first_k_dense_replace=1,
        num_experts=256,
        num_experts_per_token=8,
        moe_intermediate_size=1024,
        num_shared_experts=1,
        moe_renormalize=True,
        routed_scaling_factor=2.446,
    ),
    # The same family at a size the CPU tests serve: a dense layer, two
    # whole periods and the published list's short last one (11 layers:
    # 8 KDA of 2 heads x 16, 3 latent of 4 heads over a 32 + 8 row), and
    # a SHARE of the experts: 4 of 16 routed ones held here, from id 4.
    "kimi-linear-tiny": dict(
        model_type="kimi_linear",
        vocab_size=128,
        hidden_size=64,
        intermediate_size=96,
        num_hidden_layers=11,
        num_attention_heads=4,
        num_key_value_heads=4,
        rms_norm_eps=1e-5,
        max_position_embeddings=4096,
        tie_word_embeddings=False,
        linear_attn_config=dict(
            kda_layers=[1, 2, 3, 5, 6, 7, 9, 10],
            full_attn_layers=[4, 8, 11],
            head_dim=16, num_heads=2, short_conv_kernel_size=4),
        q_lora_rank=None,
        kv_lora_rank=32,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=16,
        first_k_dense_replace=1,
        num_experts=4,
        num_routed_experts=16,
        first_expert_id=4,
        num_experts_per_token=3,
        moe_intermediate_size=32,
        num_shared_experts=1,
        moe_renormalize=True,
        routed_scaling_factor=2.446,
    ),
    # XiaomiMiMo/MiMo-V2-Flash (309B-A15B), config.json as published:
    # 48 layers, five 128-key window layers (8 K/V heads, a learned sink
    # a head, rotary base 1e4) to one full layer (4 K/V heads, base
    # 5e6), keys 192 wide on values 128, the rotary embedding on a
    # head's first 64 dims, a leading dense layer and 47 of 256
    # sigmoid-routed experts, top 8, no shared expert. Far past one
    # chip: the benchmark serves a chip's share
    # (benchmarks/configs/mimo-v2-flash-serve.json):
    # --num_hidden_layers 7 with the two lists' first seven entries,
    # --n_routed_experts 16 --num_routed_experts 256, an eighth of the
    # vocabulary.
    "mimo-v2-flash": dict(
        model_type="mimo_v2_flash",
        vocab_size=152576,
        hidden_size=4096,
        intermediate_size=16384,
        num_hidden_layers=48,
        num_attention_heads=64,
        num_key_value_heads=4,
        swa_num_key_value_heads=8,
        head_dim=192,
        v_head_dim=128,
        rope_theta=5000000.0,
        swa_rope_theta=10000.0,
        partial_rotary_factor=0.334,
        layernorm_epsilon=1e-5,
        max_position_embeddings=262144,
        tie_word_embeddings=False,
        sliding_window_size=128,
        attention_value_scale=0.707,
        hybrid_layer_pattern=[0, 1, 1, 1, 1, 0] + [1, 1, 1, 1, 1, 0] * 7,
        moe_layer_freq=[0] + [1] * 47,
        add_swa_attention_sink_bias=True,
        add_full_attention_sink_bias=False,
        n_routed_experts=256,
        n_shared_experts=None,
        num_experts_per_tok=8,
        moe_intermediate_size=2048,
        norm_topk_prob=True,
        routed_scaling_factor=None,
    ),
    # The same family at a size the CPU tests serve: the published
    # pattern's first seven layers (a dense full layer, then 5 window +
    # 1 full sparse layers), keys 24 wide on values 16 with 8 dims
    # turned, a window of 20 tokens (a ring of 4 pages of 8), 2 and 4
    # K/V heads, and a SHARE of the experts: 4 of 16 routed ones held
    # here, from id 4.
    "mimo-v2-flash-tiny": dict(
        model_type="mimo_v2_flash",
        vocab_size=128,
        hidden_size=64,
        intermediate_size=96,
        num_hidden_layers=7,
        num_attention_heads=8,
        num_key_value_heads=2,
        swa_num_key_value_heads=4,
        head_dim=24,
        v_head_dim=16,
        rope_theta=5000000.0,
        swa_rope_theta=10000.0,
        partial_rotary_factor=0.334,
        layernorm_epsilon=1e-5,
        max_position_embeddings=4096,
        tie_word_embeddings=False,
        sliding_window_size=20,
        attention_value_scale=0.707,
        hybrid_layer_pattern=[0, 1, 1, 1, 1, 0, 1],
        moe_layer_freq=[0, 1, 1, 1, 1, 1, 1],
        n_routed_experts=4,
        num_routed_experts=16,
        first_expert_id=4,
        n_shared_experts=None,
        num_experts_per_tok=3,
        moe_intermediate_size=32,
        norm_topk_prob=True,
        routed_scaling_factor=None,
        sink_init_mean=2.5,
    ),
    # ibm-granite/granite-4.0-h-small (32B-A9B), config.json as
    # published (model_type granitemoehybrid): 40 layers, nine Mamba-2
    # layers (128 heads x 64 channels, a [64, 128] float32 state a head,
    # one group of B / C, chunks of 256) to one rope-free attention layer
    # (32 heads on 8 K/V heads of 128, scores x 1/128), every layer 72
    # softmax-routed experts of width 768 (top 10) beside an ungated
    # shared expert of 1,536, the four muP multipliers; 32 B parameters
    # = 64 GB in bf16. One chip serves a share
    # (benchmarks/configs/granite-4.0-h-small-serve.json):
    # --num_hidden_layers 10 (one whole period), --num_local_experts 36
    # --num_routed_experts 72, half the vocabulary.
    "granite-4.0-h-small": dict(
        model_type="granitemoehybrid",
        vocab_size=100352,
        hidden_size=4096,
        intermediate_size=768,
        num_hidden_layers=40,
        num_attention_heads=32,
        num_key_value_heads=8,
        rms_norm_eps=1e-5,
        max_position_embeddings=131072,
        tie_word_embeddings=True,
        mamba_n_heads=128,
        mamba_d_head=64,
        mamba_d_state=128,
        mamba_n_groups=1,
        mamba_d_conv=4,
        mamba_expand=2,
        mamba_chunk_size=256,
        mamba_conv_bias=True,
        mamba_proj_bias=False,
        num_local_experts=72,
        num_experts_per_tok=10,
        shared_intermediate_size=1536,
        embedding_multiplier=12.0,
        attention_multiplier=0.0078125,
        residual_multiplier=0.22,
        logits_scaling=16.0,
        position_embedding_type="nope",
    ),
    # The same family at a size the CPU tests serve: one period cut to
    # five layers (m m a m m: 4 Mamba-2 layers of 4 heads x 16 channels
    # on a state of 8, chunks of 8; one attention layer of 4 heads on 2
    # K/V heads), and a SHARE of the experts: 4 of 8 routed ones held
    # here, from id 4, top 3.
    "granite-moe-hybrid-tiny": dict(
        model_type="granitemoehybrid",
        vocab_size=128,
        hidden_size=32,
        intermediate_size=16,
        num_hidden_layers=5,
        layer_types=["mamba", "mamba", "attention", "mamba", "mamba"],
        num_attention_heads=4,
        num_key_value_heads=2,
        rms_norm_eps=1e-5,
        max_position_embeddings=4096,
        tie_word_embeddings=True,
        mamba_n_heads=4,
        mamba_d_head=16,
        mamba_d_state=8,
        mamba_n_groups=1,
        mamba_d_conv=4,
        mamba_expand=2,
        mamba_chunk_size=8,
        mamba_conv_bias=True,
        mamba_proj_bias=False,
        num_local_experts=4,
        num_routed_experts=8,
        first_expert_id=4,
        num_experts_per_tok=3,
        shared_intermediate_size=24,
        embedding_multiplier=12.0,
        attention_multiplier=0.125,
        residual_multiplier=0.22,
        logits_scaling=16.0,
        position_embedding_type="nope",
    ),
    # Downscaled dense model for 8-chip correctness/system sweeps.
    "dense-tiny": dict(
        model_type="qwen3",
        vocab_size=4096,
        hidden_size=256,
        intermediate_size=512,
        num_hidden_layers=4,
        num_attention_heads=8,
        num_key_value_heads=4,
        head_dim=32,
        rope_theta=1e6,
        max_position_embeddings=8192,
        tie_word_embeddings=True,
    ),
}


def preset(name: str) -> Dict[str, Any]:
    try:
        return dict(MODEL_PRESETS[name.lower()])
    except KeyError:
        raise KeyError(
            f"unknown model preset {name!r}; available: "
            f"{', '.join(sorted(MODEL_PRESETS))}"
        ) from None
