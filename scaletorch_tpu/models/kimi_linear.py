"""kimi_linear (Kimi-Linear) — Kimi Delta Attention layers beside rope-free
latent attention, a leading dense layer, sigmoid-routed experts.

``config.json`` of moonshotai/Kimi-Linear-48B-A3B-Instruct
(``model_type: kimi_linear``; "Kimi Linear: An Expressive, Efficient
Attention Architecture", arXiv:2510.26692): ``linear_attn_config`` lists,
1-based and in any order of kinds, the ``kda_layers`` and the
``full_attn_layers`` (published: three KDA, one latent, repeated, and a
last short period of two KDA and one latent). With the plain gain ``N(x;
g) = x / sqrt(mean(x^2) + eps) * g`` every layer is pre-norm:

    h <- h + Mix(N(h; g_in))        h <- h + MLP(N(h; g_post))
    logits = N(h; g_f) W_head

*KDA layer* (``kda_mix``), ``x`` the normed input, ``H`` heads of ``d``:

    q^ = silu(conv(x Wq))   k^ = silu(conv(x Wk))   v = silu(conv(x Wv))
    q = l2norm(q^) / sqrt(d)   k = l2norm(k^)                per head
    g = -exp(A_log) softplus((x Wfa) Wfb + dt_bias)   per KEY CHANNEL
    beta = sigmoid(x Wb)                               per head
    S' = diag(exp(g_t)) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t
    y = N(o; g_o) * sigmoid((x Wga) Wgb)      Mix = concat(y) Wo

the three convolutions depthwise, causal, width
``short_conv_kernel_size``, no bias (kept as ONE ``[K, 3 H d]`` weight,
q | k | v side by side); ``A_log`` one a head, ``dt_bias`` one a
channel. It is the gated delta rule of ``olmo_hybrid`` with the decay a
vector over the key channels: ``gated_delta_step`` for one token,
``gated_delta_chunked`` for a prompt (its per-channel form), the state
``f32[KDA layers, slots, H, d, d]`` and the convolution's tail by slot.

*Latent layer*: ``pangu_ultra_moe.latent_attention`` without a query
latent (``q_lora_rank`` null: ``q = x Wq``) and with NO rotary embedding
on ``q_r`` / ``k_r`` (``mla_use_nope``): ``[c | k_r]`` a token in the
latent page pool, a prompt attended to itself in the expanded form, a
decode step in the absorbed form.

*MLP*: the first ``first_k_dense_replace`` layers a SwiGLU of
``intermediate_size``; the others ``qwen3_moe.dropless_mlp`` told
``score_func sigmoid`` under a selection bias (``expert_bias``, a
float32 buffer: it steers the choice, never the weight), the top
``num_experts_per_token`` weighted ``s / (their sum + 1e-20)``
(``moe_renormalize``) times ``routed_scaling_factor``, dropless, plus
``num_shared_experts`` ungated shared SwiGLUs. ``num_experts`` counts
the experts HELD here (``qwen3_moe.ExpertShare``).

The cache is ``kv_cache.HybridCache`` with ``k`` the latent pool over
the latent layers, no ``v``, and ``state`` / ``conv`` by slot.

Parameters are stacked by kind: ``layers["block"]`` every layer's two
norms ``[layers, ...]``, ``layers["kda"]`` / ``layers["mla"]`` the mixers
``[layers of the kind, ...]``, ``layers["dense"]`` / ``layers["moe"]``
the MLPs. The layers run unrolled, each told its id and its place among
the layers of its kind.

Not written: the trainer's step (the scan has no backward kernel and
no loss wiring), tensor / context / pipeline / expert parallelism over
this family, HF weight loading, prefix sharing, a contiguous cache, a
group-limited choice of experts (``topk_group`` must be 1).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from scaletorch_tpu.models import llama as _llama
from scaletorch_tpu.models import olmo_hybrid as _hybrid
from scaletorch_tpu.models import qwen3_moe as _moe
from scaletorch_tpu.models.afmoe import _NO_ROUTING, _layer_of
from scaletorch_tpu.models.layers import fan_in_uniform, rms_norm
from scaletorch_tpu.models.llama import LlamaConfig, Params
from scaletorch_tpu.models.pangu_ultra_moe import (
    _SelfLatent,
    latent_attention,
)
from scaletorch_tpu.models.qwen3_moe import ExpertShare

KDA, FULL = "kda", "full"
F32 = jnp.float32
# ``linear_attn_config`` as published; the two low-rank gates' inner width
# is the KDA head's (``modeling_kimi.py``: the config has no key for it)
_PUBLISHED_LAYERS = dict(
    kda_layers=(1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21,
                22, 23, 25, 26),
    full_attn_layers=(4, 8, 12, 16, 20, 24, 27),
    head_dim=128, num_heads=32, short_conv_kernel_size=4)


@dataclass(frozen=True)
class KimiLinearConfig(ExpertShare, LlamaConfig):
    # Kimi-Linear-48B-A3B-Instruct defaults (the published config.json)
    vocab_size: int = 163840
    hidden_size: int = 2304
    intermediate_size: int = 9216          # the leading dense layer's
    num_hidden_layers: int = 27
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 1048576     # model_max_length
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    # linear_attn_config, 1-based as published
    kda_layers: Tuple[int, ...] = _PUBLISHED_LAYERS["kda_layers"]
    full_attn_layers: Tuple[int, ...] = _PUBLISHED_LAYERS["full_attn_layers"]
    kda_num_heads: int = 32
    kda_head_dim: int = 128
    short_conv_kernel_size: int = 4
    # latent attention
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # the MLPs; num_experts counts the experts HELD here
    first_k_dense_replace: int = 1
    num_experts: int = 256
    num_experts_per_token: int = 8
    moe_intermediate_size: int = 1024
    num_shared_experts: int = 1
    moe_renormalize: bool = True
    routed_scaling_factor: float = 2.446
    num_routed_experts: Optional[int] = None
    first_expert_id: int = 0
    # random weights only (models/families.py)
    embed_init_std: float = 0.02
    routed_expert_init_scale: float = 1.0
    query_init_scale: float = 1.0
    score_func = "sigmoid"
    shared_expert_gated = False
    aux_loss_coef = 0.0
    z_loss_coef = 0.0

    def __post_init__(self) -> None:
        listed = sorted(self.kda_layers + self.full_attn_layers)
        if listed != list(range(1, self.num_hidden_layers + 1)):
            raise ValueError(
                "linear_attn_config: kda_layers and full_attn_layers must "
                f"name each of the layers 1..{self.num_hidden_layers} "
                f"once, got {tuple(self.kda_layers)} and "
                f"{tuple(self.full_attn_layers)}")
        if not self.kda_layers or not self.full_attn_layers:
            raise ValueError(
                "kimi_linear has layers of both kinds (a stack of one "
                "kind is another family's)")
        if not 0 <= self.first_k_dense_replace < self.num_hidden_layers:
            raise ValueError(
                f"first_k_dense_replace {self.first_k_dense_replace} of "
                f"{self.num_hidden_layers} layers: at least one is sparse")
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError(
                f"num_key_value_heads {self.num_key_value_heads} != "
                f"num_attention_heads {self.num_attention_heads}: latent "
                "attention expands a key and a value for every head")
        self.check_expert_share()

    # ---- the layer list -------------------------------------------------
    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        full = set(self.full_attn_layers)
        return tuple(FULL if i + 1 in full else KDA
                     for i in range(self.num_hidden_layers))

    @property
    def num_kda_layers(self) -> int:
        return len(self.kda_layers)

    @property
    def num_kv_cache_layers(self) -> int:
        """Layers that keep latent rows: the page pool's leading axis."""
        return len(self.full_attn_layers)

    # ---- the latent cache (kv_cache.latent_of reads these) --------------
    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def attn_scale(self) -> float:
        return 1.0 / math.sqrt(self.qk_head_dim)

    # ---- the recurrent state (kv_cache.carries_state) -------------------
    @property
    def kda_size(self) -> int:
        return self.kda_num_heads * self.kda_head_dim

    def recurrent_state_shapes(
        self, slots: int
    ) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
        """(state, convolution tail) shapes of a cache of ``slots``
        sequences: ``[KDA layers, slots, H, d, d]`` (float32) and
        ``[KDA layers, slots, kernel - 1, 3 H d]``."""
        n, d = self.num_kda_layers, self.kda_head_dim
        return ((n, slots, self.kda_num_heads, d, d),
                (n, slots, self.short_conv_kernel_size - 1,
                 3 * self.kda_size))

    # ---- what qwen3_moe.dropless_mlp reads under its own names ----------
    @property
    def num_experts_per_tok(self) -> int:
        return self.num_experts_per_token

    @property
    def norm_topk_prob(self) -> bool:
        return self.moe_renormalize

    @property
    def route_scale(self) -> float:
        return self.routed_scaling_factor

    @property
    def shared_expert_intermediate_size(self) -> int:
        return self.num_shared_experts * self.moe_intermediate_size

    def sparse_layer_ids(self) -> Tuple[int, ...]:
        """The layers that route (what the engine sizes its counters
        by)."""
        return tuple(range(self.first_k_dense_replace,
                           self.num_hidden_layers))

    def kda_params(self) -> int:
        h, w, d = self.hidden_size, self.kda_size, self.kda_head_dim
        return (4 * h * w + 2 * (h * d + d * w) + h * self.kda_num_heads
                + 3 * w * self.short_conv_kernel_size
                + self.kda_num_heads + w + d)

    def attention_params(self) -> int:
        h, heads = self.hidden_size, self.num_attention_heads
        return (h * heads * self.qk_head_dim
                + h * (self.kv_lora_rank + self.qk_rope_head_dim)
                + self.kv_lora_rank
                + self.kv_lora_rank * heads
                * (self.qk_nope_head_dim + self.v_head_dim)
                + heads * self.v_head_dim * h)

    def num_params(self) -> int:
        """Parameters as ``init_params`` builds them (the selection bias
        is a buffer and counted with them)."""
        h, v = self.hidden_size, self.vocab_size
        dense = 3 * h * self.intermediate_size
        moe = (h * self.router_width + self.router_width
               + self.num_experts * 3 * h * self.moe_intermediate_size
               + _moe.shared_expert_params(self))
        n_dense = self.first_k_dense_replace
        return (self.num_kda_layers * self.kda_params()
                + self.num_kv_cache_layers * self.attention_params()
                + self.num_hidden_layers * 2 * h + n_dense * dense
                + (self.num_hidden_layers - n_dense) * moe
                + v * h + h + (0 if self.tie_word_embeddings else v * h))


def config_from_args(args, common: dict) -> KimiLinearConfig:
    """The published config.json names. ``head_dim`` (72 = hidden /
    heads as published) is read by nothing here: latent attention's
    widths are ``qk_nope_head_dim``, ``qk_rope_head_dim`` and
    ``v_head_dim``, KDA's ``linear_attn_config.head_dim``. Four
    published keys are no arguments: their one published value is what
    this module computes (``mla_use_nope`` true, the sigmoid
    ``moe_router_activation_func``, ``num_expert_group`` 1,
    ``moe_layer_freq`` 1); whoever writes the loader of a published
    checkpoint checks them there."""
    if args.q_lora_rank is not None:
        raise NotImplementedError(
            f"kimi_linear with q_lora_rank {args.q_lora_rank}: its latent "
            "layers have no query latent (the published key is null); "
            "pangu_ultra_moe is the family with one")
    if args.topk_group != 1:
        raise NotImplementedError(
            f"kimi_linear with topk_group {args.topk_group}: a "
            "group-limited choice of experts is not written")
    if args.mlp_only_layers or (args.decoder_sparse_step or 1) != 1:
        raise NotImplementedError(
            "kimi_linear with mlp_only_layers / decoder_sparse_step: its "
            "dense layers are the leading first_k_dense_replace "
            "(models/kimi_linear.py)")
    if args.moe_dispatch != "auto" or args.moe_capacity_factor != 1.25:
        raise NotImplementedError(
            "kimi_linear under capacity dispatch (--moe_dispatch "
            f"{args.moe_dispatch}, --moe_capacity_factor "
            f"{args.moe_capacity_factor}): the family routes dropless "
            "(qwen3_moe.dropless_mlp)")
    lists = dict(_PUBLISHED_LAYERS, **(args.linear_attn_config or {}))
    common = {k: v for k, v in common.items() if k != "head_dim"}
    return KimiLinearConfig(**{
        **common,
        "kda_layers": tuple(lists["kda_layers"]),
        "full_attn_layers": tuple(lists["full_attn_layers"]),
        "kda_num_heads": lists["num_heads"],
        "kda_head_dim": lists["head_dim"],
        "short_conv_kernel_size": lists["short_conv_kernel_size"],
        "moe_intermediate_size": args.moe_intermediate_size
        or common["intermediate_size"],
        **{name: getattr(args, name) for name in (
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "first_k_dense_replace", "num_experts",
            "num_experts_per_token", "num_shared_experts",
            "moe_renormalize", "routed_scaling_factor",
            "num_routed_experts", "first_expert_id")}})


def init_params(key: jax.Array, cfg: KimiLinearConfig) -> Params:
    """Random init: fan-in uniform projections and experts, the router
    normal(0.02), the selection bias 0, the embedding
    normal(``cfg.embed_init_std``), every gain 1; the decay's own
    parameters as the rule's published initialisers draw them (fla
    ``KimiDeltaAttention``, Mamba2's ranges): ``A_log = log U(1, 16)``
    a head, ``dt_bias`` the inverse softplus of a step log-uniform in
    [1e-3, 1e-1] a channel. The held routed experts' down projection
    times ``cfg.routed_expert_init_scale`` and the latent layers'
    ``q_proj`` times ``cfg.query_init_scale`` (both 1 unless a launch
    says otherwise: ``pangu_ultra_moe.init_params`` has the reasons)."""
    h, v, pd = cfg.hidden_size, cfg.vocab_size, cfg.param_dtype
    n, n_dense = cfg.num_hidden_layers, cfg.first_k_dense_replace
    n_kda, n_mla = cfg.num_kda_layers, cfg.num_kv_cache_layers
    heads, d, w = cfg.kda_num_heads, cfg.kda_head_dim, cfg.kda_size
    rkv = cfg.kv_lora_rank
    keys = iter(jax.random.split(key, 40))

    def draw(lead, shape, fan_in):
        return fan_in_uniform(next(keys), (lead,) + shape, fan_in, pd)

    step = jnp.exp(jax.random.uniform(
        next(keys), (n_kda, w), F32, jnp.log(1e-3), jnp.log(1e-1)))
    kda = {
        "q_proj": draw(n_kda, (h, w), h), "k_proj": draw(n_kda, (h, w), h),
        "v_proj": draw(n_kda, (h, w), h),
        "conv": draw(n_kda, (cfg.short_conv_kernel_size, 3 * w),
                     cfg.short_conv_kernel_size),
        "f_a_proj": draw(n_kda, (h, d), h),
        "f_b_proj": draw(n_kda, (d, w), d),
        "A_log": jnp.log(jax.random.uniform(
            next(keys), (n_kda, heads), F32, 1.0, 16.0)).astype(pd),
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(pd),
        "b_proj": draw(n_kda, (h, heads), h),
        "g_a_proj": draw(n_kda, (h, d), h),
        "g_b_proj": draw(n_kda, (d, w), d),
        "o_norm": jnp.ones((n_kda, d), pd),
        "o_proj": draw(n_kda, (w, h), w),
    }
    a_heads = cfg.num_attention_heads
    mla = {
        "q_proj": draw(n_mla, (h, a_heads * cfg.qk_head_dim), h),
        "kv_a_proj_with_mqa": draw(
            n_mla, (h, rkv + cfg.qk_rope_head_dim), h),
        "kv_a_layernorm": jnp.ones((n_mla, rkv), pd),
        "kv_b_proj": draw(
            n_mla, (rkv, a_heads, cfg.qk_nope_head_dim + cfg.v_head_dim),
            rkv),
        "o_proj": draw(n_mla, (a_heads * cfg.v_head_dim, h),
                       a_heads * cfg.v_head_dim),
    }
    di = cfg.intermediate_size
    dense = {
        "gate_proj": draw(n_dense, (h, di), h),
        "up_proj": draw(n_dense, (h, di), h),
        "down_proj": draw(n_dense, (di, h), di),
    }
    moe = _moe.init_moe_params(
        [next(keys) for _ in range(8)], cfg, (n - n_dense,))
    moe["expert_bias"] = jnp.zeros((n - n_dense, cfg.router_width), F32)
    for tree, name, scale in (
            (moe, "expert_down_proj", cfg.routed_expert_init_scale),
            (mla, "q_proj", cfg.query_init_scale)):
        if scale != 1.0:
            tree[name] = (tree[name].astype(F32) * scale).astype(pd)
    params: Params = {
        "embed_tokens": cfg.embed_init_std * jax.random.normal(
            next(keys), (v, h), pd),
        "layers": {
            "block": {"input_layernorm": jnp.ones((n, h), pd),
                      "post_attention_layernorm": jnp.ones((n, h), pd)},
            "kda": kda, "mla": mla, "dense": dense, "moe": moe},
        "norm": jnp.ones((h,), pd),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = fan_in_uniform(next(keys), (h, v), h, pd)
    return params


def kda_mix(
    u: jax.Array,
    layer: Params,
    cfg: KimiLinearConfig,
    state: jax.Array,
    tail: jax.Array,
    *,
    row_mask: Optional[jax.Array] = None,
    sequential: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The KDA mixer of the normed ``u`` [B, S, hidden], continuing from
    ``state`` [B, H, d, d] (float32) and the convolution ``tail`` [B,
    K-1, 3 H d]. Rows outside ``row_mask`` [B, S] (a prefix of each
    sequence is inside) are no tokens: they leave the state alone and
    stay out of the tail. Returns (the mixer's output [B, S, hidden],
    the state after the last token, the new tail). One row is the
    recurrence itself, more rows its chunked form (``sequential``: row
    after row, the oracle)."""
    cdt = cfg.dtype
    b, s, _ = u.shape
    heads, d, w = cfg.kda_num_heads, cfg.kda_head_dim, cfg.kda_size

    with jax.named_scope("kda.conv"):
        qkv = jnp.concatenate(
            [u @ layer[name].astype(cdt)
             for name in ("q_proj", "k_proj", "v_proj")], axis=-1)
        mixed, rows = _hybrid.short_conv(qkv, layer["conv"], tail)
        mixed = jax.nn.silu(mixed)
        new_tail = _hybrid.conv_tail_after(rows, tail, row_mask)

    with jax.named_scope("kda.gate"):
        # the gates leave their last matmul in float32: a decay is a
        # product over every token since the prompt began
        log_alpha = -jnp.exp(layer["A_log"].astype(F32))[:, None] * (
            jax.nn.softplus(
                jnp.matmul(u @ layer["f_a_proj"].astype(cdt),
                           layer["f_b_proj"].astype(cdt),
                           preferred_element_type=F32)
                + layer["dt_bias"].astype(F32)).reshape(b, s, heads, d))
        beta = jax.nn.sigmoid(jnp.matmul(
            u, layer["b_proj"].astype(cdt), preferred_element_type=F32))
        if row_mask is not None:
            beta = jnp.where(row_mask[..., None], beta, 0.0)
            log_alpha = jnp.where(row_mask[..., None, None], log_alpha, 0.0)

    with jax.named_scope("kda.recurrence"):
        q = _hybrid.l2norm(mixed[..., :w].reshape(b, s, heads, d)) * d ** -0.5
        k = _hybrid.l2norm(mixed[..., w:2 * w].reshape(b, s, heads, d))
        v = mixed[..., 2 * w:].reshape(b, s, heads, d)
        if s == 1:
            o, state = _hybrid.gated_delta_step(
                q[:, 0], k[:, 0], v[:, 0], log_alpha[:, 0], beta[:, 0],
                state)
            o = o[:, None]
        elif sequential:
            o, state = _hybrid.gated_delta_sequential(
                q, k, v, log_alpha, beta, state)
        else:
            with jax.named_scope("kda.scan"):
                o, state = _hybrid.gated_delta_chunked(
                    q, k, v, log_alpha, beta, state)

    with jax.named_scope("kda.gate"):
        z = (u @ layer["g_a_proj"].astype(cdt)) @ layer["g_b_proj"].astype(
            cdt)
        y = rms_norm(o.astype(cdt), layer["o_norm"], cfg.rms_norm_eps)
        y = y.reshape(b, s, w) * jax.nn.sigmoid(z.astype(F32)).astype(cdt)
        out = y @ layer["o_proj"].astype(cdt)
    return out, state, new_tail


def _layer(h, cache, params, cfg, kind, layer, place, io, positions,
           write_mask, row_mask, fresh, written, sequential=False):
    """One layer: ``layer`` its id among all layers, ``place`` among
    those of its ``kind``. Returns (h, the cache, the layer's routing
    counts)."""
    eps = cfg.rms_norm_eps
    layers = params["layers"]
    block = _layer_of(layers["block"], layer)
    pool, _none, state, conv = cache
    u = rms_norm(h, block["input_layernorm"], eps)
    if kind == KDA:
        mixer = _layer_of(layers["kda"], place)
        old_s = jax.lax.dynamic_index_in_dim(state, place, 0, False)
        old_t = jax.lax.dynamic_index_in_dim(conv, place, 0, False)
        out, new_s, new_t = kda_mix(
            u, mixer, cfg,
            jnp.where(fresh[:, None, None, None], 0.0, old_s),
            jnp.where(fresh[:, None, None], 0, old_t),
            row_mask=row_mask, sequential=sequential)
        state = jax.lax.dynamic_update_index_in_dim(
            state, jnp.where(written[:, None, None, None], new_s, old_s),
            place, 0)
        conv = jax.lax.dynamic_update_index_in_dim(
            conv, jnp.where(written[:, None, None], new_t, old_t), place, 0)
    else:
        with jax.named_scope("latent_attn"):
            out, pool = latent_attention(
                u, _layer_of(layers["mla"], place), place, pool, None,
                positions, cfg, io, write_mask)
    h = h + out
    m = rms_norm(h, block["post_attention_layernorm"], eps)
    counts = dict(_NO_ROUTING)
    if layer < cfg.first_k_dense_replace:
        with jax.named_scope("mlp.dense"):
            f = _llama.swiglu_mlp(m, _layer_of(layers["dense"], layer), cfg)
    else:
        at = layer - cfg.first_k_dense_replace
        moe = layers["moe"]
        with jax.named_scope("moe"):
            f, _aux, _stats, routing = _moe.dropless_mlp(
                m, _layer_of(moe, at, skip=_moe.EXPERT_KEYS), cfg,
                row_mask, ({name: moe[name] for name in _moe.EXPERT_KEYS},
                           at))
        counts = _moe.routing_counts(routing)
    return h + f.astype(h.dtype), (pool, None, state, conv), counts


def _run_layers(x, cache, params, cfg, io, positions, write_mask, row_mask,
                sequential=False):
    """Every layer in turn. Returns (h, the cache, routing counts summed
    over the layers)."""
    kinds = cfg.layer_kinds
    fresh = positions[:, 0] == 0
    written = (jnp.ones((x.shape[0],), bool) if write_mask is None
               else write_mask)
    totals = []
    for layer, kind in enumerate(kinds):
        x, cache, counts = _layer(
            x, cache, params, cfg, kind, layer, kinds[:layer].count(kind),
            io, positions, write_mask, row_mask, fresh, written, sequential)
        totals.append(counts)
    return x, cache, jax.tree.map(lambda *xs: sum(xs), *totals)


def forward_cached(
    params: Params,
    input_ids: jax.Array,
    cfg: KimiLinearConfig,
    cache: Tuple[Any, ...],
    *,
    positions: jax.Array,
    write_mask: Optional[jax.Array] = None,
    kv_io: Optional[Any] = None,
    row_mask: Optional[jax.Array] = None,
    return_routing: bool = False,
    logit_rows: Optional[jax.Array] = None,
):
    """Cached forward: [B, S] tokens at absolute ``positions`` [B, S] ->
    (logits, the new cache). ``cache`` is ``(k, None, state, conv)``
    (``kv_cache.HybridCache`` without a ``v``): the page pool of the
    latent layers' rows ``[c | k_r]``, which ``kv_io`` (a
    ``kv_cache.PagedKVIO``) writes and reads through the engine's
    tables, and the KDA layers' recurrent state and convolution tail by
    slot (row b of the call is slot b of both). S > 1 is a prompt from
    its first token (latent attention over itself in the expanded form,
    the delta rule in its chunked form from ``S = 0``); S == 1 a decode
    step. ``row_mask`` [B, S]: the rows that are tokens (a prefix of
    each sequence; None: all). ``logit_rows`` and ``return_routing`` as
    in ``qwen3_moe.forward_cached``."""
    if not hasattr(kv_io, "attend_latent"):
        raise NotImplementedError(
            "kimi_linear's cached forward is written for the paged latent "
            "cache (kv_cache.HybridCache through kv_cache.PagedKVIO); a "
            "contiguous latent cache is not")
    x = _llama.embed(params, input_ids, cfg)
    x, cache, counts = _run_layers(
        x, tuple(cache), params, cfg, kv_io, positions, write_mask, row_mask)
    x = rms_norm(_llama.select_logit_rows(x, logit_rows), params["norm"],
                 cfg.rms_norm_eps)
    logits = x @ _llama.lm_head_weight(params, cfg)
    if return_routing:
        return logits, cache, counts
    return logits, cache


def forward(
    params: Params,
    input_ids: jax.Array,
    cfg: KimiLinearConfig,
    *,
    sequential: bool = False,
    return_hidden: bool = False,
) -> jax.Array:
    """Full forward without a cache: [B, S] tokens -> logits [B, S, V]
    (``return_hidden``: the final-normed hidden states); S > 1. Latent
    attention in the expanded form, the delta rule from an empty state
    in its chunked form, or row after row with ``sequential`` (the
    oracle the tests hold the chunked form and the cache to)."""
    b, s = input_ids.shape
    if s < 2:
        raise ValueError("kimi_linear.forward attends a sequence to "
                         "itself: give it at least two tokens")
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    state, conv = cfg.recurrent_state_shapes(b)
    cache = (None, None, jnp.zeros(state, F32), jnp.zeros(conv, cfg.dtype))
    x, _, _ = _run_layers(
        _llama.embed(params, input_ids, cfg), cache, params, cfg,
        _SelfLatent(), positions, None, None, sequential)
    x = rms_norm(x, params["norm"], cfg.rms_norm_eps)
    if return_hidden:
        return x
    return x @ _llama.lm_head_weight(params, cfg)


class KimiLinear:
    config_cls = KimiLinearConfig

    def __init__(self, config: KimiLinearConfig):
        self.config = config

    def init(self, key: jax.Array) -> Params:
        return init_params(key, self.config)

    def __call__(self, params: Params, input_ids: jax.Array, **kw):
        return forward(params, input_ids, self.config, **kw)
