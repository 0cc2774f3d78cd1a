"""Qwen3-Next — layers of two kinds of mixer, every one with a sparse MLP.

``config.json`` of Qwen/Qwen3-Next-80B-A3B-Instruct (``model_type:
qwen3_next``): layer ``i`` is ``full_attention`` when ``(i + 1) %
full_attention_interval == 0`` and ``linear_attention`` otherwise
(published: three linear, one full, repeated); ``decoder_sparse_step``
1 and no ``mlp_only_layers``, so every layer's MLP is the sparse one.
Pre-norm blocks under the zero-centred gain
``N(x; w) = x / sqrt(mean(x^2) + eps) * (1 + w)``
(``layers.rms_norm_zero_centered``):

    x <- x + Mix(N(x; w1))        x <- x + MoE(N(x; w2))

*Full-attention layer* (``gated_attention_mix_cached``): ``q_proj``
gives each head a query and a gate side by side (``heads x 2
head_dim``); q and k are normed per head (zero-centred gain), rotary
embedding turns the first ``partial_rotary_factor`` of each head and
the rest passes through, causal softmax attention against the cache
through the ``kv_io`` adapter (the page pool, the Mosaic pair: 256-wide
heads, 8 query heads a K/V head), then ``o_proj(attn * sigmoid(gate))``.

*Linear-attention layer*: ``olmo_hybrid.linear_attention_mix``, the
gated delta rule, here with fewer key heads than value heads (a key head
repeated over consecutive value heads) and ``beta = sigmoid`` without
the factor 2; the float32 state per slot and the convolution tail live
in the same ``kv_cache.HybridCache`` beside the page pool.

*Sparse MLP*: ``qwen3_moe.dropless_block``: softmax over all routed
experts in float32, top k renormalised, dropless; a shared expert under
a sigmoid gate added to the routed sum. The configuration may hold a
SHARE of the experts (``qwen3_moe.ExpertShare``: ``num_experts`` held
here of ``num_routed_experts``, from ``first_expert_id``): the block's
result is then the partial sum of its own experts' choices plus the
shared expert, and that is what goes on to the next layer; no exchange
between shares is written.

Parameters: ``layers["linear"]`` / ``layers["full"]`` hold the mixers
with their input norm, stacked ``[periods, layers of the kind in a
period, ...]`` (the hybrid family's layout); ``layers["moe"]`` holds
every layer's sparse MLP with its norm, stacked ``[layers, ...]``, so
that the grouped matmul reads a layer's experts out of the whole stack
(``dropless_expert_mlp(layer=...)``).

Not written: the multi-token-prediction module, the trainer's step and
tensor / context / pipeline / expert parallelism over this family, HF
weight loading, prefix sharing over the recurrent state.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from scaletorch_tpu.models import llama as _llama
from scaletorch_tpu.models import olmo_hybrid as _hybrid
from scaletorch_tpu.models import qwen3_moe as _moe
from scaletorch_tpu.models.layers import (
    DenseKVIO,
    apply_rotary_pos_emb,
    fan_in_uniform,
    get_cos_sin,
    rms_norm_zero_centered,
)
from scaletorch_tpu.models.llama import Params
from scaletorch_tpu.models.olmo_hybrid import (
    FULL,
    LINEAR,
    OlmoHybridConfig,
)
from scaletorch_tpu.models.qwen3_moe import ExpertShare

F32 = jnp.float32


@dataclass(frozen=True)
class Qwen3NextConfig(ExpertShare, OlmoHybridConfig):
    # Qwen3-Next-80B-A3B defaults (the published config.json)
    vocab_size: int = 151936
    hidden_size: int = 2048
    intermediate_size: int = 5120          # no layer has a dense MLP
    num_hidden_layers: int = 48
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: Optional[int] = 256
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    qk_norm: bool = True
    qk_norm_scope: str = "head"
    rope_theta: Optional[float] = 1e7
    partial_rotary_factor: float = 0.25
    full_attention_interval: int = 4
    layer_types: Optional[Tuple[str, ...]] = None
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = False
    # the sparse MLP (qwen3_moe.dropless_block reads these)
    num_experts: int = 512
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    norm_topk_prob: bool = True
    num_routed_experts: Optional[int] = None
    first_expert_id: int = 0
    aux_loss_coef: float = 0.001
    z_loss_coef: float = 0.0
    # random weights only: what init_params draws the embedding at (HF's
    # initializer_range); a loaded checkpoint would not read it
    embed_init_std: float = 0.02

    def __post_init__(self) -> None:
        super().__post_init__()
        self.check_expert_share()
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= \
                self.actual_head_dim:
            raise ValueError(
                f"partial_rotary_factor {self.partial_rotary_factor} of a "
                f"{self.actual_head_dim}-wide head is no even number of "
                "dims to rotate")

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        if self.layer_types is not None:
            return tuple(self.layer_types)
        return tuple(
            FULL if (i + 1) % self.full_attention_interval == 0 else LINEAR
            for i in range(self.num_hidden_layers))

    @property
    def rotary_dim(self) -> int:
        return int(self.actual_head_dim * self.partial_rotary_factor)

    def sparse_layer_ids(self) -> Tuple[int, ...]:
        """Every layer routes (what the engine sizes its counters by)."""
        return tuple(range(self.num_hidden_layers))

    def num_params(self) -> int:
        h, v, heads = (self.hidden_size, self.vocab_size,
                       self.linear_num_value_heads)
        moe = (h * self.router_width
               + self.num_experts * 3 * h * self.moe_intermediate_size
               + _moe.shared_expert_params(self) + h)
        linear = (h * (2 * self.linear_key_size + 3 * self.linear_value_size)
                  + 2 * h * heads + 2 * heads
                  + self.conv_channels * self.linear_conv_kernel_dim
                  + self.linear_value_head_dim + h)
        full = (3 * h * self.q_size + 2 * h * self.kv_size
                + sum(self.qk_norm_sizes) + h)
        n_lin = self.num_linear_layers
        return (n_lin * linear + (self.num_hidden_layers - n_lin) * full
                + self.num_hidden_layers * moe
                + v * h + h + (0 if self.tie_word_embeddings else v * h))


def config_from_args(args, common: dict) -> Qwen3NextConfig:
    """The published config.json names."""
    if args.mlp_only_layers or (args.decoder_sparse_step or 1) != 1:
        raise NotImplementedError(
            "qwen3_next with dense-MLP layers (mlp_only_layers "
            f"{args.mlp_only_layers}, decoder_sparse_step "
            f"{args.decoder_sparse_step}): every layer's MLP is the "
            "sparse one in models/qwen3_next.py")
    return Qwen3NextConfig(**{
        **common, **_moe.expert_share_from_args(args),
        "layer_types": (None if args.layer_types is None
                        else tuple(args.layer_types)),
        "num_experts": args.num_experts,
        "num_experts_per_tok": args.num_experts_per_tok,
        "moe_intermediate_size": args.moe_intermediate_size
        or common["intermediate_size"],
        "aux_loss_coef": args.router_aux_loss_coef,
        "z_loss_coef": args.router_z_loss_coef,
        **{name: getattr(args, name) for name in (
            "full_attention_interval", "partial_rotary_factor",
            "shared_expert_intermediate_size",
            "linear_num_key_heads", "linear_num_value_heads",
            "linear_key_head_dim", "linear_value_head_dim",
            "linear_conv_kernel_dim")}})


def init_params(key: jax.Array, cfg: Qwen3NextConfig) -> Params:
    """Random init: fan-in uniform projections and experts, router
    normal(0.02), the embedding normal(``cfg.embed_init_std``) (0.02 as
    in the other families), zero-centred gains at 0 and the gate norm's
    plain gain at 1; the decay's own parameters as
    ``olmo_hybrid.init_params`` draws them (``A ~ U(1, 16)``, the step
    bias the inverse softplus of a step log-uniform in [1e-3, 1e-1]).
    ``a_proj`` is drawn like any projection: this block norms the
    mixer's input, which is what the published initialisers assume."""
    pattern = cfg.period_pattern
    periods = cfg.num_periods
    h, v = cfg.hidden_size, cfg.vocab_size
    pd = cfg.param_dtype
    heads = cfg.linear_num_value_heads
    kq, kv_ = cfg.linear_key_size, cfg.linear_value_size
    keys = iter(jax.random.split(key, 32))

    def stacks(n):
        lead = (periods, n)

        def w(shape, fan_in):
            return fan_in_uniform(next(keys), lead + shape, fan_in, pd)

        return lead, w

    lead, w = stacks(pattern.count(LINEAR))
    step = jnp.exp(jax.random.uniform(
        next(keys), lead + (heads,), F32, jnp.log(1e-3), jnp.log(1e-1)))
    linear = {
        "input_layernorm": jnp.zeros(lead + (h,), pd),
        "q_proj": w((h, kq), h), "k_proj": w((h, kq), h),
        "v_proj": w((h, kv_), h), "g_proj": w((h, kv_), h),
        "o_proj": w((kv_, h), kv_),
        "a_proj": w((h, heads), h), "b_proj": w((h, heads), h),
        "A_log": jnp.log(jax.random.uniform(
            next(keys), lead + (heads,), F32, 1.0, 16.0)).astype(pd),
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(pd),
        "conv": w((cfg.linear_conv_kernel_dim, cfg.conv_channels),
                  cfg.linear_conv_kernel_dim),
        "o_norm": jnp.ones(lead + (cfg.linear_value_head_dim,), pd),
    }
    lead, w = stacks(pattern.count(FULL))
    dh = cfg.actual_head_dim
    full = {
        "input_layernorm": jnp.zeros(lead + (h,), pd),
        # each head's query and gate side by side
        "q_proj": w((h, 2 * cfg.q_size), h),
        "k_proj": w((h, cfg.kv_size), h), "v_proj": w((h, cfg.kv_size), h),
        "o_proj": w((cfg.q_size, h), cfg.q_size),
        "q_norm": jnp.zeros(lead + (dh,), pd),
        "k_norm": jnp.zeros(lead + (dh,), pd),
    }
    n = cfg.num_hidden_layers
    moe = _moe.init_moe_params([next(keys) for _ in range(8)], cfg, (n,))
    moe["post_attention_layernorm"] = jnp.zeros((n, h), pd)
    params: Params = {
        "embed_tokens": cfg.embed_init_std * jax.random.normal(
            next(keys), (v, h), pd),
        "layers": {"linear": linear, "full": full, "moe": moe},
        "norm": jnp.zeros((h,), pd),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = fan_in_uniform(next(keys), (h, v), h, pd)
    return params


def gated_attention_mix_cached(
    h: jax.Array,
    layer: Params,
    index: jax.Array,
    cache_k: jax.Array,
    cache_v: jax.Array,
    cos: jax.Array,
    sin: jax.Array,
    positions: jax.Array,
    cfg: Qwen3NextConfig,
    *,
    write_mask: Optional[jax.Array] = None,
    kv_io: Optional[Any] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The gated attention mixer of the normed hidden states ``h``
    [B, S, H] (``llama.attention_mix_cached`` with a gate): per-head
    zero-centred q/k norm, rotary embedding on the first
    ``cfg.rotary_dim`` dims of each head (``cos`` / ``sin`` are that
    wide), K/V appended at ``index`` of the whole cache through
    ``kv_io``, attention against the cache, the heads' outputs times
    the sigmoid of the gate ``q_proj`` gave beside each query,
    ``o_proj``. Returns (the residual's increment, cache_k, cache_v)."""
    cdt = cfg.dtype
    dh = cfg.actual_head_dim
    kv_io = kv_io or DenseKVIO()
    b, s, _ = h.shape
    qg = (h @ layer["q_proj"].astype(cdt)).reshape(b, s, -1, 2 * dh)
    q, gate = qg[..., :dh], qg[..., dh:]
    k = (h @ layer["k_proj"].astype(cdt)).reshape(b, s, -1, dh)
    v = (h @ layer["v_proj"].astype(cdt)).reshape(b, s, -1, dh)
    q = rms_norm_zero_centered(q, layer["q_norm"], cfg.rms_norm_eps)
    k = rms_norm_zero_centered(k, layer["k_norm"], cfg.rms_norm_eps)
    q = q.transpose(0, 2, 1, 3)  # [B, Hq, S, D]
    k = k.transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)
    q, k = apply_rotary_pos_emb(q, k, cos, sin)
    cache_k = kv_io.write(cache_k, index, k, positions, write_mask)
    cache_v = kv_io.write(cache_v, index, v, positions, write_mask)
    attn = kv_io.attend(q, cache_k, cache_v, index, positions, own=(k, v))
    attn = attn.transpose(0, 2, 1, 3).reshape(b, s, -1)
    with jax.named_scope("attn.output_gate"):
        attn = (attn.astype(F32) * jax.nn.sigmoid(
            gate.reshape(b, s, -1).astype(F32))).astype(cdt)
    return attn @ layer["o_proj"].astype(cdt), cache_k, cache_v


def _moe_layer_of(stack: Params, index: jax.Array) -> Params:
    """Layer ``index`` of the sparse MLPs' stack but for the experts,
    which the grouped matmul reads out of the whole stack."""
    def one(a):
        return jax.lax.dynamic_index_in_dim(a, index, 0, keepdims=False)

    return {name: one(a) for name, a in stack.items()
            if name not in _moe.EXPERT_KEYS}


def _sparse_mlp(h, moe: Params, index, cfg, row_mask):
    """``x <- x + MoE(N(x; w2))`` at layer ``index`` of the stack; the
    block's routing counts beside it."""
    layer = _moe_layer_of(moe, index)
    with jax.named_scope("moe"):
        normed = rms_norm_zero_centered(
            h, layer["post_attention_layernorm"], cfg.rms_norm_eps)
        h, _aux, _stats, routing = _moe.dropless_block(
            h, normed, layer, cfg, row_mask,
            ({name: moe[name] for name in _moe.EXPERT_KEYS}, index))
    return h, _moe.routing_counts(routing)


def forward_cached(
    params: Params,
    input_ids: jax.Array,
    cfg: Qwen3NextConfig,
    cache: Tuple[jax.Array, jax.Array, jax.Array, jax.Array],
    *,
    positions: jax.Array,
    write_mask: Optional[jax.Array] = None,
    kv_io: Optional[Any] = None,
    row_mask: Optional[jax.Array] = None,
    return_routing: bool = False,
    logit_rows: Optional[jax.Array] = None,
):
    """Cached forward: [B, S] tokens at absolute ``positions`` [B, S] ->
    (logits, the new cache). The cache, ``row_mask``, ``write_mask``,
    ``logit_rows`` (logits [B, 1, V] for the named row a sequence in
    place of [B, S, V]) and the loop over periods with the cache as its
    carry are ``olmo_hybrid.forward_cached``'s; ``return_routing``
    appends the call's routing counts as ``qwen3_moe.forward_cached``
    does (int32 scalars summed over the layers)."""
    pattern = cfg.period_pattern
    layers = _hybrid.period_layers(pattern)
    n_lin, n_full = pattern.count(LINEAR), pattern.count(FULL)
    kv_io = kv_io or DenseKVIO()
    x = _llama.embed(params, input_ids, cfg)
    b, s = input_ids.shape
    cos, sin = get_cos_sin(s, cfg.rotary_dim, cfg.rope_theta,
                           positions=positions)
    fresh = positions[:, 0] == 0
    written = (jnp.ones((b,), bool) if write_mask is None else write_mask)
    moe = params["layers"]["moe"]

    def period_fn(carry, index):
        h, (ck, cv, state, conv) = carry
        counts = []
        for place, (kind, stack, j) in enumerate(layers):
            layer = _hybrid.layer_of(params["layers"][stack], index, j)
            u = rms_norm_zero_centered(
                h, layer["input_layernorm"], cfg.rms_norm_eps)
            if kind == LINEAR:
                at = index * n_lin + j
                old_s = jax.lax.dynamic_index_in_dim(state, at, 0, False)
                old_t = jax.lax.dynamic_index_in_dim(conv, at, 0, False)
                out, new_s, new_t = _hybrid.linear_attention_mix(
                    u, layer, cfg,
                    jnp.where(fresh[:, None, None, None], 0.0, old_s),
                    jnp.where(fresh[:, None, None], 0, old_t),
                    row_mask=row_mask)
                state = jax.lax.dynamic_update_index_in_dim(
                    state, jnp.where(written[:, None, None, None],
                                     new_s, old_s), at, 0)
                conv = jax.lax.dynamic_update_index_in_dim(
                    conv, jnp.where(written[:, None, None], new_t, old_t),
                    at, 0)
            else:
                with jax.named_scope("attn"):
                    out, ck, cv = gated_attention_mix_cached(
                        u, layer, index * n_full + j, ck, cv, cos, sin,
                        positions, cfg, write_mask=write_mask, kv_io=kv_io)
            h, routed = _sparse_mlp(
                h + out, moe, index * len(layers) + place, cfg, row_mask)
            counts.append(routed)
        return ((h, (ck, cv, state, conv)),
                jax.tree.map(lambda *xs: sum(xs), *counts))

    # the cache whole as the carry, never a scanned operand, and no
    # parameters scanned (olmo_hybrid.forward_cached)
    (x, cache), counts = jax.lax.scan(
        period_fn, (x, tuple(cache)),
        jnp.arange(cfg.num_periods, dtype=jnp.int32))
    x = rms_norm_zero_centered(_llama.select_logit_rows(x, logit_rows),
                               params["norm"], cfg.rms_norm_eps)
    logits = x @ _llama.lm_head_weight(params, cfg)
    if return_routing:
        return logits, cache, jax.tree.map(jnp.sum, counts)
    return logits, cache


def forward(
    params: Params,
    input_ids: jax.Array,
    cfg: Qwen3NextConfig,
    *,
    sequential: bool = False,
    return_hidden: bool = False,
) -> jax.Array:
    """Full forward without a cache: [B, S] tokens -> logits [B, S, V]
    (``return_hidden``: the final-normed hidden states). Attention over
    the sequence itself, the delta rule from an empty state in its
    chunked form, or row after row with ``sequential`` (the tests'
    oracle for the chunked form and the cache)."""
    layers = _hybrid.period_layers(cfg.period_pattern)
    b, s = input_ids.shape
    x = _llama.embed(params, input_ids, cfg)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    cos, sin = get_cos_sin(s, cfg.rotary_dim, cfg.rope_theta,
                           positions=positions)
    state_shape, tail_shape = cfg.recurrent_state_shapes(b)
    state0 = jnp.zeros(state_shape[1:], F32)
    tail0 = jnp.zeros(tail_shape[1:], cfg.dtype)
    moe = params["layers"]["moe"]

    def period_fn(h, index):
        for place, (kind, stack, j) in enumerate(layers):
            layer = _hybrid.layer_of(params["layers"][stack], index, j)
            u = rms_norm_zero_centered(
                h, layer["input_layernorm"], cfg.rms_norm_eps)
            if kind == LINEAR:
                out, _, _ = _hybrid.linear_attention_mix(
                    u, layer, cfg, state0, tail0, sequential=sequential)
            else:
                with jax.named_scope("attn"):
                    out, _, _ = gated_attention_mix_cached(
                        u, layer, 0, None, None, cos, sin, positions, cfg,
                        kv_io=_hybrid.SelfKV())
            h, _ = _sparse_mlp(
                h + out, moe, index * len(layers) + place, cfg, None)
        return h, None

    x, _ = jax.lax.scan(
        period_fn, x, jnp.arange(cfg.num_periods, dtype=jnp.int32))
    x = rms_norm_zero_centered(x, params["norm"], cfg.rms_norm_eps)
    if return_hidden:
        return x
    return x @ _llama.lm_head_weight(params, cfg)


class Qwen3Next:
    config_cls = Qwen3NextConfig

    def __init__(self, config: Qwen3NextConfig):
        self.config = config

    def init(self, key: jax.Array) -> Params:
        return init_params(key, self.config)

    def __call__(self, params: Params, input_ids: jax.Array, **kw):
        return forward(params, input_ids, self.config, **kw)
