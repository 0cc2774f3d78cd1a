"""mimo_v2_flash (Xiaomi MiMo-V2-Flash) — window layers with a learned
sink between full layers, keys wider than values, K/V heads by layer
kind, sigmoid-routed experts without a shared one.

``config.json`` of XiaomiMiMo/MiMo-V2-Flash (``model_type:
mimo_v2_flash``): ``hybrid_layer_pattern`` names each layer 0 (full
attention) or 1 (sliding-window attention; published: ``0 1 1 1 1 0``
then ``1 1 1 1 1 0`` seven times), ``moe_layer_freq`` each layer 0 (a
dense SwiGLU of ``intermediate_size``) or 1 (``n_routed_experts``
routed experts of ``moe_intermediate_size``, top
``num_experts_per_tok``, no shared expert). With the plain gain ``N(x;
g) = x / sqrt(mean(x^2) + layernorm_epsilon) * g`` (float32
statistics):

    h0 = E[token]                                      (not scaled)
    h <- h + Mix(N(h; g_in))
    h <- h + MLP(N(h; g_post))
    logits = N(h; g_f) W_head                          (untied)

``attention_bias`` false: no bias anywhere.

*Mixer* of a layer of kind c (``attention_mix``). Full: ``Hkv =
num_key_value_heads`` (4), rotary base ``rope_theta`` (5e6), no sink,
every earlier key. Window: ``Hkv = swa_num_key_value_heads`` (8), base
``swa_rope_theta`` (1e4), a sink logit a query head
(``attention_sink_bias``, float32, ``add_swa_attention_sink_bias``),
keys with ``0 <= i - j < sliding_window`` (128, the query's own
included). In both ``q = x W_q`` (heads x ``head_dim`` 192), ``k = x
W_k`` (Hkv x 192), ``v = attention_value_scale * (x W_v)`` (Hkv x
``v_head_dim`` 128); no q/k norm; the rotary embedding (rotate-half,
absolute positions) turns the FIRST ``int(head_dim *
partial_rotary_factor)`` = 64 dims of each q and k head, the others
pass; scores ``q . k * head_dim ** -0.5`` in float32; the softmax of a
window layer has the sink as one more column that carries no value
(``p(i, j) = exp(s(i, j) - m) / (exp(b_h - m) + sum_j' exp(s(i, j') -
m))``); ``Mix = concat_h(o_h) W_o`` (heads x 128 -> hidden).

*Sparse MLP*: ``qwen3_moe.dropless_mlp`` told ``score_func sigmoid``:
``s = sigmoid(m W_r)`` in float32 over all routed experts, the top k of
``s + b`` (``b`` the ``e_score_correction_bias`` of ``noaux_tc``: a
float32 buffer, ``expert_bias`` in the parameter tree as the shared
code reads it; it steers the choice, never the weight), weighted by
``s / (sum + 1e-20)`` (``norm_topk_prob``) times
``routed_scaling_factor`` (null: 1); dropless; ``n_shared_experts``
null: none. ``n_group`` / ``topk_group`` must be 1. A configuration may
hold a SHARE of the experts (``qwen3_moe.ExpertShare``:
``n_routed_experts`` held of ``num_routed_experts``).

*The cache* (``forward_cached``; ``kv_cache.WindowCache`` with two
shapes of K/V, ``kv_head_shapes``): the page pool over the full layers
``k [full, pages, 4, page, 256]`` / ``v [.., 128]`` through the
engine's tables, and by slot a ring of ``ceil(window / page) + 1``
pages a window layer ``wk [window, 1 + slots * ring, 8, page, 256]`` /
``wv [.., 128]`` (``kv_cache.RingKVIO``). A key is stored at 256 (its
192 numbers and 64 zeros: whole 128-lane tiles, which a Mosaic copy of
a page needs), and a decode step's query is padded alike, so the
padding adds nothing to a score. A call of several rows is a prompt
from its first token: it attends to itself in key blocks
(``ops/flash_attention.prefill_self_attention``) and only writes the
cache; a one-row call reads the cache (the paged decode kernel, told
the window and the sink on a window layer). A prefill row NAMES its
slot (``slot_ids``; ``families.rows_name_slots``): the engine's one
prefill program is one row, which writes the pool at its pages and the
rings at its slot.

Parameters: ``layers["block"]`` holds what every layer has (the two
norms, ``q_proj``, ``o_proj``) stacked ``[layers, ...]``;
``layers["full"]`` / ``layers["window"]`` the ``k_proj`` / ``v_proj``
of each kind (and the window layers' ``attention_sink_bias``);
``layers["dense"]`` / ``layers["moe"]`` the MLPs of each kind. The
layer loop is unrolled: no two consecutive layers of the published
stack's head are alike.

Not built, and said so where it can be reached: the three
multi-token-prediction layers of the model card (a step yields one
token; the config has no key for them), the trainer's step, tensor /
context / pipeline / expert parallelism, prefix sharing, HF weight
loading, a contiguous cache, a sink on the full layers
(``add_full_attention_sink_bias`` true).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from scaletorch_tpu.models import llama as _llama
from scaletorch_tpu.models import qwen3_moe as _moe
from scaletorch_tpu.models.afmoe import SelfKV
from scaletorch_tpu.models.layers import (
    apply_rotary_pos_emb,
    fan_in_uniform,
    get_cos_sin,
    rms_norm,
)
from scaletorch_tpu.models.llama import LlamaConfig, Params
from scaletorch_tpu.models.qwen3_moe import ExpertShare

F32 = jnp.float32
FULL, WINDOW = 0, 1            # hybrid_layer_pattern's two values
# random weights only: the standard deviations ``init_params`` draws the
# selection bias and the sinks at (a trained model's are whatever
# training left; zeros would leave the bias untested, and equal sinks
# would make every head's share of the mass the same). The bias at the
# spacing of this router's top scores: over 256 experts at hidden 4,096
# the eighth-largest sigmoid score is ~0.92 and its neighbours lie
# ~0.005 apart. At afmoe's 0.05 the bias, which is the same for every
# token, reorders the top 8 whole: every token then prefers the same
# experts, a step of 32 tokens touched 7.4-7.9 of 16 held experts a
# layer where even routing touches 10.2, and how many choices fell on
# the held ones was the seed's luck (1.32-1.57 M a window; the step and
# with it ``serve_itl_p95_ms`` 5 % apart from seed to seed: PERF.md,
# PR 59). A trained model's bias evens the load; it does not make it
EXPERT_BIAS_INIT_STD = 0.005
SINK_INIT_STD = 0.5

_PUBLISHED_PATTERN = (0, 1, 1, 1, 1, 0) + (1, 1, 1, 1, 1, 0) * 7
_PUBLISHED_MOE_FREQ = (0,) + (1,) * 47


@dataclass(frozen=True)
class MimoV2FlashConfig(ExpertShare, LlamaConfig):
    # MiMo-V2-Flash defaults (the published config.json)
    vocab_size: int = 152576
    hidden_size: int = 4096
    intermediate_size: int = 16384         # the dense layers'
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 4           # a full layer's
    swa_num_key_value_heads: int = 8       # a window layer's
    head_dim: Optional[int] = 192          # q and k
    v_head_dim: int = 128
    # a window layer's head widths, where a file repeats them (None:
    # the full layer's); one that differs is refused
    swa_head_dim: Optional[int] = None
    swa_v_head_dim: Optional[int] = None
    max_position_embeddings: int = 262144
    rope_theta: float = 5000000.0          # a full layer's
    swa_rope_theta: float = 10000.0        # a window layer's
    partial_rotary_factor: float = 0.334
    rms_norm_eps: float = 1e-5             # layernorm_epsilon
    tie_word_embeddings: bool = False
    sliding_window: int = 128
    attention_value_scale: float = 0.707
    hybrid_layer_pattern: Tuple[int, ...] = _PUBLISHED_PATTERN
    moe_layer_freq: Tuple[int, ...] = _PUBLISHED_MOE_FREQ
    add_swa_attention_sink_bias: bool = True
    add_full_attention_sink_bias: bool = False
    # the sparse MLP (qwen3_moe.dropless_mlp reads these through the
    # properties below); n_routed_experts counts the experts HELD here
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 2048
    n_shared_experts: Optional[int] = None
    norm_topk_prob: bool = True
    routed_scaling_factor: Optional[float] = None
    n_group: int = 1
    topk_group: int = 1
    num_routed_experts: Optional[int] = None
    first_expert_id: int = 0
    # random weights only (models/families.py): the embedding's standard
    # deviation, the multiples of their fan-in bounds the held routed
    # experts' down projection and q_proj are drawn at, and the mean the
    # sinks are drawn around
    embed_init_std: float = 0.02
    routed_expert_init_scale: float = 1.0
    query_init_scale: float = 1.0
    sink_init_mean: float = 0.0
    score_func = "sigmoid"
    shared_expert_gated = False
    aux_loss_coef = 0.0
    z_loss_coef = 0.0

    def __post_init__(self) -> None:
        for name in ("hybrid_layer_pattern", "moe_layer_freq"):
            values = tuple(int(x) for x in getattr(self, name))
            object.__setattr__(self, name, values)
            if len(values) != self.num_hidden_layers:
                raise ValueError(
                    f"{name} names {len(values)} layers, "
                    f"num_hidden_layers is {self.num_hidden_layers}")
            if set(values) - {0, 1}:
                raise ValueError(f"{name} holds other than 0 / 1: {values}")
        if 1 not in self.moe_layer_freq:
            raise ValueError("moe_layer_freq names no sparse layer")
        for name, full in (("swa_head_dim", self.actual_head_dim),
                           ("swa_v_head_dim", self.v_head_dim)):
            if getattr(self, name) not in (None, full):
                raise NotImplementedError(
                    f"mimo_v2_flash with {name} {getattr(self, name)} "
                    f"beside the full layers' {full}: one head width for "
                    "both kinds of layer is written "
                    "(models/mimo_v2_flash.py); the published configuration "
                    "repeats the full layers'")
        if self.add_full_attention_sink_bias:
            raise NotImplementedError(
                "mimo_v2_flash with add_full_attention_sink_bias true: a "
                "sink on the full layers is not written; the published "
                "configuration has false")
        if self.n_group != 1 or self.topk_group != 1:
            raise NotImplementedError(
                f"mimo_v2_flash with n_group {self.n_group} / topk_group "
                f"{self.topk_group}: the group-limited choice of experts "
                "is not written (models/mimo_v2_flash.py); the published "
                "configuration has 1 / 1")
        if self.n_shared_experts:
            raise NotImplementedError(
                f"mimo_v2_flash with n_shared_experts "
                f"{self.n_shared_experts}: no shared expert is written; the "
                "published configuration has null")
        if self.sliding_window < 1:
            raise ValueError(f"sliding_window {self.sliding_window} < 1")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= \
                self.actual_head_dim:
            raise ValueError(
                f"partial_rotary_factor {self.partial_rotary_factor} of a "
                f"head of {self.actual_head_dim}: {self.rotary_dim} dims")
        for heads in (self.num_key_value_heads,
                      self.swa_num_key_value_heads):
            if self.num_attention_heads % heads:
                raise ValueError(
                    f"{self.num_attention_heads} query heads on {heads} "
                    "K/V heads")
        self.check_expert_share()

    # ---- the two kinds of layer ---------------------------------------
    @property
    def rotary_dim(self) -> int:
        """Dims of a q or k head the rotary embedding turns (64 of
        192)."""
        return int(self.actual_head_dim * self.partial_rotary_factor)

    @property
    def attn_scale(self) -> float:
        return 1.0 / math.sqrt(self.actual_head_dim)

    def kv_heads(self, kind: int) -> int:
        return (self.swa_num_key_value_heads if kind == WINDOW
                else self.num_key_value_heads)

    def has_sink(self, kind: int) -> bool:
        return kind == WINDOW and self.add_swa_attention_sink_bias

    # ---- the cache (kv_cache reads these) -----------------------------
    @property
    def num_window_layers(self) -> int:
        """Layers that keep a ring of K/V by slot (``WindowCache``)."""
        return self.hybrid_layer_pattern.count(WINDOW)

    @property
    def num_kv_cache_layers(self) -> int:
        """Layers that keep every token's K/V: the page pool's leading
        axis."""
        return self.hybrid_layer_pattern.count(FULL)

    @property
    def kv_head_shapes(self):
        """``(K/V heads, key width as stored, value width)`` of the page
        pool and of the rings (``kv_cache.kv_head_shapes``)."""
        from scaletorch_tpu.inference.kv_cache import stored_key_width

        d_k = stored_key_width(self.actual_head_dim)
        return ((self.num_key_value_heads, d_k, self.v_head_dim),
                (self.swa_num_key_value_heads, d_k, self.v_head_dim))

    # ---- what qwen3_moe.dropless_mlp reads under its own names --------
    @property
    def num_experts(self) -> int:
        return self.n_routed_experts

    @property
    def route_scale(self) -> float:
        return (1.0 if self.routed_scaling_factor is None
                else self.routed_scaling_factor)

    @property
    def shared_expert_intermediate_size(self) -> int:
        return 0

    def sparse_layer_ids(self) -> Tuple[int, ...]:
        """The layers that route (what the engine sizes its counters
        by)."""
        return tuple(i for i, sparse in enumerate(self.moe_layer_freq)
                     if sparse)

    def mixer_params(self, kind: int) -> int:
        h, heads = self.hidden_size, self.num_attention_heads
        return (h * heads * self.actual_head_dim
                + h * self.kv_heads(kind)
                * (self.actual_head_dim + self.v_head_dim)
                + heads * self.v_head_dim * h
                + (heads if self.has_sink(kind) else 0))

    def num_params(self) -> int:
        """Parameters as ``init_params`` builds them (the selection bias
        is a buffer and counts with them)."""
        h, v = self.hidden_size, self.vocab_size
        n_sparse = len(self.sparse_layer_ids())
        moe = (h * self.router_width + self.router_width
               + self.num_experts * 3 * h * self.moe_intermediate_size)
        return (sum(self.mixer_params(kind) + 2 * h
                    for kind in self.hybrid_layer_pattern)
                + (self.num_hidden_layers - n_sparse)
                * 3 * h * self.intermediate_size
                + n_sparse * moe
                + v * h + h + (0 if self.tie_word_embeddings else v * h))


def config_from_args(args, common: dict) -> MimoV2FlashConfig:
    """The published config.json names; ``layernorm_epsilon`` is the
    norm's epsilon and ``sliding_window_size`` the window among the
    launch arguments (the published file has it beside
    ``sliding_window``, both 128)."""
    if args.mlp_only_layers or (args.decoder_sparse_step or 1) != 1:
        raise NotImplementedError(
            "mimo_v2_flash with mlp_only_layers / decoder_sparse_step: "
            "its dense layers are moe_layer_freq's zeros "
            "(models/mimo_v2_flash.py)")
    if args.moe_dispatch != "auto" or args.moe_capacity_factor != 1.25:
        raise NotImplementedError(
            "mimo_v2_flash under capacity dispatch (--moe_dispatch "
            f"{args.moe_dispatch}, --moe_capacity_factor "
            f"{args.moe_capacity_factor}): the family routes dropless "
            "(qwen3_moe.dropless_mlp) and no capacity path is written "
            "for a sigmoid router")
    given = {name: getattr(args, name) for name in (
        "hybrid_layer_pattern", "moe_layer_freq", "layernorm_epsilon",
        "sink_init_mean") if getattr(args, name) is not None}
    if "layernorm_epsilon" in given:
        given["rms_norm_eps"] = given.pop("layernorm_epsilon")
    return MimoV2FlashConfig(**{
        **common,
        "moe_intermediate_size": args.moe_intermediate_size
        or common["intermediate_size"],
        "norm_topk_prob": (True if args.norm_topk_prob is None
                           else args.norm_topk_prob),
        "sliding_window": args.sliding_window_size,
        **{name: getattr(args, name) for name in (
            "v_head_dim", "swa_num_key_value_heads", "swa_rope_theta",
            "swa_head_dim", "swa_v_head_dim", "partial_rotary_factor",
            "attention_value_scale", "add_swa_attention_sink_bias",
            "add_full_attention_sink_bias", "n_routed_experts",
            "num_experts_per_tok", "n_shared_experts",
            "routed_scaling_factor", "n_group", "topk_group",
            "num_routed_experts", "first_expert_id")},
        **given})


def init_params(key: jax.Array, cfg: MimoV2FlashConfig) -> Params:
    """Random init: fan-in uniform projections and experts, the router
    normal(0.02), the embedding normal(``cfg.embed_init_std``) (0.02 as
    every family), every gain 1, the selection bias
    normal(``EXPERT_BIAS_INIT_STD``) and the window layers' sinks
    normal(``cfg.sink_init_mean``, ``SINK_INIT_STD``), both float32; the
    held routed experts' down projection times
    ``cfg.routed_expert_init_scale`` and ``q_proj`` times
    ``cfg.query_init_scale`` (both 1, and the sinks' mean 0, unless a
    launch says otherwise: they are properties of random weights,
    ``models/families.py``)."""
    h, v, pd = cfg.hidden_size, cfg.vocab_size, cfg.param_dtype
    n, heads = cfg.num_hidden_layers, cfg.num_attention_heads
    dk, dv = cfg.actual_head_dim, cfg.v_head_dim
    n_sparse = len(cfg.sparse_layer_ids())
    keys = iter(jax.random.split(key, 24))

    def w(lead, shape, fan_in):
        return fan_in_uniform(next(keys), (lead,) + shape, fan_in, pd)

    block = {
        "input_layernorm": jnp.ones((n, h), pd),
        "post_attention_layernorm": jnp.ones((n, h), pd),
        "q_proj": w(n, (h, heads * dk), h),
        "o_proj": w(n, (heads * dv, h), heads * dv),
    }
    if cfg.query_init_scale != 1.0:
        block["q_proj"] = (block["q_proj"].astype(F32)
                           * cfg.query_init_scale).astype(pd)

    def kv(kind, count):
        hkv = cfg.kv_heads(kind)
        return {"k_proj": w(count, (h, hkv * dk), h),
                "v_proj": w(count, (h, hkv * dv), h)}

    full = kv(FULL, cfg.num_kv_cache_layers)
    window = kv(WINDOW, cfg.num_window_layers)
    if cfg.add_swa_attention_sink_bias:
        window["attention_sink_bias"] = (
            cfg.sink_init_mean + SINK_INIT_STD * jax.random.normal(
                next(keys), (cfg.num_window_layers, heads), F32))
    di, n_dense = cfg.intermediate_size, n - n_sparse
    dense = {
        "gate_proj": w(n_dense, (h, di), h),
        "up_proj": w(n_dense, (h, di), h),
        "down_proj": w(n_dense, (di, h), di),
    }
    moe = _moe.init_moe_params(
        [next(keys) for _ in range(8)], cfg, (n_sparse,))
    if cfg.routed_expert_init_scale != 1.0:
        moe["expert_down_proj"] = (
            moe["expert_down_proj"].astype(F32)
            * cfg.routed_expert_init_scale).astype(pd)
    moe["expert_bias"] = EXPERT_BIAS_INIT_STD * jax.random.normal(
        next(keys), (n_sparse, cfg.router_width), F32)
    params: Params = {
        "embed_tokens": cfg.embed_init_std * jax.random.normal(
            next(keys), (v, h), pd),
        "layers": {"block": block, "full": full, "window": window,
                   "dense": dense, "moe": moe},
        "norm": jnp.ones((h,), pd),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = fan_in_uniform(next(keys), (h, v), h, pd)
    return params


def _stored(x: jax.Array, width: int) -> jax.Array:
    """``x`` [..., d] with zeros after it up to ``width``: a key as the
    cache stores it, or the query that reads it."""
    if x.shape[-1] == width:
        return x
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, width - x.shape[-1]),))


def attention_mix(
    u: jax.Array,
    block: Params,
    own: Params,
    kind: int,
    index: int,
    cache_k: Any,
    cache_v: Any,
    rope: Tuple[jax.Array, jax.Array],
    positions: jax.Array,
    cfg: MimoV2FlashConfig,
    io: Any,
    write_mask: Optional[jax.Array],
) -> Tuple[jax.Array, Any, Any]:
    """The attention mixer of the normed hidden states ``u`` [B, S, H]
    of a layer of ``kind`` (``block``: its ``q_proj`` / ``o_proj``;
    ``own``: its kind's ``k_proj`` / ``v_proj`` and sink): K/V written
    at ``index`` of that kind's cache through ``io``; a call of one row
    reads the cache, a call of several rows attends to itself (module
    docstring). Returns (the mixer's output, cache_k, cache_v)."""
    from scaletorch_tpu.ops.flash_attention import prefill_self_attention

    cdt = cfg.dtype
    b, s, _ = u.shape
    dk, dv = cfg.actual_head_dim, cfg.v_head_dim
    q = (u @ block["q_proj"].astype(cdt)).reshape(b, s, -1, dk)
    k = (u @ own["k_proj"].astype(cdt)).reshape(b, s, -1, dk)
    v = (u @ own["v_proj"].astype(cdt)).reshape(b, s, -1, dv)
    v = v * jnp.asarray(cfg.attention_value_scale, cdt)
    q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))  # [B, H, S, D]
    q, k = apply_rotary_pos_emb(q, k, *rope)
    sink = own["attention_sink_bias"] if cfg.has_sink(kind) else None
    window = cfg.sliding_window if kind == WINDOW else None
    if cache_k is not None:
        stored = cache_k.shape[-1]
        cache_k = io.write(cache_k, index, _stored(k, stored), positions,
                           write_mask)
        cache_v = io.write(cache_v, index, v, positions, write_mask)
    if s == 1:
        attn = io.attend(_stored(q, stored), cache_k, cache_v, index,
                         positions, scale=cfg.attn_scale, sink=sink)
    else:
        attn = prefill_self_attention(q, k, v, window=window, sink=sink,
                                      scale=cfg.attn_scale)
    attn = attn.transpose(0, 2, 1, 3).reshape(b, s, -1)
    return attn @ block["o_proj"].astype(cdt), cache_k, cache_v


def _at(stack: Params, index: int, skip=()) -> Params:
    """Layer ``index`` (static) of a ``[layers, ...]`` stack."""
    return {name: a[index] for name, a in stack.items() if name not in skip}


_NO_ROUTING = {"routed": 0, "dropped": 0, "elsewhere": 0,
               "expert_visits": 0, "peak_load_rows": 0}


def _run_layers(x, caches, params, cfg, ios, positions, write_mask,
                row_mask):
    """Every layer in order, unrolled (module docstring). ``caches`` =
    ((k, v), (wk, wv)), ``ios`` the adapter of each. Returns (h, caches,
    routing counts summed over the sparse layers)."""
    s = positions.shape[1]
    ropes = tuple(get_cos_sin(s, cfg.rotary_dim, theta, positions=positions)
                  for theta in (cfg.rope_theta, cfg.swa_rope_theta))
    layers, eps = params["layers"], cfg.rms_norm_eps
    kind_stacks = (layers["full"], layers["window"])
    seen = [0, 0]                      # layers of each kind so far
    n_dense = n_sparse = 0
    total = dict(_NO_ROUTING)
    for index, kind in enumerate(cfg.hybrid_layer_pattern):
        block = _at(layers["block"], index)
        with jax.named_scope("attn"), jax.named_scope(
                "attn.full" if kind == FULL else "attn.window"):
            out, ck, cv = attention_mix(
                rms_norm(x, block["input_layernorm"], eps), block,
                _at(kind_stacks[kind], seen[kind]), kind, seen[kind],
                *caches[kind], ropes[kind], positions, cfg, ios[kind],
                write_mask)
        caches = tuple((ck, cv) if i == kind else c
                       for i, c in enumerate(caches))
        seen[kind] += 1
        x = x + out
        m = rms_norm(x, block["post_attention_layernorm"], eps)
        if cfg.moe_layer_freq[index]:
            moe = layers["moe"]
            with jax.named_scope("moe"):
                f, _aux, _stats, routing = _moe.dropless_mlp(
                    m, _at(moe, n_sparse, skip=_moe.EXPERT_KEYS), cfg,
                    row_mask,
                    ({name: moe[name] for name in _moe.EXPERT_KEYS},
                     n_sparse))
            total = jax.tree.map(lambda a, c: a + c, total,
                                 _moe.routing_counts(routing))
            n_sparse += 1
        else:
            with jax.named_scope("mlp.dense"):
                f = _llama.swiglu_mlp(m, _at(layers["dense"], n_dense), cfg)
            n_dense += 1
        x = x + f.astype(x.dtype)
    return x, caches, total


def forward_cached(
    params: Params,
    input_ids: jax.Array,
    cfg: MimoV2FlashConfig,
    cache: Tuple[jax.Array, jax.Array, jax.Array, jax.Array],
    *,
    positions: jax.Array,
    write_mask: Optional[jax.Array] = None,
    kv_io: Optional[Any] = None,
    row_mask: Optional[jax.Array] = None,
    return_routing: bool = False,
    logit_rows: Optional[jax.Array] = None,
    slot_ids: Optional[jax.Array] = None,
):
    """Cached forward: [B, S] tokens at absolute ``positions`` [B, S] ->
    (logits, the new cache). ``cache`` is ``(k, v, wk, wv)``
    (``kv_cache.WindowCache``, each pair in its kind's shape): the page
    pool of the full layers, which ``kv_io`` (a ``kv_cache.PagedKVIO``)
    writes and reads through the engine's tables, and the window
    layers' rings by slot, through a ``kv_cache.RingKVIO`` built here
    from the positions and ``slot_ids`` [B] (the slot each row is; None:
    row b is slot b, a decode step). S > 1 is a prompt from its first
    token, attended to itself; S == 1 a decode step against the cache:
    ONE token (the multi-token-prediction layers are not built).
    ``row_mask``, ``logit_rows`` and ``return_routing`` as in
    ``afmoe.forward_cached``."""
    from scaletorch_tpu.inference.kv_cache import RingKVIO

    if not hasattr(kv_io, "page_tables"):
        raise NotImplementedError(
            "mimo_v2_flash's cached forward is written for the paged cache "
            "(kv_cache.WindowCache through kv_cache.PagedKVIO); a "
            "contiguous cache for window layers and two widths of K/V is "
            "not")
    b, s = input_ids.shape
    live = (jnp.full((b,), s, jnp.int32) if row_mask is None
            else jnp.sum(row_mask, axis=1, dtype=jnp.int32))
    ring_io = RingKVIO(kv_io, cfg.sliding_window, positions[:, 0],
                       jnp.maximum(live, 1), slot_ids)
    k, v, wk, wv = cache
    x = _llama.embed(params, input_ids, cfg)
    x, ((k, v), (wk, wv)), counts = _run_layers(
        x, ((k, v), (wk, wv)), params, cfg, (kv_io, ring_io), positions,
        write_mask, row_mask)
    x = rms_norm(_llama.select_logit_rows(x, logit_rows), params["norm"],
                 cfg.rms_norm_eps)
    logits = x @ _llama.lm_head_weight(params, cfg)
    if return_routing:
        return logits, (k, v, wk, wv), counts
    return logits, (k, v, wk, wv)


def forward(
    params: Params,
    input_ids: jax.Array,
    cfg: MimoV2FlashConfig,
    *,
    return_hidden: bool = False,
) -> jax.Array:
    """Full forward without a cache: [B, S] tokens -> logits [B, S, V]
    (``return_hidden``: the final-normed hidden states); S > 1."""
    b, s = input_ids.shape
    if s < 2:
        raise ValueError("mimo_v2_flash.forward attends a sequence to "
                         "itself: give it at least two tokens")
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    none = ((None, None), (None, None))
    x, _, _ = _run_layers(
        _llama.embed(params, input_ids, cfg), none, params, cfg,
        (SelfKV(), SelfKV()), positions, None, None)
    x = rms_norm(x, params["norm"], cfg.rms_norm_eps)
    if return_hidden:
        return x
    return x @ _llama.lm_head_weight(params, cfg)


class MimoV2Flash:
    config_cls = MimoV2FlashConfig

    def __init__(self, config: MimoV2FlashConfig):
        self.config = config

    def init(self, key: jax.Array) -> Params:
        return init_params(key, self.config)

    def __call__(self, params: Params, input_ids: jax.Array, **kw):
        return forward(params, input_ids, self.config, **kw)
