"""afmoe (Arcee Trinity) — window and full attention mixed, leading
dense layers, sigmoid-routed experts beside an ungated shared expert.

``config.json`` of arcee-ai/Trinity-Mini (``model_type: afmoe``):
``layer_types`` names each layer ``sliding_attention`` or
``full_attention`` (published: three to one, repeated; without the list
every ``global_attn_every_n_layers``-th is full); the first
``num_dense_layers`` have a dense SwiGLU MLP of ``intermediate_size``,
the others ``num_experts`` routed experts of ``moe_intermediate_size``
(top ``num_experts_per_tok``) and ``num_shared_experts`` shared ones.
With the plain gain ``N(x; w) = x / sqrt(mean(x^2) + eps) * w``:

    h0 = E[token] * sqrt(hidden)                     (mup_enabled)
    h <- h + N(Attn(N(h; w_in)); w_post_attn)
    h <- h + N(MLP(N(h; w_pre_mlp)); w_post_mlp)
    logits = N(h; w_f) W_head

four norms a layer: both sub-blocks norm what they read AND what they
add.

*Attention* (``attention_mix``): ``q_proj`` / ``k_proj`` / ``v_proj``
and a ``gate_proj`` of the query's width, from the normed input; q and
k normed per head; a ``sliding_attention`` layer turns q and k by the
rotary embedding (rotate-half, the whole head, absolute positions) and
lets query i see key j iff ``0 <= i - j < sliding_window``; a
``full_attention`` layer has NO rotary embedding and sees every j <= i;
``o_proj(concat_heads(softmax(q k^T / sqrt(d)) v) * sigmoid(gate))``.

*Sparse MLP*: ``qwen3_moe.dropless_mlp`` told ``score_func sigmoid``:
``s = sigmoid(x W_r)`` in float32 over all routed experts, the top k of
``s + expert_bias`` (a float32 buffer of the layer, no parameter: it
steers the choice), weighted by ``s`` WITHOUT the bias, divided by their
sum (``route_norm``) and multiplied by ``route_scale``; dropless; the
shared expert's SwiGLU added ungated. ``n_group`` / ``topk_group`` must
be 1 (no group-limited choice is written). A configuration may hold a
SHARE of the experts (``qwen3_moe.ExpertShare``).

*The cache* (``forward_cached``; ``kv_cache.WindowCache``): the page
pool over the full layers, through the engine's tables, and by slot a
ring of ``ceil(window / page) + 1`` pages a window layer
(``kv_cache.RingKVIO``, computed inside the step from the positions). A
call of several rows is a prompt from its first token: its attention
reads the call's own K/V in key blocks
(``ops/flash_attention.prefill_self_attention``) and only writes the
cache; a one-row call reads the cache (the paged decode kernel, told
the window on a window layer). So this family has no prefix to share:
a window layer holds a suffix of what a prefix page would stand for.

Parameters: ``layers["block"]`` holds every layer's attention and its
four norms, stacked ``[layers, ...]``; ``layers["dense"]`` the leading
dense MLPs ``[num_dense_layers, ...]``; ``layers["moe"]`` the sparse
MLPs ``[layers - num_dense_layers, ...]``, read out of the whole stack
by the grouped matmul (``dropless_expert_mlp(layer=...)``). The layer
loop runs the periods that hold a dense layer unrolled and scans the
rest, the cache its carry (``llama.scan_layers_cached``'s rule).

Not written: the trainer's step (``load_balance_coeff`` names a loss
whose equation and bias update the configuration does not give),
tensor / context / pipeline / expert parallelism over this family, HF
weight loading, a contiguous cache, prefix sharing, ``rope_scaling``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from scaletorch_tpu.models import llama as _llama
from scaletorch_tpu.models import qwen3_moe as _moe
from scaletorch_tpu.models.layers import (
    apply_rotary_pos_emb,
    fan_in_uniform,
    get_cos_sin,
    rms_norm,
)
from scaletorch_tpu.models.llama import LlamaConfig, Params
from scaletorch_tpu.models.qwen3_moe import ExpertShare

F32 = jnp.float32
SLIDING, FULL = "sliding_attention", "full_attention"
# random weights only: the standard deviation ``init_params`` draws
# ``expert_bias`` at (a trained model's is whatever balancing left)
EXPERT_BIAS_INIT_STD = 0.05


@dataclass(frozen=True)
class AfmoeConfig(ExpertShare, LlamaConfig):
    # Trinity-Mini defaults (the published config.json)
    vocab_size: int = 200192
    hidden_size: int = 2048
    intermediate_size: int = 6144          # the leading dense layers'
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: Optional[int] = 128
    max_position_embeddings: int = 131072
    rope_theta: float = 10000.0
    rope_scaling: Optional[Any] = None
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    qk_norm: bool = True
    qk_norm_scope: str = "head"
    layer_types: Optional[Tuple[str, ...]] = None
    global_attn_every_n_layers: int = 4
    sliding_window: int = 2048
    mup_enabled: bool = True
    num_dense_layers: int = 2
    # the sparse MLP (qwen3_moe.dropless_mlp reads these)
    num_experts: int = 128
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 1024
    num_shared_experts: int = 1
    score_func: str = "sigmoid"
    route_norm: bool = True
    route_scale: float = 2.826
    n_group: int = 1
    topk_group: int = 1
    num_routed_experts: Optional[int] = None
    first_expert_id: int = 0
    # training's (load_balance_coeff): not built
    aux_loss_coef: float = 0.0
    z_loss_coef: float = 0.0
    # random weights only: the standard deviation ``init_params`` draws
    # the embedding at (0.02 as every family; a benchmark that checks
    # logits under a flat random router states its own: PERF.md, PR 42)
    embed_init_std: float = 0.02
    shared_expert_gated = False

    def __post_init__(self) -> None:
        if self.layer_types is not None:
            object.__setattr__(self, "layer_types", tuple(self.layer_types))
        kinds = self.layer_kinds
        if len(kinds) != self.num_hidden_layers:
            raise ValueError(
                f"layer_types names {len(kinds)} layers, "
                f"num_hidden_layers is {self.num_hidden_layers}")
        unknown = sorted(set(kinds) - {SLIDING, FULL})
        if unknown:
            raise ValueError(f"unknown layer_types {unknown}")
        self.period_pattern  # raises where the stack is no repetition
        if not 0 <= self.num_dense_layers < self.num_hidden_layers:
            raise ValueError(
                f"num_dense_layers {self.num_dense_layers} of "
                f"{self.num_hidden_layers} layers: at least one is sparse")
        if self.n_group != 1 or self.topk_group != 1:
            raise NotImplementedError(
                f"afmoe with n_group {self.n_group} / topk_group "
                f"{self.topk_group}: the group-limited choice of experts "
                "is not written (models/afmoe.py); the published "
                "configuration has 1 / 1")
        if self.rope_scaling is not None:
            raise NotImplementedError(
                f"afmoe with rope_scaling {self.rope_scaling!r}: only the "
                "plain rotary embedding of the published configuration "
                "(rope_scaling null) is written")
        if self.sliding_window < 1:
            raise ValueError(f"sliding_window {self.sliding_window} < 1")
        self.check_expert_share()

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        if self.layer_types is not None:
            return tuple(self.layer_types)
        n = self.global_attn_every_n_layers
        return tuple(FULL if (i + 1) % n == 0 else SLIDING
                     for i in range(self.num_hidden_layers))

    @property
    def period_pattern(self) -> Tuple[str, ...]:
        """The shortest run of layer kinds whose repetition is the whole
        stack (a stack of one kind is a period of one layer)."""
        kinds = self.layer_kinds
        for p in range(1, len(kinds) + 1):
            if len(kinds) % p == 0 and kinds == kinds[:p] * (len(kinds) // p):
                return kinds[:p]
        raise ValueError(f"no layers: {kinds}")

    @property
    def num_window_layers(self) -> int:
        """Layers that keep a ring of K/V by slot (``WindowCache``)."""
        return self.layer_kinds.count(SLIDING)

    @property
    def num_kv_cache_layers(self) -> int:
        """Layers that keep every token's K/V: the page pool's leading
        axis."""
        return self.layer_kinds.count(FULL)

    # what qwen3_moe.dropless_mlp reads under its own names
    @property
    def norm_topk_prob(self) -> bool:
        return self.route_norm

    @property
    def shared_expert_intermediate_size(self) -> int:
        return self.num_shared_experts * self.moe_intermediate_size

    def sparse_layer_ids(self) -> Tuple[int, ...]:
        """The layers that route (what the engine sizes its counters
        by)."""
        return tuple(range(self.num_dense_layers, self.num_hidden_layers))

    def num_params(self) -> int:
        """Parameters as ``init_params`` builds them (``expert_bias`` is
        a buffer and counts with them)."""
        h, v = self.hidden_size, self.vocab_size
        dh = self.actual_head_dim
        block = 3 * h * self.q_size + 2 * h * self.kv_size + 2 * dh + 4 * h
        dense = 3 * h * self.intermediate_size
        moe = (h * self.router_width + self.router_width
               + self.num_experts * 3 * h * self.moe_intermediate_size
               + _moe.shared_expert_params(self))
        n_dense = self.num_dense_layers
        return (self.num_hidden_layers * block + n_dense * dense
                + (self.num_hidden_layers - n_dense) * moe
                + v * h + h + (0 if self.tie_word_embeddings else v * h))


def config_from_args(args, common: dict) -> AfmoeConfig:
    """The published config.json names; the window is
    ``sliding_window_size`` among the launch arguments."""
    if args.mlp_only_layers or (args.decoder_sparse_step or 1) != 1:
        raise NotImplementedError(
            "afmoe with mlp_only_layers / decoder_sparse_step: its "
            "dense layers are the leading num_dense_layers "
            "(models/afmoe.py)")
    if args.moe_dispatch != "auto" or args.moe_capacity_factor != 1.25:
        raise NotImplementedError(
            "afmoe under capacity dispatch (--moe_dispatch "
            f"{args.moe_dispatch}, --moe_capacity_factor "
            f"{args.moe_capacity_factor}): the family routes dropless "
            "(qwen3_moe.dropless_mlp) and no capacity path is written "
            "for a sigmoid router")
    return AfmoeConfig(**{
        **common,
        "num_routed_experts": args.num_routed_experts,
        "first_expert_id": args.first_expert_id,
        "layer_types": args.layer_types,
        "num_experts": args.num_experts,
        "num_experts_per_tok": args.num_experts_per_tok,
        "moe_intermediate_size": args.moe_intermediate_size
        or common["intermediate_size"],
        "sliding_window": args.sliding_window_size,
        **{name: getattr(args, name) for name in (
            "global_attn_every_n_layers",
            "num_dense_layers", "num_shared_experts", "score_func",
            "route_norm", "route_scale", "n_group", "topk_group",
            "mup_enabled")}})


def init_params(key: jax.Array, cfg: AfmoeConfig) -> Params:
    """Random init: fan-in uniform projections and experts, the router
    normal(0.02), the embedding normal(``cfg.embed_init_std``) (0.02 as
    every family), every gain 1, and
    ``expert_bias`` normal(``EXPERT_BIAS_INIT_STD``) in float32:
    at 0.05 under this router, whose top scores lie ~0.01 apart, it
    replaces 28 % of the top 8 of 128 (tests/models/test_afmoe.py
    measures the share; zeros would leave the bias untested)."""
    h, v, pd = cfg.hidden_size, cfg.vocab_size, cfg.param_dtype
    n, n_dense = cfg.num_hidden_layers, cfg.num_dense_layers
    dh = cfg.actual_head_dim
    keys = iter(jax.random.split(key, 24))

    def w(lead, shape, fan_in):
        return fan_in_uniform(next(keys), (lead,) + shape, fan_in, pd)

    block = {
        "input_layernorm": jnp.ones((n, h), pd),
        "post_attention_layernorm": jnp.ones((n, h), pd),
        "pre_mlp_layernorm": jnp.ones((n, h), pd),
        "post_mlp_layernorm": jnp.ones((n, h), pd),
        "q_proj": w(n, (h, cfg.q_size), h),
        "k_proj": w(n, (h, cfg.kv_size), h),
        "v_proj": w(n, (h, cfg.kv_size), h),
        "gate_proj": w(n, (h, cfg.q_size), h),
        "o_proj": w(n, (cfg.q_size, h), cfg.q_size),
        "q_norm": jnp.ones((n, dh), pd),
        "k_norm": jnp.ones((n, dh), pd),
    }
    di = cfg.intermediate_size
    dense = {
        "gate_proj": w(n_dense, (h, di), h),
        "up_proj": w(n_dense, (h, di), h),
        "down_proj": w(n_dense, (di, h), di),
    }
    sparse = n - n_dense
    moe = _moe.init_moe_params(
        [next(keys) for _ in range(8)], cfg, (sparse,))
    moe["expert_bias"] = EXPERT_BIAS_INIT_STD * jax.random.normal(
        next(keys), (sparse, cfg.router_width), F32)
    params: Params = {
        "embed_tokens": cfg.embed_init_std * jax.random.normal(
            next(keys), (v, h), pd),
        "layers": {"block": block, "dense": dense, "moe": moe},
        "norm": jnp.ones((h,), pd),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = fan_in_uniform(next(keys), (h, v), h, pd)
    return params


class SelfKV:
    """No cache (``forward``): a write keeps nothing."""

    def write(self, cache, layer, new, positions, write_mask):
        return cache


def attention_mix(
    u: jax.Array,
    layer: Params,
    kind: str,
    index: Any,
    cache_k: Any,
    cache_v: Any,
    rope: Tuple[jax.Array, jax.Array],
    positions: jax.Array,
    cfg: AfmoeConfig,
    io: Any,
    write_mask: Optional[jax.Array],
) -> Tuple[jax.Array, Any, Any]:
    """The attention mixer of the normed hidden states ``u`` [B, S, H]
    of a layer of ``kind``: K/V written at ``index`` of that kind's
    cache through ``io``; a call of one row reads the cache, a call of
    several rows attends to itself (module docstring). Returns (the
    mixer's output before its norm, cache_k, cache_v)."""
    from scaletorch_tpu.ops.flash_attention import prefill_self_attention

    cdt = cfg.dtype
    dh = cfg.actual_head_dim
    b, s, _ = u.shape
    window = cfg.sliding_window if kind == SLIDING else None

    def heads(name):
        return (u @ layer[name].astype(cdt)).reshape(b, s, -1, dh)

    q = rms_norm(heads("q_proj"), layer["q_norm"], cfg.rms_norm_eps)
    k = rms_norm(heads("k_proj"), layer["k_norm"], cfg.rms_norm_eps)
    v = heads("v_proj")
    gate = u @ layer["gate_proj"].astype(cdt)
    q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))  # [B, H, S, D]
    if kind == SLIDING:
        q, k = apply_rotary_pos_emb(q, k, *rope)
    cache_k = io.write(cache_k, index, k, positions, write_mask)
    cache_v = io.write(cache_v, index, v, positions, write_mask)
    if s == 1:
        attn = io.attend(q, cache_k, cache_v, index, positions)
    else:
        attn = prefill_self_attention(q, k, v, window=window)
    attn = attn.transpose(0, 2, 1, 3).reshape(b, s, -1)
    with jax.named_scope("attn.output_gate"):
        attn = (attn.astype(F32) * jax.nn.sigmoid(gate.astype(F32))
                ).astype(cdt)
    return attn @ layer["o_proj"].astype(cdt), cache_k, cache_v


def _layer_of(stack: Params, index: Any, skip=()) -> Params:
    """Layer ``index`` (static or traced) of a ``[layers, ...]`` stack,
    each matrix by one slice that its consumer fuses."""
    def one(a):
        return jax.lax.dynamic_index_in_dim(a, index, 0, keepdims=False)

    return {name: one(a) for name, a in stack.items() if name not in skip}


_NO_ROUTING = {"routed": 0, "dropped": 0, "elsewhere": 0,
               "expert_visits": 0, "peak_load_rows": 0}


def _layer(h, caches, params, cfg, at, ios, rope, positions, write_mask,
           row_mask):
    """One layer: ``at`` = (layer index, its kind, its index among the
    layers of its kind), static or traced by the loop that calls it.
    Returns (h, caches, the layer's routing counts)."""
    index, kind, kind_index = at
    eps = cfg.rms_norm_eps
    layers = params["layers"]
    block = _layer_of(layers["block"], index)
    pair = 0 if kind == FULL else 1
    with jax.named_scope("attn"), jax.named_scope(
            "attn.full" if kind == FULL else "attn.window"):
        out, ck, cv = attention_mix(
            rms_norm(h, block["input_layernorm"], eps), block, kind,
            kind_index, *caches[pair], rope, positions, cfg, ios[pair],
            write_mask)
    caches = tuple((ck, cv) if i == pair else c
                   for i, c in enumerate(caches))
    h = h + rms_norm(out, block["post_attention_layernorm"], eps)
    m = rms_norm(h, block["pre_mlp_layernorm"], eps)
    counts = dict(_NO_ROUTING)
    if isinstance(index, int) and index < cfg.num_dense_layers:
        with jax.named_scope("mlp.dense"):
            f = _llama.swiglu_mlp(
                m, _layer_of(layers["dense"], index), cfg)
    else:
        place = index - cfg.num_dense_layers
        moe = layers["moe"]
        with jax.named_scope("moe"):
            f, _aux, _stats, routing = _moe.dropless_mlp(
                m, _layer_of(moe, place, skip=_moe.EXPERT_KEYS), cfg,
                row_mask, ({name: moe[name] for name in _moe.EXPERT_KEYS},
                           place))
        counts = _moe.routing_counts(routing)
    h = h + rms_norm(f.astype(h.dtype), block["post_mlp_layernorm"], eps)
    return h, caches, counts


def _run_layers(x, caches, params, cfg, ios, positions, write_mask,
                row_mask):
    """Every layer in order: the periods that hold a dense layer
    unrolled (static indices), the others scanned with ``(h, caches)``
    as the carry and no parameter scanned. Returns (h, caches, routing
    counts summed over the layers)."""
    pattern = cfg.period_pattern
    span = len(pattern)
    n_win, n_full = pattern.count(SLIDING), pattern.count(FULL)
    before = [(pattern[:i].count(SLIDING), pattern[:i].count(FULL))
              for i in range(span)]
    s = positions.shape[1]
    rope = get_cos_sin(s, cfg.actual_head_dim, cfg.rope_theta,
                       positions=positions)

    def period(h, held, p):
        total = None
        for place, kind in enumerate(pattern):
            wins, fulls = before[place]
            kind_index = (p * n_full + fulls if kind == FULL
                          else p * n_win + wins)
            h, held, counts = _layer(
                h, held, params, cfg, (p * span + place, kind, kind_index),
                ios, rope, positions, write_mask, row_mask)
            total = counts if total is None else jax.tree.map(
                lambda a, c: a + c, total, counts)
        return h, held, total

    head = -(-cfg.num_dense_layers // span)
    totals = []
    for p in range(head):
        x, caches, counts = period(x, caches, p)
        totals.append(counts)

    def body(carry, p):
        h, held, counts = period(*carry, p)
        return (h, held), counts

    periods = cfg.num_hidden_layers // span
    if periods > head:
        (x, caches), counts = jax.lax.scan(
            body, (x, caches), jnp.arange(head, periods, dtype=jnp.int32))
        totals.append(jax.tree.map(jnp.sum, counts))
    return x, caches, jax.tree.map(lambda *xs: sum(xs), *totals)


def _embed(params: Params, input_ids: jax.Array,
           cfg: AfmoeConfig) -> jax.Array:
    x = _llama.embed(params, input_ids, cfg)
    if cfg.mup_enabled:
        x = x * jnp.asarray(math.sqrt(cfg.hidden_size), x.dtype)
    return x


def forward_cached(
    params: Params,
    input_ids: jax.Array,
    cfg: AfmoeConfig,
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
    (logits, the new cache). ``cache`` is ``(k, v, wk, wv)``
    (``kv_cache.WindowCache``): the page pool of the full layers, which
    ``kv_io`` (a ``kv_cache.PagedKVIO``) writes and reads through the
    engine's tables, and the window layers' rings by slot, through a
    ``kv_cache.RingKVIO`` built here from the positions. S > 1 is a
    prompt from its first token, attended to itself; S == 1 a decode
    step against the cache. ``row_mask`` [B, S]: the rows that are
    tokens (a prefix of each sequence; None: all): what the routing
    counts and the ring's page choice go by. ``logit_rows`` and
    ``return_routing`` as in ``qwen3_moe.forward_cached``."""
    from scaletorch_tpu.inference.kv_cache import RingKVIO

    if not hasattr(kv_io, "page_tables"):
        raise NotImplementedError(
            "afmoe's cached forward is written for the paged cache "
            "(kv_cache.WindowCache through kv_cache.PagedKVIO); a "
            "contiguous cache for window layers is not")
    b, s = input_ids.shape
    live = (jnp.full((b,), s, jnp.int32) if row_mask is None
            else jnp.sum(row_mask, axis=1, dtype=jnp.int32))
    ring_io = RingKVIO(kv_io, cfg.sliding_window, positions[:, 0],
                       jnp.maximum(live, 1))
    k, v, wk, wv = cache
    x = _embed(params, input_ids, cfg)
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
    cfg: AfmoeConfig,
    *,
    return_hidden: bool = False,
) -> jax.Array:
    """Full forward without a cache: [B, S] tokens -> logits [B, S, V]
    (``return_hidden``: the final-normed hidden states); S > 1."""
    b, s = input_ids.shape
    if s < 2:
        raise ValueError("afmoe.forward attends a sequence to itself: "
                         "give it at least two tokens")
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    none = ((None, None), (None, None))
    x, _, _ = _run_layers(
        _embed(params, input_ids, cfg), none, params, cfg,
        (SelfKV(), SelfKV()), positions, None, None)
    x = rms_norm(x, params["norm"], cfg.rms_norm_eps)
    if return_hidden:
        return x
    return x @ _llama.lm_head_weight(params, cfg)


class Afmoe:
    config_cls = AfmoeConfig

    def __init__(self, config: AfmoeConfig):
        self.config = config

    def init(self, key: jax.Array) -> Params:
        return init_params(key, self.config)

    def __call__(self, params: Params, input_ids: jax.Array, **kw):
        return forward(params, input_ids, self.config, **kw)
