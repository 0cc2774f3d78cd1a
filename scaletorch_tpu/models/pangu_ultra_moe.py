"""pangu_ultra_moe (openPangu-Ultra-MoE) — latent attention under four
norms a layer, leading dense layers, sigmoid-routed experts beside an
ungated shared expert.

``config.json`` of FreedomIntelligence/openPangu-Ultra-MoE-718B
(``model_type: pangu_ultra_moe``): the first ``first_k_dense_replace``
layers have a dense SwiGLU MLP of ``intermediate_size``, the others
``n_routed_experts`` routed experts of ``moe_intermediate_size`` (top
``num_experts_per_tok``) and ``n_shared_experts`` shared ones. With the
plain gain ``N(x; g) = x / sqrt(mean(x^2) + eps) * g``
(``sandwich_norm`` true):

    h <- h + N(Attn(N(h; g_in)); g_post_attn)
    h <- h + N(MLP(N(h; g_pre_mlp)); g_post_mlp)
    logits = N(h; g_f) W_head            (the embedding is not scaled)

*Latent attention*, ``x`` the normed input, per token:

    c_q = N(x W_dq; g_q)                          hidden -> q_lora_rank
    q   = c_q W_uq     a head's q = [q_n (qk_nope_head_dim), q_r (rope)]
    [c_raw, k_raw] = x W_dkv        hidden -> kv_lora_rank + rope
    c   = N(c_raw; g_kv)
    k_r = rope(k_raw)        ONE rotary key, shared by every head
    q_r <- rope(q_r)         rotate-half over the rope dims, absolute
    [k_n,h, v_h] = c W_ukv,h      kv_lora_rank -> heads x (nope + v)
    s_h(i, j) = (q_n,h(i) . k_n,h(j) + q_r,h(i) . k_r(j)) / sqrt(nope + rope)
    o_h = sum_j softmax_j(s_h)(i, j) v_h(j);   out = concat(o_h) W_o

no bias anywhere. **What the cache keeps is ``(c, k_r)``**, after the
norm and after the rotation (``kv_cache.LatentCache``). Two paths over
one set of weights:

* *expanded* (a call of several rows: a prompt from its first token):
  ``k_n`` / ``v`` of every head from ``c``, the prompt attended to
  itself in key blocks (``ops/flash_attention.prefill_self_attention``,
  key heads ``nope + rope`` wide, value heads ``v_head_dim``); the
  cache is only written.
* *absorbed* (a call of one row: a decode step): ``q~_h = W_uk,h^T
  q_n,h`` (nope -> kv_lora_rank); ``s_h = (q~_h . c(j) + q_r,h .
  k_r(j)) / sqrt(nope + rope)``; ``o~_h = sum_j p_h c(j)``; ``o_h =
  W_uv,h o~_h``: every query head on ONE cached head whose value is its
  key's latent part (``PagedKVIO.attend_latent``). ``W_uk`` / ``W_uv``
  are slices of ``kv_b_proj`` [kv_lora_rank, heads, nope + v], no
  second copies.

*Sparse MLP*: ``qwen3_moe.dropless_mlp`` told ``score_func sigmoid``
without a selection bias (the configuration declares none): ``s =
sigmoid(m W_r)`` in float32 over all routed experts, the top k of
``s``, weighted by ``s / (their sum + 1e-20)`` (``norm_topk_prob``)
times ``routed_scaling_factor``; dropless; the shared expert's SwiGLU
added ungated. A configuration may hold a SHARE of the experts
(``qwen3_moe.ExpertShare``: ``n_routed_experts`` held here of
``num_routed_experts``).

Parameters: ``layers["block"]`` every layer's attention and its four
norms, stacked ``[layers, ...]``; ``layers["dense"]`` the leading dense
MLPs; ``layers["moe"]`` the sparse MLPs, read out of the whole stack by
the grouped matmul (``dropless_expert_mlp(layer=...)``). The leading
dense layers run unrolled, the sparse ones scanned with ``(h, cache)``
as the carry and no parameter scanned (``llama.scan_layers_cached``'s
rule).

Not written: the multi-token-prediction module
(``num_nextn_predict_layers`` is carried unused: the main model is exact
without it), the trainer's step, tensor / context / pipeline / expert
parallelism over this family, HF weight loading, a contiguous cache,
prefix sharing (a shared prefix would have to be expanded through
``W_ukv`` in the prefill), a group-limited choice of experts,
``rope_scaling``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from scaletorch_tpu.models import llama as _llama
from scaletorch_tpu.models import qwen3_moe as _moe
from scaletorch_tpu.models.afmoe import _NO_ROUTING, _layer_of
from scaletorch_tpu.models.layers import (
    apply_rotary_pos_emb,
    fan_in_uniform,
    get_cos_sin,
    rms_norm,
)
from scaletorch_tpu.models.llama import LlamaConfig, Params
from scaletorch_tpu.models.qwen3_moe import ExpertShare


@dataclass(frozen=True)
class PanguUltraMoEConfig(ExpertShare, LlamaConfig):
    # openPangu-Ultra-MoE-718B defaults (the published config.json)
    vocab_size: int = 153600
    hidden_size: int = 7680
    intermediate_size: int = 18432         # the leading dense layers'
    num_hidden_layers: int = 61
    num_attention_heads: int = 128
    num_key_value_heads: int = 128         # every head its own expanded K/V
    max_position_embeddings: int = 131072
    rope_theta: float = 25600000.0
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    first_k_dense_replace: int = 3
    sandwich_norm: bool = True
    num_nextn_predict_layers: int = 1      # names a module not built
    # the sparse MLP (qwen3_moe.dropless_mlp reads these through the
    # properties below); n_routed_experts counts the experts HELD here
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 2048
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    num_routed_experts: Optional[int] = None
    first_expert_id: int = 0
    # random weights only (models/families.py): the embedding's standard
    # deviation, and the multiples of their fan-in bounds the held routed
    # experts' down projection and the query up-projection are drawn at
    embed_init_std: float = 0.02
    routed_expert_init_scale: float = 1.0
    query_init_scale: float = 1.0
    score_func = "sigmoid"
    shared_expert_gated = False
    aux_loss_coef = 0.0
    z_loss_coef = 0.0

    def __post_init__(self) -> None:
        if not self.sandwich_norm:
            raise NotImplementedError(
                "pangu_ultra_moe with sandwich_norm false: only the "
                "four-norm block of the published configuration is "
                "written (models/pangu_ultra_moe.py)")
        if not 0 <= self.first_k_dense_replace < self.num_hidden_layers:
            raise ValueError(
                f"first_k_dense_replace {self.first_k_dense_replace} of "
                f"{self.num_hidden_layers} layers: at least one is sparse")
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError(
                f"num_key_value_heads {self.num_key_value_heads} != "
                f"num_attention_heads {self.num_attention_heads}: latent "
                "attention expands a key and a value for every head")
        if self.head_dim is not None:
            raise ValueError(
                f"head_dim {self.head_dim}: latent attention's widths are "
                "qk_nope_head_dim, qk_rope_head_dim and v_head_dim")
        if self.qk_rope_head_dim % 2:
            raise ValueError(
                f"qk_rope_head_dim {self.qk_rope_head_dim} is odd")
        self.check_expert_share()

    # ---- the latent cache (kv_cache.latent_of reads these) -------------
    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def attn_scale(self) -> float:
        return 1.0 / math.sqrt(self.qk_head_dim)

    @property
    def num_kv_cache_layers(self) -> int:
        return self.num_hidden_layers

    # ---- what qwen3_moe.dropless_mlp reads under its own names ---------
    @property
    def num_experts(self) -> int:
        return self.n_routed_experts

    @property
    def route_scale(self) -> float:
        return self.routed_scaling_factor

    @property
    def shared_expert_intermediate_size(self) -> int:
        return self.n_shared_experts * self.moe_intermediate_size

    def sparse_layer_ids(self) -> Tuple[int, ...]:
        """The layers that route (what the engine sizes its counters
        by)."""
        return tuple(range(self.first_k_dense_replace,
                           self.num_hidden_layers))

    def attention_params(self) -> int:
        h, heads = self.hidden_size, self.num_attention_heads
        return (h * self.q_lora_rank + self.q_lora_rank
                + self.q_lora_rank * heads * self.qk_head_dim
                + h * (self.kv_lora_rank + self.qk_rope_head_dim)
                + self.kv_lora_rank
                + self.kv_lora_rank * heads
                * (self.qk_nope_head_dim + self.v_head_dim)
                + heads * self.v_head_dim * h)

    def num_params(self) -> int:
        """Parameters as ``init_params`` builds them."""
        h, v = self.hidden_size, self.vocab_size
        block = self.attention_params() + 4 * h
        dense = 3 * h * self.intermediate_size
        moe = (h * self.router_width
               + self.num_experts * 3 * h * self.moe_intermediate_size
               + _moe.shared_expert_params(self))
        n_dense = self.first_k_dense_replace
        return (self.num_hidden_layers * block + n_dense * dense
                + (self.num_hidden_layers - n_dense) * moe
                + v * h + h + (0 if self.tie_word_embeddings else v * h))


def config_from_args(args, common: dict) -> PanguUltraMoEConfig:
    """The published config.json names."""
    if args.mlp_only_layers or (args.decoder_sparse_step or 1) != 1:
        raise NotImplementedError(
            "pangu_ultra_moe with mlp_only_layers / decoder_sparse_step: "
            "its dense layers are the leading first_k_dense_replace "
            "(models/pangu_ultra_moe.py)")
    if args.moe_dispatch != "auto" or args.moe_capacity_factor != 1.25:
        raise NotImplementedError(
            "pangu_ultra_moe under capacity dispatch (--moe_dispatch "
            f"{args.moe_dispatch}, --moe_capacity_factor "
            f"{args.moe_capacity_factor}): the family routes dropless "
            "(qwen3_moe.dropless_mlp) and no capacity path is written "
            "for a sigmoid router")
    return PanguUltraMoEConfig(**{
        **common,
        "moe_intermediate_size": args.moe_intermediate_size
        or common["intermediate_size"],
        "norm_topk_prob": (True if args.norm_topk_prob is None
                           else args.norm_topk_prob),
        **{name: getattr(args, name) for name in (
            "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "first_k_dense_replace",
            "sandwich_norm", "num_nextn_predict_layers",
            "n_routed_experts", "num_experts_per_tok", "n_shared_experts",
            "routed_scaling_factor", "num_routed_experts",
            "first_expert_id")}})


def init_params(key: jax.Array, cfg: PanguUltraMoEConfig) -> Params:
    """Random init: fan-in uniform projections and experts, the router
    normal(0.02), the embedding normal(``cfg.embed_init_std``) (0.02 as
    every family), every gain 1; the held routed experts' down
    projection times ``cfg.routed_expert_init_scale`` and ``q_b_proj``
    times ``cfg.query_init_scale`` (both 1 unless a launch says
    otherwise: under 256 flat random scores a routed expert that weighs
    what the shared one does makes every near-tie of the router a third
    of the layer's output, and at 1 the attention scores' std is 0.33,
    so every token of a sequence gets the same mean of the values and
    the router sees the sequence, not the token). ``kv_b_proj`` lies ``[kv_lora_rank,
    heads, nope + v]``: a head's ``W_uk`` and ``W_uv`` are slices of
    its last axis."""
    h, v, pd = cfg.hidden_size, cfg.vocab_size, cfg.param_dtype
    n, n_dense = cfg.num_hidden_layers, cfg.first_k_dense_replace
    heads, rq, rkv = (cfg.num_attention_heads, cfg.q_lora_rank,
                      cfg.kv_lora_rank)
    keys = iter(jax.random.split(key, 24))

    def w(lead, shape, fan_in):
        return fan_in_uniform(next(keys), (lead,) + shape, fan_in, pd)

    block = {
        "input_layernorm": jnp.ones((n, h), pd),
        "post_attention_layernorm": jnp.ones((n, h), pd),
        "pre_mlp_layernorm": jnp.ones((n, h), pd),
        "post_mlp_layernorm": jnp.ones((n, h), pd),
        "q_a_proj": w(n, (h, rq), h),
        "q_a_layernorm": jnp.ones((n, rq), pd),
        "q_b_proj": w(n, (rq, heads * cfg.qk_head_dim), rq),
        "kv_a_proj_with_mqa": w(n, (h, rkv + cfg.qk_rope_head_dim), h),
        "kv_a_layernorm": jnp.ones((n, rkv), pd),
        "kv_b_proj": w(
            n, (rkv, heads, cfg.qk_nope_head_dim + cfg.v_head_dim), rkv),
        "o_proj": w(n, (heads * cfg.v_head_dim, h), heads * cfg.v_head_dim),
    }
    di = cfg.intermediate_size
    dense = {
        "gate_proj": w(n_dense, (h, di), h),
        "up_proj": w(n_dense, (h, di), h),
        "down_proj": w(n_dense, (di, h), di),
    }
    moe = _moe.init_moe_params(
        [next(keys) for _ in range(8)], cfg, (n - n_dense,))
    for tree, name, scale in (
            (moe, "expert_down_proj", cfg.routed_expert_init_scale),
            (block, "q_b_proj", cfg.query_init_scale)):
        if scale != 1.0:
            tree[name] = (tree[name].astype(jnp.float32) * scale).astype(pd)
    params: Params = {
        "embed_tokens": cfg.embed_init_std * jax.random.normal(
            next(keys), (v, h), pd),
        "layers": {"block": block, "dense": dense, "moe": moe},
        "norm": jnp.ones((h,), pd),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = fan_in_uniform(next(keys), (h, v), h, pd)
    return params


# A prompt's expanded heads are large: at 8 x 3,072 rows the queries of
# 128 heads are bf16[8,128,3072,192] = 1.21 GB, the keys as much, values
# and output 0.81 GB each, and a call that holds them all beside 8 GB of
# weights leaves the allocator no room (PERF.md, PR 51: two prefill
# calls in 200 waited 4-5 s for memory). Past this many bytes of
# expanded queries the heads go through the attention in equal groups,
# one after the other; a decode step and a short prompt are one group.
_EXPANDED_Q_BYTES = 1 << 29


def head_groups(rows: int, heads: int, width: int, itemsize: int) -> int:
    """In how many equal groups of heads a prompt of ``rows`` tokens is
    expanded and attended: the fewest that divide ``heads`` and keep a
    group's queries ``[rows, heads / groups, width]`` within
    ``_EXPANDED_Q_BYTES``. Static shapes in, one integer out."""
    total = rows * heads * width * itemsize
    return next(g for g in range(1, heads + 1)
                if heads % g == 0 and total // g <= _EXPANDED_Q_BYTES)


def _rotated(x: jax.Array, rope) -> jax.Array:
    """``x`` under the rotary tables ``rope``; as it is where the model
    has none (``None``: kimi_linear's latent layers, ``mla_use_nope``)."""
    return x if rope is None else apply_rotary_pos_emb(x, x, *rope)[0]


def _expanded_attention(c_q, c, k_r, w_uq, w_ukv, rope, cfg):
    """The expanded form over the heads of ``w_uq`` [q rank, H, nope +
    rope] and ``w_ukv`` [kv rank, H, nope + v]: a prompt ``c_q`` / ``c``
    [B, S, rank] with its rotated shared key ``k_r`` [B, 1, S, rope]
    attended to itself in key blocks; [B, H, S, v] back."""
    from scaletorch_tpu.ops.flash_attention import prefill_self_attention

    nope = cfg.qk_nope_head_dim
    b, s, _ = c.shape
    with jax.named_scope("mla.expand"):
        q = jnp.einsum("bsr,rhd->bhsd", c_q, w_uq)
        kv = jnp.einsum("bsc,chd->bhsd", c, w_ukv)
        heads = q.shape[1]
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_r, (b, heads, s, k_r.shape[-1]))], axis=-1)
        q = jnp.concatenate(
            [q[..., :nope], _rotated(q[..., nope:], rope)], axis=-1)
    with jax.named_scope("mla.attend"):
        return prefill_self_attention(
            q, k, kv[..., nope:], scale=cfg.attn_scale)


def latent_attention(
    u: jax.Array,
    layer: Params,
    index: Any,
    pool: Any,
    rope: Tuple[jax.Array, jax.Array],
    positions: jax.Array,
    cfg: PanguUltraMoEConfig,
    io: Any,
    write_mask: Optional[jax.Array],
) -> Tuple[jax.Array, Any]:
    """The latent-attention mixer of the normed hidden states ``u``
    [B, S, H]: ``[c | k_r]`` written at ``index`` of the latent cache
    ``pool`` through ``io`` (``rope`` None: nothing is rotated; a layer
    without ``q_a_proj`` has no query latent); a call of one row reads the cache in the
    absorbed form, a call of several rows attends to itself in the
    expanded form, ``head_groups`` groups of heads at a time (module
    docstring). Returns (the mixer's output before its norm, the
    pool)."""
    cdt, eps = cfg.dtype, cfg.rms_norm_eps
    heads, nope, rot = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                        cfg.qk_rope_head_dim)
    b, s, _ = u.shape
    w_ukv = layer["kv_b_proj"].astype(cdt)       # [rank, heads, nope + v]
    if "q_a_proj" in layer:
        w_uq = layer["q_b_proj"].astype(cdt).reshape(-1, heads, nope + rot)
        with jax.named_scope("mla.q_latent"):
            c_q = rms_norm(u @ layer["q_a_proj"].astype(cdt),
                           layer["q_a_layernorm"], eps)
    else:
        # no query latent (``q_lora_rank`` null, kimi_linear): the
        # heads' queries come straight from the normed hidden states
        w_uq = layer["q_proj"].astype(cdt).reshape(-1, heads, nope + rot)
        c_q = u
    with jax.named_scope("mla.kv_latent"):
        kv_a = u @ layer["kv_a_proj_with_mqa"].astype(cdt)
        c = rms_norm(kv_a[..., :cfg.kv_lora_rank],
                     layer["kv_a_layernorm"], eps)
        k_r = _rotated(kv_a[..., cfg.kv_lora_rank:][:, None], rope)
        pool = io.write_latent(pool, index, c[:, None], k_r, positions,
                               write_mask)
    if s == 1:
        with jax.named_scope("mla.q_latent"):
            q = jnp.einsum("br,rhd->bhd", c_q[:, 0], w_uq)
            q_r = _rotated(q[:, :, None, nope:], rope)[:, :, 0]
        with jax.named_scope("mla.absorb"):
            q_c = jnp.einsum("bhn,chn->bhc", q[..., :nope],
                             w_ukv[..., :nope])
        with jax.named_scope("mla.attend"):
            o_c = io.attend_latent(q_c, q_r, pool, index, positions[:, 0],
                                   scale=cfg.attn_scale)
        with jax.named_scope("mla.out"):
            attn = jnp.einsum("bhc,chv->bhv", o_c, w_ukv[..., nope:])
            attn = attn.reshape(b, 1, -1)
    else:
        groups = head_groups(b * s, heads, nope + rot,
                             jnp.dtype(cdt).itemsize)
        if groups == 1:
            attn = _expanded_attention(c_q, c, k_r, w_uq, w_ukv, rope, cfg)
        else:
            def cut(w):
                return w.reshape(w.shape[0], groups, heads // groups,
                                 w.shape[-1])

            attn = jax.lax.map(
                lambda g: _expanded_attention(
                    c_q, c, k_r, cut(w_uq)[:, g], cut(w_ukv)[:, g], rope,
                    cfg), jnp.arange(groups))             # [G, B, H/G, S, v]
            attn = jnp.moveaxis(attn, 0, 1).reshape(b, heads, s, -1)
        attn = attn.transpose(0, 2, 1, 3).reshape(b, s, -1)
    with jax.named_scope("mla.out"):
        return attn @ layer["o_proj"].astype(cdt), pool


class _SelfLatent:
    """No cache (``forward``): a write keeps nothing."""

    def write_latent(self, pool, layer, c, k_r, positions, write_mask):
        return pool


def _layer(h, pool, params, cfg, index, io, rope, positions, write_mask,
           row_mask):
    """One layer at ``index`` (static for a dense layer, traced by the
    scan for a sparse one). Returns (h, the pool, the layer's routing
    counts)."""
    eps = cfg.rms_norm_eps
    layers = params["layers"]
    block = _layer_of(layers["block"], index)
    with jax.named_scope("attn"):
        out, pool = latent_attention(
            rms_norm(h, block["input_layernorm"], eps), block, index,
            pool, rope, positions, cfg, io, write_mask)
    h = h + rms_norm(out, block["post_attention_layernorm"], eps)
    m = rms_norm(h, block["pre_mlp_layernorm"], eps)
    counts = dict(_NO_ROUTING)
    if isinstance(index, int) and index < cfg.first_k_dense_replace:
        with jax.named_scope("mlp.dense"):
            f = _llama.swiglu_mlp(m, _layer_of(layers["dense"], index), cfg)
    else:
        place = index - cfg.first_k_dense_replace
        moe = layers["moe"]
        with jax.named_scope("moe"):
            f, _aux, _stats, routing = _moe.dropless_mlp(
                m, _layer_of(moe, place, skip=_moe.EXPERT_KEYS), cfg,
                row_mask, ({name: moe[name] for name in _moe.EXPERT_KEYS},
                           place))
        counts = _moe.routing_counts(routing)
    h = h + rms_norm(f.astype(h.dtype), block["post_mlp_layernorm"], eps)
    return h, pool, counts


def _run_layers(x, pool, params, cfg, io, positions, write_mask, row_mask):
    """Every layer in order: the leading dense layers unrolled (static
    indices), the sparse ones scanned with ``(h, pool)`` as the carry
    and no parameter scanned. Returns (h, the pool, routing counts summed
    over the layers)."""
    rope = get_cos_sin(positions.shape[1], cfg.qk_rope_head_dim,
                       cfg.rope_theta, positions=positions)
    totals = []
    for index in range(cfg.first_k_dense_replace):
        x, pool, counts = _layer(x, pool, params, cfg, index, io, rope,
                                 positions, write_mask, row_mask)
        totals.append(counts)

    def body(carry, index):
        h, held, counts = _layer(*carry, params, cfg, index, io, rope,
                                 positions, write_mask, row_mask)
        return (h, held), counts

    (x, pool), counts = jax.lax.scan(
        body, (x, pool),
        jnp.arange(cfg.first_k_dense_replace, cfg.num_hidden_layers,
                   dtype=jnp.int32))
    totals.append(jax.tree.map(jnp.sum, counts))
    return x, pool, jax.tree.map(lambda *xs: sum(xs), *totals)


def forward_cached(
    params: Params,
    input_ids: jax.Array,
    cfg: PanguUltraMoEConfig,
    cache: Tuple[jax.Array],
    *,
    positions: jax.Array,
    write_mask: Optional[jax.Array] = None,
    kv_io: Optional[Any] = None,
    row_mask: Optional[jax.Array] = None,
    return_routing: bool = False,
    logit_rows: Optional[jax.Array] = None,
):
    """Cached forward: [B, S] tokens at absolute ``positions`` [B, S] ->
    (logits, the new cache). ``cache`` is ``(rows,)``
    (``kv_cache.LatentCache``): the page pool of the latent rows ``[c |
    k_r]``, which ``kv_io`` (a ``kv_cache.PagedKVIO``) writes and reads
    through the engine's tables. S > 1 is a prompt from
    its first token, attended to itself in the expanded form; S == 1 a
    decode step against the cache in the absorbed form. ``row_mask``
    [B, S]: the rows that are tokens (None: all): what the routing
    counts go by. ``logit_rows`` and ``return_routing`` as in
    ``qwen3_moe.forward_cached``."""
    if not hasattr(kv_io, "attend_latent"):
        raise NotImplementedError(
            "pangu_ultra_moe's cached forward is written for the paged "
            "latent cache (kv_cache.LatentCache through "
            "kv_cache.PagedKVIO); a contiguous latent cache is not")
    x = _llama.embed(params, input_ids, cfg)
    (pool,) = cache
    x, pool, counts = _run_layers(
        x, pool, params, cfg, kv_io, positions, write_mask, row_mask)
    cache = (pool,)
    x = rms_norm(_llama.select_logit_rows(x, logit_rows), params["norm"],
                 cfg.rms_norm_eps)
    logits = x @ _llama.lm_head_weight(params, cfg)
    if return_routing:
        return logits, cache, counts
    return logits, cache


def forward(
    params: Params,
    input_ids: jax.Array,
    cfg: PanguUltraMoEConfig,
    *,
    return_hidden: bool = False,
) -> jax.Array:
    """Full forward without a cache, the expanded form: [B, S] tokens ->
    logits [B, S, V] (``return_hidden``: the final-normed hidden
    states); S > 1."""
    b, s = input_ids.shape
    if s < 2:
        raise ValueError("pangu_ultra_moe.forward attends a sequence to "
                         "itself: give it at least two tokens")
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    x, _, _ = _run_layers(
        _llama.embed(params, input_ids, cfg), None, params, cfg,
        _SelfLatent(), positions, None, None)
    x = rms_norm(x, params["norm"], cfg.rms_norm_eps)
    if return_hidden:
        return x
    return x @ _llama.lm_head_weight(params, cfg)


class PanguUltraMoE:
    config_cls = PanguUltraMoEConfig

    def __init__(self, config: PanguUltraMoEConfig):
        self.config = config

    def init(self, key: jax.Array) -> Params:
        return init_params(key, self.config)

    def __call__(self, params: Params, input_ids: jax.Array, **kw):
        return forward(params, input_ids, self.config, **kw)
