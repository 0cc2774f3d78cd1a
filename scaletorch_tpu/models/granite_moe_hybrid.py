"""granitemoehybrid (Granite 4.0-H) — Mamba-2 layers between rope-free
attention layers, every layer a routed-expert MLP beside a shared expert,
four muP multipliers.

``config.json`` of ibm-granite/granite-4.0-h-small (``model_type:
granitemoehybrid``; transformers' ``modeling_granitemoehybrid.py``, whose
mixer follows ``modeling_bamba.py`` and state-spaces/mamba ``mamba2.py``):
``layer_types`` names each layer ``mamba`` or ``attention`` (published:
nine to one, ``m m m m m a m m m m``, four times). With ``N(x; g) = g *
x / sqrt(mean(x^2) + eps)`` (float32 statistics):

    h0 = embedding_multiplier * E[ids]
    h <- h + residual_multiplier * Mix(N(h; g_1))
    u = N(h; g_2);   h <- h + residual_multiplier * (MoE(u) + Shared(u))
    logits = (N(h; g_f) E^T) / logits_scaling           (tied embedding)

*Attention layer.* ``q = x Wq`` (heads of ``hidden / heads``), ``k = x
Wk``, ``v = x Wv`` (``num_key_value_heads`` heads), no bias, no q/k norm,
NO rotary embedding (``position_embedding_type: nope``), causal softmax
of ``q k^T * attention_multiplier`` (published 1/128, not 1/sqrt(128)),
``Wo``. A call of several rows is a prompt from its first token and
attends to itself in key blocks; a call of one row reads the page pool.

*Mamba-2 layer* (``mamba2_mix``), ``H = mamba_n_heads`` heads of ``P =
mamba_d_head`` channels (``H P = mamba_expand * hidden``), ``N =
mamba_d_state``, ONE group of ``B`` / ``C`` for all heads
(``mamba_n_groups`` 1):

    [z, xBC, dt] = x W_in                (H P + (H P + 2 N) + H, no bias)
    xBC <- silu(conv(xBC) + b_conv)      depthwise, causal, width 4, over
                                         x, B and C alike
    dt_h = softplus(dt_h + dt_bias_h)    a_h = exp(-dt_h exp(A_log_h))
    S_t[h] = a_h S_{t-1}[h] + dt_h x_t[h] B_t^T            [P, N] a head
    y_t[h] = S_t[h] C_t + D_h x_t[h]
    y <- N(y * silu(z); g_n)             over all H P channels at once:
                                         the gate goes in BEFORE the norm
    out = y W_out

Projections and activations run in the compute dtype with float32
accumulation; ``dt``, ``a``, the state, ``y`` and both norms' statistics
are float32. A head's state is held TRANSPOSED with the heads side by
side, ``f32[N, H P]``: the channels on the lanes, so that ``a`` and ``dt
x`` (a number a channel) are row vectors and ``B`` and ``C`` (a number a
state row) columns, and ``y`` a sum over rows. Three forms of the
recurrence: ``ssd_step`` (one token; on a TPU ``mamba2_decode`` takes
``ops/pallas/ssd_update.py`` instead: the state of every slot read once
and written once in place, ``y`` taken in the same pass, where XLA makes
two fusions that each read it),
``ssd_chunked`` (a prompt, ``mamba_chunk_size`` rows at a time: inside a
chunk ``Y = (L o (C B^T)) (dt x)`` with ``L_ij = exp(c_i - c_j)`` for ``i
>= j``, ``c`` the running sum of ``log a``, ``C B^T`` computed once for
all heads, plus the carried state's term; the state handed chunk to
chunk) and ``ssd_sequential`` (``ssd_step`` row after row: the
definition, the tests' oracle).

*Experts*: ``qwen3_moe.dropless_mlp`` told ``score_func softmax`` with
``norm_topk_prob`` (the softmax over the kept logits), dropless, plus the
ungated shared SwiGLU of ``shared_intermediate_size``. The published
``num_local_experts`` counts the experts HELD here
(``qwen3_moe.ExpertShare``: ``num_routed_experts`` the router's width,
``first_expert_id`` the first held); ``intermediate_size`` is ONE
expert's width (the file has no other key for it).

The cache is ``kv_cache.HybridCache``: the page pool over the attention
layers, and by slot the state ``f32[mamba layers, slots, N, H P]`` and
the convolution's tail ``[mamba layers, slots, d_conv - 1, H P + 2 N]``.
Parameters are stacked by kind: ``layers["block"]`` every layer's two
norms, ``layers["mamba"]`` / ``layers["attention"]`` the mixers
``[layers of the kind, ...]``, ``layers["moe"]`` ``[layers, ...]``. The
layers run unrolled, each told its id and its place among its kind.

Not written: ``mamba_n_groups`` > 1, ``mamba_proj_bias``, a rotary
``position_embedding_type``, the scan's backward and the trainer's step,
tensor / context / pipeline / expert parallelism over this family,
prefix sharing and state snapshots, HF weight loading, a contiguous
cache.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from scaletorch_tpu.models import llama as _llama
from scaletorch_tpu.models import qwen3_moe as _moe
from scaletorch_tpu.models.afmoe import SelfKV, _layer_of
from scaletorch_tpu.models.jamba import attention_mix
from scaletorch_tpu.models.layers import fan_in_uniform, rms_norm
from scaletorch_tpu.models.llama import LlamaConfig, Params
from scaletorch_tpu.models.olmo_hybrid import conv_tail_after, short_conv
from scaletorch_tpu.models.qwen3_moe import ExpertShare

MAMBA, ATTENTION = "mamba", "attention"
F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
_PUBLISHED_PERIOD = (MAMBA,) * 5 + (ATTENTION,) + (MAMBA,) * 4


@dataclass(frozen=True)
class GraniteMoeHybridConfig(ExpertShare, LlamaConfig):
    # granite-4.0-h-small defaults (the published config.json)
    vocab_size: int = 100352
    hidden_size: int = 4096
    intermediate_size: int = 768           # ONE routed expert's width
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = True
    # position_embedding_type nope: the published rope_theta is unused
    rope_theta: Optional[float] = None
    layer_types: Tuple[str, ...] = _PUBLISHED_PERIOD * 4
    mamba_n_heads: int = 128
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_chunk_size: int = 256
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    # the experts; num_local_experts counts the experts HELD here
    num_local_experts: int = 72
    num_experts_per_tok: int = 10
    shared_intermediate_size: int = 1536
    num_routed_experts: Optional[int] = None
    first_expert_id: int = 0
    # the four muP multipliers
    embedding_multiplier: float = 12.0
    attention_multiplier: float = 0.0078125
    residual_multiplier: float = 0.22
    logits_scaling: float = 16.0
    # random weights only (models/families.py)
    query_init_scale: float = 1.0
    ssm_decay_init_scale: float = 1.0
    norm_topk_prob = True
    shared_expert_gated = False
    aux_loss_coef = 0.0
    z_loss_coef = 0.0

    def __post_init__(self) -> None:
        kinds = tuple(self.layer_types)
        if len(kinds) != self.num_hidden_layers or set(kinds) - {
                MAMBA, ATTENTION}:
            raise ValueError(
                f"layer_types must name each of the {self.num_hidden_layers}"
                f" layers {MAMBA!r} or {ATTENTION!r}, got {kinds}")
        if MAMBA not in kinds or ATTENTION not in kinds:
            raise ValueError(
                "granitemoehybrid has layers of both kinds (a stack of one "
                "kind is another family's)")
        if self.mamba_n_groups != 1:
            raise NotImplementedError(
                f"mamba_n_groups {self.mamba_n_groups}: one B and one C "
                "for all heads is what models/granite_moe_hybrid.py "
                "writes (the published granite-4.0-h configurations')")
        if self.mamba_proj_bias:
            raise NotImplementedError(
                "mamba_proj_bias: the in / out projections are written "
                "without a bias (the published configuration's)")
        if self.mamba_n_heads * self.mamba_d_head != self.mamba_inner:
            raise ValueError(
                f"mamba_n_heads {self.mamba_n_heads} x mamba_d_head "
                f"{self.mamba_d_head} != mamba_expand {self.mamba_expand} x "
                f"hidden_size {self.hidden_size}")
        if self.qk_norm or self.rope_theta is not None:
            raise ValueError(
                "granitemoehybrid's attention layers have no q/k norm and "
                "no rotary embedding (position_embedding_type nope)")
        self.check_expert_share()

    # ---- the layer list -------------------------------------------------
    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        return tuple(self.layer_types)

    @property
    def num_mamba_layers(self) -> int:
        return self.layer_kinds.count(MAMBA)

    @property
    def num_kv_cache_layers(self) -> int:
        """Layers that keep K/V: the page pool's leading axis."""
        return self.layer_kinds.count(ATTENTION)

    # ---- the recurrent state (kv_cache.carries_state) -------------------
    @property
    def mamba_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def mamba_conv_dim(self) -> int:
        """Channels the convolution runs over: x, B and C."""
        return self.mamba_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    def recurrent_state_shapes(
        self, slots: int
    ) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
        """(state, convolution tail) shapes of a cache of ``slots``
        sequences: ``[mamba layers, slots, N, H P]`` (float32: a head's
        ``[P, N]`` matrix transposed, the channels on the lanes) and
        ``[mamba layers, slots, d_conv - 1, H P + 2 N]``."""
        n = self.num_mamba_layers
        return ((n, slots, self.mamba_d_state, self.mamba_inner),
                (n, slots, self.mamba_d_conv - 1, self.mamba_conv_dim))

    # ---- what qwen3_moe.dropless_mlp reads under its own names ----------
    @property
    def num_experts(self) -> int:
        return self.num_local_experts

    @property
    def moe_intermediate_size(self) -> int:
        return self.intermediate_size

    @property
    def shared_expert_intermediate_size(self) -> int:
        return self.shared_intermediate_size

    def sparse_layer_ids(self) -> Tuple[int, ...]:
        """The layers that route (what the engine sizes its counters
        by): every one."""
        return tuple(range(self.num_hidden_layers))

    def mamba_params(self) -> int:
        h, c, heads = self.hidden_size, self.mamba_inner, self.mamba_n_heads
        conv = self.mamba_conv_dim
        return (h * (c + conv + heads) + conv * self.mamba_d_conv
                + (conv if self.mamba_conv_bias else 0) + 3 * heads + c
                + c * h)

    def attention_params(self) -> int:
        return 2 * self.hidden_size * (self.q_size + self.kv_size)

    def num_params(self) -> int:
        """Parameters as ``init_params`` builds them."""
        h, v = self.hidden_size, self.vocab_size
        moe = (h * self.router_width
               + self.num_experts * 3 * h * self.moe_intermediate_size
               + _moe.shared_expert_params(self))
        return (self.num_mamba_layers * self.mamba_params()
                + self.num_kv_cache_layers * self.attention_params()
                + self.num_hidden_layers * (2 * h + moe)
                + v * h + h + (0 if self.tie_word_embeddings else v * h))


def config_from_args(args, common: dict) -> GraniteMoeHybridConfig:
    """The published config.json names. ``layer_types`` omitted: the
    published period (five Mamba layers, one attention layer, four Mamba
    layers) repeated over ``num_hidden_layers``, which it must then
    divide. ``rope_theta`` (published beside ``position_embedding_type:
    nope``) is read by nothing."""
    if args.position_embedding_type != "nope":
        raise NotImplementedError(
            f"granitemoehybrid with position_embedding_type "
            f"{args.position_embedding_type!r}: its attention layers are "
            "written without a rotary embedding (the published 'nope')")
    if args.moe_dispatch != "auto" or args.moe_capacity_factor != 1.25:
        raise NotImplementedError(
            "granitemoehybrid under capacity dispatch (--moe_dispatch "
            f"{args.moe_dispatch}, --moe_capacity_factor "
            f"{args.moe_capacity_factor}): the family routes dropless "
            "(qwen3_moe.dropless_mlp)")
    kinds = args.layer_types
    if kinds is None:
        n, period = common["num_hidden_layers"], len(_PUBLISHED_PERIOD)
        if n % period:
            raise ValueError(
                f"num_hidden_layers {n} is no multiple of the published "
                f"period of {period}: name the layers (--layer_types)")
        kinds = _PUBLISHED_PERIOD * (n // period)
    return GraniteMoeHybridConfig(**{
        **common, "rope_theta": None, "layer_types": tuple(kinds),
        **{name: getattr(args, name) for name in (
            "mamba_n_heads", "mamba_d_head", "mamba_d_state",
            "mamba_n_groups", "mamba_d_conv", "mamba_expand",
            "mamba_chunk_size", "mamba_conv_bias", "mamba_proj_bias",
            "num_local_experts", "num_experts_per_tok",
            "shared_intermediate_size", "num_routed_experts",
            "first_expert_id", "embedding_multiplier",
            "attention_multiplier", "residual_multiplier",
            "logits_scaling")}})


def init_params(key: jax.Array, cfg: GraniteMoeHybridConfig) -> Params:
    """Random init: fan-in uniform projections and experts (the depthwise
    convolution's weight and bias at its fan-in, the kernel width), the
    router and the embedding normal(0.02, HF's ``initializer_range``),
    every gain 1. The recurrence's own parameters as Mamba-2's published
    initialisers draw them (state-spaces/mamba ``mamba2.py``): ``A_log =
    log U(1, 16)`` a head, ``dt_bias`` the inverse softplus of a step
    log-uniform in [1e-3, 1e-1] a head, ``D = 1``. (transformers'
    ``A_log = log(arange(1, H + 1))`` with ``dt_bias = 1`` forgets the
    state within a token for most heads: a comparison could not see the
    state at all.) ``A`` times ``cfg.ssm_decay_init_scale`` (1 unless a
    launch says otherwise: a head remembers ``~1 / (dt A)`` tokens, and
    at the published range the heads with a step large enough to carry a
    random model's signal forget within a few tokens, so that a state
    kept one precision down reads under bfloat16's own rounding: PERF.md,
    PR 61). The attention layers' ``q_proj`` times
    ``cfg.query_init_scale`` (1 unless a launch says otherwise: at 1
    random scores are flat and an attention layer hands every token of a
    sequence the same vector; ``pangu_ultra_moe.init_params``)."""
    h, v, pd = cfg.hidden_size, cfg.vocab_size, cfg.param_dtype
    n, n_m, n_a = (cfg.num_hidden_layers, cfg.num_mamba_layers,
                   cfg.num_kv_cache_layers)
    heads, c, conv, k = (cfg.mamba_n_heads, cfg.mamba_inner,
                         cfg.mamba_conv_dim, cfg.mamba_d_conv)
    keys = iter(jax.random.split(key, 32))

    def draw(lead, shape, fan_in):
        return fan_in_uniform(next(keys), (lead,) + shape, fan_in, pd)

    step = jnp.exp(jax.random.uniform(
        next(keys), (n_m, heads), F32, math.log(1e-3), math.log(1e-1)))
    mamba = {
        "in_proj": draw(n_m, (h, c + conv + heads), h),
        "conv": draw(n_m, (k, conv), k),
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(pd),
        "A_log": jnp.log(cfg.ssm_decay_init_scale * jax.random.uniform(
            next(keys), (n_m, heads), F32, 1.0, 16.0)).astype(pd),
        "D": jnp.ones((n_m, heads), pd),
        "norm": jnp.ones((n_m, c), pd),
        "out_proj": draw(n_m, (c, h), c),
    }
    if cfg.mamba_conv_bias:
        mamba["conv_bias"] = draw(n_m, (conv,), k)
    attention = {
        "q_proj": draw(n_a, (h, cfg.q_size), h),
        "k_proj": draw(n_a, (h, cfg.kv_size), h),
        "v_proj": draw(n_a, (h, cfg.kv_size), h),
        "o_proj": draw(n_a, (cfg.q_size, h), cfg.q_size),
    }
    moe = _moe.init_moe_params([next(keys) for _ in range(8)], cfg, (n,))
    if cfg.query_init_scale != 1.0:
        attention["q_proj"] = (attention["q_proj"].astype(F32)
                               * cfg.query_init_scale).astype(pd)
    params: Params = {
        "embed_tokens": 0.02 * jax.random.normal(next(keys), (v, h), pd),
        "layers": {
            "block": {"input_layernorm": jnp.ones((n, h), pd),
                      "post_attention_layernorm": jnp.ones((n, h), pd)},
            "mamba": mamba, "attention": attention, "moe": moe},
        "norm": jnp.ones((h,), pd),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = fan_in_uniform(next(keys), (h, v), h, pd)
    return params


# ---- the recurrence ----------------------------------------------------------
#
# Shapes, all float32: x [B, S, H, P], dt and log_a [B, S, H] (``log_a =
# -dt exp(A_log)``, the log of a head's decay: 0 where a row is no
# token), bm and cm [B, S, N], state [B, N, H P] (a head's matrix
# transposed, the heads side by side). Each form returns (y [B, S, H, P]
# without the ``D x`` skip, the state after the last row).

def _by_channel(per_head: jax.Array, p: int) -> jax.Array:
    """[..., H] -> [..., H P]: a head's number for each of its channels."""
    return jnp.repeat(per_head, p, axis=-1)


def ssd_step(x, dt, log_a, bm, cm, state):
    """The recurrence once: x [B, H, P], dt, log_a [B, H], bm, cm [B, N]
    -> (y [B, H, P], the new state). Elementwise on the state and one
    sum over its rows (a dot would round the state to bf16 passes on a
    TPU)."""
    b, h, p = x.shape
    a = _by_channel(jnp.exp(log_a), p)[:, None, :]
    dx = (dt[..., None] * x).reshape(b, 1, h * p)
    state = a * state + bm[:, :, None] * dx
    return jnp.sum(state * cm[:, :, None], axis=1).reshape(x.shape), state


def ssd_sequential(x, dt, log_a, bm, cm, state):
    """``ssd_step`` row after row: the definition (the tests' oracle)."""
    def body(s, row):
        y, s = ssd_step(*row, s)
        return s, y

    rows = tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, log_a, bm, cm))
    state, y = jax.lax.scan(body, state, rows)
    return jnp.moveaxis(y, 0, 1), state


def ssd_chunked(x, dt, log_a, bm, cm, state, *, chunk: int):
    """The recurrence over S rows, ``chunk`` at a time (module
    docstring); S of any length (padded with rows of ``dt = 0, log_a =
    0``, which are no tokens). With ``c`` the running sum of ``log_a``
    inside a chunk and ``S0`` the state it meets: ``y_i = sum_{j<=i}
    exp(c_i - c_j) (C_i . B_j) dt_j x_j + exp(c_i) S0 C_i`` and ``S1 =
    exp(c_Q) S0 + sum_j exp(c_Q - c_j) dt_j x_j B_j^T``: every
    exponent is at most 0. ``C B^T`` is computed once for all heads.
    Matrix products in float32 at ``highest`` precision: the state they
    make is what every later token reads."""
    b, s, h, p = x.shape
    n = bm.shape[-1]
    pad = -s % chunk
    if pad:
        x, dt, log_a, bm, cm = (
            jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
            for a in (x, dt, log_a, bm, cm))

    def chunks(a):              # [B, S, ...] -> [S / chunk, B, chunk, ...]
        a = a.reshape((b, -1, chunk) + a.shape[2:])
        return jnp.moveaxis(a, 1, 0)

    rows = jnp.arange(chunk)
    lower = rows[:, None] >= rows[None, :]

    def body(s0, xs):
        x_c, dt_c, la_c, b_c, c_c = xs
        c = jnp.cumsum(la_c, axis=1)                       # [B, Q, H]
        by_head = jnp.moveaxis(c, 2, 1)                    # [B, H, Q]
        decay = jnp.exp(jnp.where(
            lower, by_head[..., :, None] - by_head[..., None, :], -jnp.inf))
        cb = jnp.einsum("bin,bjn->bij", c_c, b_c, precision=_HIGHEST)
        dx = dt_c[..., None] * x_c                         # [B, Q, H, P]
        y = jnp.einsum("bhij,bjhp->bihp", cb[:, None] * decay, dx,
                       precision=_HIGHEST)
        y = y + jnp.exp(c)[..., None] * jnp.einsum(
            "bnhp,bin->bihp", s0.reshape(b, n, h, p), c_c,
            precision=_HIGHEST)
        end = c[:, -1]                                     # [B, H]
        left = jnp.exp(end[:, None] - c)[..., None] * dx   # decayed to Q
        s1 = _by_channel(jnp.exp(end), p)[:, None, :] * s0 + jnp.einsum(
            "bjn,bjhp->bnhp", b_c, left, precision=_HIGHEST
        ).reshape(s0.shape)
        return s1, y

    state, y = jax.lax.scan(
        body, state, tuple(map(chunks, (x, dt, log_a, bm, cm))))
    y = jnp.moveaxis(y, 0, 1).reshape(x.shape)
    return y[:, :s], state


def update_kernel_serves(cfg: "GraniteMoeHybridConfig") -> bool:
    """Whether a decode step's state update takes the Mosaic kernel: on
    a TPU (the repo's one kernel-vs-XLA predicate) and with a state of
    whole vector registers."""
    from scaletorch_tpu.ops.flash_attention import _pallas_available
    from scaletorch_tpu.ops.pallas import ssd_update

    return (ssd_update.kernel_serves(cfg.mamba_d_state, cfg.mamba_inner)
            and _pallas_available())


def _mamba2_inputs(u, layer, cfg, tail, row_mask):
    """What the recurrence reads of the normed ``u`` [B, S, hidden] after
    the convolution ``tail``: (z [B, S, H P], x [B, S, H, P], dt and
    log_a [B, S, H], bm and cm [B, S, N], the new tail)."""
    cdt = cfg.dtype
    b, s, _ = u.shape
    heads, p, n, c = (cfg.mamba_n_heads, cfg.mamba_d_head,
                      cfg.mamba_d_state, cfg.mamba_inner)
    conv = cfg.mamba_conv_dim
    with jax.named_scope("ssd.conv"):
        # dt leaves the matmul in float32: a decay is a product over
        # every token since the prompt began
        zxbcdt = jnp.matmul(u, layer["in_proj"].astype(cdt),
                            preferred_element_type=F32)
        z = zxbcdt[..., :c].astype(cdt)
        mixed, rows = short_conv(zxbcdt[..., c:c + conv].astype(cdt),
                                 layer["conv"], tail, layer.get("conv_bias"))
        xbc = jax.nn.silu(mixed)
        new_tail = conv_tail_after(rows, tail, row_mask)
    with jax.named_scope("ssd.params"):
        dt = jax.nn.softplus(
            zxbcdt[..., c + conv:] + layer["dt_bias"].astype(F32))
        if row_mask is not None:
            dt = jnp.where(row_mask[..., None], dt, 0.0)
        log_a = -dt * jnp.exp(layer["A_log"].astype(F32))
        x = xbc[..., :c].reshape(b, s, heads, p)
    return z, x, dt, log_a, xbc[..., c:c + n], xbc[..., c + n:], new_tail


def _mamba2_output(y, x, z, layer, cfg):
    """The mixer's output of the recurrence's ``y`` [B, S, H, P]: the
    ``D x`` skip, the gate, the norm over all channels, ``W_out``."""
    b, s = y.shape[:2]
    with jax.named_scope("ssd.gate"):
        y = y + layer["D"].astype(F32)[:, None] * x
        gated = y.reshape(b, s, -1) * jax.nn.silu(z.astype(F32))
        normed = rms_norm(gated, layer["norm"], cfg.rms_norm_eps)
        return normed.astype(cfg.dtype) @ layer["out_proj"].astype(cfg.dtype)


def mamba2_mix(
    u: jax.Array,
    layer: Params,
    cfg: GraniteMoeHybridConfig,
    state: jax.Array,
    tail: jax.Array,
    *,
    row_mask: Optional[jax.Array] = None,
    sequential: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The Mamba-2 mixer of the normed ``u`` [B, S, hidden], continuing
    from ``state`` [B, N, H P] (float32) and the convolution ``tail``
    [B, K-1, H P + 2 N]. Rows outside ``row_mask`` [B, S] (a prefix of
    each sequence is inside) are no tokens: their ``dt`` is 0, which
    leaves the state alone, and they stay out of the tail. Returns (the
    mixer's output [B, S, hidden], the state after the last token, the
    new tail). One row is the recurrence itself, more rows its chunked
    form (``sequential``: row after row, the oracle)."""
    z, x, dt, log_a, bm, cm, new_tail = _mamba2_inputs(
        u, layer, cfg, tail, row_mask)
    if u.shape[1] == 1:
        with jax.named_scope("ssd.update"):
            y, state = ssd_step(x[:, 0], dt[:, 0], log_a[:, 0], bm[:, 0],
                                cm[:, 0], state)
            y = y[:, None]
    elif sequential:
        y, state = ssd_sequential(x, dt, log_a, bm, cm, state)
    else:
        with jax.named_scope("ssd.scan"):
            y, state = ssd_chunked(x, dt, log_a, bm, cm, state,
                                   chunk=cfg.mamba_chunk_size)
    return _mamba2_output(y, x, z, layer, cfg), state, new_tail


def mamba2_decode(
    u: jax.Array,
    layer: Params,
    cfg: GraniteMoeHybridConfig,
    states: jax.Array,
    place: int,
    tail: jax.Array,
    fresh: jax.Array,
    written: jax.Array,
    *,
    row_mask: Optional[jax.Array] = None,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``mamba2_mix`` of ONE row a slot, ``u`` [slots, 1, hidden], on the
    whole buffer ``states`` [mamba layers, slots, N, H P], of which layer
    ``place`` is advanced in place by the Mosaic kernel
    (``ops/pallas/ssd_update.py``): a slot in ``fresh`` starts from an
    empty state, a slot outside ``written`` keeps its own bit for bit.
    Returns (the mixer's output, ``states``, the new tail)."""
    from scaletorch_tpu.ops.pallas.ssd_update import ssd_state_update

    z, x, dt, log_a, bm, cm, new_tail = _mamba2_inputs(
        u, layer, cfg, tail, row_mask)
    b, _, heads, p = x.shape
    with jax.named_scope("ssd.update"):
        a = _by_channel(jnp.exp(log_a[:, 0]), p)
        dx = (dt[:, 0, :, None] * x[:, 0]).reshape(b, heads * p)
        y, states = ssd_state_update(
            states, jnp.where(written[:, None], a, 1.0),
            jnp.where(written[:, None], dx, 0.0), ~fresh | ~written,
            bm[:, 0], cm[:, 0], layer=place, interpret=interpret)
    y = y.reshape(b, 1, heads, p)
    return _mamba2_output(y, x, z, layer, cfg), states, new_tail


def _residual(h: jax.Array, branch: jax.Array, multiplier: float):
    """``h + multiplier * branch``, summed in float32."""
    return (h.astype(F32) + multiplier * branch.astype(F32)).astype(h.dtype)


def _layer(h, cache, params, cfg, kind, layer, place, io, positions,
           write_mask, row_mask, fresh, written, sequential=False):
    """One layer: ``layer`` its id among all layers, ``place`` among
    those of its ``kind``. Returns (h, the cache, the layer's routing
    counts)."""
    eps = cfg.rms_norm_eps
    layers = params["layers"]
    block = _layer_of(layers["block"], layer)
    ck, cv, state, conv = cache
    u = rms_norm(h, block["input_layernorm"], eps)
    if kind == MAMBA:
        mixer = _layer_of(layers["mamba"], place)
        old_t = jax.lax.dynamic_index_in_dim(conv, place, 0, False)
        tail = jnp.where(fresh[:, None, None], 0, old_t)
        if u.shape[1] == 1 and update_kernel_serves(cfg):
            out, state, new_t = mamba2_decode(
                u, mixer, cfg, state, place, tail, fresh, written,
                row_mask=row_mask)
        else:
            old_s = jax.lax.dynamic_index_in_dim(state, place, 0, False)
            out, new_s, new_t = mamba2_mix(
                u, mixer, cfg, jnp.where(fresh[:, None, None], 0.0, old_s),
                tail, row_mask=row_mask, sequential=sequential)
            state = jax.lax.dynamic_update_index_in_dim(
                state, jnp.where(written[:, None, None], new_s, old_s),
                place, 0)
        conv = jax.lax.dynamic_update_index_in_dim(
            conv, jnp.where(written[:, None, None], new_t, old_t), place, 0)
    else:
        with jax.named_scope("attn"), jax.named_scope("attn.full"):
            out, ck, cv = attention_mix(
                u, _layer_of(layers["attention"], place), place, ck, cv,
                positions, cfg, io, write_mask,
                scale=cfg.attention_multiplier)
    h = _residual(h, out, cfg.residual_multiplier)
    m = rms_norm(h, block["post_attention_layernorm"], eps)
    moe = layers["moe"]
    with jax.named_scope("moe"):
        f, _aux, _stats, routing = _moe.dropless_mlp(
            m, _layer_of(moe, layer, skip=_moe.EXPERT_KEYS), cfg, row_mask,
            ({name: moe[name] for name in _moe.EXPERT_KEYS}, layer))
    return (_residual(h, f, cfg.residual_multiplier),
            (ck, cv, state, conv), _moe.routing_counts(routing))


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


def embed(params: Params, input_ids: jax.Array,
          cfg: GraniteMoeHybridConfig) -> jax.Array:
    """``embedding_multiplier * E[ids]`` in the compute dtype."""
    rows = params["embed_tokens"][input_ids].astype(F32)
    return (cfg.embedding_multiplier * rows).astype(cfg.dtype)


def _logits(x: jax.Array, params: Params,
            cfg: GraniteMoeHybridConfig) -> jax.Array:
    """The final-normed ``x`` through the head, over ``logits_scaling``."""
    logits = x @ _llama.lm_head_weight(params, cfg)
    return (logits.astype(F32) / cfg.logits_scaling).astype(logits.dtype)


def forward_cached(
    params: Params,
    input_ids: jax.Array,
    cfg: GraniteMoeHybridConfig,
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
    (logits, the new cache). ``cache`` is ``(k, v, state, conv)``
    (``kv_cache.HybridCache``): the page pool of the attention layers,
    which ``kv_io`` (a ``kv_cache.PagedKVIO``) writes and reads through
    the engine's tables, and by slot the Mamba-2 layers' state and
    convolution tail (row b of the call is slot b of both). S > 1 is a
    prompt from its first token (attention over itself, the recurrence in
    its chunked form from ``S = 0``); S == 1 a decode step. ``row_mask``
    [B, S]: the rows that are tokens (a prefix of each sequence; None:
    all). ``logit_rows`` and ``return_routing`` as in
    ``qwen3_moe.forward_cached``."""
    if not hasattr(kv_io, "page_tables"):
        raise NotImplementedError(
            "granitemoehybrid's cached forward is written for the paged "
            "cache (kv_cache.HybridCache through kv_cache.PagedKVIO): a "
            "prompt attends to itself and a contiguous cache is not "
            "written")
    x, cache, counts = _run_layers(
        embed(params, input_ids, cfg), tuple(cache), params, cfg, kv_io,
        positions, write_mask, row_mask)
    x = rms_norm(_llama.select_logit_rows(x, logit_rows), params["norm"],
                 cfg.rms_norm_eps)
    logits = _logits(x, params, cfg)
    if return_routing:
        return logits, cache, counts
    return logits, cache


def forward(
    params: Params,
    input_ids: jax.Array,
    cfg: GraniteMoeHybridConfig,
    *,
    sequential: bool = False,
    return_hidden: bool = False,
) -> jax.Array:
    """Full forward without a cache: [B, S] tokens -> logits [B, S, V]
    (``return_hidden``: the final-normed hidden states); S > 1. Attention
    over the sequence itself, the recurrence from an empty state in its
    chunked form, or row after row with ``sequential`` (the oracle the
    tests hold the chunked form and the cache to)."""
    b, s = input_ids.shape
    if s < 2:
        raise ValueError("granite_moe_hybrid.forward attends a sequence to "
                         "itself: give it at least two tokens")
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    state, conv = cfg.recurrent_state_shapes(b)
    cache = (None, None, jnp.zeros(state, F32), jnp.zeros(conv, cfg.dtype))
    x, _, _ = _run_layers(
        embed(params, input_ids, cfg), cache, params, cfg, SelfKV(),
        positions, None, None, sequential)
    x = rms_norm(x, params["norm"], cfg.rms_norm_eps)
    if return_hidden:
        return x
    return _logits(x, params, cfg)


class GraniteMoeHybrid:
    config_cls = GraniteMoeHybridConfig

    def __init__(self, config: GraniteMoeHybridConfig):
        self.config = config

    def init(self, key: jax.Array) -> Params:
        return init_params(key, self.config)

    def __call__(self, params: Params, input_ids: jax.Array, **kw):
        return forward(params, input_ids, self.config, **kw)
