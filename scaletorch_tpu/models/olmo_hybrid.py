"""Olmo-Hybrid — a decoder whose layers are of two kinds.

``config.json`` of allenai/Olmo-Hybrid-7B (``model_type: olmo_hybrid``):
``layer_types`` lists, layer by layer, ``linear_attention`` or
``full_attention`` (published: three linear, one full, repeated). Both
kinds share the Olmo family's block, which norms what a sub-block
returns instead of what it reads:

    x <- x + RMSNorm(Mix(x))        x <- x + RMSNorm(SwiGLU-MLP(x))

*Full-attention layer.* ``Mix`` is softmax attention with q/k RMSNorm
over the whole projection width (OLMoE's ``qk_norm_scope="projection"``)
and NO rotary embedding (``rope_parameters.rope_theta`` is null). It is
``llama.attention_mix_cached``: the paged pool, the Mosaic pair.

*Linear-attention layer* (gated delta rule). With ``u`` the layer's
input, ``H`` heads of key width ``d_k`` and value width ``d_v``
(``linear_num_key_heads`` < ``H``: q and k have that many heads, each
repeated over ``H / key heads`` consecutive value heads):

    q~ = u Wq   k~ = u Wk   v~ = u Wv   z = u Wg
    (q', k', v') = silu(conv(q~, k~, v~))     depthwise, causal, width 4
    q = l2norm(q') / sqrt(d_k)   k = l2norm(k')   v = v'      per head
    beta  = 2 sigmoid(u Wb)                    (2: linear_allow_neg_eigval)
    alpha = exp(-exp(A_log) softplus(u Wa + dt_bias))
    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
    o_t = S_t^T q_t
    y_t = RMSNorm_[d_v](o_t) * silu(z_t)      out = y Wo

``S`` is a ``[d_k, d_v]`` float32 matrix per head and sequence: the
layer's memory of everything before ``t``, in place of K/V. One token
(``gated_delta_step``) is that recurrence once; a prompt
(``gated_delta_chunked``) is its chunked form: inside a chunk of 64 rows
the ``u_t = beta_t (v_t - alpha_t S_{t-1}^T k_t)`` solve one unit lower
triangular system (the WY representation), between chunks the state
moves by matrix products.

Serving keeps, beside the page pool of the full layers, a state
``f32[linear layers, slots, H, d_k, d_v]`` and the convolution's tail
(the last 3 rows of ``q~, k~, v~``) ``[linear layers, slots, 3, C]``:
the cache is the 4-tuple ``(k, v, state, conv)``
(``inference.kv_cache.HybridCache``). ``forward_cached`` carries it
whole (the lesson of ``llama.scan_layers_cached``) through a
``lax.scan`` over PERIODS of the layer pattern: parameters are stacked
``[periods, layers of the kind in a period, ...]`` under
``layers["linear"]`` / ``layers["full"]`` and each layer reads its
matrices out of the whole stacks at ``[period, j]``.
A row at position 0 starts its slot's recurrence from ``S = 0`` and an
empty tail, whatever the slot held; rows outside ``row_mask`` are no
tokens (``beta = 0, alpha = 1``, not in the tail); slots outside
``write_mask`` keep state and tail bit for bit.

Not written: tensor / context / pipeline parallelism over the
linear-attention layers, the backward of the chunked scan as a kernel
(``forward`` differentiates through ``jax.numpy``), HF weight loading.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from scaletorch_tpu.models import llama as _llama
from scaletorch_tpu.models.layers import (
    DenseKVIO,
    fan_in_uniform,
    get_cos_sin,
    rms_norm,
    sdpa_attention,
)
from scaletorch_tpu.models.llama import LlamaConfig, Params

LINEAR, FULL = "linear_attention", "full_attention"
# rows of one chunk of the chunked recurrence
CHUNK = 64
_HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


@dataclass(frozen=True)
class OlmoHybridConfig(LlamaConfig):
    # Olmo-Hybrid-7B defaults (the published config.json)
    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    head_dim: Optional[int] = None          # hidden // heads = 128
    max_position_embeddings: int = 65536
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    qk_norm: bool = True
    qk_norm_scope: str = "projection"
    # rope_parameters.rope_theta; None: no rotary embedding
    rope_theta: Optional[float] = None
    # None: three linear_attention layers, one full_attention, repeated
    layer_types: Optional[Tuple[str, ...]] = None
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True

    def __post_init__(self) -> None:
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError(
                f"linear_num_value_heads {self.linear_num_value_heads} is "
                f"no multiple of the {self.linear_num_key_heads} key heads "
                "that are repeated over them")
        kinds = self.layer_kinds
        if len(kinds) != self.num_hidden_layers:
            raise ValueError(
                f"layer_types names {len(kinds)} layers, "
                f"num_hidden_layers is {self.num_hidden_layers}")
        unknown = sorted(set(kinds) - {LINEAR, FULL})
        if unknown:
            raise ValueError(f"unknown layer_types {unknown}")
        self.period_pattern  # raises where the stack is no repetition

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        if self.layer_types is not None:
            return tuple(self.layer_types)
        return tuple(FULL if (i + 1) % 4 == 0 else LINEAR
                     for i in range(self.num_hidden_layers))

    @property
    def period_pattern(self) -> Tuple[str, ...]:
        """The shortest run of layer kinds whose repetition is the whole
        stack; it holds both kinds (a stack of one kind is another
        family's)."""
        kinds = self.layer_kinds
        for p in range(1, len(kinds) + 1):
            if len(kinds) % p == 0 and kinds == kinds[:p] * (len(kinds) // p):
                if LINEAR in kinds[:p] and FULL in kinds[:p]:
                    return kinds[:p]
                break
        raise ValueError(
            "layer_types must repeat one period that holds both "
            f"linear_attention and full_attention layers, got {kinds}")

    @property
    def num_periods(self) -> int:
        return self.num_hidden_layers // len(self.period_pattern)

    @property
    def num_linear_layers(self) -> int:
        return self.layer_kinds.count(LINEAR)

    @property
    def num_kv_cache_layers(self) -> int:
        """Layers that keep K/V: the page pool's leading axis."""
        return self.layer_kinds.count(FULL)

    @property
    def linear_key_size(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def linear_value_size(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_channels(self) -> int:
        """Channels of the short convolution: q~, k~, v~ side by side."""
        return 2 * self.linear_key_size + self.linear_value_size

    def recurrent_state_shapes(
        self, slots: int
    ) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
        """(state, convolution tail) shapes of a cache of ``slots``
        sequences: ``[linear layers, slots, H, d_k, d_v]`` (float32)
        and ``[linear layers, slots, kernel - 1, channels]``."""
        n = self.num_linear_layers
        return ((n, slots, self.linear_num_value_heads,
                 self.linear_key_head_dim, self.linear_value_head_dim),
                (n, slots, self.linear_conv_kernel_dim - 1,
                 self.conv_channels))

    def num_params(self) -> int:
        h, i, v = self.hidden_size, self.intermediate_size, self.vocab_size
        mlp = 3 * h * i + 2 * h            # + the two output norms
        heads = self.linear_num_value_heads
        linear = (h * (2 * self.linear_key_size + 3 * self.linear_value_size)
                  + 2 * h * heads + 2 * heads
                  + self.conv_channels * self.linear_conv_kernel_dim
                  + self.linear_value_head_dim)
        full = (2 * h * self.q_size + 2 * h * self.kv_size
                + sum(self.qk_norm_sizes))
        n_lin = self.num_linear_layers
        return (n_lin * (linear + mlp)
                + (self.num_hidden_layers - n_lin) * (full + mlp)
                + v * h + h + (0 if self.tie_word_embeddings else v * h))


def config_from_args(args, common: dict) -> OlmoHybridConfig:
    """The published config.json names; ``rope_parameters`` carries the
    family's rope_theta (null: no rotary embedding)."""
    rope = ({} if args.rope_parameters is None
            else {"rope_theta": args.rope_parameters.get("rope_theta")})
    return OlmoHybridConfig(**{
        **common, **rope,
        "layer_types": (None if args.layer_types is None
                        else tuple(args.layer_types)),
        **{name: getattr(args, name) for name in (
            "linear_num_key_heads", "linear_num_value_heads",
            "linear_key_head_dim", "linear_value_head_dim",
            "linear_conv_kernel_dim", "linear_allow_neg_eigval")}})


def init_params(key: jax.Array, cfg: OlmoHybridConfig) -> Params:
    """Random init: fan-in uniform projections, ones for norm gains,
    normal(0.02) embedding. The decay's own parameters as the rule's
    published initialisers draw them (Mamba2's ``A_init_range=(1, 16)``,
    ``dt_min=1e-3``, ``dt_max=1e-1``, which the gated delta rule's
    reference layer, fla ``GatedDeltaNet``, takes over): per head ``A ~
    U(1, 16)`` (``A_log`` its log) and ``dt_bias`` the inverse softplus
    of a step log-uniform in [1e-3, 1e-1]: decays from 0.999 down to
    0.2 a token. ``a_proj`` a sixteenth of a projection's bound: this
    repo's choice, no published one (below). Layers of a kind are
    stacked ``[periods, layers of the kind in one period, ...]``."""
    pattern = cfg.period_pattern
    periods = cfg.num_periods
    h, i, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    pd = cfg.param_dtype
    heads = cfg.linear_num_value_heads
    kq, kv_ = cfg.linear_key_size, cfg.linear_value_size
    keys = iter(jax.random.split(key, 24))

    def stacks(n):
        lead = (periods, n)

        def w(shape, fan_in):
            return fan_in_uniform(next(keys), lead + shape, fan_in, pd)

        def ones(*shape):
            return jnp.ones(lead + shape, pd)

        return lead, w, ones

    lead, w, ones = stacks(pattern.count(LINEAR))
    step = jnp.exp(jax.random.uniform(
        next(keys), lead + (heads,), F32,
        jnp.log(1e-3), jnp.log(1e-1)))
    linear = {
        "q_proj": w((h, kq), h), "k_proj": w((h, kq), h),
        "v_proj": w((h, kv_), h), "g_proj": w((h, kv_), h),
        "o_proj": w((kv_, h), kv_),
        # the decay's projection at a sixteenth of a projection's bound
        # (fan-in 256 h). The published initialisers draw it like any
        # linear layer, for a block that NORMS the mixer's input; this
        # block hands the mixer the un-normed stream (rms 1 to 6 over
        # 16 layers), so at the full bound ``u Wa`` has a deviation of
        # 0.6 to 3.5 and ``exp(A_log) softplus(u Wa + dt_bias)`` swings
        # by e^{+-3} from token to token: random decays flip between 1
        # and 0, and bf16 rounding of the stream moves one by tens of
        # per cent. Measured on the v5e (PERF.md, PR 32): one seed in
        # eight read 2.3 x the others' logit error at one token; the
        # same seed and weights through the same paged steps in float32
        # read 4.5e-6 of the largest logit, so it is rounding and not
        # the step. At a sixteenth the deviation is 0.04 to 0.2 (a decay
        # within about 20 % of its head's own). No source gives this
        # factor: it is an assumption of serving RANDOM weights, stated
        # in the benchmark configuration's ``assumed.weights``.
        "a_proj": w((h, heads), 256 * h), "b_proj": w((h, heads), h),
        "A_log": jnp.log(jax.random.uniform(
            next(keys), lead + (heads,), F32, 1.0, 16.0)).astype(pd),
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(pd),
        "conv": w((cfg.linear_conv_kernel_dim, cfg.conv_channels),
                  cfg.linear_conv_kernel_dim),
        "o_norm": ones(cfg.linear_value_head_dim),
        "post_attention_layernorm": ones(h),
        "gate_proj": w((h, i), h), "up_proj": w((h, i), h),
        "down_proj": w((i, h), i),
        "post_feedforward_layernorm": ones(h),
    }
    lead, w, ones = stacks(pattern.count(FULL))
    q_gain, k_gain = cfg.qk_norm_sizes
    full = {
        "q_proj": w((h, cfg.q_size), h), "k_proj": w((h, cfg.kv_size), h),
        "v_proj": w((h, cfg.kv_size), h),
        "o_proj": w((cfg.q_size, h), cfg.q_size),
        "q_norm": ones(q_gain), "k_norm": ones(k_gain),
        "post_attention_layernorm": ones(h),
        "gate_proj": w((h, i), h), "up_proj": w((h, i), h),
        "down_proj": w((i, h), i),
        "post_feedforward_layernorm": ones(h),
    }
    params: Params = {
        "embed_tokens": 0.02 * jax.random.normal(next(keys), (v, h), pd),
        "layers": {"linear": linear, "full": full},
        "norm": jnp.ones((h,), pd),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = fan_in_uniform(next(keys), (h, v), h, pd)
    return params


# ---- the gated delta rule ---------------------------------------------------

def l2norm(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def gated_delta_step(q, k, v, log_alpha, beta, state):
    """The recurrence once. q, k [B, H, d_k], v [B, H, d_v], log_alpha,
    beta [B, H], state [B, H, d_k, d_v], all float32 -> (o [B, H, d_v],
    the new state). Products and sums on the state are elementwise: the
    step is bound by reading and writing it, and keeps float32 exactly
    (a dot would round the state to bf16 passes on a TPU).

    ``log_alpha`` [B, H, d_k] is a decay per KEY CHANNEL (Kimi Delta
    Attention): ``S' = diag(alpha) S``, then the same rule on ``S'``."""
    if log_alpha.ndim == k.ndim:
        decayed = jnp.exp(log_alpha)[..., None] * state
        s_k = jnp.sum(decayed * k[..., None], axis=-2)     # S'^T k
        s_q = jnp.sum(decayed * q[..., None], axis=-2)     # S'^T q
        u = beta[..., None] * (v - s_k)
        new = decayed + k[..., None] * u[..., None, :]
        return s_q + jnp.sum(k * q, axis=-1, keepdims=True) * u, new
    alpha = jnp.exp(log_alpha)[..., None]
    s_k = jnp.sum(state * k[..., None], axis=-2)           # S^T k
    s_q = jnp.sum(state * q[..., None], axis=-2)           # S^T q
    u = beta[..., None] * (v - alpha * s_k)
    new = alpha[..., None] * state + k[..., None] * u[..., None, :]
    o = alpha * s_q + jnp.sum(k * q, axis=-1, keepdims=True) * u
    return o, new


def gated_delta_sequential(q, k, v, log_alpha, beta, state):
    """``gated_delta_step`` over time, one row after another: q, k
    [B, S, H, d_k], v [B, S, H, d_v], log_alpha, beta [B, S, H] ->
    (o [B, S, H, d_v], the last state); ``log_alpha`` [B, S, H, d_k]
    where the decay is per key channel. What ``gated_delta_chunked``
    computes, written as the definition (the tests' oracle)."""
    def body(s, row):
        o, s = gated_delta_step(*row, s)
        return s, o

    rows = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, log_alpha, beta))
    state, o = jax.lax.scan(body, state, rows)
    return jnp.moveaxis(o, 0, 1), state


def _mm(x, y):
    return jnp.matmul(x, y, precision=_HIGHEST)


def unit_lower_inverse(a: jax.Array, base: int = 16) -> jax.Array:
    """``(I + A)^-1`` for strictly lower triangular ``A`` [..., n, n],
    n = ``base`` times a power of two. Diagonal blocks of ``base`` rows
    by forward substitution, row after row (exact in the order a
    sequential solve takes; 15 small steps), then pairs of blocks
    merged upwards by matrix products: ``[[Ta, 0], [-Tb A_ba Ta,
    Tb]]``. (``lax.linalg.triangular_solve`` is right too, and on a
    v5e its 64 x 64 diagonal-block inversion took 19 ms a layer of a
    512-row prefill call, a quarter of the call: PERF.md, PR 32.)"""
    n = a.shape[-1]
    if n <= base:
        eye = jnp.eye(n, dtype=a.dtype)

        def row(r, t):
            # rows of t at and past r are still the identity's, and A's
            # row r is zero there: the product sees the rows before r
            a_r = jax.lax.dynamic_slice_in_dim(a, r, 1, axis=-2)
            e_r = jax.lax.dynamic_slice_in_dim(eye, r, 1, axis=0)
            return jax.lax.dynamic_update_slice_in_dim(
                t, e_r - _mm(a_r, t), r, axis=-2)

        return jax.lax.fori_loop(
            1, n, row, jnp.broadcast_to(eye, a.shape))
    h = n // 2
    t = unit_lower_inverse(
        jnp.stack([a[..., :h, :h], a[..., h:, h:]]), base)
    ta, tb = t[0], t[1]
    lower = -_mm(_mm(tb, a[..., h:, :h]), ta)
    return jnp.concatenate([
        jnp.concatenate([ta, jnp.zeros_like(ta)], axis=-1),
        jnp.concatenate([lower, tb], axis=-1)], axis=-2)


def gated_delta_chunked(q, k, v, log_alpha, beta, state, *,
                        chunk: int = CHUNK):
    """The recurrence over S rows in chunks of ``chunk``; shapes as
    ``gated_delta_sequential``, S of any length (padded with rows of
    ``beta = 0, alpha = 1``, which are no tokens).

    Inside a chunk with incoming state ``S0`` and ``G_r`` the product of
    the alphas up to row r, ``S_r = G_r S0 + sum_{i<=r} (G_r/G_i) k_i
    u_i^T`` where the ``u`` solve ``(I + A) U = diag(beta) (V - diag(G)
    K S0)``, ``A_ri = beta_r (G_r/G_i) k_r.k_i`` for i < r: one unit
    lower triangular inverse (``unit_lower_inverse``) gives ``U_v`` and
    ``W`` (``U = U_v - W S0``), once for all chunks. Then a scan over the chunks moves the state:
    ``O = diag(G) Q S0 + (QK^T * G_r/G_i, i<=r) U``, ``S_C = G_C S0 +
    (diag(G_C/G) K)^T U``. Every ratio ``G_r/G_i`` has i <= r and is at
    most 1. Matrix products in float32 at ``highest`` precision: a few
    per cent of a prefill call's time.

    ``log_alpha`` [B, S, H, d_k], a decay per key channel, takes
    ``_chunked_by_channel``: the ratios sit inside the contractions."""
    if log_alpha.ndim == q.ndim:
        return _chunked_by_channel(q, k, v, log_alpha, beta, state,
                                   chunk=chunk)
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    pad = -s % chunk
    if pad:
        q, k, v, log_alpha, beta = (
            jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
            for a in (q, k, v, log_alpha, beta))
    n = (s + pad) // chunk

    def chunks(a):          # [B, S, H, ...] -> [N, B, H, C, ...]
        a = a.reshape((b, n, chunk) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

    q, k, v, log_alpha, beta = map(chunks, (q, k, v, log_alpha, beta))
    g = jnp.cumsum(log_alpha, axis=-1)                     # [N, B, H, C]
    rows = jnp.arange(chunk)
    diff = g[..., :, None] - g[..., None, :]               # log G_r/G_i
    decay = jnp.exp(jnp.where(rows[:, None] >= rows[None, :], diff, -jnp.inf))
    kk = jnp.einsum("nbhrd,nbhid->nbhri", k, k, precision=_HIGHEST)
    a = jnp.where(rows[:, None] > rows[None, :],
                  beta[..., None] * decay * kk, 0.0)
    rhs = jnp.concatenate(
        [beta[..., None] * v, (beta * jnp.exp(g))[..., None] * k], axis=-1)
    solved = _mm(unit_lower_inverse(a), rhs)
    u_v, w = solved[..., :dv], solved[..., dv:]
    qk = decay * jnp.einsum("nbhrd,nbhid->nbhri", q, k, precision=_HIGHEST)
    g_out = jnp.exp(g)[..., None]                          # G_r
    g_end = jnp.exp(g[..., -1])[..., None, None]           # G_C
    k_end = k * jnp.exp(g[..., -1:] - g)[..., None]        # (G_C/G_i) k_i

    def body(s0, xs):
        q_n, u_v_n, w_n, qk_n, g_out_n, g_end_n, k_end_n = xs
        u = u_v_n - jnp.einsum("bhrk,bhkv->bhrv", w_n, s0,
                               precision=_HIGHEST)
        o = (g_out_n * jnp.einsum("bhrk,bhkv->bhrv", q_n, s0,
                                  precision=_HIGHEST)
             + jnp.einsum("bhri,bhiv->bhrv", qk_n, u, precision=_HIGHEST))
        s1 = g_end_n * s0 + jnp.einsum("bhrk,bhrv->bhkv", k_end_n, u,
                                       precision=_HIGHEST)
        return s1, o

    state, o = jax.lax.scan(
        body, state, (q, u_v, w, qk, g_out, g_end, k_end))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 3, 2)          # [B, N, C, H, dv]
    return o.reshape(b, n * chunk, h, dv)[:, :s], state


# rows of one sub-block of a chunk where the decay is per key channel
SUB_BLOCK = 16
# elements of the diagonal sub-blocks' pairwise decays ``[chunks, B, H,
# C / c, c, c, d_k]`` that are alive at once (float32: 256 MB): the
# chunks of a long prompt go through in groups of this many
_PAIRWISE_ELEMENTS = 1 << 26


def _channel_chunk_parts(q, k, v, log_alpha, beta, block):
    """What one chunk contributes whatever state it meets, the decay per
    key channel: q, k, log_alpha [..., C, d_k], v [..., C, d_v], beta
    [..., C] -> (``U_v`` [..., C, d_v], ``W`` [..., C, d_k], the decayed
    ``Q K^T`` [..., C, C], ``diag(G) Q``, ``G_C`` [..., d_k],
    ``diag(G_C / G) K``) with ``G_r = exp(g_r)`` the product of the
    alphas up to row r, a VECTOR over the key channels. The ratio
    ``G_r / G_i`` sits inside the contraction: ``A_ri = beta_r sum_d
    k_rd k_id exp(g_rd - g_id)``, and ``(k_r e^{g_r}) . (k_i e^{-g_i})``
    overflows float32 within one chunk (``-g`` reaches hundreds at a
    decay of e^-30 a step). So the chunk is cut in sub-blocks of
    ``block`` rows: on the diagonal sub-blocks the differences ``g_r -
    g_i`` are taken pair by pair, exactly; off the diagonal both
    factors are taken relative to the row block's first row b, ``exp(g_r
    - g_b) <= 1`` and ``exp(g_b - g_i) <= 1`` (i lies before b), whose
    product is the ratio and can only underflow where the ratio does."""
    c = q.shape[-2]
    nb = c // block
    dv = v.shape[-1]
    g = jnp.cumsum(log_alpha, axis=-2)                     # [..., C, dk]

    def blocks(a):
        return a.reshape(a.shape[:-2] + (nb, block, a.shape[-1]))

    gb, kb, qb = blocks(g), blocks(k), blocks(q)
    rows = jnp.arange(block)
    pair = gb[..., :, None, :] - gb[..., None, :, :]       # g_r - g_i
    pair = jnp.exp(jnp.where(
        (rows[:, None] >= rows[None, :])[..., None], pair, -jnp.inf))
    k_pair = kb[..., None, :, :] * pair
    kk_diag = jnp.sum(kb[..., :, None, :] * k_pair, axis=-1)
    qk_diag = jnp.sum(qb[..., :, None, :] * k_pair, axis=-1)
    kk_rows, qk_rows = [], []
    for r in range(nb):
        parts_k, parts_q = [kk_diag[..., r, :, :]], [qk_diag[..., r, :, :]]
        if r:
            first = gb[..., r, :1, :]                      # g_b
            shrink = jnp.exp(gb[..., r, :, :] - first)
            before = k[..., :r * block, :] * jnp.exp(
                first - g[..., :r * block, :])
            parts_k.insert(0, jnp.einsum(
                "...rd,...id->...ri", kb[..., r, :, :] * shrink, before,
                precision=_HIGHEST))
            parts_q.insert(0, jnp.einsum(
                "...rd,...id->...ri", qb[..., r, :, :] * shrink, before,
                precision=_HIGHEST))
        after = jnp.zeros(q.shape[:-2] + (block, (nb - 1 - r) * block),
                          q.dtype)
        kk_rows.append(jnp.concatenate(parts_k + [after], axis=-1))
        qk_rows.append(jnp.concatenate(parts_q + [after], axis=-1))
    kk = jnp.concatenate(kk_rows, axis=-2)                 # [..., C, C]
    qk = jnp.concatenate(qk_rows, axis=-2)
    at = jnp.arange(c)
    a = jnp.where(at[:, None] > at[None, :], beta[..., None] * kk, 0.0)
    grown = jnp.exp(g)                                     # G_r
    rhs = jnp.concatenate(
        [beta[..., None] * v, beta[..., None] * grown * k], axis=-1)
    solved = _mm(unit_lower_inverse(a, block), rhs)
    return (solved[..., :dv], solved[..., dv:], qk, grown * q,
            grown[..., -1, :], k * jnp.exp(g[..., -1:, :] - g))


def _chunked_by_channel(q, k, v, log_alpha, beta, state, *, chunk: int,
                        block: int = SUB_BLOCK):
    """``gated_delta_chunked`` where ``log_alpha`` [B, S, H, d_k] is a
    decay per key channel: ``S_r = diag(G_r) S0 + sum_{i<=r} diag(G_r /
    G_i) k_i u_i^T``, the ``u`` solving ``(I + A) U = diag(beta) (V -
    (diag(G) K) S0)`` (``_channel_chunk_parts``); a scan over the chunks
    moves the state: ``O = (diag(G) Q) S0 + (decayed Q K^T) U``, ``S_C =
    diag(G_C) S0 + (diag(G_C / G) K)^T U``."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    pad = -s % chunk
    if pad:
        q, k, v, log_alpha, beta = (
            jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
            for a in (q, k, v, log_alpha, beta))
    n = (s + pad) // chunk

    def chunks(a):          # [B, S, H, ...] -> [N, B, H, C, ...]
        a = a.reshape((b, n, chunk) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

    rows = tuple(map(chunks, (q, k, v, log_alpha, beta)))
    group = max(1, min(n, _PAIRWISE_ELEMENTS
                       // (b * h * chunk * block * dk)))
    while n % group:
        group -= 1
    parts = jax.lax.map(
        lambda xs: _channel_chunk_parts(*xs, block),
        tuple(a.reshape((n // group, group) + a.shape[1:]) for a in rows))
    parts = tuple(a.reshape((n,) + a.shape[2:]) for a in parts)

    def body(s0, xs):
        u_v, w, qk, q_out, g_end, k_end = xs
        u = u_v - _mm(w, s0)
        o = _mm(q_out, s0) + _mm(qk, u)
        s1 = g_end[..., None] * s0 + jnp.einsum(
            "bhrk,bhrv->bhkv", k_end, u, precision=_HIGHEST)
        return s1, o

    state, o = jax.lax.scan(body, state, parts)
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 3, 2)          # [B, N, C, H, dv]
    return o.reshape(b, n * chunk, h, dv)[:, :s], state


def short_conv(x: jax.Array, weight: jax.Array, tail: jax.Array,
               bias: Optional[jax.Array] = None):
    """Depthwise causal convolution over time: x [B, S, C] after the
    ``tail`` [B, K-1, C] of rows that came before it, weight [K, C],
    ``bias`` [C] or none (the delta-rule families have none; Jamba's
    Mamba layers have one). Returns (y [B, S, C] float32 with ``y_t =
    sum_j w_j x_{t-K+1+j} (+ bias)``, the rows ``[tail; x]`` [B, S+K-1,
    C])."""
    rows = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    s = x.shape[1]
    wide, w = rows.astype(F32), weight.astype(F32)
    y = sum(wide[:, j:j + s] * w[j] for j in range(weight.shape[0]))
    if bias is not None:
        y = y + bias.astype(F32)
    return y, rows


def conv_tail_after(rows: jax.Array, tail: jax.Array,
                    row_mask: Optional[jax.Array]) -> jax.Array:
    """The tail a call leaves: of ``rows`` = ``[tail; x]`` [B, S+K-1, C]
    (``short_conv``) the last K-1 rows before each sequence's next
    token, where ``row_mask`` [B, S] says which rows of ``x`` are
    tokens (a prefix of each sequence; None: all)."""
    b, s = rows.shape[0], rows.shape[1] - tail.shape[1]
    valid = (jnp.full((b,), s, jnp.int32) if row_mask is None
             else jnp.sum(row_mask, axis=1, dtype=jnp.int32))
    # rows[valid + j] is the j-th of the last K-1 rows before the next
    # token
    keep = valid[:, None] + jnp.arange(tail.shape[1])[None, :]
    return jnp.take_along_axis(
        rows, keep[:, :, None], axis=1).astype(tail.dtype)


def linear_attention_mix(
    u: jax.Array,
    layer: Params,
    cfg: OlmoHybridConfig,
    state: jax.Array,
    tail: jax.Array,
    *,
    row_mask: Optional[jax.Array] = None,
    sequential: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The gated delta-rule mixer of u [B, S, hidden], continuing from
    ``state`` [B, H, d_k, d_v] (float32) and the convolution ``tail``
    [B, K-1, C]. Rows outside ``row_mask`` [B, S] (a prefix of each
    sequence is inside) are no tokens: they leave the state alone and
    stay out of the tail. Returns (the residual's increment before its
    output norm [B, S, hidden], the state after the last token, the new
    tail). One row is the recurrence itself, more rows its chunked form
    (``sequential``: row after row, the oracle)."""
    cdt = cfg.dtype
    b, s, _ = u.shape
    heads, dk, dv = (cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                     cfg.linear_value_head_dim)
    key_heads, kq = cfg.linear_num_key_heads, cfg.linear_key_size

    with jax.named_scope("gdn.conv"):
        qkv = jnp.concatenate(
            [u @ layer[name].astype(cdt)
             for name in ("q_proj", "k_proj", "v_proj")], axis=-1)
        mixed, rows = short_conv(qkv, layer["conv"], tail)
        mixed = jax.nn.silu(mixed)
        new_tail = conv_tail_after(rows, tail, row_mask)

    with jax.named_scope("gdn.recurrence"):
        q = l2norm(mixed[..., :kq].reshape(b, s, key_heads, dk)) * dk ** -0.5
        k = l2norm(mixed[..., kq:2 * kq].reshape(b, s, key_heads, dk))
        if key_heads != heads:
            # a key head serves ``heads // key_heads`` consecutive value
            # heads, each with a state of its own
            q, k = (jnp.repeat(a, heads // key_heads, axis=2)
                    for a in (q, k))
        v = mixed[..., 2 * kq:].reshape(b, s, heads, dv)
        # the two gates' projections (one column a head) leave their
        # matmul in float32: a decay is a product over every token
        # since the prompt began, and rounds no step of it to bf16
        beta = jax.nn.sigmoid(jnp.matmul(
            u, layer["b_proj"].astype(cdt), preferred_element_type=F32))
        if cfg.linear_allow_neg_eigval:
            beta = 2.0 * beta
        log_alpha = -jnp.exp(layer["A_log"].astype(F32)) * jax.nn.softplus(
            jnp.matmul(u, layer["a_proj"].astype(cdt),
                       preferred_element_type=F32)
            + layer["dt_bias"].astype(F32))
        if row_mask is not None:
            beta = jnp.where(row_mask[..., None], beta, 0.0)
            log_alpha = jnp.where(row_mask[..., None], log_alpha, 0.0)
        if s == 1:
            o, state = gated_delta_step(
                q[:, 0], k[:, 0], v[:, 0], log_alpha[:, 0], beta[:, 0],
                state)
            o = o[:, None]
        elif sequential:
            o, state = gated_delta_sequential(
                q, k, v, log_alpha, beta, state)
        else:
            o, state = gated_delta_chunked(q, k, v, log_alpha, beta, state)

    with jax.named_scope("gdn.gate_norm"):
        # the gate stays [B, S, H * d_v] as projected: split into heads,
        # XLA re-lays the whole g_proj stack for it, every step
        z = u @ layer["g_proj"].astype(cdt)
        y = rms_norm(o.astype(cdt), layer["o_norm"], cfg.rms_norm_eps)
        y = y.reshape(b, s, heads * dv) * jax.nn.silu(z)
        out = y @ layer["o_proj"].astype(cdt)
    return out, state, new_tail


@jax.named_scope("mlp")
def _mlp_block(x: jax.Array, layer: Params,
               cfg: OlmoHybridConfig) -> jax.Array:
    return x + rms_norm(_llama.swiglu_mlp(x, layer, cfg),
                        layer["post_feedforward_layernorm"],
                        cfg.rms_norm_eps)


def _rope_tables(cfg: OlmoHybridConfig, seq: int, positions):
    if cfg.rope_theta is None:
        return None, None
    return get_cos_sin(seq, cfg.actual_head_dim, cfg.rope_theta,
                       positions=positions)


def layer_of(stack: Params, period: jax.Array, j: int) -> Params:
    """Layer ``j`` of period ``period`` out of the whole stacks
    ``[periods, layers of the kind in a period, ...]``, each matrix by
    ONE dynamic slice that its consumer fuses. (Scanned over periods
    and then indexed at ``j``, XLA copies the period's ``[3, hidden,
    width]`` slice out first: every linear layer's weights written and
    read once more a decode step.)"""
    def one(a):
        return jax.lax.dynamic_slice(
            a, (period, j) + (0,) * (a.ndim - 2),
            (1, 1) + a.shape[2:]).reshape(a.shape[2:])

    return {name: one(a) for name, a in stack.items()}


def period_layers(pattern: Tuple[str, ...]):
    """The layers of one period in order, statically: (kind, the name of
    its parameter stack, its place among the period's layers of that
    kind)."""
    seen = {LINEAR: 0, FULL: 0}
    out = []
    for kind in pattern:
        out.append((kind, "linear" if kind == LINEAR else "full", seen[kind]))
        seen[kind] += 1
    return tuple(out)


def _close_block(h: jax.Array, mixed: jax.Array, layer: Params,
                 cfg: OlmoHybridConfig) -> jax.Array:
    """``x <- x + RMSNorm(Mix(x))`` given ``Mix(x)``, then the MLP half."""
    h = h + rms_norm(mixed, layer["post_attention_layernorm"],
                     cfg.rms_norm_eps)
    return _mlp_block(h, layer, cfg)


class SelfKV:
    """K/V of the call itself (``forward``): no cache to write or read."""

    def write(self, cache, layer, new, positions, write_mask):
        return new

    def attend(self, q, k, v, layer, positions, own=None):
        return sdpa_attention(q, k, v, causal=True)


def forward_cached(
    params: Params,
    input_ids: jax.Array,
    cfg: OlmoHybridConfig,
    cache: Tuple[jax.Array, jax.Array, jax.Array, jax.Array],
    *,
    positions: jax.Array,
    write_mask: Optional[jax.Array] = None,
    kv_io: Optional[Any] = None,
    row_mask: Optional[jax.Array] = None,
    logit_rows: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Tuple[jax.Array, ...]]:
    """Cached forward: [B, S] tokens at absolute ``positions`` [B, S] ->
    (logits, the new cache); ``logits`` [B, S, V], or [B, 1, V] for the
    row a sequence that ``logit_rows`` [B] names
    (``llama.select_logit_rows``). ``cache`` is ``(k, v, state,
    conv)``: K/V of the full-attention layers in ``kv_io``'s layout
    (``[full layers, ...]``: the paged pool, or the dense reference),
    the recurrent state and the convolution tail of the linear layers
    (module docstring), each carried whole and touched at a layer index
    of its own kind. ``row_mask`` [B, S]: the rows that are tokens (a
    prefix of each sequence; None: all). A sequence whose first row is
    at position 0 starts from an empty state."""
    pattern = cfg.period_pattern
    layers = period_layers(pattern)
    n_lin, n_full = pattern.count(LINEAR), pattern.count(FULL)
    kv_io = kv_io or DenseKVIO()
    x = _llama.embed(params, input_ids, cfg)
    b, s = input_ids.shape
    cos, sin = _rope_tables(cfg, s, positions)
    fresh = positions[:, 0] == 0
    written = (jnp.ones((b,), bool) if write_mask is None else write_mask)

    def period_fn(carry, index):
        h, (ck, cv, state, conv) = carry
        for kind, stack, j in layers:
            layer = layer_of(params["layers"][stack], index, j)
            if kind == LINEAR:
                at = index * n_lin + j
                old_s = jax.lax.dynamic_index_in_dim(state, at, 0, False)
                old_t = jax.lax.dynamic_index_in_dim(conv, at, 0, False)
                out, new_s, new_t = linear_attention_mix(
                    h, layer, cfg,
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
                    out, ck, cv = _llama.attention_mix_cached(
                        h, layer, index * n_full + j, ck, cv, cos, sin,
                        positions, cfg, write_mask=write_mask, kv_io=kv_io)
            h = _close_block(h, out, layer, cfg)
        return (h, (ck, cv, state, conv)), None

    # the carry of ``llama.scan_layers_cached``: the cache whole, never
    # a scanned operand (PERF.md, PR 28); no parameters are scanned
    (x, cache), _ = jax.lax.scan(
        period_fn, (x, tuple(cache)),
        jnp.arange(cfg.num_periods, dtype=jnp.int32))
    x = rms_norm(_llama.select_logit_rows(x, logit_rows), params["norm"],
                 cfg.rms_norm_eps)
    return x @ _llama.lm_head_weight(params, cfg), cache


def forward(
    params: Params,
    input_ids: jax.Array,
    cfg: OlmoHybridConfig,
    *,
    sequential: bool = False,
    return_hidden: bool = False,
) -> jax.Array:
    """Full forward without a cache: [B, S] tokens -> logits [B, S, V]
    (``return_hidden``: the final-normed hidden states). Attention over
    the sequence itself, the delta rule from an empty state in its
    chunked form, or row after row with ``sequential`` (the oracle the
    tests hold the chunked form and the cache to)."""
    layers = period_layers(cfg.period_pattern)
    b, s = input_ids.shape
    x = _llama.embed(params, input_ids, cfg)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    cos, sin = _rope_tables(cfg, s, positions)
    state_shape, tail_shape = cfg.recurrent_state_shapes(b)
    state0 = jnp.zeros(state_shape[1:], F32)
    tail0 = jnp.zeros(tail_shape[1:], cfg.dtype)

    def period_fn(h, index):
        for kind, stack, j in layers:
            layer = layer_of(params["layers"][stack], index, j)
            if kind == LINEAR:
                out, _, _ = linear_attention_mix(
                    h, layer, cfg, state0, tail0, sequential=sequential)
            else:
                with jax.named_scope("attn"):
                    out, _, _ = _llama.attention_mix_cached(
                        h, layer, 0, None, None, cos, sin, positions, cfg,
                        kv_io=SelfKV())
            h = _close_block(h, out, layer, cfg)
        return h, None

    x, _ = jax.lax.scan(
        period_fn, x, jnp.arange(cfg.num_periods, dtype=jnp.int32))
    x = rms_norm(x, params["norm"], cfg.rms_norm_eps)
    if return_hidden:
        return x
    return x @ _llama.lm_head_weight(params, cfg)


class OlmoHybrid:
    config_cls = OlmoHybridConfig

    def __init__(self, config: OlmoHybridConfig):
        self.config = config

    def init(self, key: jax.Array) -> Params:
        return init_params(key, self.config)

    def __call__(self, params: Params, input_ids: jax.Array, **kw):
        return forward(params, input_ids, self.config, **kw)
