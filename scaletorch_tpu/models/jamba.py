"""Jamba — Mamba-1 selective-scan layers between rope-free attention
layers.

``config.json`` of ai21labs/AI21-Jamba2-3B (``model_type: jamba``):
layer ``i`` is an attention layer where ``i % attn_layer_period ==
attn_layer_offset`` (published: 14 and 7: layers 7 and 21 of 28) and a
Mamba layer elsewhere; ``num_experts`` 1, so every layer's feed-forward
is a plain SwiGLU MLP. Both kinds share one pre-norm block (the
published ``modeling_jamba.py``), RMSNorm ``w * x / rms(x)``:

    h <- h + Mix(RMSNorm_in(h))       h <- h + MLP(RMSNorm_ff(h))

a final RMSNorm, logits through the embedding's transpose, and NO
positional embedding anywhere: the Mamba layers carry the order.

*Attention layer.* ``q = x Wq`` (heads of ``hidden / heads``), ``k = x
Wk``, ``v = x Wv`` (``num_key_value_heads`` heads: ONE, shared by all
20 query heads), no bias, no q/k norm, no rotary embedding, causal
softmax, ``Wo``. A call of several rows is a prompt from its first
token and attends to itself in key blocks
(``ops/flash_attention.prefill_self_attention``); a call of one row
reads the page pool (the paged decode kernel).

*Mamba layer* (``mamba_mix``), ``C = mamba_expand * hidden`` channels,
``N = mamba_d_state``:

    [u, z] = x W_in                              (u first, no bias)
    u <- silu(conv(u) + b_conv)                  depthwise, causal, width 4
    [dt_r, B, C] = u W_x                         (dt_rank + N + N, no bias)
    dt_r, B, C <- RMSNorm each, a gain each      (Jamba's addition to Mamba-1)
    dt = softplus(dt_r W_dt + b_dt)
    S_t[n, c] = exp(dt_t[c] A[n, c]) S_{t-1}[n, c] + dt_t[c] B_t[n] u_t[c]
    y_t[c] = sum_n S_t[n, c] C_t[n] + D[c] u_t[c]          A = -exp(A_log)
    out = (y * silu(z)) W_out

Projections and activations run in the compute dtype with float32
accumulation; ``W_x``'s result, the three norms, ``dt``, ``exp(dt A)``,
the state and ``y`` are float32. The state is elementwise in (n, c), no
matrix per head, and is held with the CHANNELS ON THE LANES: the
published ``[C, N]`` transposed and the channels viewed as ``[R,
128]``, so ``A_log`` is ``[N, C]`` and a sequence's state ``f32[N, R,
128]`` (``[16, 40, 128]``; as ``[C, 16]`` a TPU would pad every row of
16 to 128 lanes, eight times the bytes). Three forms of the scan:
``selective_scan_step`` (one token), ``selective_scan_chunked`` (a
prompt in plain XLA: the fallback and the CPU path) and
``ops/pallas/ssm_scan.ssm_scan_fwd`` (a prompt on a TPU); the
definition row after row, ``selective_scan_sequential``, is the tests'
oracle.

Serving keeps, beside the page pool of the attention layers, the state
``f32[mamba layers, slots, N, R, 128]`` and the convolution's tail (the
last ``d_conv - 1`` rows of ``u`` before the convolution) ``[mamba
layers, slots, 3, C]``: the cache is ``kv_cache.HybridCache``.
``forward_cached`` carries it whole through a ``lax.scan`` over PERIODS
of the layer pattern; inside a period a run of Mamba layers is a
``lax.scan`` of its own over the layer's place, so a period of 14 layers
traces three layer bodies. Parameters are stacked ``[periods, layers of
the kind in a period, ...]`` under ``layers["mamba"]`` /
``layers["attention"]`` (``olmo_hybrid.layer_of``). A row at position 0
starts its slot from ``S = 0`` and an empty tail; rows outside
``row_mask`` are no tokens (``dt = 0``: ``exp(0) = 1`` and ``dt B u =
0`` leave the state as it is; they stay out of the tail); slots outside
``write_mask`` keep state and tail bit for bit.

Not written: ``num_experts`` > 1 (Jamba-1.5's routed layers:
``expert_layer_period`` / ``expert_layer_offset``), ``mamba_proj_bias``,
the scan's backward and the trainer's step, tensor / context / pipeline
parallelism over the Mamba layers, prefix sharing and state snapshots,
HF weight loading, a contiguous cache.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from scaletorch_tpu.models import llama as _llama
from scaletorch_tpu.models.afmoe import SelfKV
from scaletorch_tpu.models.layers import fan_in_uniform, rms_norm
from scaletorch_tpu.models.llama import LlamaConfig, Params
from scaletorch_tpu.models.olmo_hybrid import (
    conv_tail_after,
    layer_of,
    short_conv,
)

MAMBA, ATTENTION = "mamba", "attention"
# tokens of one chunk of the XLA scan: exp(dt A) and dt B u of a chunk
# are f32[slots, CHUNK, N, channels], 42 MB each at 8 slots of 5,120
CHUNK = 16
_LANES = 128
F32 = jnp.float32


@dataclass(frozen=True)
class JambaConfig(LlamaConfig):
    # AI21-Jamba2-3B defaults (the published config.json)
    vocab_size: int = 65536
    hidden_size: int = 2560
    intermediate_size: int = 8192
    num_hidden_layers: int = 28
    num_attention_heads: int = 20
    num_key_value_heads: int = 1
    head_dim: Optional[int] = None          # hidden // heads = 128
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = True
    # no rotary embedding, and no key for one
    rope_theta: Optional[float] = None
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    # random weights only: the token embedding's standard deviation
    # (HF ``initializer_range``)
    embed_init_std: float = 0.02

    def __post_init__(self) -> None:
        period, offset = self.attn_layer_period, self.attn_layer_offset
        if period < 2 or not 0 <= offset < period:
            raise ValueError(
                f"attn_layer_period {period} / attn_layer_offset {offset}: "
                "a period holds one attention layer among Mamba layers")
        if self.num_hidden_layers % period:
            raise ValueError(
                f"num_hidden_layers {self.num_hidden_layers} is no "
                f"multiple of attn_layer_period {period}: the layer loop "
                "runs whole periods")
        if self.mamba_proj_bias:
            raise NotImplementedError(
                "mamba_proj_bias: the in / out projections are written "
                "without a bias (the published Jamba2 configuration's)")
        if self.qk_norm or self.rope_theta is not None:
            raise ValueError(
                "jamba's attention layers have no q/k norm and no rotary "
                "embedding")

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        return tuple(
            ATTENTION if i % self.attn_layer_period == self.attn_layer_offset
            else MAMBA for i in range(self.num_hidden_layers))

    @property
    def period_pattern(self) -> Tuple[str, ...]:
        return self.layer_kinds[:self.attn_layer_period]

    @property
    def num_periods(self) -> int:
        return self.num_hidden_layers // self.attn_layer_period

    @property
    def num_mamba_layers(self) -> int:
        return self.num_periods * (self.attn_layer_period - 1)

    @property
    def num_kv_cache_layers(self) -> int:
        """Layers that keep K/V: the page pool's leading axis."""
        return self.num_periods

    @property
    def mamba_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def channel_view(self) -> Tuple[int, int]:
        """The channels as ``[R, lanes]``: whole 128-lane rows where
        they divide (every published size), one row otherwise."""
        c = self.mamba_inner
        return (c // _LANES, _LANES) if c % _LANES == 0 else (1, c)

    def recurrent_state_shapes(
        self, slots: int
    ) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
        """(state, convolution tail) shapes of a cache of ``slots``
        sequences: ``[mamba layers, slots, N, R, lanes]`` (float32) and
        ``[mamba layers, slots, d_conv - 1, channels]``."""
        n = self.num_mamba_layers
        return ((n, slots, self.mamba_d_state) + self.channel_view,
                (n, slots, self.mamba_d_conv - 1, self.mamba_inner))

    def num_params(self) -> int:
        h, i, v = self.hidden_size, self.intermediate_size, self.vocab_size
        c, n, r = self.mamba_inner, self.mamba_d_state, self.mamba_dt_rank
        mlp = 3 * h * i + 2 * h                 # + the block's two norms
        mixer = (h * 2 * c + c * self.mamba_d_conv
                 + (c if self.mamba_conv_bias else 0)
                 + c * (r + 2 * n) + (r + 2 * n)
                 + r * c + c + n * c + c + c * h)
        attn = 2 * h * self.q_size + 2 * h * self.kv_size
        n_mamba = self.num_mamba_layers
        return (n_mamba * (mixer + mlp)
                + (self.num_hidden_layers - n_mamba) * (attn + mlp)
                + v * h + h + (0 if self.tie_word_embeddings else v * h))


# What ``init_params`` multiplies the fan-in bound of ``W_q`` and ``W_k``
# by, so the scores ``q k^T / sqrt(D)`` by 4: no published initialiser,
# random weights only. At the bound itself the scores deviate by 0.33,
# every softmax over a few thousand keys is flat, and an attention layer
# adds next to nothing that a comparison of logits can see (a rotary
# embedding put on by mistake read UNDER bfloat16's own error on the
# chip); a trained model's attention is sharp. A sweep of 1 / 2 / 2.8 /
# 4 chose 2: the smallest that shows the layer and the largest that
# leaves bfloat16's error where it was (PERF.md, PR 47).
QK_INIT_SCALE = 2.0


def config_from_args(args, common: dict) -> JambaConfig:
    """The published config.json names; no rotary embedding and no key
    for one."""
    if args.num_experts != 1 or args.num_experts_per_tok != 1:
        raise NotImplementedError(
            f"jamba with num_experts {args.num_experts} / "
            f"num_experts_per_tok {args.num_experts_per_tok}: every "
            "layer's feed-forward is the dense SwiGLU MLP of "
            "models/jamba.py (Jamba2's num_experts 1); the routed "
            "layers of the larger Jambas are not written")
    return JambaConfig(**{
        **common, "rope_theta": None,
        **{name: getattr(args, name) for name in (
            "attn_layer_period", "attn_layer_offset", "mamba_d_state",
            "mamba_d_conv", "mamba_expand", "mamba_dt_rank",
            "mamba_conv_bias", "mamba_proj_bias")}})


def init_params(key: jax.Array, cfg: JambaConfig) -> Params:
    """Random init: fan-in uniform projections (the depthwise
    convolution's weight and bias at its fan-in, the kernel width),
    ones for norm gains, normal(``embed_init_std``) embedding. The
    scan's own parameters as Mamba-1's published initialisers draw them
    (state-spaces/mamba ``mamba_simple.py``, which ``modeling_jamba.py``
    follows for ``A_log`` and ``D``): ``A_log = log(1..N)`` for every
    channel, ``D = 1``, ``W_dt`` uniform in ``+-dt_rank^-0.5``, ``b_dt``
    the inverse softplus of a step log-uniform in [1e-3, 1e-1]. ``W_q``
    and ``W_k`` at ``QK_INIT_SCALE`` times their bound. Layers of a kind
    are stacked ``[periods, layers of the kind in one period, ...]``."""
    periods, per = cfg.num_periods, cfg.attn_layer_period - 1
    h, i, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    c, n, r, k = (cfg.mamba_inner, cfg.mamba_d_state, cfg.mamba_dt_rank,
                  cfg.mamba_d_conv)
    pd = cfg.param_dtype
    keys = iter(jax.random.split(key, 24))

    def stacks(count):
        lead = (periods, count)

        def w(shape, fan_in):
            return fan_in_uniform(next(keys), lead + shape, fan_in, pd)

        def ones(*shape):
            return jnp.ones(lead + shape, pd)

        return lead, w, ones

    def mlp_and_norms(w, ones):
        return {
            "input_layernorm": ones(h), "pre_ff_layernorm": ones(h),
            "gate_proj": w((h, i), h), "up_proj": w((h, i), h),
            "down_proj": w((i, h), i),
        }

    lead, w, ones = stacks(per)
    step = jnp.exp(jax.random.uniform(
        next(keys), lead + (c,), F32, math.log(1e-3), math.log(1e-1)))
    mamba = {
        "in_proj": w((h, 2 * c), h),
        "conv": w((k, c), k),
        "x_proj": w((c, r + 2 * n), c),
        "dt_norm": ones(r), "b_norm": ones(n), "c_norm": ones(n),
        "dt_proj": w((r, c), r),
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(pd),
        "A_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, n + 1, dtype=F32))[:, None],
            lead + (n, c)).astype(pd),
        "D": ones(c),
        "out_proj": w((c, h), c),
        **mlp_and_norms(w, ones),
    }
    if cfg.mamba_conv_bias:
        mamba["conv_bias"] = w((c,), k)
    lead, w, ones = stacks(1)
    sharp = jnp.asarray(QK_INIT_SCALE, pd)
    attention = {
        "q_proj": w((h, cfg.q_size), h) * sharp,
        "k_proj": w((h, cfg.kv_size), h) * sharp,
        "v_proj": w((h, cfg.kv_size), h),
        "o_proj": w((cfg.q_size, h), cfg.q_size),
        **mlp_and_norms(w, ones),
    }
    params: Params = {
        "embed_tokens": cfg.embed_init_std * jax.random.normal(
            next(keys), (v, h), pd),
        "layers": {"mamba": mamba, "attention": attention},
        "norm": jnp.ones((h,), pd),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = fan_in_uniform(next(keys), (h, v), h, pd)
    return params


# ---- the selective scan ------------------------------------------------------
#
# Shapes, all float32: u, dt [B, S, R, L] (channels viewed [R, L]), a
# [N, R, L] (negative), bm, cm [B, S, N], state [B, N, R, L]. Each form
# returns (y [B, S, R, L] without the ``D u`` skip, the state after the
# last row). (The Mosaic kernel takes u, dt and gives y as [B, S, C]:
# ops/pallas/ssm_scan.py says why.)

def selective_scan_step(u, dt, a, bm, cm, state):
    """The recurrence once: u, dt [B, R, L], bm, cm [B, N] -> (y [B, R,
    L], the new state). Elementwise on the state, which is read and
    written once."""
    decay = jnp.exp(dt[:, None] * a)
    state = decay * state + (dt * u)[:, None] * bm[:, :, None, None]
    return jnp.sum(state * cm[:, :, None, None], axis=1), state


def selective_scan_sequential(u, dt, a, bm, cm, state):
    """``selective_scan_step`` row after row: the definition (the tests'
    oracle)."""
    def body(s, row):
        u_t, dt_t, b_t, c_t = row
        y, s = selective_scan_step(u_t, dt_t, a, b_t, c_t, s)
        return s, y

    rows = tuple(jnp.moveaxis(x, 1, 0) for x in (u, dt, bm, cm))
    state, y = jax.lax.scan(body, state, rows)
    return jnp.moveaxis(y, 0, 1), state


def selective_scan_chunked(u, dt, a, bm, cm, state, *, chunk: int = CHUNK):
    """The recurrence over S rows, ``chunk`` at a time, in plain XLA: a
    chunk's ``exp(dt A)`` and ``dt B u`` ``[B, chunk, N, R, L]`` are
    computed at once, its rows then chained (unrolled: one fusion may
    keep the state of a chunk out of HBM); a ``lax.scan`` over the
    chunks carries the state. S of any length (padded with rows of ``dt
    = 0``, which are no tokens). No operand of the whole prompt's length
    has a state axis."""
    s = u.shape[1]
    pad = -s % chunk
    if pad:
        u, dt, bm, cm = (
            jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
            for x in (u, dt, bm, cm))

    def chunks(x):              # [B, S, ...] -> [S / chunk, B, chunk, ...]
        x = x.reshape((x.shape[0], -1, chunk) + x.shape[2:])
        return jnp.moveaxis(x, 1, 0)

    def body(held, xs):
        u_c, dt_c, b_c, c_c = xs
        decay = jnp.exp(dt_c[:, :, None] * a)
        drive = (dt_c * u_c)[:, :, None] * b_c[..., None, None]
        ys = []
        for t in range(chunk):
            held = decay[:, t] * held + drive[:, t]
            ys.append(jnp.sum(held * c_c[:, t, :, None, None], axis=1))
        return held, jnp.stack(ys, axis=1)

    state, y = jax.lax.scan(body, state, tuple(map(chunks, (u, dt, bm, cm))))
    y = jnp.moveaxis(y, 0, 1).reshape(u.shape)
    return y[:, :s], state


def scan_kernel_serves(cfg: JambaConfig) -> bool:
    """Whether a prompt's scan takes the Mosaic kernel: on a TPU (the
    repo's one kernel-vs-XLA predicate) and with channels that fill
    whole vector registers."""
    from scaletorch_tpu.ops.flash_attention import _pallas_available
    from scaletorch_tpu.ops.pallas import ssm_scan

    return ssm_scan.kernel_serves(*cfg.channel_view) and _pallas_available()


def mamba_mix(
    x: jax.Array,
    layer: Params,
    cfg: JambaConfig,
    state: jax.Array,
    tail: jax.Array,
    *,
    row_mask: Optional[jax.Array] = None,
    scan: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The Mamba mixer of the normed hidden states ``x`` [B, S, hidden],
    continuing from ``state`` [B, N, R, L] (float32) and the convolution
    ``tail`` [B, K-1, C]. Rows outside ``row_mask`` [B, S] (a prefix of
    each sequence is inside) are no tokens: their ``dt`` is 0, which
    leaves the state alone, and they stay out of the tail. Returns (the
    residual's increment [B, S, hidden], the state after the last token,
    the new tail). One row is the recurrence itself; more rows take the
    Mosaic kernel where ``scan_kernel_serves`` and the chunked XLA form
    elsewhere (``scan``: ``"kernel"`` / ``"chunked"`` / ``"sequential"``
    names one)."""
    cdt = cfg.dtype
    b, s, _ = x.shape
    c, n, r = cfg.mamba_inner, cfg.mamba_d_state, cfg.mamba_dt_rank
    view = cfg.channel_view

    with jax.named_scope("ssm.conv"):
        uz = x @ layer["in_proj"].astype(cdt)
        pre, z = uz[..., :c], uz[..., c:]
        mixed, rows = short_conv(pre, layer["conv"], tail,
                                 layer.get("conv_bias"))
        u = jax.nn.silu(mixed).astype(cdt)
        new_tail = conv_tail_after(rows, tail, row_mask)

    with jax.named_scope("ssm.params"):
        eps = cfg.rms_norm_eps
        dbc = jnp.matmul(u, layer["x_proj"].astype(cdt),
                         preferred_element_type=F32)
        dt_r = rms_norm(dbc[..., :r], layer["dt_norm"], eps)
        bm = rms_norm(dbc[..., r:r + n], layer["b_norm"], eps)
        cm = rms_norm(dbc[..., r + n:], layer["c_norm"], eps)
        dt = jax.nn.softplus(
            jnp.matmul(dt_r.astype(cdt), layer["dt_proj"].astype(cdt),
                       preferred_element_type=F32)
            + layer["dt_bias"].astype(F32))
        if row_mask is not None:
            dt = jnp.where(row_mask[..., None], dt, 0.0)
        a = -jnp.exp(layer["A_log"].astype(F32)).reshape((n,) + view)

    with jax.named_scope("ssm.scan"):
        u32 = u.astype(F32)
        if scan is None and s > 1:
            scan = "kernel" if scan_kernel_serves(cfg) else "chunked"
        if scan == "kernel" and s > 1:
            from scaletorch_tpu.ops.pallas.ssm_scan import ssm_scan_fwd

            # u, dt and y as the projections hold them: the kernel
            # re-lays a token's row in registers
            y, state = ssm_scan_fwd(u32, dt, a, bm, cm, state)
        else:
            tiled = (u32.reshape((b, s) + view), dt.reshape((b, s) + view))
            if s == 1:
                y, state = selective_scan_step(
                    tiled[0][:, 0], tiled[1][:, 0], a, bm[:, 0], cm[:, 0],
                    state)
            else:
                form = {"chunked": selective_scan_chunked,
                        "sequential": selective_scan_sequential}[scan]
                y, state = form(*tiled, a, bm, cm, state)

    with jax.named_scope("ssm.gate"):
        y = y.reshape(b, s, c) + layer["D"].astype(F32) * u32
        gated = (y * jax.nn.silu(z.astype(F32))).astype(cdt)
        out = gated @ layer["out_proj"].astype(cdt)
    return out, state, new_tail


def attention_mix(
    x: jax.Array,
    layer: Params,
    index: Any,
    cache_k: Any,
    cache_v: Any,
    positions: jax.Array,
    cfg: JambaConfig,
    io: Any,
    write_mask: Optional[jax.Array],
    *,
    scale: Optional[float] = None,
) -> Tuple[jax.Array, Any, Any]:
    """The attention mixer of the normed hidden states ``x`` [B, S, H]:
    no q/k norm, no rotary embedding; K/V written at ``index`` of the
    pool through ``io``; a call of one row reads the pool, a call of
    several rows is a prompt from its first token and attends to itself
    (module docstring). ``scale``: the scores' factor where it is not
    ``head_dim ** -0.5`` (granitemoehybrid's ``attention_multiplier``).
    Returns (the mixer's output, cache_k, cache_v)."""
    from scaletorch_tpu.ops.flash_attention import prefill_self_attention

    cdt = cfg.dtype
    b, s, _ = x.shape

    def heads(name):            # [B, heads, S, D]
        return (x @ layer[name].astype(cdt)).reshape(
            b, s, -1, cfg.actual_head_dim).transpose(0, 2, 1, 3)

    q, k, v = heads("q_proj"), heads("k_proj"), heads("v_proj")
    cache_k = io.write(cache_k, index, k, positions, write_mask)
    cache_v = io.write(cache_v, index, v, positions, write_mask)
    if s == 1:
        attn = io.attend(q, cache_k, cache_v, index, positions, scale=scale)
    else:
        attn = prefill_self_attention(q, k, v, scale=scale)
    attn = attn.transpose(0, 2, 1, 3).reshape(b, s, -1)
    return attn @ layer["o_proj"].astype(cdt), cache_k, cache_v


@jax.named_scope("mlp.dense")
def _mlp_block(h: jax.Array, layer: Params, cfg: JambaConfig) -> jax.Array:
    return h + _llama.swiglu_mlp(
        rms_norm(h, layer["pre_ff_layernorm"], cfg.rms_norm_eps), layer, cfg)


def period_runs(pattern: Tuple[str, ...]):
    """The layers of one period as runs of one kind, statically: (kind,
    the place of the run's first layer among the period's layers of
    that kind, the run's length). Published: ``(mamba, 0, 7),
    (attention, 0, 1), (mamba, 7, 6)``."""
    seen = {MAMBA: 0, ATTENTION: 0}
    runs = []
    for kind in pattern:
        if runs and runs[-1][0] == kind:
            runs[-1][2] += 1
        else:
            runs.append([kind, seen[kind], 1])
        seen[kind] += 1
    return tuple(tuple(run) for run in runs)


def _run_layers(x, cache, params, cfg, positions, kv_io, write_mask,
                row_mask, scan):
    """Every layer in order. ``cache`` is ``(k, v, state, conv)``, or
    None for a call without one (every Mamba layer then starts from an
    empty state). The carry of ``llama.scan_layers_cached``: the cache
    whole, never a scanned operand; no parameters are scanned."""
    runs = period_runs(cfg.period_pattern)
    per = cfg.attn_layer_period - 1
    b = x.shape[0]
    cached = cache is not None
    if cached:
        fresh = positions[:, 0] == 0
        written = (jnp.ones((b,), bool) if write_mask is None
                   else write_mask)
    else:
        state_shape, tail_shape = cfg.recurrent_state_shapes(b)
        state0 = jnp.zeros(state_shape[1:], F32)
        tail0 = jnp.zeros(tail_shape[1:], cfg.dtype)
        cache = (None, None, None, None)

    def mamba_layer(h, held, period, j):
        layer = layer_of(params["layers"]["mamba"], period, j)
        normed = rms_norm(h, layer["input_layernorm"], cfg.rms_norm_eps)
        if not cached:
            out, _, _ = mamba_mix(normed, layer, cfg, state0, tail0,
                                  scan=scan)
            return _mlp_block(h + out, layer, cfg), held
        ck, cv, state, conv = held
        at = period * per + j
        old_s = jax.lax.dynamic_index_in_dim(state, at, 0, False)
        old_t = jax.lax.dynamic_index_in_dim(conv, at, 0, False)
        lead = (slice(None),) + (None,) * (old_s.ndim - 1)
        out, new_s, new_t = mamba_mix(
            normed, layer, cfg, jnp.where(fresh[lead], 0.0, old_s),
            jnp.where(fresh[:, None, None], 0, old_t),
            row_mask=row_mask, scan=scan)
        state = jax.lax.dynamic_update_index_in_dim(
            state, jnp.where(written[lead], new_s, old_s), at, 0)
        conv = jax.lax.dynamic_update_index_in_dim(
            conv, jnp.where(written[:, None, None], new_t, old_t), at, 0)
        return _mlp_block(h + out, layer, cfg), (ck, cv, state, conv)

    def attention_layer(h, held, period, j):
        layer = layer_of(params["layers"]["attention"], period, j)
        ck, cv, state, conv = held
        with jax.named_scope("attn"), jax.named_scope("attn.full"):
            out, ck, cv = attention_mix(
                rms_norm(h, layer["input_layernorm"], cfg.rms_norm_eps),
                layer, period + j, ck, cv, positions, cfg, kv_io,
                write_mask)
        return _mlp_block(h + out, layer, cfg), (ck, cv, state, conv)

    def period_fn(carry, period):
        h, held = carry
        for kind, first, length in runs:
            one = mamba_layer if kind == MAMBA else attention_layer
            if length == 1:
                h, held = one(h, held, period, first)
                continue

            def body(inner, j, one=one):
                return one(*inner, period, j), None

            (h, held), _ = jax.lax.scan(
                body, (h, held),
                jnp.arange(first, first + length, dtype=jnp.int32))
        return (h, held), None

    (x, cache), _ = jax.lax.scan(
        period_fn, (x, tuple(cache)),
        jnp.arange(cfg.num_periods, dtype=jnp.int32))
    return x, cache


def forward_cached(
    params: Params,
    input_ids: jax.Array,
    cfg: JambaConfig,
    cache: Tuple[jax.Array, jax.Array, jax.Array, jax.Array],
    *,
    positions: jax.Array,
    write_mask: Optional[jax.Array] = None,
    kv_io: Optional[Any] = None,
    row_mask: Optional[jax.Array] = None,
    logit_rows: Optional[jax.Array] = None,
    scan: Optional[str] = None,
) -> Tuple[jax.Array, Tuple[jax.Array, ...]]:
    """Cached forward: [B, S] tokens at absolute ``positions`` [B, S] ->
    (logits, the new cache); ``logits`` [B, S, V], or [B, 1, V] for the
    row a sequence that ``logit_rows`` [B] names
    (``llama.select_logit_rows``). ``cache`` is ``(k, v, state, conv)``
    (``kv_cache.HybridCache``): the page pool of the attention layers,
    which ``kv_io`` (a ``kv_cache.PagedKVIO``) writes and reads through
    the engine's tables, and by slot the Mamba layers' state and
    convolution tail (module docstring). S > 1 is a prompt from its
    first token, attended to itself; S == 1 a decode step against the
    cache. ``row_mask`` [B, S]: the rows that are tokens (a prefix of
    each sequence; None: all)."""
    if not hasattr(kv_io, "page_tables"):
        raise NotImplementedError(
            "jamba's cached forward is written for the paged cache "
            "(kv_cache.HybridCache through kv_cache.PagedKVIO): a prompt "
            "attends to itself and a contiguous cache is not written")
    x = _llama.embed(params, input_ids, cfg)
    x, cache = _run_layers(x, cache, params, cfg, positions, kv_io,
                           write_mask, row_mask, scan)
    x = rms_norm(_llama.select_logit_rows(x, logit_rows), params["norm"],
                 cfg.rms_norm_eps)
    return x @ _llama.lm_head_weight(params, cfg), cache


def forward(
    params: Params,
    input_ids: jax.Array,
    cfg: JambaConfig,
    *,
    scan: Optional[str] = None,
    return_hidden: bool = False,
) -> jax.Array:
    """Full forward without a cache: [B, S] tokens -> logits [B, S, V]
    (``return_hidden``: the final-normed hidden states); S > 1.
    Attention over the sequence itself, the scan from an empty state in
    the form ``mamba_mix`` picks or ``scan`` names (``"sequential"``:
    the oracle the tests hold the other forms and the cache to)."""
    b, s = input_ids.shape
    if s < 2:
        raise ValueError("jamba.forward attends a sequence to itself: "
                         "give it at least two tokens")
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    x, _ = _run_layers(_llama.embed(params, input_ids, cfg), None, params,
                       cfg, positions, SelfKV(), None, None, scan)
    x = rms_norm(x, params["norm"], cfg.rms_norm_eps)
    if return_hidden:
        return x
    return x @ _llama.lm_head_weight(params, cfg)


class Jamba:
    config_cls = JambaConfig

    def __init__(self, config: JambaConfig):
        self.config = config

    def init(self, key: jax.Array) -> Params:
        return init_params(key, self.config)

    def __call__(self, params: Params, input_ids: jax.Array, **kw):
        return forward(params, input_ids, self.config, **kw)
