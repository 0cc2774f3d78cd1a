"""Shared model building blocks: RMSNorm, RoPE, SDPA attention, initializers.

Functional counterparts of reference scaletorch/models/attention_utils.py:
RMSNorm computed internally in fp32 (:247-271), RoPE ``get_cos_sin`` /
``apply_rotary_pos_emb`` (:170-239), fan-in uniform ``_init_weights``
(:160-167). All functions are pure and jit/scan-friendly (static shapes,
no Python control flow on traced values).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


# ---- initialisation ---------------------------------------------------------
def fan_in_uniform(key: jax.Array, shape: Tuple[int, ...], fan_in: int,
                   dtype=jnp.float32) -> jax.Array:
    """U(-1/sqrt(fan_in), 1/sqrt(fan_in)) — the reference's Linear init
    (attention_utils.py:160-167)."""
    bound = 1.0 / math.sqrt(fan_in)
    return jax.random.uniform(key, shape, dtype, minval=-bound, maxval=bound)


def normal_init(key: jax.Array, shape: Tuple[int, ...], std: float = 0.02,
                dtype=jnp.float32) -> jax.Array:
    return std * jax.random.normal(key, shape, dtype)


# ---- RMSNorm ----------------------------------------------------------------
def _rms_norm_fwd_math(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    variance = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(variance + eps)
    return (x32 * inv * weight.astype(jnp.float32)).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _rms_norm_p(eps: float, x: jax.Array, weight: jax.Array) -> jax.Array:
    return _rms_norm_fwd_math(x, weight, eps)


def _rms_norm_fwd(eps, x, weight):
    return _rms_norm_fwd_math(x, weight, eps), (x, weight)


def _rms_norm_bwd(eps, res, g):
    x, weight = res
    x32 = x.astype(jnp.float32)
    variance = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(variance + eps)
    xhat = x32 * inv
    g32 = g.astype(jnp.float32)
    w32 = weight.astype(jnp.float32)
    gw = g32 * w32
    # d/dx of xhat·w: (1/rms)·(g·w − xhat·mean(g·w·xhat)) over the norm axis.
    dx = inv * (gw - xhat * jnp.mean(gw * xhat, axis=-1, keepdims=True))
    # weight broadcasts over all leading axes of x (per-head q/k norms use
    # a [Dh] weight against [B, S, H, Dh] activations).
    reduce_axes = tuple(range(x.ndim - weight.ndim))
    dw = jnp.sum(g32 * xhat, axis=reduce_axes)
    return dx.astype(x.dtype), dw.astype(weight.dtype)


_rms_norm_p.defvjp(_rms_norm_fwd, _rms_norm_bwd)


# ---- SwiGLU -----------------------------------------------------------------
@jax.custom_vjp
def swiglu(gate: jax.Array, up: jax.Array) -> jax.Array:
    """``silu(gate) * up`` with a memory-lean VJP.

    Plain autodiff stashes silu(gate) and the product alongside gate/up —
    four FFN-wide buffers per layer where two suffice (measured 6x672 MB
    of SwiGLU residuals at 0.6B/seq2048/bs2 no-remat, tools/aot_memory.py).
    This VJP saves only (gate, up) and recomputes the cheap elementwise
    pieces in backward, exactly like fused SwiGLU kernels do.
    """
    return jax.nn.silu(gate) * up


def _swiglu_fwd(gate, up):
    return jax.nn.silu(gate) * up, (gate, up)


def _swiglu_bwd(res, ct):
    gate, up = res
    g32 = gate.astype(jnp.float32)
    s = jax.nn.sigmoid(g32)
    silu = g32 * s
    dsilu = s + silu * (1.0 - s)  # d/dg [g·sigmoid(g)]
    ct32 = ct.astype(jnp.float32)
    dgate = (ct32 * up.astype(jnp.float32) * dsilu).astype(gate.dtype)
    dup = (ct32 * silu).astype(up.dtype)
    return dgate, dup


swiglu.defvjp(_swiglu_fwd, _swiglu_bwd)


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm with fp32 internal math (parity: attention_utils.py:247-271).

    Memory-lean custom VJP: plain autodiff would stash the fp32 upcast
    and the normalised fp32 product as residuals — for a no-remat
    (gradient_checkpointing=False) train step those fp32 copies of every
    norm input dominate HBM (measured 4.4 GB of the 13.4 GB activation
    arena at 0.6B/seq2048/bs2, tools/aot_memory.py). The VJP saves only
    the ORIGINAL-dtype ``x`` and ``weight`` and recomputes the fp32
    internals in the backward — the same trade every fused RMSNorm kernel
    (e.g. the reference's NPU fused norm) makes.

    Under shard_map, ``x`` (activation) and ``weight`` (replicated param,
    pvaried over every mesh axis) may carry different varying-axis sets; a
    custom VJP must return cotangents typed exactly like its primal
    inputs, so both are aligned to their vma union here, OUTSIDE the VJP
    — the pvary's psum transpose is then autodiff's job, not ours.
    Outside shard_map both sets are empty and this is a no-op.
    """
    vma_x = jax.typeof(x).vma
    vma_w = jax.typeof(weight).vma
    if vma_x != vma_w:
        x = jax.lax.pvary(x, tuple(vma_w - vma_x))
        weight = jax.lax.pvary(weight, tuple(vma_x - vma_w))
    return _rms_norm_p(float(eps), x, weight)


def rms_norm_zero_centered(x: jax.Array, weight: jax.Array,
                           eps: float = 1e-6) -> jax.Array:
    """RMSNorm under the zero-centred gain ``1 + weight`` (Qwen3-Next's
    layer, q/k and final norms: a gain initialised at 0)."""
    return rms_norm(x, 1.0 + weight.astype(jnp.float32), eps)


# ---- RoPE -------------------------------------------------------------------
def get_cos_sin(
    seq_len: int,
    head_dim: int,
    rope_theta: float = 10000.0,
    dtype=jnp.float32,
    positions: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Precompute rotary cos/sin tables ``[seq, head_dim]``.

    Matches the HF/reference convention (attention_utils.py:170-210): inverse
    frequencies over even dims, angles duplicated across the two halves.
    ``positions`` overrides 0..seq_len-1 (used by CP to slice this rank's
    sequence shard, reference context_parallel.py:427-473). A 2-D
    ``positions`` [B, S] yields per-batch tables ``[B, S, head_dim]`` —
    the decode path's per-slot absolute positions (inference/decode.py).
    """
    inv_freq = 1.0 / (
        rope_theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    if positions is None:
        positions = jnp.arange(seq_len, dtype=jnp.float32)
    else:
        positions = positions.astype(jnp.float32)
    freqs = positions[..., None] * inv_freq  # [..., S, Dh/2]
    emb = jnp.concatenate([freqs, freqs], axis=-1)  # [..., S, Dh]
    return jnp.cos(emb).astype(dtype), jnp.sin(emb).astype(dtype)


def rotate_half(x: jax.Array) -> jax.Array:
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def apply_rotary_pos_emb(
    q: jax.Array, k: jax.Array, cos: jax.Array, sin: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Apply RoPE. q/k: [B, H, S, Dh]; cos/sin: [S, Dh] (broadcast over
    B, H) or per-batch [B, S, Dh] (decode's per-slot positions; broadcast
    over H only). Tables narrower than the head (HF
    ``partial_rotary_factor``): the first ``cos.shape[-1]`` dims of each
    head rotate among themselves, the others pass through."""
    rotary = cos.shape[-1]
    if rotary < q.shape[-1]:
        q_rot, k_rot = apply_rotary_pos_emb(
            q[..., :rotary], k[..., :rotary], cos, sin)
        return (jnp.concatenate([q_rot, q[..., rotary:]], axis=-1),
                jnp.concatenate([k_rot, k[..., rotary:]], axis=-1))
    if cos.ndim == 3:
        cos = cos[:, None, :, :].astype(q.dtype)
        sin = sin[:, None, :, :].astype(q.dtype)
    else:
        cos = cos[None, None, :, :].astype(q.dtype)
        sin = sin[None, None, :, :].astype(q.dtype)
    q_rot = q * cos + rotate_half(q) * sin
    k_rot = k * cos + rotate_half(k) * sin
    return q_rot, k_rot


# ---- attention --------------------------------------------------------------
def repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """GQA KV head expansion [B, Hkv, S, D] -> [B, Hkv*n_rep, S, D].

    The reference uses a zero-copy ``expand`` (llama.py:176-192); under XLA
    the broadcast is fused away, so an explicit broadcast is equally free.
    """
    if n_rep == 1:
        return k
    b, h_kv, s, d = k.shape
    k = jnp.broadcast_to(k[:, :, None, :, :], (b, h_kv, n_rep, s, d))
    return k.reshape(b, h_kv * n_rep, s, d)


def softmax_with_sink(scores: jax.Array,
                      sink: Optional[jax.Array] = None) -> jax.Array:
    """Softmax over the last axis of ``scores`` [B, H, Sq, Sk] float32;
    with ``sink`` [H] (a learned logit a query head) the sink is one
    more column of every row's softmax that carries no value: it takes
    its share of the mass and the column is dropped (``exp(s - m) /
    (exp(sink - m) + sum exp(s - m))``)."""
    if sink is None:
        return jax.nn.softmax(scores, axis=-1)
    with jax.named_scope("attn.sink"):
        column = jnp.broadcast_to(
            sink.astype(jnp.float32)[None, :, None, None],
            scores.shape[:-1] + (1,))
        return jax.nn.softmax(
            jnp.concatenate([scores, column], axis=-1), axis=-1)[..., :-1]


def sdpa_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    bias: Optional[jax.Array] = None,
    sink: Optional[jax.Array] = None,
) -> jax.Array:
    """Plain XLA scaled-dot-product attention with fp32 softmax.

    q: [B, Hq, S, D]; k/v: [B, Hkv, Skv, D] (GQA expanded here; the
    value's width may be its own). ``sink``: ``softmax_with_sink``.
    The default/portable backend (reference 'sdpa', attention_utils.py:130-152).
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    n_rep = q.shape[1] // k.shape[1]
    k = repeat_kv(k, n_rep)
    v = repeat_kv(v, n_rep)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        s_q, s_k = scores.shape[-2], scores.shape[-1]
        mask = jnp.tril(jnp.ones((s_q, s_k), dtype=bool), k=s_k - s_q)
        scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
    if bias is not None:
        scores = scores + bias.astype(jnp.float32)
    probs = softmax_with_sink(scores, sink).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def sdpa_attention_with_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
) -> Tuple[jax.Array, jax.Array]:
    """SDPA that also returns the log-sum-exp ``[B, H, S]`` (fp32).

    Building block for ring attention's blockwise LSE merge (reference
    ring_attention_forward, context_parallel.py:266-330).
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    n_rep = q.shape[1] // k.shape[1]
    k = repeat_kv(k, n_rep)
    v = repeat_kv(v, n_rep)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        s_q, s_k = scores.shape[-2], scores.shape[-1]
        mask = jnp.tril(jnp.ones((s_q, s_k), dtype=bool), k=s_k - s_q)
        scores = jnp.where(mask, scores, -jnp.inf)
    lse = jax.nn.logsumexp(scores, axis=-1)  # [B, H, S]
    # Rows with no visible keys (fully masked) have lse = -inf; their output
    # is defined as 0 so the ring merge can rescale them safely.
    probs = jnp.exp(scores - jnp.where(jnp.isfinite(lse), lse, 0.0)[..., None])
    probs = jnp.where(jnp.isfinite(lse)[..., None], probs, 0.0).astype(q.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
    return out, lse


def cached_sdpa_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    q_positions: jax.Array,
    *,
    scale: Optional[float] = None,
    window: Optional[int] = None,
    sink: Optional[jax.Array] = None,
) -> jax.Array:
    """SDPA against a fixed-size KV cache with absolute-position masking.

    q: [B, Hq, S, D] (S = prompt length at prefill, 1 at decode);
    k_cache/v_cache: [B, Hkv, S_max, D]; q_positions: [B, S] absolute
    token positions. Query at position p attends cache entries j <= p —
    causal over the cache, independent of how much of it is stale, which
    is exactly right under the engine invariant that positions [0, p] of
    a live slot have always been written (prefill fills [0, len), decode
    overwrites position p before reading it).

    Same fp32-softmax math as ``sdpa_attention``, so prefill logits match
    the full-sequence training forward to float tolerance. With
    ``window`` the query at p sees only the entries with ``p - j <
    window`` (a window layer's ring, where index j holds the newest
    position that is j modulo the ring's length). ``sink``:
    ``softmax_with_sink``; the value cache may be narrower than the key
    cache.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    n_rep = q.shape[1] // k_cache.shape[1]
    k = repeat_kv(k_cache, n_rep)
    v = repeat_kv(v_cache, n_rep)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    key_idx = jnp.arange(k_cache.shape[2], dtype=jnp.int32)
    mask = key_idx[None, None, :] <= q_positions[:, :, None]  # [B, S, S_max]
    if window is not None:
        mask &= q_positions[:, :, None] - key_idx[None, None, :] < window
    scores = jnp.where(mask[:, None], scores, jnp.finfo(jnp.float32).min)
    probs = softmax_with_sink(scores, sink).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def write_kv_cache(
    cache: jax.Array,
    new: jax.Array,
    starts: jax.Array,
    write_mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Append ``new`` [B, H, S, D] into ``cache`` [B, H, S_max, D] at
    per-slot sequence offsets ``starts`` [B] (``lax.dynamic_update_slice``
    vmapped over the slot axis — XLA lowers the batched variant to an
    in-place scatter under buffer donation). ``write_mask`` [B] bool
    keeps unlisted slots' cache bytes untouched (continuous batching
    admits new requests without perturbing live ones)."""

    def one(c, n, st):
        return jax.lax.dynamic_update_slice(c, n, (0, st, 0))

    updated = jax.vmap(one)(cache, new.astype(cache.dtype),
                            starts.astype(jnp.int32))
    if write_mask is not None:
        updated = jnp.where(write_mask[:, None, None, None], updated, cache)
    return updated


class DenseKVIO:
    """K/V adapter of the contiguous reference cache: what ``PagedKVIO``
    (inference/kv_cache.py) is to the page pool. The cache-aware forwards
    carry the whole stacked cache [L, B, Hkv, S_max, D] through their
    layer loop and touch it only through an adapter's ``write`` and
    ``attend`` at a layer index; this one is ``write_kv_cache`` +
    ``cached_sdpa_attention`` on that layer. Reference and
    single-sequence sampling (``teacher_forced_decode``,
    ``gpt_moe.generate``), never the engine: it serves from the pool."""

    def write(self, cache: jax.Array, layer: jax.Array, new: jax.Array,
              positions: jax.Array,
              write_mask: Optional[jax.Array]) -> jax.Array:
        updated = write_kv_cache(
            jax.lax.dynamic_index_in_dim(cache, layer, 0, keepdims=False),
            new, positions[:, 0], write_mask)
        return jax.lax.dynamic_update_index_in_dim(cache, updated, layer, 0)

    def attend(self, q: jax.Array, cache_k: jax.Array, cache_v: jax.Array,
               layer: jax.Array, q_positions: jax.Array,
               own: Optional[Tuple[jax.Array, jax.Array]] = None
               ) -> jax.Array:
        """``own`` (the call's K/V, which ``PagedKVIO.attend`` may
        attend to in place of the pool) is not read: the reference
        reads its cache at every call."""
        def at_layer(cache):
            return jax.lax.dynamic_index_in_dim(
                cache, layer, 0, keepdims=False)

        return cached_sdpa_attention(
            q, at_layer(cache_k), at_layer(cache_v), q_positions)


# ---- losses -----------------------------------------------------------------
def cross_entropy_loss(
    logits: jax.Array,
    targets: jax.Array,
    ignore_index: int = -100,
) -> jax.Array:
    """Token-mean cross entropy with ignore_index masking (fp32 internally).

    logits: [..., V]; targets: [...] int32. Matches the reference's
    F.cross_entropy(ignore_index=-100) semantics (train_step.py:98-103).
    """
    logits = logits.astype(jnp.float32)
    mask = targets != ignore_index
    safe_targets = jnp.where(mask, targets, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe_targets[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * mask
    denom = jnp.maximum(mask.sum(), 1)
    return nll.sum() / denom
