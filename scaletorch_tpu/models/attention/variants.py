"""MHA / MQA / GQA / MLA attention variants.

Parity with reference scaletorch/models/attention/:
  * ``MultiHeadAttention`` (mha.py:9) — full per-head K/V
  * ``MultiQueryAttention`` (mqa.py:9) — single shared K/V head
  * ``GroupQueryAttention`` (gqa.py:9) — grouped K/V heads
  * ``MultiHeadLatentAttention`` (mla.py:9,60-66) — DeepSeek-style
    low-rank q/kv down-up projections through a latent bottleneck

All four are one parameterised implementation: MHA/MQA are GQA with
kv_heads = heads / 1 (the same collapse the reference's class hierarchy
expresses), MLA adds the latent projections in front.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from scaletorch_tpu.models.attention.base import AttentionConfig, AttentionVariant
from scaletorch_tpu.models.layers import (
    fan_in_uniform,
    repeat_kv,
    sdpa_attention,
)

Params = Dict[str, jax.Array]


def _gqa_init(key: jax.Array, cfg: AttentionConfig, kv_heads: int) -> Params:
    d, dh = cfg.embed_dim, cfg.actual_head_dim
    nh = cfg.num_heads
    ks = jax.random.split(key, 4)
    pd = cfg.dtype
    return {
        "q_proj": fan_in_uniform(ks[0], (d, nh * dh), d, pd),
        "k_proj": fan_in_uniform(ks[1], (d, kv_heads * dh), d, pd),
        "v_proj": fan_in_uniform(ks[2], (d, kv_heads * dh), d, pd),
        "o_proj": fan_in_uniform(ks[3], (nh * dh, d), nh * dh, pd),
    }


def _gqa_apply(
    params: Params, x: jax.Array, cfg: AttentionConfig, kv_heads: int,
    *, causal: bool = True,
) -> jax.Array:
    b, s, _ = x.shape
    nh, dh = cfg.num_heads, cfg.actual_head_dim
    q = (x @ params["q_proj"]).reshape(b, s, nh, dh).transpose(0, 2, 1, 3)
    k = (x @ params["k_proj"]).reshape(b, s, kv_heads, dh).transpose(0, 2, 1, 3)
    v = (x @ params["v_proj"]).reshape(b, s, kv_heads, dh).transpose(0, 2, 1, 3)
    k = repeat_kv(k, nh // kv_heads)
    v = repeat_kv(v, nh // kv_heads)
    o = sdpa_attention(q, k, v, causal=causal)
    return o.transpose(0, 2, 1, 3).reshape(b, s, nh * dh) @ params["o_proj"]


class MultiHeadAttention(AttentionVariant):
    """Per-head K/V (reference mha.py:9)."""

    def init(self, key):
        return _gqa_init(key, self.cfg, self.cfg.num_heads)

    def __call__(self, params, x, *, causal: bool = True):
        return _gqa_apply(params, x, self.cfg, self.cfg.num_heads, causal=causal)


class MultiQueryAttention(AttentionVariant):
    """One shared K/V head (reference mqa.py:9)."""

    def init(self, key):
        return _gqa_init(key, self.cfg, 1)

    def __call__(self, params, x, *, causal: bool = True):
        return _gqa_apply(params, x, self.cfg, 1, causal=causal)


class GroupQueryAttention(AttentionVariant):
    """Grouped K/V heads (reference gqa.py:9)."""

    def init(self, key):
        return _gqa_init(key, self.cfg, self.cfg.actual_num_kv_heads)

    def __call__(self, params, x, *, causal: bool = True):
        return _gqa_apply(
            params, x, self.cfg, self.cfg.actual_num_kv_heads, causal=causal
        )


class MultiHeadLatentAttention(AttentionVariant):
    """Low-rank latent q/kv projections (reference mla.py:9,60-66):
    x -> down-project to a small latent -> up-project to per-head q/k/v.
    The KV cache (in inference) stores ONLY the latent: ``init_cache`` /
    ``prefill`` / ``decode`` keep a [B, S_max, kv_rank] buffer and
    re-expand K/V from it per step — per-token cache cost R floats
    instead of 2·H·D (inference/kv_cache.MLACache wraps the buffer).

    The TEACHING variant: no decoupled rotary key, no norm on the
    latent, a dense cache, K/V re-expanded per step; no model's forward
    runs it and the engine never builds its cache. The latent attention
    that is SERVED is models/pangu_ultra_moe.py on
    inference/kv_cache.LatentCache (a paged pool of ``[c | k_r]`` rows,
    read in the absorbed form)."""

    def init(self, key):
        cfg = self.cfg
        d, dh, nh = cfg.embed_dim, cfg.actual_head_dim, cfg.num_heads
        qr = cfg.q_lora_rank or d
        kr = cfg.kv_lora_rank
        ks = jax.random.split(key, 6)
        pd = cfg.dtype
        params: Params = {
            "kv_down": fan_in_uniform(ks[0], (d, kr), d, pd),
            "k_up": fan_in_uniform(ks[1], (kr, nh * dh), kr, pd),
            "v_up": fan_in_uniform(ks[2], (kr, nh * dh), kr, pd),
            "o_proj": fan_in_uniform(ks[3], (nh * dh, d), nh * dh, pd),
        }
        if cfg.q_lora_rank:
            params["q_down"] = fan_in_uniform(ks[4], (d, qr), d, pd)
            params["q_up"] = fan_in_uniform(ks[5], (qr, nh * dh), qr, pd)
        else:
            params["q_proj"] = fan_in_uniform(ks[4], (d, nh * dh), d, pd)
        return params

    def __call__(self, params, x, *, causal: bool = True):
        cfg = self.cfg
        b, s, _ = x.shape
        nh, dh = cfg.num_heads, cfg.actual_head_dim
        if "q_down" in params:
            q = (x @ params["q_down"]) @ params["q_up"]
        else:
            q = x @ params["q_proj"]
        latent = x @ params["kv_down"]  # [B, S, kv_rank] — the cacheable state
        k = latent @ params["k_up"]
        v = latent @ params["v_up"]
        q = q.reshape(b, s, nh, dh).transpose(0, 2, 1, 3)
        k = k.reshape(b, s, nh, dh).transpose(0, 2, 1, 3)
        v = v.reshape(b, s, nh, dh).transpose(0, 2, 1, 3)
        o = sdpa_attention(q, k, v, causal=causal)
        return o.transpose(0, 2, 1, 3).reshape(b, s, nh * dh) @ params["o_proj"]

    # ---- latent-only KV cache (decode engine hook) -----------------------

    def init_cache(self, batch: int, max_seq: int,
                   dtype=None) -> jax.Array:
        """Zeroed latent cache [B, S_max, kv_rank] — the ONLY decode
        state MLA keeps (K/V re-expand from it through k_up/v_up)."""
        return jnp.zeros((batch, max_seq, self.cfg.kv_lora_rank),
                         dtype or self.cfg.dtype)

    def _query(self, params, x):
        if "q_down" in params:
            return (x @ params["q_down"]) @ params["q_up"]
        return x @ params["q_proj"]

    def _attend_cache(self, params, q, latent_cache, q_positions):
        """q: [B, S, nh·dh] flat; latent_cache: [B, S_max, R];
        q_positions: [B, S]. Up-projects the whole cached latent to K/V
        and attends with the j <= p mask."""
        from scaletorch_tpu.models.layers import cached_sdpa_attention

        cfg = self.cfg
        b, s, _ = q.shape
        nh, dh = cfg.num_heads, cfg.actual_head_dim
        k = (latent_cache @ params["k_up"]).reshape(b, -1, nh, dh)
        v = (latent_cache @ params["v_up"]).reshape(b, -1, nh, dh)
        q = q.reshape(b, s, nh, dh).transpose(0, 2, 1, 3)
        o = cached_sdpa_attention(
            q, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), q_positions
        )
        return o.transpose(0, 2, 1, 3).reshape(b, s, nh * dh) @ params["o_proj"]

    def prefill(self, params, x, cache):
        """Full-prompt pass that also fills the latent cache.

        x: [B, P, E]; cache: [B, S_max, R] (zeroed or being reused).
        Returns (out [B, P, E], new_cache) — ``out`` matches
        ``__call__(params, x)`` to float tolerance.
        """
        b, p, _ = x.shape
        latent = x @ params["kv_down"]  # [B, P, R]
        cache = jax.lax.dynamic_update_slice(
            cache, latent.astype(cache.dtype), (0, 0, 0))
        positions = jnp.broadcast_to(jnp.arange(p, dtype=jnp.int32), (b, p))
        return self._attend_cache(
            params, self._query(params, x), cache, positions), cache

    def decode(self, params, x_t, cache, positions):
        """One decode step. x_t: [B, 1, E] (the new token's hidden);
        positions: [B] absolute position per slot. Appends the token's
        latent at ``positions`` and attends the query against the cached
        latents [0, p]. Returns (out [B, 1, E], new_cache)."""
        latent_t = x_t @ params["kv_down"]  # [B, 1, R]

        def write(c, l, p):
            return jax.lax.dynamic_update_slice(c, l, (p, 0))

        cache = jax.vmap(write)(cache, latent_t.astype(cache.dtype),
                                positions.astype(jnp.int32))
        return self._attend_cache(
            params, self._query(params, x_t), cache,
            positions.astype(jnp.int32)[:, None]), cache
