"""Flash attention backend — Pallas blockwise kernel on TPU.

The role the reference fills with flash-attn 2 / Ascend's
``npu_flash_attn_func`` (reference models/attention_utils.py:72-122) is on
TPU a Pallas blockwise-softmax kernel: QK^T tiles stream through VMEM with
running-max/sum accumulation, so the O(S^2) score matrix never
materialises in HBM, and the custom VJP recomputes tiles in the backward.
The kernel lives in scaletorch_tpu/ops/pallas/flash.py (GQA-aware — KV
heads are read unexpanded via index maps); this module is the dispatch
surface, with an XLA softmax fallback on CPU (tests) or when
``SCALETORCH_TPU_DISABLE_PALLAS=1``.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from scaletorch_tpu.env import get_env
from scaletorch_tpu.models.layers import sdpa_attention
from scaletorch_tpu.models.registry import register_attention_backend


def _pallas_available() -> bool:
    """The one kernel-vs-XLA predicate (flash, ring, ulysses, grouped
    MLP, paged decode): Pallas iff the platform is ``tpu``. An error
    from the backend propagates — it must never read as "no TPU" and
    silently select the score-materialising SDPA path."""
    if get_env("SCALETORCH_TPU_DISABLE_PALLAS"):
        return False
    if get_env("SCALETORCH_TPU_FORCE_PALLAS"):  # AOT sessions only (env.py)
        return True
    from scaletorch_tpu.utils.device import is_tpu

    return is_tpu()


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
) -> jax.Array:
    """[B, Hq, S, D] x [B, Hkv, S, D]^2 -> [B, Hq, S, D]."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if _pallas_available():
        from scaletorch_tpu.ops.pallas.flash import pallas_flash_attention

        # each kernel takes its tile sizes from the shapes inside the
        # kernel entry (pallas/flash.py flash_blocks, which
        # SCALETORCH_TPU_FLASH_BLOCK_Q/KV override when set), as on the
        # ring-attention composition path
        return pallas_flash_attention(q, k, v, causal=causal, scale=scale)
    return sdpa_attention(q, k, v, causal=causal, scale=scale)


def prefill_self_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    sink: Optional[jax.Array] = None,
) -> jax.Array:
    """Causal attention of a prompt over itself, forward only: row i
    sees keys j <= i, and with ``window`` only those with ``i - j <
    window``; with ``sink`` [Hq] float32 a logit a query head joins
    every row's softmax and carries no value. [B, Hq, S, D] x [B, Hkv, S, D] x [B, Hkv, S, Dv] -> [B, Hq,
    S, Dv] (the value's width is its own: latent attention's expanded
    heads are 192 / 128). What a
    serving prefill of a sequence that starts at position 0 computes,
    in key blocks (the flash forward: no [S, S] scores in HBM, and the
    blocks a window cannot see are no grid step); off the TPU the plain
    softmax with the band as a bias."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if _pallas_available():
        from scaletorch_tpu.ops.pallas.flash import flash_forward_with_lse

        return flash_forward_with_lse(
            q, k, v, causal=True, scale=scale, window=window, sink=sink)[0]
    bias = None
    if window is not None:
        rows = jnp.arange(q.shape[2])[:, None]
        cols = jnp.arange(k.shape[2])[None, :]
        bias = jnp.where(rows - cols >= window,
                         jnp.finfo(jnp.float32).min, 0.0)
    return sdpa_attention(q, k, v, causal=True, scale=scale, bias=bias,
                          sink=sink)


register_attention_backend("flash", flash_attention)


def flash_attention_jax(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
) -> jax.Array:
    """JAX's reference TPU flash kernel as an alternative backend.

    ``jax.experimental.pallas.ops.tpu.flash_attention`` is the
    public, heavily-tuned Mosaic implementation — registering it as
    ``flash_jax`` gives the benchmark an on-chip A/B partner for the
    in-repo kernel (ops/pallas/flash.py), the same role the reference's
    backend registry plays between its sdpa / flash-attn / npu paths
    (reference models/attention_utils.py:56-70). It predates GQA index
    maps, so grouped K/V heads (layout ``[B, Hkv, S, D]``) are expanded
    to ``[B, Hq, S, D]`` here — post-expansion K/V memory and DMA
    traffic scale with Hq, not Hkv (n_rep x larger: ~0.5 GB at
    0.6B/seq8192 with Hq=14/Hkv=2 bf16). Acceptable for an A/B probe;
    the in-repo kernel's unexpanded Hkv reads stay the default.

    Off-TPU (CPU tests, AOT-less sessions) falls back to SDPA like the
    ``flash`` backend does.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if q.shape[1] % k.shape[1]:
        # mirror the explicit guard the in-repo Pallas entry points raise
        # (pallas/flash.py) — a silent floor-division here would surface
        # as an obscure head-count mismatch inside jax's kernel
        raise ValueError(
            f"flash_attention_jax: query heads {q.shape[1]} must be a "
            f"multiple of key/value heads {k.shape[1]}"
        )
    if _pallas_available():
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            flash_attention as _jax_flash,
        )

        from scaletorch_tpu.models.layers import repeat_kv

        n_rep = q.shape[1] // k.shape[1]
        return _jax_flash(q, repeat_kv(k, n_rep), repeat_kv(v, n_rep),
                          causal=causal, sm_scale=scale)
    return sdpa_attention(q, k, v, causal=causal, scale=scale)


register_attention_backend("flash_jax", flash_attention_jax)
