"""Pallas TPU paged-decode attention + the paged-cache KV primitives.

The paged KV cache (inference/kv_cache.py ``PagedKVCache``) keeps one
global pool of fixed-size pages ``[n_pages, Hkv, page_size, D]`` per
layer; each decode slot owns a per-slot *page table* ``[max_pages]``
int32 mapping logical page ``t // page_size`` to a physical pool page.
Attention reads therefore become gathers over the page table. Two
implementations live here:

  * **Pallas decode kernel** (``pallas_paged_decode_attention``): one
    query token per slot against its paged cache. The grid is
    ``(B, Hkv, max_pages)`` and the page table + positions ride the
    TPU scalar-prefetch path (``pltpu.PrefetchScalarGridSpec``), so the
    K/V *index maps themselves* chase the page table: page ``j``'s
    physical block is DMA'd HBM→VMEM directly — the gathered reads stay
    in VMEM and the dense ``[B, Hkv, S_max, D]`` view is never
    materialised in HBM. Pages past the slot's live length are skipped
    flash-style: compute predicated off with ``pl.when`` and the index
    map clamped to an already-resident page so no DMA is issued
    (the causal block-skip idiom from ops/pallas/flash.py). GQA reads
    grouped K/V unexpanded — the ``n_rep`` query heads of one KV head
    are the rows of a single ``[n_rep, page_size]`` score tile.
  * **Pure-lax fallback** (``paged_gather_kv`` + the models' shared
    ``cached_sdpa_attention``): a whole-table gather that reconstructs
    the dense cache view. This is the off-TPU path and the reference
    the kernel is compared against (tests in interpret mode,
    ``chip_smoke.py`` on the chip) — it performs the same reduction
    the dense engine's attention performs.

``paged_attention`` dispatches between them: the kernel serves
single-token decode when the platform is ``tpu`` (toggle:
``SCALETORCH_TPU_PAGED_KERNEL``); prefill (S > 1) and other platforms
take the gather fallback.

Writes (``paged_write_kv``) are a batched scatter: token at absolute
position ``t`` lands at ``(table[b, t // page_size], t % page_size)``.
Masked-off slots and positions beyond the table are redirected to the
reserved TRASH page (page 0 — never allocated, read only through masked
attention lanes), which keeps the write unconditional — data changes,
shapes never do, so the engine's one-compile discipline survives
admissions, prefix hits, and frees.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Page 0 is reserved: never allocated, present in page tables only as
# the sentinel for "no page here" (table padding, masked-off writes).
# Reads of it only ever flow through attention lanes the j <= p mask has
# already zeroed.
TRASH_PAGE = 0

_NEG_INF = -1e30  # large-negative, not -inf: keeps masked rows NaN-free


def _semantics(*dims):
    """Mosaic grid dimension semantics ('p' parallel / 'a' arbitrary) —
    see ops/pallas/flash.py."""
    m = {"p": pltpu.PARALLEL, "a": pltpu.ARBITRARY}
    return pltpu.CompilerParams(
        dimension_semantics=tuple(m[d] for d in dims))


# ---------------------------------------------------------------------------
# paged cache primitives (pure lax — shared by fallback and engine steps)
# ---------------------------------------------------------------------------
def paged_gather_kv(pool: jax.Array, page_tables: jax.Array) -> jax.Array:
    """Reconstruct the dense cache view from the page pool.

    pool: [n_pages, Hkv, page_size, D]; page_tables: [B, max_pages]
    -> [B, Hkv, max_pages * page_size, D], logical position ``t`` of slot
    ``b`` at sequence index ``t`` exactly as the dense layout stores it.
    """
    view = pool[page_tables]  # [B, max_pages, Hkv, page_size, D]
    b, mp, h, p, d = view.shape
    return view.transpose(0, 2, 1, 3, 4).reshape(b, h, mp * p, d)


def paged_write_kv(
    pool: jax.Array,
    new: jax.Array,
    positions: jax.Array,
    page_tables: jax.Array,
    page_size: int,
    write_mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Scatter ``new`` [B, H, S, D] into ``pool`` [n_pages, H, page_size,
    D] at per-token absolute ``positions`` [B, S] through ``page_tables``
    [B, max_pages]. ``write_mask`` [B] bool redirects unlisted slots'
    writes to the TRASH page (their own pages stay byte-identical —
    continuous batching admits new requests without perturbing live
    ones); positions past the table's reach go to TRASH too.
    """
    max_pages = page_tables.shape[1]
    logical = positions // page_size                       # [B, S]
    offsets = positions % page_size
    valid = logical < max_pages
    pages = jnp.take_along_axis(
        page_tables, jnp.minimum(logical, max_pages - 1), axis=1)
    if write_mask is not None:
        valid = valid & write_mask[:, None]
    pages = jnp.where(valid, pages, TRASH_PAGE)
    vals = new.astype(pool.dtype).transpose(0, 2, 1, 3)    # [B, S, H, D]
    return pool.at[pages, :, offsets, :].set(vals)


# ---------------------------------------------------------------------------
# the decode kernel
# ---------------------------------------------------------------------------
def _paged_decode_kernel(pt_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                         acc_sc, m_sc, l_sc, *, scale, page_size):
    b = pl.program_id(0)   # slot
    j = pl.program_id(2)   # logical page
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc)
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    # pages past the slot's live length carry no visible keys: skip their
    # compute; their DMA was already clamped to a resident page.
    n_live = pos_ref[b] // page_size + 1

    @pl.when(j < n_live)
    def _page():
        q = q_ref[0, 0]   # [n_rep, D]
        k = k_ref[0, 0]   # [page_size, D]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [n_rep, page_size]
        # causal-over-the-cache mask at logical positions: key o of
        # logical page j sits at absolute position j*page_size + o
        nrep = q.shape[0]
        key_pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (nrep, page_size), 1)
        s = jnp.where(key_pos <= pos_ref[b], s, _NEG_INF)
        m_prev, l_prev = m_sc[:], l_sc[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_sc[:] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        m_sc[:] = m_new
        acc_sc[:] = acc_sc[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == nj - 1)
    def _finalize():
        l = jnp.maximum(l_sc[:], 1e-30)
        o_ref[0, 0] = (acc_sc[:] / l).astype(o_ref.dtype)


def pallas_paged_decode_attention(
    q: jax.Array,
    pool_k: jax.Array,
    pool_v: jax.Array,
    page_tables: jax.Array,
    positions: jax.Array,
    *,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    """One-token paged attention: q [B, Hq, D] against the page pool.

    pool_k/pool_v: [n_pages, Hkv, page_size, D]; page_tables:
    [B, max_pages] int32; positions: [B] int32 absolute position of the
    query token (attends keys j <= position). Returns [B, Hq, D].

    The page table and positions are scalar-prefetched so the K/V block
    index maps resolve physical pages before each grid step's DMA; only
    live pages are fetched, and the per-page flash accumulation keeps
    everything after the HBM page read in VMEM.
    """
    b, hq, d = q.shape
    n_pages, hkv, page_size, _ = pool_k.shape
    if hq % hkv:
        raise ValueError(f"query heads {hq} not a multiple of kv heads {hkv}")
    n_rep = hq // hkv
    max_pages = page_tables.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(d)

    q_r = q.reshape(b, hkv, n_rep, d)

    def q_idx(b_, h, j, pt_ref, pos_ref):
        return (b_, h, 0, 0)

    def kv_idx(b_, h, j, pt_ref, pos_ref):
        # clamp dead pages to the last live one (already resident — no
        # DMA is spent on pages the mask would zero anyway)
        n_live = pos_ref[b_] // page_size + 1
        return (pt_ref[b_, jnp.minimum(j, n_live - 1)], h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, max_pages),
        in_specs=[
            pl.BlockSpec((1, 1, n_rep, d), q_idx),
            pl.BlockSpec((1, 1, page_size, d), kv_idx),
            pl.BlockSpec((1, 1, page_size, d), kv_idx),
        ],
        out_specs=pl.BlockSpec((1, 1, n_rep, d), q_idx),
        scratch_shapes=[
            pltpu.VMEM((n_rep, d), jnp.float32),
            pltpu.VMEM((n_rep, 1), jnp.float32),
            pltpu.VMEM((n_rep, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, scale=scale,
                          page_size=page_size),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, n_rep, d), q.dtype),
        compiler_params=_semantics("p", "p", "a"),
        interpret=interpret,
        name="paged_decode",
    )(page_tables.astype(jnp.int32), positions.astype(jnp.int32),
      q_r, pool_k, pool_v)
    return out.reshape(b, hq, d)


# ---------------------------------------------------------------------------
# dispatcher
# ---------------------------------------------------------------------------
def paged_attention(
    q: jax.Array,
    pool_k: jax.Array,
    pool_v: jax.Array,
    page_tables: jax.Array,
    q_positions: jax.Array,
    *,
    page_size: int,
    seq_limit: Optional[int] = None,
    scale: Optional[float] = None,
    kernel: Optional[bool] = None,
    interpret: bool = False,
) -> jax.Array:
    """Attention against the paged cache, kernel or fallback.

    q: [B, Hq, S, D] (S = tail length at prefill, 1 at decode);
    q_positions: [B, S] absolute positions. ``kernel=None`` auto-selects:
    the Pallas kernel for single-token decode when the platform is
    ``tpu`` (the same predicate the flash backend uses;
    ``SCALETORCH_TPU_PAGED_KERNEL`` gates it), the lax gather +
    ``cached_sdpa_attention`` everywhere else — other platforms and
    prefill. ``seq_limit`` crops the gathered view to the engine's
    ``max_seq`` so the fallback's reduction has the dense layout's
    operand shapes.
    """
    from scaletorch_tpu.models.layers import cached_sdpa_attention

    s = q.shape[2]
    use_kernel = kernel
    if use_kernel is None:
        from scaletorch_tpu.env import get_env
        from scaletorch_tpu.ops.flash_attention import _pallas_available

        use_kernel = (
            s == 1
            and _pallas_available()
            and bool(get_env("SCALETORCH_TPU_PAGED_KERNEL"))
        )
    if use_kernel:
        if s != 1:
            raise ValueError(
                f"the paged-decode kernel serves single-token queries; "
                f"got S={s} (prefill goes through the gather fallback)"
            )
        out = pallas_paged_decode_attention(
            q[:, :, 0, :], pool_k, pool_v, page_tables, q_positions[:, 0],
            scale=scale, interpret=interpret,
        )
        return out[:, :, None, :]
    k = paged_gather_kv(pool_k, page_tables)
    v = paged_gather_kv(pool_v, page_tables)
    if seq_limit is not None and k.shape[2] > seq_limit:
        k = k[:, :, :seq_limit, :]
        v = v[:, :, :seq_limit, :]
    return cached_sdpa_attention(q, k, v, q_positions, scale=scale)
