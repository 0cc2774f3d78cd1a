"""Pallas TPU paged-decode attention + the paged-cache KV primitives.

The paged KV cache (inference/kv_cache.py ``PagedKVCache``) keeps one
global pool of fixed-size pages ``[n_pages, Hkv, page_size, D]`` per
layer; each decode slot owns a per-slot *page table* ``[max_pages]``
int32 mapping logical page ``t // page_size`` to a physical pool page.
Attention reads therefore become gathers over the page table. Two
implementations live here:

  * **Pallas decode kernel** (``pallas_paged_decode_attention``): one
    query token per slot against its paged cache. The grid is ``(B,)``,
    one step per slot; the pools stay in HBM (``memory_space=pl.ANY``)
    and the page table + positions ride the TPU scalar-prefetch path
    (``pltpu.PrefetchScalarGridSpec``). A step walks the slot's *live*
    pages only, a block of ``_pages_per_block`` at a time: one async
    copy per page brings ``[Hkv, page_size, D]`` (all KV heads of a
    page, contiguous in the pool) into a double-buffered VMEM landing
    zone ``[2, Hkv, block, D]`` while the block before it is reduced
    flash-style, so the dense ``[B, Hkv, S_max, D]`` view is never
    materialised in HBM and a page past the slot's length costs
    nothing — no grid step, no DMA (the last block's dead pages
    re-read the last live page and are masked). GQA reads grouped K/V
    unexpanded — one batched dot over the KV heads, the ``n_rep`` query
    heads of a KV head the rows of its ``[n_rep, block]`` score tile.
    ``_pages_per_block`` follows from shapes alone: enough pages for a
    128-lane score tile (8 at page 16), under a fixed VMEM budget.
    Mosaic can slice an HBM ref only along whole 128-lane tiles, so the
    kernel serves a head_dim that is a multiple of 128
    (``kernel_serves``); narrower heads take the fallback. What it
    takes on the chip, and what the one-page-of-one-head grid it
    replaced took, is in PERF.md (PR 25).
  * **Pure-lax fallback** (``paged_gather_kv`` + the models' shared
    ``cached_sdpa_attention``): a whole-table gather that reconstructs
    the dense cache view. This is the off-TPU path and the reference
    the kernel is compared against (tests in interpret mode,
    ``chip_smoke.py`` on the chip) — it performs the same reduction
    the dense engine's attention performs.

``paged_attention`` dispatches between them: the kernel serves
single-token decode when the platform is ``tpu`` and the head_dim fills
the lanes (toggle: ``SCALETORCH_TPU_PAGED_KERNEL``); prefill (S > 1),
narrow heads and other platforms take the gather fallback.

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
# VMEM the kernel may spend on its K/V landing buffers (2 buffers x 2
# pools x one block of pages). A fraction of the 16 MiB scoped default,
# so the score tiles and Mosaic's own temporaries always fit beside it.
_KV_VMEM_BUDGET = 1 << 20
_LANES = 128


def _pages_per_block(page_size: int, hkv: int, d: int, dtype,
                     max_pages: int) -> int:
    """Pages one compute block covers: enough for a score tile whose
    last dimension fills the 128 lanes (8 pages at page 16), capped by
    the VMEM budget of the double-buffered landing zone and by the
    table's length. Shapes in, one integer out — nothing to configure.
    """
    page_bytes = hkv * page_size * d * jnp.dtype(dtype).itemsize
    fill_lanes = -(-_LANES // page_size)
    fit_budget = _KV_VMEM_BUDGET // (4 * page_bytes)
    return max(1, min(fill_lanes, fit_budget, max_pages))


def kernel_serves(head_dim: int) -> bool:
    """Whether Mosaic can compile the kernel for this head_dim: an HBM
    ref is padded to whole 128-lane tiles and may only be sliced along
    them, so a page of a narrower pool cannot be copied on its own."""
    return head_dim % _LANES == 0


def _paged_decode_kernel(pt_ref, pos_ref, q_ref, k_hbm, v_hbm, o_ref,
                         k_buf, v_buf, sems, *, scale, page_size,
                         pages_per_block, max_pages):
    b = pl.program_id(0)   # slot
    bk = pages_per_block * page_size
    pos = pos_ref[b]
    # live pages of this slot (0 for a negative position, never past the
    # table) and the blocks that hold them: a dead block costs nothing
    n_live = jnp.clip(pos // page_size + 1, 0, max_pages)
    n_blocks = (n_live + pages_per_block - 1) // pages_per_block

    def block_copies(i, buf):
        """One async copy per page of block ``i`` and pool: page
        ``[Hkv, page_size, D]`` (contiguous in HBM) into its rows of
        landing buffer ``buf``. Pages of the last block past the live
        length re-read the last live page: what reaches VMEM is always
        this slot's own data, never TRASH or an unallocated page."""
        out = []
        for p in range(pages_per_block):
            j = jnp.minimum(i * pages_per_block + p, n_live - 1)
            page = pt_ref[b * max_pages + j]
            rows = pl.ds(p * page_size, page_size)
            out.append(pltpu.make_async_copy(
                k_hbm.at[page], k_buf.at[buf, :, rows, :], sems.at[0, buf]))
            out.append(pltpu.make_async_copy(
                v_hbm.at[page], v_buf.at[buf, :, rows, :], sems.at[1, buf]))
        return out

    @pl.when(n_blocks > 0)
    def _first():
        for c in block_copies(0, 0):
            c.start()

    q = q_ref[0]   # [Hkv, n_rep, D]
    hkv, nrep, d = q.shape
    key_in_block = jax.lax.broadcasted_iota(jnp.int32, (hkv, nrep, bk), 2)

    def block(i, carry):
        m_prev, l_prev, acc = carry
        buf = i % 2

        @pl.when(i + 1 < n_blocks)
        def _next():
            for c in block_copies(i + 1, 1 - buf):
                c.start()

        for c in block_copies(i, buf):
            c.wait()
        k = k_buf[buf]   # [Hkv, bk, D]
        v = v_buf[buf]
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale  # [Hkv, n_rep, bk]
        # causal-over-the-cache mask at logical positions: key o of
        # block i sits at absolute position i*bk + o
        s = jnp.where(i * bk + key_in_block <= pos, s, _NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc

    _, l, acc = jax.lax.fori_loop(0, n_blocks, block, (
        jnp.full((hkv, nrep, 1), _NEG_INF, jnp.float32),
        jnp.zeros((hkv, nrep, 1), jnp.float32),
        jnp.zeros((hkv, nrep, d), jnp.float32),
    ))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


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

    The pools stay in HBM; the page table and positions are
    scalar-prefetched, and each slot's step copies its live pages, a
    block of ``_pages_per_block`` at a time and all KV heads of a page
    at once, into a double-buffered VMEM landing zone while the block
    before it is reduced flash-style.
    """
    b, hq, d = q.shape
    n_pages, hkv, page_size, _ = pool_k.shape
    if hq % hkv:
        raise ValueError(f"query heads {hq} not a multiple of kv heads {hkv}")
    n_rep = hq // hkv
    max_pages = page_tables.shape[1]
    if not interpret and not kernel_serves(d):
        raise ValueError(
            f"the paged-decode kernel copies whole pages out of HBM, which "
            f"Mosaic allows only for a head_dim that fills the {_LANES} "
            f"lanes; got {d} (paged_attention() sends it to the gather "
            f"fallback)")
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    ppb = _pages_per_block(page_size, hkv, d, pool_k.dtype, max_pages)

    def q_idx(b_, pt_ref, pos_ref):
        return (b_, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, hkv, n_rep, d), q_idx),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, hkv, n_rep, d), q_idx),
        scratch_shapes=[
            pltpu.VMEM((2, hkv, ppb * page_size, d), pool_k.dtype),
            pltpu.VMEM((2, hkv, ppb * page_size, d), pool_v.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, scale=scale,
                          page_size=page_size, pages_per_block=ppb,
                          max_pages=max_pages),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, n_rep, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL,)),  # slots share no state
        interpret=interpret,
        name="paged_decode",
    )(page_tables.astype(jnp.int32).reshape(-1),
      positions.astype(jnp.int32), q.reshape(b, hkv, n_rep, d),
      pool_k, pool_v)
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
    ``SCALETORCH_TPU_PAGED_KERNEL`` gates it) and ``kernel_serves`` the
    head_dim, the lax gather + ``cached_sdpa_attention`` everywhere
    else — other platforms, narrow heads and prefill. ``seq_limit``
    crops the gathered view to the engine's ``max_seq`` so the
    fallback's reduction has the dense layout's operand shapes.
    """
    from scaletorch_tpu.models.layers import cached_sdpa_attention

    s = q.shape[2]
    use_kernel = kernel
    if use_kernel is None:
        from scaletorch_tpu.env import get_env
        from scaletorch_tpu.ops.flash_attention import _pallas_available

        use_kernel = (
            s == 1
            and kernel_serves(q.shape[3])
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
