"""Pallas TPU kernels for the paged KV cache + the lax pair they replace.

The paged KV cache (inference/kv_cache.py ``PagedKVCache``) keeps one
global pool of fixed-size pages per K and V, all layers in one array
``[L, n_pages, Hkv, page_size, D]``; each decode slot owns a per-slot
*page table* ``[max_pages]`` int32 mapping logical page
``t // page_size`` to a physical pool page. The models' cache-aware
forwards carry the pool WHOLE through their layer loop
(``llama.scan_layers_cached``) and touch it at a layer index through
one of two pairs of a write and a read:

  * **The Mosaic pair** (``pallas_paged_write`` +
    ``pallas_paged_decode_attention``). The pool is never an operand
    XLA may slice, re-lay or re-stack: both calls take it in HBM
    (``memory_space=pl.ANY``) with the layer, the pages and the
    positions on the TPU scalar-prefetch path
    (``pltpu.PrefetchScalarGridSpec``).
    The write aliases the pool to its only result and moves nothing
    but the pages it writes: at decode (one row a slot) a chunk of
    slots' pages come to VMEM, take their row and go back; at prefill
    (a run of rows from a page boundary) whole pages go from the
    blocked operand straight to their place. It serves both step
    programs, because a pool that XLA writes anywhere is given the
    layout XLA's scatter likes and converted back, whole, for every
    Mosaic read (PERF.md, PR 28).
    The decode kernel reads one query token per slot against its paged
    cache. The grid is ``(B,)``, one step per slot, in order. A step
    walks the slot's *live* pages only, a block of ``_pages_per_block``
    at a time: one async copy per page brings ``pool.at[layer, page]``,
    ``[Hkv, page_size, D]`` (all KV heads of a page, contiguous in the
    pool), into a double-buffered VMEM landing zone
    ``[2, Hkv, block, D]`` while the block before it is reduced
    flash-style, so the dense ``[B, Hkv, S_max, D]`` view is never
    materialised in HBM and a page past the slot's length costs
    nothing — no grid step, no DMA (the last block's dead pages
    re-read the last live page and are masked). **The copy pipeline
    does not stop at a slot's end**: while a slot's LAST block is
    waited for and reduced, the copies of block 0 of the next slot that
    has a live page (a slot at a negative position is looked past) are
    already on their way into the other landing buffer, and that
    slot's step finds them in flight; only the first walked slot of a
    call starts its own first block in the open. What a slot hands the
    next one, the buffer its walk starts on and "your first block is in
    flight", lives in two SMEM words of scratch; the next slot's walk
    comes from the positions and tables the kernel already holds, so
    full layers and ``window`` layers chain alike. On the v5e, alone, a
    live slot costs nothing over its blocks this way (0.75 us before:
    PERF.md, PR 55). ``chained_first_blocks`` is the same rule on the
    host, for the engine's counters. GQA reads grouped K/V
    unexpanded — one batched dot over the KV heads, the ``n_rep`` query
    heads of a KV head the rows of its ``[n_rep, block]`` score tile.
    ``_pages_per_block`` follows from shapes alone: enough pages for a
    128-lane score tile (8 at page 16), under a fixed VMEM budget.
    Mosaic can slice an HBM ref only along whole 128-lane tiles, so the
    pair serves a head_dim that is a multiple of 128
    (``kernel_serves``); narrower heads take the lax pair. What the
    kernel takes on the chip, and what the one-page-of-one-head grid it
    replaced took, is in PERF.md (PRs 25 and 55).
  * **The lax pair** (``paged_write_kv`` + ``paged_gather_kv`` and the
    models' shared ``cached_sdpa_attention``): a batched scatter at
    ``pool.at[layer, pages, :, offsets, :]`` and a whole-table gather
    ``pool[layer, page_tables]`` that reconstructs the contiguous
    view. This is the off-TPU path and the reference the kernels are
    compared against (tests in interpret mode, ``chip_smoke.py`` on
    the chip) — it performs the same reduction the contiguous
    reference cache's attention performs. A multi-row call (S > 1) that
    reads the pool reads through the gather on every platform.

``paged_write`` and ``paged_attention`` dispatch between them on ONE
predicate, ``in_place_pair``: the Mosaic pair when the platform is
``tpu`` and the head_dim fills the lanes (toggle:
``SCALETORCH_TPU_PAGED_KERNEL``); the lax pair for narrow heads and on
other platforms. The engine's snapshot says which
(``paged_pool_in_place``).

A write puts the token at absolute position ``t`` at
``(table[b, t // page_size], t % page_size)``. Masked-off slots and
positions beyond the table are redirected to the reserved TRASH page
(page 0 — never allocated, read only through masked attention lanes),
which keeps the write unconditional — data changes, shapes never do, so
the engine's one-compile discipline survives admissions, prefix hits,
and frees.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Page 0 is reserved: never allocated, present in page tables only as
# the sentinel for "no page here" (table padding, masked-off writes).
# Reads of it only ever flow through attention lanes the j <= p mask has
# already zeroed.
TRASH_PAGE = 0

_NEG_INF = -1e30  # large-negative, not -inf: keeps masked rows NaN-free


# ---------------------------------------------------------------------------
# paged cache primitives (pure lax — the fallback pair and its oracle)
# ---------------------------------------------------------------------------
def paged_gather_kv(pool: jax.Array, page_tables: jax.Array,
                    layer: Optional[jax.Array] = None) -> jax.Array:
    """Reconstruct the dense cache view from the page pool.

    pool: [n_pages, Hkv, page_size, D], or the whole [L, n_pages, Hkv,
    page_size, D] pool with the ``layer`` to read (one gather of whole
    pages, the layer never sliced out); page_tables: [B, max_pages]
    -> [B, Hkv, max_pages * page_size, D], logical position ``t`` of slot
    ``b`` at sequence index ``t`` exactly as the contiguous reference
    cache stores it.
    """
    view = pool[page_tables] if layer is None else pool[layer, page_tables]
    b, mp, h, p, d = view.shape  # [B, max_pages, Hkv, page_size, D]
    return view.transpose(0, 2, 1, 3, 4).reshape(b, h, mp * p, d)


def _write_targets(positions, page_tables, page_size, write_mask):
    """(page, offset) each of ``positions`` [B, S] lands on: the slot's
    own page through its table, TRASH for a masked-off slot or a position
    past the table's reach."""
    max_pages = page_tables.shape[1]
    logical = positions // page_size
    valid = logical < max_pages
    pages = jnp.take_along_axis(
        page_tables, jnp.minimum(logical, max_pages - 1), axis=1)
    if write_mask is not None:
        valid = valid & write_mask[:, None]
    return jnp.where(valid, pages, TRASH_PAGE), positions % page_size


def paged_write_kv(
    pool: jax.Array,
    new: jax.Array,
    positions: jax.Array,
    page_tables: jax.Array,
    page_size: int,
    write_mask: Optional[jax.Array] = None,
    layer: Optional[jax.Array] = None,
) -> jax.Array:
    """Scatter ``new`` [B, H, S, D] into ``pool`` [n_pages, H, page_size,
    D] (or into ``layer`` of the whole [L, n_pages, H, page_size, D]
    pool) at per-token absolute ``positions`` [B, S] through
    ``page_tables`` [B, max_pages]. ``write_mask`` [B] bool redirects
    unlisted slots' writes to the TRASH page (their own pages stay
    byte-identical — continuous batching admits new requests without
    perturbing live ones); positions past the table's reach go to TRASH
    too.
    """
    pages, offsets = _write_targets(
        positions, page_tables, page_size, write_mask)
    vals = new.astype(pool.dtype).transpose(0, 2, 1, 3)    # [B, S, H, D]
    if layer is None:
        return pool.at[pages, :, offsets, :].set(vals)
    return pool.at[layer, pages, :, offsets, :].set(vals)


# ---------------------------------------------------------------------------
# the page-write kernel
# ---------------------------------------------------------------------------
# VMEM a kernel may spend on pages in flight (the write's landing zone,
# the decode kernel's double-buffered K/V blocks). A fraction of the
# 16 MiB scoped default, so the blocked operands, the score tiles and
# Mosaic's own temporaries always fit beside it.
_KV_VMEM_BUDGET = 1 << 20
_LANES = 128


def kernel_serves(head_dim: int) -> bool:
    """Whether Mosaic can compile the kernels for this head_dim: an HBM
    ref is padded to whole 128-lane tiles and may only be sliced along
    them, so a page of a narrower pool cannot be copied on its own."""
    return head_dim % _LANES == 0


def in_place_pair(head_dim: int) -> bool:
    """Which pair touches the pool: the Mosaic pair (``paged_write`` +
    the decode kernel at a layer index) when the platform is ``tpu`` (the
    repo's one kernel-vs-XLA predicate; ``SCALETORCH_TPU_PAGED_KERNEL``
    gates it) and ``kernel_serves`` the head_dim, the lax pair (scatter
    + gather) everywhere else. One predicate for the write and the read:
    a pool that XLA writes and Mosaic reads is re-laid whole between the
    two, every layer (PERF.md, PR 28)."""
    from scaletorch_tpu.env import get_env
    from scaletorch_tpu.ops.flash_attention import _pallas_available

    return (kernel_serves(head_dim) and _pallas_available()
            and bool(get_env("SCALETORCH_TPU_PAGED_KERNEL")))


def _slots_per_step(n_slots: int, page_bytes: int) -> int:
    """Slots whose pages one step of the row write holds in VMEM at once:
    the largest divisor of ``n_slots`` that fits the budget."""
    cap = max(1, min(n_slots, _KV_VMEM_BUDGET // page_bytes))
    return max(c for c in range(1, cap + 1) if n_slots % c == 0)


def _row_in_page(shape):
    return jax.lax.broadcasted_iota(jnp.int32, shape, 1)


def _paged_write_row_kernel(pages_ref, offs_ref, layer_ref, new_ref, _pool,
                            pool_ref, buf, sems, *, chunk):
    """One token per slot (decode): bring each slot's page to VMEM, put
    its row in, send the page back; a chunk of slots in flight at once.
    Masked slots all name TRASH and race on it: garbage by contract."""
    first = pl.program_id(0) * chunk
    layer = layer_ref[0]

    def page_copy(i, back):
        hbm = pool_ref.at[layer, pages_ref[first + i]]
        src, dst = (buf.at[i], hbm) if back else (hbm, buf.at[i])
        return pltpu.make_async_copy(src, dst, sems.at[int(back)])

    for i in range(chunk):
        page_copy(i, False).start()
    for i in range(chunk):   # one semaphore: all in before any is touched
        page_copy(i, False).wait()
    row = _row_in_page(buf.shape[1:])
    for i in range(chunk):
        buf[i] = jnp.where(row == offs_ref[first + i], new_ref[i], buf[i])
        page_copy(i, True).start()
    for i in range(chunk):
        page_copy(i, True).wait()


def _paged_write_pages_kernel(pages_ref, layer_ref, new_ref, _pool, pool_ref,
                              buf, sems, *, page_size, pages_per_block,
                              n_rows):
    """A run of rows per slot that starts on a page boundary (prefill):
    every whole page of this block of rows goes from the blocked operand
    straight to its place; the run's last, partly filled page is read,
    merged and written back."""
    b, j = pl.program_id(0), pl.program_id(1)
    layer = layer_ref[0]
    n_whole, rest = divmod(n_rows, page_size)
    slot_pages = n_whole + bool(rest)

    def rows_and_page(p):
        """Page ``p`` of this block: its rows of the operand, its place."""
        g = jnp.minimum(j * pages_per_block + p, slot_pages - 1)
        return (new_ref.at[0, :, pl.ds(p * page_size, page_size), :],
                pool_ref.at[layer, pages_ref[b * slot_pages + g]])

    whole = [(j * pages_per_block + p < n_whole,
              pltpu.make_async_copy(*rows_and_page(p), sems.at[0]))
             for p in range(pages_per_block)]
    for live, copy in whole:
        pl.when(live)(copy.start)
    if rest:
        # the partly filled page: a fixed page of one block of the run
        rows, hbm = rows_and_page(n_whole % pages_per_block)

        @pl.when(j == n_whole // pages_per_block)
        def _merge():
            fetch = pltpu.make_async_copy(hbm, buf, sems.at[1])
            fetch.start()
            fetch.wait()
            buf[...] = jnp.where(
                _row_in_page(buf.shape) < rest, rows[...], buf[...])
            store = pltpu.make_async_copy(buf, hbm, sems.at[1])
            store.start()
            store.wait()
    for live, copy in whole:
        pl.when(live)(copy.wait)


def pallas_paged_write(
    pool: jax.Array,
    new: jax.Array,
    positions: jax.Array,
    page_tables: jax.Array,
    write_mask: Optional[jax.Array] = None,
    *,
    layer: jax.Array,
    interpret: bool = False,
) -> jax.Array:
    """``paged_write_kv`` into ``layer`` of the whole pool, in place.

    pool: [L, n_pages, Hkv, page_size, D], aliased to the result and left
    in HBM (``pl.ANY``): nothing but the pages written moves, and XLA
    never sees an operation on the pool whose layout it could choose.
    new: [B, Hkv, S, D]; positions: [B, S], contiguous per slot. S = 1
    (decode) writes one row of one page per slot at any offset; S > 1
    (prefill) needs each slot's first position on a page boundary, which
    the engine's page-aligned ``starts`` give. Pages and offsets are
    computed as ``paged_write_kv`` computes them and scalar-prefetched
    with the layer. Every page but TRASH ends bit-identical to the
    scatter's; TRASH holds some writer's rows.
    """
    _, _, hkv, page_size, d = pool.shape
    b, _, s, _ = new.shape
    if not interpret and not kernel_serves(d):
        raise ValueError(
            f"the page write copies whole pages in and out of HBM, which "
            f"Mosaic allows only for a head_dim that fills the {_LANES} "
            f"lanes; got {d} (paged_write() sends it to the scatter)")
    page_bytes = hkv * page_size * d * jnp.dtype(pool.dtype).itemsize
    new = new.astype(pool.dtype)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    if s == 1:
        pages, offsets = _write_targets(
            positions, page_tables, page_size, write_mask)
        chunk = _slots_per_step(b, page_bytes)
        scalars = (pages.reshape(-1), offsets.reshape(-1), layer)
        kernel = functools.partial(_paged_write_row_kernel, chunk=chunk)
        grid = (b // chunk,)
        new_spec = pl.BlockSpec(
            (chunk, hkv, 1, d), lambda c, *_: (c, 0, 0, 0))
        scratch = [pltpu.VMEM((chunk, hkv, page_size, d), pool.dtype)]
    else:
        pages, _ = _write_targets(
            positions[:, ::page_size], page_tables, page_size, write_mask)
        ppb = max(1, min(_KV_VMEM_BUDGET // (2 * page_bytes),
                         pages.shape[1]))
        scalars = (pages.reshape(-1), layer)
        kernel = functools.partial(
            _paged_write_pages_kernel, page_size=page_size,
            pages_per_block=ppb, n_rows=s)
        grid = (b, -(-pages.shape[1] // ppb))
        new_spec = pl.BlockSpec(
            (1, hkv, ppb * page_size, d), lambda b_, j, *_: (b_, 0, j, 0))
        scratch = [pltpu.VMEM((hkv, page_size, d), pool.dtype)]
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=grid,
            in_specs=[new_spec, any_space],
            out_specs=any_space,
            scratch_shapes=scratch + [pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={len(scalars) + 1: 0},
        compiler_params=pltpu.CompilerParams(
            # sequential: masked slots share TRASH
            dimension_semantics=(pltpu.ARBITRARY,) * len(grid)),
        interpret=interpret,
        name="paged_write",
    )(*scalars, new, pool)


# ---------------------------------------------------------------------------
# the decode kernel
# ---------------------------------------------------------------------------
def _pages_per_block(page_size: int, hkv: int, d: int, dtype,
                     max_pages: int, d_v: Optional[int] = None) -> int:
    """Pages one compute block covers: enough for a score tile whose
    last dimension fills the 128 lanes (8 pages at page 16), capped by
    the VMEM budget of the double-buffered landing zone (2 buffers x 2
    pools x one block of pages; ``d_v``: the V pool's width where it is
    not the K pool's ``d``) and by the table's length. Shapes in, one
    integer out — nothing to configure.
    """
    row_bytes = hkv * page_size * jnp.dtype(dtype).itemsize
    page_bytes = row_bytes * (d + (d if d_v is None else d_v))
    fill_lanes = -(-_LANES // page_size)
    fit_budget = _KV_VMEM_BUDGET // (2 * page_bytes)
    return max(1, min(fill_lanes, fit_budget, max_pages))


def _slot_walk(pos, page_size, max_pages, window, xp=jnp):
    """(first logical page, live pages) of the walk of a slot whose
    query sits at ``pos``: no page for a negative position, never past
    the table; under a ``window`` the walk starts at the page that holds
    the window's first key. One rule for the kernel (traced scalars) and
    for the host's count of what it does (``xp=np``)."""
    n_live = xp.clip(pos // page_size + 1, 0, max_pages)
    if window is None:
        return 0, n_live
    first = xp.maximum(pos - window + 1, 0) // page_size
    return first, xp.maximum(n_live - first, 0)


def _next_live_slot(pos_ref, b, n_slots, live_pages):
    """The first slot after ``b`` with a page to walk (``live_pages`` of
    its position over 0), ``n_slots`` where there is none: dead slots
    are looked past. ``pos_ref`` is anything ``[slot]`` reads a
    position from (the kernel's SMEM operand, an array in the tests)."""
    def dead(s):
        there = jnp.minimum(s, n_slots - 1)
        return (s < n_slots) & (live_pages(pos_ref[there]) <= 0)

    return jax.lax.while_loop(dead, lambda s: s + 1, b + 1)


def chained_first_blocks(positions, page_size: int, max_pages: int,
                         window: Optional[int] = None) -> Tuple[int, int]:
    """What one call of the decode kernel does with these ``positions``
    [slots], on the host (numpy): (slots walked, slots whose first block
    the slot before them started). A slot is walked where ``_slot_walk``
    gives it a live page; every walked slot starts the first block of
    the next one, so all but the first are chained."""
    _, n_live = _slot_walk(np.asarray(positions), page_size, max_pages,
                           window, xp=np)
    walked = int(np.count_nonzero(n_live > 0))
    return walked, max(walked - 1, 0)


def _paged_decode_kernel(pt_ref, pos_ref, layer_ref, q_ref, *refs, scale,
                         page_size, pages_per_block, max_pages, window=None,
                         sink=False):
    """``refs``: with ``sink`` the per-head sink logits ``[Hkv, n_rep,
    1]`` float32 come first (a column of the softmax that carries no
    value: the running maximum starts at the sink and the denominator at
    1, where without one they start at -inf and 0); then the K and V
    pools, the output and the scratch. The value's width is ``v_buf``'s
    and the output's, the key's ``q``'s and ``k_buf``'s."""
    sink_ref, refs = (refs[0], refs[1:]) if sink else (None, refs)
    k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, chain = refs
    b = pl.program_id(0)   # slot
    n_slots = pl.num_programs(0)
    layer = layer_ref[0]
    bk = pages_per_block * page_size

    def walk(pos):
        """(first page, live pages, blocks that hold them): a dead
        block costs nothing"""
        first, n_live = _slot_walk(pos, page_size, max_pages, window)
        return first, n_live, (n_live + pages_per_block - 1) // pages_per_block

    pos = pos_ref[b]
    first, n_live, n_blocks = walk(pos)
    # the slot whose first block this one starts while its own last
    # block is on its way: the next with a live page (none: n_slots; a
    # dead slot starts nothing, so its search begins past the last slot)
    nxt = _next_live_slot(pos_ref, jnp.where(n_blocks > 0, b, n_slots - 1),
                          n_slots, lambda p: walk(p)[1])
    nxt_first, nxt_live, _ = walk(pos_ref[jnp.minimum(nxt, n_slots - 1)])

    # what a walked slot hands the next one (the grid is sequential):
    # the landing buffer its walk starts on, and whether the copies of
    # its first block are already in flight there
    @pl.when(b == 0)
    def _cold():
        chain[0] = 0
        chain[1] = 0
    buf0 = chain[0]

    def start_block(slot, first, n_live, i, buf):
        """Start one async copy per page of block ``i`` of ``slot`` and
        pool: page ``[Hkv, page_size, D]`` (contiguous in HBM) into its
        rows of landing buffer ``buf``. Pages of the last block past the
        live length re-read the last live page: what reaches VMEM is
        always the slot's own data, never TRASH or an unallocated page."""
        for p in range(pages_per_block):
            j = first + jnp.minimum(i * pages_per_block + p, n_live - 1)
            page = pt_ref[slot * max_pages + j]
            rows = pl.ds(p * page_size, page_size)
            pltpu.make_async_copy(
                k_hbm.at[layer, page], k_buf.at[buf, :, rows, :],
                sems.at[0, buf]).start()
            pltpu.make_async_copy(
                v_hbm.at[layer, page], v_buf.at[buf, :, rows, :],
                sems.at[1, buf]).start()

    def wait_block(buf):
        """Wait for the copies of the block in ``buf``, whoever started
        them (a wait reads its semaphore and the copy's size, never its
        source: the table is looked up once a page)."""
        for p in range(pages_per_block):
            rows = pl.ds(p * page_size, page_size)
            pltpu.make_async_copy(
                k_hbm.at[layer, TRASH_PAGE], k_buf.at[buf, :, rows, :],
                sems.at[0, buf]).wait()
            pltpu.make_async_copy(
                v_hbm.at[layer, TRASH_PAGE], v_buf.at[buf, :, rows, :],
                sems.at[1, buf]).wait()

    @pl.when((n_blocks > 0) & (chain[1] == 0))
    def _first():   # slot 0, or nobody walked before this slot
        start_block(b, first, n_live, 0, buf0)

    q = q_ref[0]   # [Hkv, n_rep, D]
    hkv, nrep, _ = q.shape
    d = v_buf.shape[-1]
    key_in_block = jax.lax.broadcasted_iota(jnp.int32, (hkv, nrep, bk), 2)

    def block(i, carry):
        m_prev, l_prev, acc = carry
        buf = (buf0 + i) % 2
        last = i + 1 == n_blocks

        # the block after this one goes to the other buffer while this
        # one is waited for and reduced: the slot's own next block, or,
        # behind its last, block 0 of the next live slot
        @pl.when(jnp.logical_not(last) | (nxt < n_slots))
        def _next():
            start_block(jnp.where(last, nxt, b),
                        jnp.where(last, nxt_first, first),
                        jnp.where(last, nxt_live, n_live),
                        jnp.where(last, 0, i + 1), 1 - buf)

        wait_block(buf)
        k = k_buf[buf]   # [Hkv, bk, D]
        v = v_buf[buf]
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale  # [Hkv, n_rep, bk]
        # causal-over-the-cache mask at logical positions: key o of
        # block i sits at absolute position i*bk + o (past the window's
        # first page, where there is a window)
        if window is None:
            seen = i * bk + key_in_block <= pos
        else:
            key = first * page_size + i * bk + key_in_block
            seen = (key <= pos) & (pos - key < window)
        s = jnp.where(seen, s, _NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc

    if sink_ref is None:
        m0 = jnp.full((hkv, nrep, 1), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((hkv, nrep, 1), jnp.float32)
    else:
        m0 = sink_ref[...]
        l0 = jnp.ones((hkv, nrep, 1), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, n_blocks, block, (
        m0, l0, jnp.zeros((hkv, nrep, d), jnp.float32)))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)

    @pl.when(n_blocks > 0)
    def _hand_on():
        chain[0] = (buf0 + n_blocks) % 2
        chain[1] = (nxt < n_slots).astype(jnp.int32)


def pallas_paged_decode_attention(
    q: jax.Array,
    pool_k: jax.Array,
    pool_v: jax.Array,
    page_tables: jax.Array,
    positions: jax.Array,
    *,
    layer: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    interpret: bool = False,
    window: Optional[int] = None,
    sink: Optional[jax.Array] = None,
) -> jax.Array:
    """One-token paged attention: q [B, Hq, D] against the page pool.

    pool_k/pool_v: the whole [L, n_pages, Hkv, page_size, D] pools with
    the ``layer`` to read (a third scalar-prefetch operand: the kernel
    copies ``pool.at[layer, page]``, nothing slices a layer out), or one
    layer's [n_pages, Hkv, page_size, D]; page_tables: [B, max_pages]
    int32; positions: [B] int32 absolute position of the query token
    (attends keys j <= position; with ``window`` only those with
    ``position - j < window``, and the walk starts at the logical page
    of the window's first key: the pages before it cost no DMA, so a
    table whose logical pages repeat a ring of ``ceil(window /
    page_size) + 1`` physical ones serves a window layer).
    The V pool's last dimension may be narrower or wider than the K
    pool's (a key 192 wide stored at 256 beside a value of 128): the
    landing zone, the accumulator and the result take the value's.
    ``sink`` [Hq] float32: a logit a query head that joins every
    softmax and carries no value (``p = exp(s - m) / (exp(sink - m) +
    sum exp(s - m))``).
    Returns [B, Hq, D of V].

    The pools stay in HBM; the page table and positions are
    scalar-prefetched, and each slot's step copies its live pages, a
    block of ``_pages_per_block`` at a time and all KV heads of a page
    at once, into a double-buffered VMEM landing zone while the block
    before it is reduced flash-style; behind a slot's last block come
    the copies of the next live slot's first (the module docstring).
    """
    if layer is None:   # one layer's pool is a pool of one layer
        pool_k, pool_v, layer = pool_k[None], pool_v[None], 0
    b, hq, d = q.shape
    _, n_pages, hkv, page_size, _ = pool_k.shape
    d_v = pool_v.shape[-1]
    if hq % hkv:
        raise ValueError(f"query heads {hq} not a multiple of kv heads {hkv}")
    if pool_k.shape[-1] != d:
        raise ValueError(
            f"a query {d} wide against keys stored {pool_k.shape[-1]} wide: "
            "pad the query to the stored key's width (zeros) and give the "
            "scale of the key as written")
    n_rep = hq // hkv
    max_pages = page_tables.shape[1]
    if not interpret and not (kernel_serves(d) and kernel_serves(d_v)):
        raise ValueError(
            f"the paged-decode kernel copies whole pages out of HBM, which "
            f"Mosaic allows only for a head_dim that fills the {_LANES} "
            f"lanes; got {d} / {d_v} (paged_attention() sends it to the "
            f"gather fallback)")
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    ppb = _pages_per_block(page_size, hkv, d, pool_k.dtype, max_pages, d_v)
    if not interpret:
        # "the pools stay in HBM", said to XLA too (inside a jitted
        # program: the constraint is no eager operation). A Mosaic
        # call's operand may otherwise be fetched WHOLE into fast memory
        # ahead of the call, and was once a step's weight copies had
        # left room there: qwen3-next's 75 MB K pool, three times a
        # step, +0.13 ms of an 8.6 ms step for a kernel that reads the
        # live pages of one layer (PERF.md, PR 48)
        pool_k = pltpu.with_memory_space_constraint(pool_k, pltpu.HBM)
        pool_v = pltpu.with_memory_space_constraint(pool_v, pltpu.HBM)

    def q_idx(b_, *_):
        return (b_, 0, 0, 0)

    sinks, sink_specs = (), []
    if sink is not None:   # every slot reads the same [Hkv, n_rep, 1]
        sinks = (sink.astype(jnp.float32).reshape(hkv, n_rep, 1),)
        sink_specs = [pl.BlockSpec((hkv, n_rep, 1), lambda *_: (0, 0, 0))]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, hkv, n_rep, d), q_idx),
            *sink_specs,
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, hkv, n_rep, d_v), q_idx),
        scratch_shapes=[
            pltpu.VMEM((2, hkv, ppb * page_size, d), pool_k.dtype),
            pltpu.VMEM((2, hkv, ppb * page_size, d_v), pool_v.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((2,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, scale=scale,
                          page_size=page_size, pages_per_block=ppb,
                          max_pages=max_pages, window=window,
                          sink=sink is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, n_rep, d_v), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # sequential: a slot starts the next one's first block
            dimension_semantics=(pltpu.ARBITRARY,)),
        interpret=interpret,
        name="paged_decode",
    )(page_tables.astype(jnp.int32).reshape(-1),
      positions.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      q.reshape(b, hkv, n_rep, d), *sinks, pool_k, pool_v)
    return out.reshape(b, hq, d_v)


# ---------------------------------------------------------------------------
# the latent decode kernel
# ---------------------------------------------------------------------------
# keys one compute block of the latent kernel covers: every query head
# shares the block (one cached head). The walk is bound by issuing one
# copy a page (144 ns a page of 16 rows on the v5e, whatever the
# block: 211 / 198 / 210 / 226 us a call of 22,000 rows at 128 / 256 /
# 512 / 1,024 keys: PERF.md, PR 51), so the block only has to keep the
# score tile [heads, keys] in float32 (128 KB at 128 heads) and the
# landing zone (0.64 MB of rows double-buffered at 640 wide) small.
# That is the block at 128 heads or more; fewer heads take as many more
# keys as keep the score tile's size, up to four times (32 heads: 1,024
# keys, a landing zone of 2.6 MB on a bfloat16 pool).
# Alone on the v5e, 32 slots of 6,700 rows at 32 heads: 0.705 ms a call
# at 256 keys, 0.559 at 512, 0.490 at 1,024 (PERF.md, PR 54)
_LATENT_BLOCK_KEYS = 256


def _latent_block_keys(heads: int) -> int:
    """Keys of one compute block of the latent kernel for ``heads``
    query heads."""
    return _LATENT_BLOCK_KEYS * min(4, max(1, 128 // heads))


def _latent_decode_kernel(pt_ref, pos_ref, layer_ref, q_ref, pool_hbm,
                          o_ref, buf, sems, *, scale, page_size,
                          pages_per_block, max_pages, value_width):
    """``_paged_decode_kernel`` for a pool of latent rows: ONE cached
    head, whose row is the key and whose first ``value_width`` columns
    are the value, against every query head at once (the heads are the
    rows of the score tile)."""
    b = pl.program_id(0)   # slot
    layer = layer_ref[0]
    bk = pages_per_block * page_size
    pos = pos_ref[b]
    n_live = jnp.clip(pos // page_size + 1, 0, max_pages)
    n_blocks = (n_live + pages_per_block - 1) // pages_per_block

    def block_copies(i, slot):
        """One async copy per page of block ``i``: the page's rows
        ``[page_size, row]`` (contiguous in HBM) into their place in
        landing buffer ``slot``; a page past the live length re-reads
        the last live page and is masked. ``i`` None: the same copies
        from page 0, to WAIT on (a wait reads its semaphore and the
        copy's size, never its source: the table is looked up once a
        page, not twice; the walk is bound by issuing copies)."""
        out = []
        for p in range(pages_per_block):
            page = 0
            if i is not None:
                j = jnp.minimum(i * pages_per_block + p, n_live - 1)
                page = pt_ref[b * max_pages + j]
            out.append(pltpu.make_async_copy(
                pool_hbm.at[layer, page, 0],
                buf.at[slot, pl.ds(p * page_size, page_size), :],
                sems.at[slot]))
        return out

    @pl.when(n_blocks > 0)
    def _first():
        for c in block_copies(0, 0):
            c.start()

    q = q_ref[0]   # [H, row]
    heads = q.shape[0]
    key_in_block = jax.lax.broadcasted_iota(jnp.int32, (heads, bk), 1)

    def block(i, carry):
        m_prev, l_prev, acc = carry
        slot = i % 2

        @pl.when(i + 1 < n_blocks)
        def _next():
            for c in block_copies(i + 1, 1 - slot):
                c.start()

        for c in block_copies(None, slot):
            c.wait()
        rows = buf[slot]   # [bk, row]
        s = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [H, bk]
        s = jnp.where(i * bk + key_in_block <= pos, s, _NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jax.lax.dot_general(
            p.astype(rows.dtype), rows[:, :value_width],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc

    _, l, acc = jax.lax.fori_loop(0, n_blocks, block, (
        jnp.full((heads, 1), _NEG_INF, jnp.float32),
        jnp.zeros((heads, 1), jnp.float32),
        jnp.zeros((heads, value_width), jnp.float32),
    ))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def pallas_latent_decode_attention(
    q: jax.Array,
    pool: jax.Array,
    page_tables: jax.Array,
    positions: jax.Array,
    *,
    layer: jax.Array,
    value_width: int,
    scale: float,
    interpret: bool = False,
) -> jax.Array:
    """One-token latent attention in the absorbed form: q [B, H, row]
    (``[q~ | q_r | 0...]`` of every head) against the latent page pool
    [L, n_pages, 1, page_size, row] (``[c | k_r | 0...]`` a token: the
    one cached head) at ``layer``; positions [B]: the query token's
    (keys j <= position). Returns [B, H, value_width] float32-accumulated
    ``sum_j p_h(j) c(j)``: a row's first ``value_width`` columns are its
    value. The pool stays in HBM; each slot's step copies its live
    pages once for ALL heads, ``_latent_block_keys(heads)`` keys a block,
    double-buffered, and reduces flash-style: no expanded key or value
    of any cached token exists anywhere."""
    b, heads, row = q.shape
    _, _, hkv, page_size, _ = pool.shape
    if hkv != 1 or pool.shape[-1] != row:
        raise ValueError(
            f"a latent pool holds one head of the query's width: pool "
            f"{pool.shape}, query {q.shape}")
    if not interpret and not (kernel_serves(row)
                              and kernel_serves(value_width)):
        raise ValueError(
            f"the latent decode kernel copies whole pages out of HBM and "
            f"slices the value off the row at a {_LANES}-lane boundary; "
            f"got row {row}, value {value_width}")
    max_pages = page_tables.shape[1]
    ppb = max(1, min(_latent_block_keys(heads) // page_size, max_pages))
    if not interpret:
        pool = pltpu.with_memory_space_constraint(pool, pltpu.HBM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, heads, row), lambda b_, *_: (b_, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, heads, value_width),
                               lambda b_, *_: (b_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ppb * page_size, row), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_latent_decode_kernel, scale=scale,
                          page_size=page_size, pages_per_block=ppb,
                          max_pages=max_pages, value_width=value_width),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, heads, value_width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL,)),  # slots share no state
        interpret=interpret,
        name="latent_decode",
    )(page_tables.astype(jnp.int32).reshape(-1),
      positions.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      q, pool)


def latent_attention(
    q: jax.Array,
    pool: jax.Array,
    page_tables: jax.Array,
    positions: jax.Array,
    *,
    layer: jax.Array,
    value_width: int,
    scale: float,
    seq_limit: Optional[int] = None,
    kernel: Optional[bool] = None,
    interpret: bool = False,
) -> jax.Array:
    """One-token attention against a latent page pool in the absorbed
    form, kernel or fallback (``pallas_latent_decode_attention`` has the
    shapes). ``kernel=None``: the Mosaic kernel when ``in_place_pair``
    serves the row's width; elsewhere the same mathematics in plain XLA
    over the slot's gathered rows ``pool[layer, page_tables]`` [B, S,
    row] (the CPU path and the reference the kernel is held to): float32
    scores and softmax, the value the rows' first columns."""
    if kernel is None:
        kernel = in_place_pair(pool.shape[-1])
    if kernel:
        return pallas_latent_decode_attention(
            q, pool, page_tables, positions, layer=layer,
            value_width=value_width, scale=scale, interpret=interpret)
    rows = paged_gather_kv(pool, page_tables, layer)[:, 0]   # [B, S, row]
    if seq_limit is not None and rows.shape[1] > seq_limit:
        rows = rows[:, :seq_limit]
    s = jnp.einsum("bhr,bsr->bhs", q, rows,
                   preferred_element_type=jnp.float32) * scale
    seen = jnp.arange(rows.shape[1], dtype=jnp.int32)[None, :] \
        <= positions[:, None]
    s = jnp.where(seen[:, None, :], s, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhs,bsc->bhc", p, rows[..., :value_width])


# ---------------------------------------------------------------------------
# dispatchers: one predicate picks the pair
# ---------------------------------------------------------------------------
def paged_write(
    pool: jax.Array,
    new: jax.Array,
    positions: jax.Array,
    page_tables: jax.Array,
    write_mask: Optional[jax.Array] = None,
    *,
    layer: jax.Array,
    kernel: Optional[bool] = None,
    interpret: bool = False,
) -> jax.Array:
    """Write ``new`` [B, Hkv, S, D] into ``layer`` of the whole pool
    [L, n_pages, Hkv, page_size, D], kernel or scatter: the Mosaic page
    write when ``in_place_pair`` says so (``kernel=None``), the lax
    scatter at ``pool.at[layer, ...]`` elsewhere."""
    if kernel is None:
        kernel = in_place_pair(pool.shape[-1])
    if kernel:
        return pallas_paged_write(pool, new, positions, page_tables,
                                  write_mask, layer=layer,
                                  interpret=interpret)
    return paged_write_kv(pool, new, positions, page_tables, pool.shape[-2],
                          write_mask, layer=layer)


def paged_attention(
    q: jax.Array,
    pool_k: jax.Array,
    pool_v: jax.Array,
    page_tables: jax.Array,
    q_positions: jax.Array,
    *,
    page_size: int,
    layer: Optional[jax.Array] = None,
    seq_limit: Optional[int] = None,
    scale: Optional[float] = None,
    kernel: Optional[bool] = None,
    interpret: bool = False,
    window: Optional[int] = None,
    own: Optional[Tuple[jax.Array, jax.Array]] = None,
    prefix_hit: Any = None,
    sink: Optional[jax.Array] = None,
) -> jax.Array:
    """Attention against the paged cache, kernel or fallback.

    q: [B, Hq, S, D] (S = tail length at prefill, 1 at decode);
    q_positions: [B, S] absolute positions; the pools whole with the
    ``layer`` to read, or one layer's. ``kernel=None`` auto-selects: the
    Pallas kernel for single-token decode when ``in_place_pair`` (the
    platform is ``tpu`` and ``kernel_serves`` the head_dim), the lax
    gather + ``cached_sdpa_attention`` everywhere else — other
    platforms, narrow heads and a multi-row call that reads a prompt's
    prefix out of the pool. ``seq_limit`` crops the gathered view to the
    engine's ``max_seq`` so the fallback's reduction has the contiguous
    reference's operand shapes. ``window``: a window layer's mask and
    first page (``pallas_paged_decode_attention``), by position in the
    fallback. ``sink`` [Hq]: a logit a query head in every softmax that
    carries no value, in the kernel and in the fallback alike. The V
    pool may be narrower than the K pool (a key stored padded): the
    result takes the value's width; ``in_place_pair`` is asked of both.

    A multi-row call may bring ``own``, the K/V [B, Hkv, S, D] it made
    (and has already written to the pool), and ``prefix_hit``, what its
    caller knows of its rows: whether any of them continues a prefix
    that lies in the pool. False (no row can: a family that refuses
    prefix sharing): each prompt attends to itself in key blocks
    (``ops/flash_attention.prefill_self_attention``: no score array)
    and nothing here reads the pool. A traced bool (the prefill step's
    ``starts``): one ``lax.cond`` between that and the fallback. Where
    the Mosaic pair writes the pool (``in_place_pair``) the gather is
    part of the fallback's branch and a call without a hit does not
    pay for it; where the lax pair does, the gather stays outside the
    choice and only the attention over the gathered view is chosen:
    the scatter keeps such a pool pages-minor, and as an operand of a
    branch it was copied whole, a layer (an AOT compile of a 64-wide
    head's program showed it). None, or no ``own``: the fallback, as
    for every call before.
    """
    from scaletorch_tpu.models.layers import cached_sdpa_attention
    from scaletorch_tpu.ops.flash_attention import prefill_self_attention

    s = q.shape[2]
    chooses = s > 1 and own is not None and prefix_hit is not None

    def to_itself():
        return prefill_self_attention(q, *own, scale=scale, window=window,
                                      sink=sink)

    if chooses and prefix_hit is False:
        return to_itself()
    use_kernel = kernel
    if use_kernel is None:
        use_kernel = (s == 1 and in_place_pair(q.shape[3])
                      and in_place_pair(pool_v.shape[-1]))
    if use_kernel:
        if s != 1:
            raise ValueError(
                f"the paged-decode kernel serves single-token queries; "
                f"got S={s} (a multi-row call reads through the gather)"
            )
        out = pallas_paged_decode_attention(
            q[:, :, 0, :], pool_k, pool_v, page_tables, q_positions[:, 0],
            layer=layer, scale=scale, interpret=interpret, window=window,
            sink=sink,
        )
        return out[:, :, None, :]

    def gathered():
        k = paged_gather_kv(pool_k, page_tables, layer)
        v = paged_gather_kv(pool_v, page_tables, layer)
        if seq_limit is not None and k.shape[2] > seq_limit:
            return k[:, :, :seq_limit, :], v[:, :, :seq_limit, :]
        return k, v

    def from_pool(k, v):
        return cached_sdpa_attention(q, k, v, q_positions, scale=scale,
                                     window=window, sink=sink)

    if not chooses:
        return from_pool(*gathered())
    if kernel is None and in_place_pair(q.shape[3]):
        view = gathered         # in the branch: nothing read without a hit
    else:
        k_v = gathered()        # the lax pair's pool: outside the choice

        def view():
            return k_v
    return jax.lax.cond(prefix_hit, lambda: from_pool(*view()), to_itself)
