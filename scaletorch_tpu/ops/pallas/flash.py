"""Pallas TPU flash attention — blockwise softmax with custom VJP.

The TPU-native equivalent of the flash-attn-2 / npu_flash_attn_func path
the reference dispatches to (reference models/attention_utils.py:72-152):
QK^T tiles stream through VMEM with running-max/sum accumulation so the
O(S^2) score matrix never reaches HBM, and the backward recomputes score
tiles from the saved log-sum-exp instead of storing probabilities.

Design points:
  * **GQA without expansion** — the K/V block index maps divide the query
    head by ``n_rep``, so grouped K/V heads are read directly from their
    unexpanded [B, Hkv, S, D] layout (the reference expands via zero-copy
    ``expand``, llama.py:176-192; here the "expansion" is pure indexing).
  * **Causal block skip, on the grid** — a causal call's grid is
    ``(batch, head, step)`` and a step is one entry of a small table of
    the LIVE ``(query block, key block)`` pairs (``causal_block_plan``,
    built on the host from the four static sizes, read on the scalar
    prefetch path): blocks above the diagonal are no grid step at all
    (120 of a head's 256 at 8192 / 512 / 512), so nothing is predicated
    off and no DMA is clamped. Every live block builds the triangle
    mask: on the chip it costs nothing a second, mask-free body for the
    blocks wholly under the diagonal would win back (PERF.md, PR 33).
    ``causal=False`` keeps the rectangular grid and no mask (ring
    attention's off-diagonal hops).
    This is the reference ring-attention causal-skip idea
    (context_parallel.py:154-171) applied at tile granularity.
  * **vma-aware** — output ShapeDtypeStructs carry the varying-mesh-axes
    of their inputs, so the kernel composes with ``jax.shard_map``'s
    vma checking (the spmd train step runs everything inside shard_map).
  * fp32 accumulators and LSE; bf16 MXU feeds.
  * **Softmax statistics a register wide** — the forward's running
    maximum and sum live in ``[bq, 128]`` float32 scratch, every lane of
    a row holding the row's value (the layout of jax's own TPU flash
    kernel). Held as a ``[bq, 1]`` column, one lane of 128 in every
    vector register, each grid step had to spread them across lanes for
    ``s - m`` and ``acc * corr``: on the v5e that was 1.66 of a call's
    4.10 ms at the training shape, and a ``[bq, 128]`` scratch read
    through its first column costs the same, so it is the spreading and
    not the scratch's traffic (PERF.md, PR 38). Now the step's row
    maximum and row sum are broadcast into the width once, and
    ``_lanes`` takes a statistic to the key block's width and to the
    head's by whole registers, or by its leading lanes under one. Which,
    it reads from the static shapes: one body, no option. The same
    float32 operations on the same values: ``out`` and ``lse`` are bit
    for bit what the column gave. The backward kernels keep their
    ``[1, bq]`` rows of lse and delta: ``flash_dq`` fed from two such
    scratches was slower (2.87 -> 3.00 ms, same PR).

Backward follows FlashAttention-2: delta = rowsum(dO * O) precomputed in
XLA, then a dq kernel (a query block at a time, reducing its key blocks)
and a dkv kernel (a key block at a time, reducing its query blocks and, at
each, the n_rep grouped query heads).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

def _resolve_blocks(block_q, block_kv):
    """None -> the SCALETORCH_TPU_FLASH_BLOCK_Q/KV env registry values.
    Resolved HERE so every entry point — the attention backend, the ring
    attention's forward/backward composition — honours the tuned tiles."""
    if block_q is None or block_kv is None:
        from scaletorch_tpu.env import get_env

        block_q = block_q or get_env("SCALETORCH_TPU_FLASH_BLOCK_Q")
        block_kv = block_kv or get_env("SCALETORCH_TPU_FLASH_BLOCK_KV")
    return block_q, block_kv


_NEG_INF = -1e30  # large-negative instead of -inf: keeps masked rows NaN-free
_LANES = 128  # of a vector register: the forward holds its statistics that wide


def _struct(shape, dtype, like):
    """ShapeDtypeStruct carrying ``like``'s varying-mesh-axes (vma), so
    the kernel's outputs type-check inside shard_map."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def _pick_block(seq: int, preferred: int) -> int:
    block = min(preferred, seq)
    while seq % block:
        block //= 2
    return max(block, 1)


# ---------------------------------------------------------------------------
# the causal structure, on the grid
# ---------------------------------------------------------------------------
# what a step of a causal walk does to its accumulation (the plan's ``flags``)
_FIRST, _LAST = 1, 2

# A walk's three tables lie in SMEM whole (1 MiB on the v5e), 12 bytes a
# step: 2**16 steps are 768 KiB, 361 blocks a side, 184,832 tokens in
# 512-wide blocks (tests/test_paged_kernel_aot.py compiles that walk).
# Past it a call fails here, by name, and not inside Mosaic.
MAX_CAUSAL_STEPS = 2 ** 16


class CausalBlockPlan(NamedTuple):
    """Which blocks of a causal score matrix the kernels visit, in the
    order they visit them: two walks, each three read-only int32
    vectors ``(outer block, inner block, flags)``, one entry a grid
    step.

    ``by_query`` = ``(q_blk, k_blk, flags)``: query block by query
    block, each one's visible key blocks in order (``flash_fwd``,
    ``flash_dq``). ``by_key`` = ``(k_blk, q_blk, flags)``: key block by
    key block, each one's contributing query blocks in order
    (``flash_dkv``, which visits a pair once per grouped query head).
    ``flags`` says whether the step opens (``_FIRST``) or closes
    (``_LAST``) its accumulation. ``live`` counts the blocks that hold
    a visible element; ``dead`` the key blocks that no query row
    reaches (``skv > sq`` only): each still gets one step, against
    query block 0, where the mask leaves nothing, so its dk / dv come
    out zeros.
    """
    by_query: tuple
    by_key: tuple
    live: int
    dead: int


@functools.lru_cache(maxsize=None)
def causal_block_plan(sq: int, skv: int, bq: int, bkv: int,
                      window: Optional[int] = None) -> CausalBlockPlan:
    """The plan of a causal ``[sq, skv]`` score matrix (row >= column is
    visible) cut into ``bq x bkv`` blocks. Static by shape, so it is
    built on the host, once, and reaches the kernels as scalar-prefetch
    tables. Block ``(i, j)`` is live iff its first key column is at or
    before its last query row: 8192 / 8192 / 512 / 512 is 136 live of
    256. With a ``window`` (a row sees the ``window`` columns that end
    at its own: ``0 <= row - column < window``) a block is live only if
    its last key column also reaches its first query row's window:
    3072 / 3072 / 512 / 512 under a window of 2048 is 20 live of the
    causal 21, so a window prunes little until the sequence is several
    windows long."""
    nq, nkv = sq // bq, skv // bkv

    def live(i, j):
        seen = j * bkv <= i * bq + bq - 1
        if window is not None:
            seen = seen and j * bkv + bkv - 1 > i * bq - window
        return seen

    def walk(accumulations):
        """[(outer, inner)] per accumulation -> the flagged tables."""
        steps = []
        for acc in accumulations:
            acc = [[*step, 0] for step in acc]
            acc[0][-1] |= _FIRST
            acc[-1][-1] |= _LAST
            steps += acc
        if len(steps) > MAX_CAUSAL_STEPS:
            raise ValueError(
                f"causal flash attention at {sq} x {skv} in {bq} x {bkv} "
                f"blocks walks {len(steps)} live blocks; its tables hold "
                f"at most {MAX_CAUSAL_STEPS}. Use larger blocks "
                "(SCALETORCH_TPU_FLASH_BLOCK_Q / _KV) or shard the "
                "sequence (context parallel).")
        tables = tuple(np.ascontiguousarray(col)
                       for col in np.asarray(steps, np.int32).T)
        for table in tables:  # the cache hands every caller the same arrays
            table.setflags(write=False)
        return tables

    by_query = walk(
        [(i, j) for j in range(nkv) if live(i, j)]
        for i in range(nq))  # the diagonal block is live for every query block
    by_key = walk(
        [(j, i) for i in range(nq) if live(i, j)] or [(j, 0)]
        for j in range(nkv))
    n_live = len(by_query[0])
    return CausalBlockPlan(by_query, by_key, n_live,
                           dead=len(by_key[0]) - n_live)


def _grid_step(causal, refs, n_blocks):
    """Where this grid step is: ``(blocks, first, last, refs)``. The
    grid past (batch, head) is the ``n_blocks`` block coordinates,
    outermost first, reduced over all but the first. Causal: ONE walked
    dimension stands for the first two, an index into a walk's tables,
    the kernel's leading refs, which are dropped from ``refs``.
    """
    if causal:
        outer, inner, flags = (ref[pl.program_id(2)] for ref in refs[:3])
        refs, inner_dims = refs[3:], range(3, n_blocks + 1)
        first, last = flags & _FIRST != 0, flags & _LAST != 0
    else:
        outer, inner = pl.program_id(2), pl.program_id(3)
        inner_dims = range(4, n_blocks + 2)
        first, last = inner == 0, inner == pl.num_programs(3) - 1
    blocks = [outer, inner]
    for a in inner_dims:
        blocks.append(pl.program_id(a))
        first &= pl.program_id(a) == 0
        last &= pl.program_id(a) == pl.num_programs(a) - 1
    return blocks, first, last, refs


def _blocks_of(causal, n_blocks):
    """``_grid_step``'s blocks for an index map, from its arguments past
    (batch, head): the grid's, then (causal) the three tables."""
    def blocks(*g):
        if causal:
            t, (outer, inner, _) = g[0], g[n_blocks - 1:]
            return [outer[t], inner[t], *g[1:n_blocks - 1]]
        return g
    return blocks


def _scores(q, k, i, j, *, scale, masked, bq, bkv, window=None):
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [bq, bkv]
    if masked:
        # every live causal block: the select is the identity on a block
        # wholly under the diagonal, and measured free there
        row = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        col = j * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        seen = row >= col
        if window is not None:
            seen &= row - col < window
        s = jnp.where(seen, s, _NEG_INF)
    return s


def _semantics(*dims):
    """Mosaic grid dimension semantics: 'p' = parallel (no cross-iteration
    carry — megacore-partitionable on 2-core chips), 'a' = arbitrary (the
    sequential reduction dims that carry scratch accumulators). Declaring
    them lets Mosaic schedule DMAs/compute across iterations instead of
    assuming every dim may carry state."""
    m = {"p": pltpu.PARALLEL, "a": pltpu.ARBITRARY}
    return pltpu.CompilerParams(
        dimension_semantics=tuple(m[d] for d in dims))


def _call(kernel, name, tables, grid, interpret, out_shape, **specs):
    """The ``pallas_call`` of one kernel. ``grid`` is the rectangular
    grid: (batch, head, outer block) are parallel, the rest carry the
    accumulators. With ``tables`` (a causal walk) the two outermost
    block dimensions fold into one sequential one, a step a table
    entry."""
    parallel = 3
    if tables:
        grid, parallel = grid[:2] + (len(tables[0]),) + grid[4:], 2
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tables), grid=grid, **specs),
        compiler_params=_semantics(
            *["p"] * parallel, *["a"] * (len(grid) - parallel)),
        out_shape=out_shape,
        interpret=interpret,
        name=name,
    )


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _lanes(stat, width):
    """A lane-replicated ``[rows, _LANES]`` statistic at ``width`` lanes:
    repeated whole registers for a multiple of a register (the training
    shapes' 512-wide blocks and 128 / 256-wide heads), the leading lanes
    under one (64-wide heads, the CPU tests' 16-64-wide blocks)."""
    return jnp.tile(stat, (1, -(-width // _LANES)))[:, :width]


def _fwd_kernel(*refs, scale, causal, bq, bkv, window=None, sink=False):
    (i, j), first, last, refs = _grid_step(causal, refs, 2)
    q_ref, k_ref, v_ref, *sink_ref, o_ref, lse_ref, acc_sc, m_sc, l_sc = refs

    @pl.when(first)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc)
        if sink:
            # the head's sink logit, lane-replicated [1, _LANES]: one more
            # column of every row's softmax that carries no value, so the
            # running maximum starts at it and the running sum at 1
            m_sc[:] = jnp.broadcast_to(sink_ref[0][0], m_sc.shape)
            l_sc[:] = jnp.ones_like(l_sc)
        else:
            m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
            l_sc[:] = jnp.zeros_like(l_sc)

    q = q_ref[0, 0]  # [bq, D]
    k = k_ref[0, 0]  # [bkv, D]
    v = v_ref[0, 0]  # [bkv, Dv]
    d = v.shape[-1]
    s = _scores(q, k, i, j, scale=scale, masked=causal, bq=bq, bkv=bkv,
                window=window)
    # m, l, corr: [bq, _LANES], every lane of a row the row's value
    m_prev, l_prev = m_sc[:], l_sc[:]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - _lanes(m_new, bkv))
    corr = jnp.exp(m_prev - m_new)
    l_sc[:] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    m_sc[:] = m_new
    acc_sc[:] = acc_sc[:] * _lanes(corr, d) + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(last)
    def _finalize():
        l = jnp.maximum(l_sc[:], 1e-30)
        o_ref[0, 0] = (acc_sc[:] / _lanes(l, d)).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_sc[:, 0] + jnp.log(l[:, 0]))[None, :]


def _flash_forward(q, k, v, causal, scale, bq, bkv, interpret, window=None,
                   sink=None):
    """``v`` may be narrower or wider than ``q`` / ``k`` (latent
    attention's expanded heads: keys 192, values 128): the output and
    the accumulator take the value's width. The backward kernels know
    one width. ``sink`` [Hq] float32: a logit a query head in every
    row's softmax, with no value (``lse`` then counts it); a call
    without one compiles to what it compiled to before there was
    one."""
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    dv = v.shape[-1]
    n_rep = hq // hkv
    if window is not None and not causal:
        raise ValueError("a window is a causal mask's: causal=False "
                         f"with window={window}")
    tables = (causal_block_plan(sq, skv, bq, bkv, window).by_query
              if causal else ())
    blocks = _blocks_of(causal, 2)

    def q_rows(b_, h, *g):
        return b_, h, blocks(*g)[0], 0

    def kv_rows(b_, h, *g):
        return b_, h // n_rep, blocks(*g)[1], 0

    sinks, sink_specs = (), []
    if sink is not None:
        sinks = (jnp.broadcast_to(
            sink.astype(jnp.float32)[:, None, None], (hq, 1, _LANES)),)
        sink_specs = [pl.BlockSpec((1, 1, _LANES),
                                   lambda b_, h, *g: (h, 0, 0))]
    out, lse = _call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal, bq=bq,
                          bkv=bkv, window=window, sink=sink is not None),
        "flash_fwd", tables, (b, hq, sq // bq, skv // bkv), interpret,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), q_rows),
            pl.BlockSpec((1, 1, bkv, d), kv_rows),
            pl.BlockSpec((1, 1, bkv, dv), kv_rows),
            *sink_specs,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, dv), q_rows),
            pl.BlockSpec((1, 1, 1, bq),
                         lambda b_, h, *g: (b_, h, 0, blocks(*g)[0])),
        ],
        out_shape=[
            _struct((b, hq, sq, dv), q.dtype, q),
            _struct((b, hq, 1, sq), jnp.float32, q),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, dv), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),  # running max
            pltpu.VMEM((bq, _LANES), jnp.float32),  # running sum
        ],
    )(*tables, q, k, v, *sinks)
    return out, lse[:, :, 0, :]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _dq_kernel(*refs, scale, causal, bq, bkv):
    (i, j), first, last, refs = _grid_step(causal, refs, 2)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_sc = refs

    @pl.when(first)
    def _init():
        dq_sc[:] = jnp.zeros_like(dq_sc)

    q = q_ref[0, 0]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    do = do_ref[0, 0]
    lse = lse_ref[0, 0]      # [1, bq]
    delta = delta_ref[0, 0]  # [1, bq]
    s = _scores(q, k, i, j, scale=scale, masked=causal, bq=bq, bkv=bkv)
    p = jnp.exp(s - lse[0][:, None])  # [bq, bkv]
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = p * (dp - delta[0][:, None]) * scale
    dq_sc[:] += jax.lax.dot_general(
        ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(last)
    def _finalize():
        dq_ref[0, 0] = dq_sc[:].astype(dq_ref.dtype)


def _dkv_kernel(*refs, scale, causal, bq, bkv):
    # key block jj, query block i, grouped query head r of the kv head
    (jj, i, r), first, last, refs = _grid_step(causal, refs, 3)
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
     dk_ref, dv_ref, dk_sc, dv_sc) = refs

    @pl.when(first)
    def _init():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    q = q_ref[0, 0]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    do = do_ref[0, 0]
    lse = lse_ref[0, 0]
    delta = delta_ref[0, 0]
    # on a key block no row reaches, s is _NEG_INF throughout and p 0
    s = _scores(q, k, i, jj, scale=scale, masked=causal, bq=bq, bkv=bkv)
    p = jnp.exp(s - lse[0][:, None])
    dv_sc[:] += jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = p * (dp - delta[0][:, None]) * scale
    dk_sc[:] += jax.lax.dot_general(
        ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(last)
    def _finalize():
        dk_ref[0, 0] = dk_sc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_sc[:].astype(dv_ref.dtype)


def _flash_backward(q, k, v, out, lse, g, causal, scale, bq, bkv, interpret):
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    if v.shape[-1] != d:
        raise NotImplementedError(
            f"flash backward with values {v.shape[-1]} wide under keys "
            f"{d} wide: only the forward takes a value width of its own")
    n_rep = hq // hkv
    nq, nkv = sq // bq, skv // bkv
    plan = causal_block_plan(sq, skv, bq, bkv) if causal else None

    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    lse4 = lse[:, :, None, :]      # [B, Hq, 1, S]
    delta4 = delta[:, :, None, :]

    # dq: a query block at a time, over its key blocks
    tables = plan.by_query if causal else ()
    blocks = _blocks_of(causal, 2)

    def q_rows(b_, h, *g_):
        return b_, h, blocks(*g_)[0], 0

    def kv_rows(b_, h, *g_):
        return b_, h // n_rep, blocks(*g_)[1], 0

    def q_stats(b_, h, *g_):
        return b_, h, 0, blocks(*g_)[0]

    dq = _call(
        functools.partial(_dq_kernel, scale=scale, causal=causal, bq=bq, bkv=bkv),
        "flash_dq", tables, (b, hq, nq, nkv), interpret,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), q_rows),
            pl.BlockSpec((1, 1, bkv, d), kv_rows),
            pl.BlockSpec((1, 1, bkv, d), kv_rows),
            pl.BlockSpec((1, 1, bq, d), q_rows),
            pl.BlockSpec((1, 1, 1, bq), q_stats),
            pl.BlockSpec((1, 1, 1, bq), q_stats),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), q_rows),
        out_shape=_struct((b, hq, sq, d), q.dtype, q),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
    )(*tables, q, k, v, g, lse4, delta4)

    # dk/dv: a key block at a time, over its query blocks and, at each,
    # the n_rep query heads of the kv head
    tables = plan.by_key if causal else ()
    blocks = _blocks_of(causal, 3)

    def q_rows(b_, hk, *g_):
        _, i, r = blocks(*g_)
        return b_, hk * n_rep + r, i, 0

    def q_stats(b_, hk, *g_):
        _, i, r = blocks(*g_)
        return b_, hk * n_rep + r, 0, i

    def kv_rows(b_, hk, *g_):
        return b_, hk, blocks(*g_)[0], 0

    dk, dv = _call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal, bq=bq, bkv=bkv),
        "flash_dkv", tables, (b, hkv, nkv, nq, n_rep), interpret,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), q_rows),
            pl.BlockSpec((1, 1, bkv, d), kv_rows),
            pl.BlockSpec((1, 1, bkv, d), kv_rows),
            pl.BlockSpec((1, 1, bq, d), q_rows),
            pl.BlockSpec((1, 1, 1, bq), q_stats),
            pl.BlockSpec((1, 1, 1, bq), q_stats),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bkv, d), kv_rows),
            pl.BlockSpec((1, 1, bkv, d), kv_rows),
        ],
        out_shape=[
            _struct((b, hkv, skv, d), k.dtype, k),
            _struct((b, hkv, skv, d), v.dtype, v),
        ],
        scratch_shapes=[
            pltpu.VMEM((bkv, d), jnp.float32),
            pltpu.VMEM((bkv, d), jnp.float32),
        ],
    )(*tables, q, k, v, g, lse4, delta4)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public op with custom VJP
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, scale, bq, bkv, interpret):
    out, _ = _flash_forward(q, k, v, causal, scale, bq, bkv, interpret)
    return out


def _flash_fwd(q, k, v, causal, scale, bq, bkv, interpret):
    out, lse = _flash_forward(q, k, v, causal, scale, bq, bkv, interpret)
    # Under jax.checkpoint the 'save_attn' policy keeps these two named
    # residuals, so the backward kernels run off the SAVED (out, lse)
    # instead of recomputing the whole flash forward inside the layer
    # remat (models/llama.py resolve_remat_policy).
    from jax.ad_checkpoint import checkpoint_name

    out_r = checkpoint_name(out, "attn_out")
    lse_r = checkpoint_name(lse, "attn_lse")
    return out, (q, k, v, out_r, lse_r)


def _flash_bwd(causal, scale, bq, bkv, interpret, res, g):
    q, k, v, out, lse = res
    return _flash_backward(q, k, v, out, lse, g, causal, scale, bq, bkv, interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def pallas_flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int | None = None,
    block_kv: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """q: [B, Hq, S, D]; k/v: [B, Hkv, Skv, D]; Hq % Hkv == 0 (GQA)."""
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    if hq % hkv:
        raise ValueError(f"query heads {hq} not a multiple of kv heads {hkv}")
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    block_q, block_kv = _resolve_blocks(block_q, block_kv)
    bq = _pick_block(sq, block_q)
    bkv = _pick_block(skv, block_kv)
    return _flash(q, k, v, causal, scale, bq, bkv, interpret)


# ---------------------------------------------------------------------------
# raw entries for composition into outer custom-VJP ops (ring attention)
# ---------------------------------------------------------------------------
def flash_forward_with_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool,
    scale: Optional[float] = None,
    block_q: int | None = None,
    block_kv: int | None = None,
    interpret: bool = False,
    window: Optional[int] = None,
    sink: Optional[jax.Array] = None,
):
    """Raw kernel forward returning ``(out, lse)``; with ``window`` each
    row sees the ``window`` keys that end at its own
    (``causal_block_plan(window=)``: blocks wholly outside are no grid
    step, the rest carry the band in their mask). Under a window
    narrower than a block (128 keys in 512 x 512 blocks) a query block
    visits two key blocks, and narrower key blocks buy nothing: on the
    v5e a window layer's call over one 8,192-token row at 64 heads reads
    5.45 ms at 512 x 512, 5.45 at 512 x 256, 5.46 at 512 x 128, 5.33 at
    256 x 128 or 256 x 256 and 6.40 at 1,024 x 128 (PERF.md, PR 59):
    with two steps a query block, what a query block costs once (its
    accumulator zeroed, its division and its stores) is most of the
    call, so the blocks stay as they are. ``sink`` [Hq]: a logit a query
    head in every softmax, with no value. A serving prefill's entry: the
    backward kernels know neither a window nor a sink.

    NOT differentiable — the caller owns the VJP (ring attention merges
    per-block (out, lse) partials across ``ppermute`` steps and drives the
    block backward itself, the role of the reference's blockwise fwd inside
    RingAttentionFunc, context_parallel.py:367-424).
    """
    if q.shape[1] % k.shape[1]:
        raise ValueError(
            f"query heads {q.shape[1]} not a multiple of kv heads {k.shape[1]}"
        )
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    block_q, block_kv = _resolve_blocks(block_q, block_kv)
    bq = _pick_block(q.shape[2], block_q)
    bkv = _pick_block(k.shape[2], block_kv)
    return _flash_forward(q, k, v, causal, scale, bq, bkv, interpret,
                          window, sink)


def flash_block_backward(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    out: jax.Array,
    lse: jax.Array,
    dout: jax.Array,
    *,
    causal: bool,
    scale: Optional[float] = None,
    block_q: int | None = None,
    block_kv: int | None = None,
    interpret: bool = False,
):
    """Gradients of one K/V block against a GLOBAL softmax statistic.

    ``out``/``lse`` are the final merged attention output and log-sum-exp
    over ALL blocks (not just this one); the returned (dq, dk, dv) are then
    exactly this block's additive contribution to the full gradients —
    the identity the reference's dual-ring backward exploits
    (context_parallel.py:184-263). dk/dv come back in the unexpanded
    [B, Hkv, S, D] layout.
    """
    if q.shape[1] % k.shape[1]:
        raise ValueError(
            f"query heads {q.shape[1]} not a multiple of kv heads {k.shape[1]}"
        )
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    block_q, block_kv = _resolve_blocks(block_q, block_kv)
    bq = _pick_block(q.shape[2], block_q)
    bkv = _pick_block(k.shape[2], block_kv)
    return _flash_backward(q, k, v, out, lse, dout, causal, scale, bq, bkv,
                           interpret)
