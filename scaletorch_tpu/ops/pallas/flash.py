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

  * **Blocks from shapes** — each of the three kernels takes its
    ``(bq, bkv)`` from a pure function of the call's static shapes
    (``flash_blocks``: the two sides, the mask, which kernel), where
    all three ran every call in 512 x 512. What a sweep
    of each kernel alone on the v5e said (PERF.md, PR 62): at the
    training cell's ``[1, 16 / 8, 8192, 128]`` ``flash_dkv`` reads 4.14
    ms at 512 x 512 and 3.50 at 1,024 x 1,024, ``flash_dq`` 2.87 and
    2.72 at 1,024 x 512, and the forward 2.44 and 2.41-2.44 at every
    shape from 512 x 512 to 1,024 x 1,024: halving its grid steps buys
    it nothing, so its time is the score elements it touches and not a
    cost a step, and the serving prefills (sides of 3,072: larger blocks
    3-9 % slower; 8,192 at keys 192: -2 to +5 %) keep their blocks.
    ``lse`` and ``delta`` are ``[B, Hq, 1, S]`` whatever the blocks, so
    forward, ``dq`` and ``dkv`` need not agree; a caller's ``block_q=``
    / ``block_kv=`` (tests, a sweep) and a hand-set
    SCALETORCH_TPU_FLASH_BLOCK_Q / _KV override all three. Past Mosaic's
    16 MiB of scoped VMEM a call carries a ``vmem_limit_bytes`` computed
    from its blocks (``_vmem_limit``); no shape the rule picks is (1,024
    x 1,024 compiles under 9 MiB).

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
# blocks from shapes
# ---------------------------------------------------------------------------
def flash_blocks(kind: str, sq: int, skv: int, *, causal: bool = True,
                 window: Optional[int] = None) -> tuple[int, int]:
    """``(bq, bkv)`` of one kernel (``kind``: ``"fwd"``, ``"dq"`` or
    ``"dkv"``) from the call's static shapes: the two sides and the
    mask. Pure, so a caller or a test can ask what a call will run in.
    The thresholds are a sweep's on the v5e, each kernel ALONE, bf16,
    16 / 8 heads x 128 unless said, ms a call against 512 x 512
    (PERF.md section 6, PR 62, has every shape tried). The head's width
    is no argument: 64- and 256-wide heads and keys 192 on values 128
    chose as 128 does, or read flat.

    * ``flash_dkv``: 1,024 x 1,024 from a side of 2,048. Causal: 0.295
      against 0.309 at 2,048, 0.978 against 1.102 at 4,096, **3.50
      against 4.14 at 8,192** (the training cell's call), 13.20 against
      16.05 at 16,384, 25.4 against 31.0 at 32,768. Unmasked (the ring's
      other hops): 0.380 against 0.467, 1.51 against 1.85, 6.00 against
      7.40 at 2,048 / 4,096 / 8,192. 512 x 1,024 and 1,024 x 512 read
      2-3 % over it, 2,048 either way 8-10 % (causal) or within 2 %.
    * ``flash_dq``: causal, 1,024 x 512 from a side of 8,192 (**2.72
      against 2.87**; 10.28 against 11.12 at 16,384; at 4,096 0.761
      against 0.766: stays): the same key blocks in the same order, so
      ``dq`` bit for bit. Unmasked, 1,024 x 1,024 from 2,048 (0.282
      against 0.324, 1.12 against 1.28, 4.44 against 5.11).
    * ``flash_fwd``: causal, NO shape earns 2 % under a side of 16,384:
      at 8,192 the best reads 2.41 against 2.44 (16 / 8 x 128), 12.55
      against 12.36 (64 / 4 heads, keys 192 on values 128) and 6.32
      against 6.43 (32 / 32, 192 / 128); at 3,072 (8 rows x 32 heads:
      full, under a window of 2,048, at 192 / 128) every larger block
      is 3-9 % SLOWER, the triangle's waste (14 % more score elements
      touched in 1,024-wide blocks) unpaid. Halving the grid's steps
      buys nothing: the forward's time follows the score elements it
      touches, not a cost a step, and every serving prefill keeps the
      blocks it had. From 16,384, with no window, 1,024 x 1,024 (8.78
      against 9.01; 16.6 against 17.3 at 32,768; 23.0 against 24.1 at
      192 / 128). Under a window narrower than a block nothing helps
      (PR 59). Unmasked, 1,024 x 512 from 2,048 (0.298 against 0.311,
      1.07 against 1.12, 4.03 against 4.22): no triangle, no waste.

    A size is halved until it divides its side (``_pick_block``)."""
    if kind not in ("fwd", "dq", "dkv"):
        raise ValueError(f"flash kernel {kind!r}: one of fwd, dq, dkv")
    side = min(sq, skv)
    bq = bkv = 512
    if kind == "dkv" or (kind == "dq" and not causal):
        if side >= 2048:
            bq = bkv = 1024
    elif kind == "dq":
        if side >= 8192:
            bq = 1024
    elif not causal:
        if side >= 2048:
            bq = 1024
    elif side >= 16384 and window is None:
        bq = bkv = 1024
    return _pick_block(sq, bq), _pick_block(skv, bkv)


def _blocks(kind, q, k, causal, window, block_q, block_kv):
    """One kernel's ``(bq, bkv)``: the caller's ``block_q`` / ``block_kv``
    (the tests, the ring, a sweep), else SCALETORCH_TPU_FLASH_BLOCK_Q / _KV
    where set by hand (``tools/optimize_mfu.py --flash-blocks``: one pair
    for all three kernels), else ``flash_blocks``. A given size is halved
    until it divides its side. Resolved HERE so every entry point — the
    attention backend, the ring's forward / backward composition —
    takes the same blocks."""
    sq, skv = q.shape[2], k.shape[2]
    if block_q is None or block_kv is None:
        from scaletorch_tpu.env import get_env

        rule_q, rule_kv = flash_blocks(kind, sq, skv, causal=causal,
                                       window=window)
        block_q = (block_q or get_env("SCALETORCH_TPU_FLASH_BLOCK_Q")
                   or rule_q)
        block_kv = (block_kv or get_env("SCALETORCH_TPU_FLASH_BLOCK_KV")
                    or rule_kv)
    return _pick_block(sq, block_q), _pick_block(skv, block_kv)


# Mosaic's scoped VMEM on the v5e when a call asks for nothing
# (``xla_tpu_scoped_vmem_limit_kib`` = 16,384) and what the core has.
_SCOPED_VMEM_DEFAULT = 16 * 2 ** 20
_VMEM_PHYSICAL = 128 * 2 ** 20


def _vmem_limit(blocks, scratch_shapes):
    """``vmem_limit_bytes`` of one call, or None where the working set
    of a grid step stays under Mosaic's default. ``blocks``: the
    ``(block shape, dtype)`` of every operand and result, ``q``'s and
    ``k``'s first (all three kernels'); ``scratch_shapes`` the call's::

        2 x (operand and result blocks)      the pipeline's two buffers
        + the float32 scratch                accumulators, statistics
        + 1.5 x 4 x bq x bkv                 the body's score-shaped values

    The body names four to six float32 ``[bq, bkv]`` values (s, p, dp,
    ds, the mask's iotas), but Mosaic streams the elementwise chain
    through registers and keeps one of them and a matmul's bf16 feed:
    compiled for the v5e under a bisected limit (AOT, PR 62), what is
    left after blocks and scratch is 1.1-1.5 of ``4 bq bkv`` for every
    kernel from 512 x 512 (3 MiB in all) to 2,048 x 2,048 (25 / 25 / 30
    MiB for fwd / dq / dkv against this sum's 31 / 30 / 32). So 1,024 x
    1,024 (8 / 9 / 9 MiB) fits the default; the first shapes past it are
    ``flash_dkv`` at 1,024 x 2,048 (17 MiB) and everything at 2,048 x
    2,048. Past the default the call asks for the sum and a quarter, in
    whole MiB."""
    def nbytes(shape, dtype):
        return math.prod(shape) * jnp.dtype(dtype).itemsize

    (q_block, _), (k_block, _) = blocks[:2]
    need = (2 * sum(nbytes(*block) for block in blocks)
            + sum(nbytes(s.shape, s.dtype) for s in scratch_shapes)
            + 6 * q_block[2] * k_block[2])
    if need <= _SCOPED_VMEM_DEFAULT:
        return None
    return min(-(-need * 5 // 4 // 2 ** 20) * 2 ** 20, _VMEM_PHYSICAL)


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


def _semantics(*dims, vmem_limit_bytes=None):
    """Mosaic grid dimension semantics: 'p' = parallel (no cross-iteration
    carry — megacore-partitionable on 2-core chips), 'a' = arbitrary (the
    sequential reduction dims that carry scratch accumulators). Declaring
    them lets Mosaic schedule DMAs/compute across iterations instead of
    assuming every dim may carry state. ``vmem_limit_bytes``: what
    ``_vmem_limit`` computed from the blocks, None (Mosaic's default)
    where the working set fits it."""
    m = {"p": pltpu.PARALLEL, "a": pltpu.ARBITRARY}
    return pltpu.CompilerParams(
        dimension_semantics=tuple(m[d] for d in dims),
        vmem_limit_bytes=vmem_limit_bytes)


def _call(kernel, name, tables, grid, interpret, out_shape, operands, *,
          in_specs, out_specs, scratch_shapes):
    """One kernel's ``pallas_call`` on ``operands``. ``grid`` is the
    rectangular grid: (batch, head, outer block) are parallel, the rest
    carry the accumulators. With ``tables`` (a causal walk) the two
    outermost block dimensions fold into one sequential one, a step a
    table entry. Past Mosaic's default the call carries the
    ``vmem_limit_bytes`` its blocks and scratch come to."""
    parallel = 3
    if tables:
        grid, parallel = grid[:2] + (len(tables[0]),) + grid[4:], 2
    results = zip(*(x if isinstance(x, list) else [x]
                    for x in (out_specs, out_shape)))
    blocks = [(spec.block_shape, x.dtype)
              for spec, x in [*zip(in_specs, operands), *results]]
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tables), grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch_shapes),
        compiler_params=_semantics(
            *["p"] * parallel, *["a"] * (len(grid) - parallel),
            vmem_limit_bytes=_vmem_limit(blocks, scratch_shapes)),
        out_shape=out_shape,
        interpret=interpret,
        name=name,
    )(*tables, *operands)


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


def _flash_forward(q, k, v, causal, scale, block_q, block_kv, interpret,
                   window=None, sink=None):
    """``block_q`` / ``block_kv``: the caller's, or None for ``_blocks``'
    choice. ``v`` may be narrower or wider than ``q`` / ``k`` (latent
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
    bq, bkv = _blocks("fwd", q, k, causal, window, block_q, block_kv)
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
        [_struct((b, hq, sq, dv), q.dtype, q),
         _struct((b, hq, 1, sq), jnp.float32, q)],
        (q, k, v, *sinks),
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
        scratch_shapes=[
            pltpu.VMEM((bq, dv), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),  # running max
            pltpu.VMEM((bq, _LANES), jnp.float32),  # running sum
        ],
    )
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


def _flash_backward(q, k, v, out, lse, g, causal, scale, block_q, block_kv,
                    interpret):
    """``flash_dq`` and ``flash_dkv`` each in blocks of its own
    (``_blocks``): ``lse`` and ``delta`` are ``[B, Hq, 1, S]`` whatever
    blocks the forward ran in, so the three need not agree."""
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    if v.shape[-1] != d:
        raise NotImplementedError(
            f"flash backward with values {v.shape[-1]} wide under keys "
            f"{d} wide: only the forward takes a value width of its own")
    n_rep = hq // hkv

    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    lse4 = lse[:, :, None, :]      # [B, Hq, 1, S]
    delta4 = delta[:, :, None, :]

    # dq: a query block at a time, over its key blocks
    bq, bkv = _blocks("dq", q, k, causal, None, block_q, block_kv)
    tables = causal_block_plan(sq, skv, bq, bkv).by_query if causal else ()
    blocks = _blocks_of(causal, 2)

    def q_rows(b_, h, *g_):
        return b_, h, blocks(*g_)[0], 0

    def kv_rows(b_, h, *g_):
        return b_, h // n_rep, blocks(*g_)[1], 0

    def q_stats(b_, h, *g_):
        return b_, h, 0, blocks(*g_)[0]

    dq = _call(
        functools.partial(_dq_kernel, scale=scale, causal=causal, bq=bq, bkv=bkv),
        "flash_dq", tables, (b, hq, sq // bq, skv // bkv), interpret,
        _struct((b, hq, sq, d), q.dtype, q), (q, k, v, g, lse4, delta4),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), q_rows),
            pl.BlockSpec((1, 1, bkv, d), kv_rows),
            pl.BlockSpec((1, 1, bkv, d), kv_rows),
            pl.BlockSpec((1, 1, bq, d), q_rows),
            pl.BlockSpec((1, 1, 1, bq), q_stats),
            pl.BlockSpec((1, 1, 1, bq), q_stats),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), q_rows),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
    )

    # dk/dv: a key block at a time, over its query blocks and, at each,
    # the n_rep query heads of the kv head
    bq, bkv = _blocks("dkv", q, k, causal, None, block_q, block_kv)
    tables = causal_block_plan(sq, skv, bq, bkv).by_key if causal else ()
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
        "flash_dkv", tables, (b, hkv, skv // bkv, sq // bq, n_rep), interpret,
        [_struct((b, hkv, skv, d), k.dtype, k),
         _struct((b, hkv, skv, d), v.dtype, v)],
        (q, k, v, g, lse4, delta4),
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
        scratch_shapes=[
            pltpu.VMEM((bkv, d), jnp.float32),
            pltpu.VMEM((bkv, d), jnp.float32),
        ],
    )
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public op with custom VJP
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, scale, block_q, block_kv, interpret):
    out, _ = _flash_forward(q, k, v, causal, scale, block_q, block_kv,
                            interpret)
    return out


def _flash_fwd(q, k, v, causal, scale, block_q, block_kv, interpret):
    out, lse = _flash_forward(q, k, v, causal, scale, block_q, block_kv,
                              interpret)
    # Under jax.checkpoint the 'save_attn' policy keeps these two named
    # residuals, so the backward kernels run off the SAVED (out, lse)
    # instead of recomputing the whole flash forward inside the layer
    # remat (models/llama.py resolve_remat_policy).
    from jax.ad_checkpoint import checkpoint_name

    out_r = checkpoint_name(out, "attn_out")
    lse_r = checkpoint_name(lse, "attn_lse")
    return out, (q, k, v, out_r, lse_r)


def _flash_bwd(causal, scale, block_q, block_kv, interpret, res, g):
    q, k, v, out, lse = res
    return _flash_backward(q, k, v, out, lse, g, causal, scale, block_q,
                           block_kv, interpret)


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
    """q: [B, Hq, S, D]; k/v: [B, Hkv, Skv, D]; Hq % Hkv == 0 (GQA).
    ``block_q`` / ``block_kv`` given: all three kernels take them;
    left None: each takes its own from the shapes (``flash_blocks``)."""
    hq, d = q.shape[1], q.shape[-1]
    hkv = k.shape[1]
    if hq % hkv:
        raise ValueError(f"query heads {hq} not a multiple of kv heads {hkv}")
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    return _flash(q, k, v, causal, scale, block_q, block_kv, interpret)


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
    return _flash_forward(q, k, v, causal, scale, block_q, block_kv,
                          interpret, window, sink)


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
    return _flash_backward(q, k, v, out, lse, dout, causal, scale, block_q,
                           block_kv, interpret)
