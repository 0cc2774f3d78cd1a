"""Grouped matmul over rows sorted by group, and the dropless expert
SwiGLU built on it.

``rows [M, K]`` hold each group's rows contiguously, ``group_sizes [G]``
says how many each has, ``weights [G, K, N]`` has one matrix a group:
``out[r] = rows[r] @ weights[group of r]``. Nothing is padded to a
per-group capacity, so memory is ``O(M (K + N))`` whatever the skew, and
no row is ever dropped. Rows past ``sum(group_sizes)`` belong to no
group; what comes back in them is unspecified (zeros from one form,
whatever the buffer held from the other) and the caller's to mask.

Two forms, chosen by the one kernel-vs-XLA predicate the repo has
(``ops/flash_attention._pallas_available``): on a TPU the megablox
``gmm`` Pallas kernel of ``jax.experimental.pallas.ops.tpu`` (it visits
only the row tiles that groups cover, so rows of no group cost nothing,
and it streams only the matrices of groups that have rows);
``jax.lax.ragged_dot`` elsewhere. Tiles come from the shapes
(``_gmm_tiling``); PERF.md, PR 27 has the on-chip table behind them.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from scaletorch_tpu.models.layers import swiglu
from scaletorch_tpu.ops.flash_attention import _pallas_available

# row tile of the Pallas form: M is padded up to a multiple of it
_ROW_TILE = 512
# A decode step's M is slots x choices: up to _DECODE_ROWS rows, a few a
# group. megablox multiplies a whole row tile for every group the tile
# meets, 2 tm K N operations for the group's 2 K N bytes of weights, so
# such a call is bound by the MXU and not by HBM once tm passes ~240
# rows on a v5e (197 TFLOP/s over 819 GB/s). Up to _STREAMING_ROW_TILE
# the tile is the whole padded M (every decode call this repo had before
# 64 slots x 10 choices came: 64 to 256 rows); past it a decode call
# takes 128-row tiles (640 rows of 36 groups x [4096, 768]: 0.69 ms a
# call at 512, 0.40 at 128, the same values; PERF.md, PR 61)
_DECODE_ROWS = 1024
_STREAMING_ROW_TILE = 256

# The dropless expert layer gathers its (token, choice) rows sorted by
# expert, ``[N k, H]``, and brings them back as ``f32[N, k, H]``: up to
# _SORTED_ROWS_WHOLE bytes of sorted rows it does so for all N tokens
# at once (every call this repo had compiled before a hidden size of
# 7680 came: the largest, a prefill of 24,576 tokens x 8 choices at
# hidden 2048, is 0.81 GB); past it the tokens go through in equal
# blocks of at most _SORTED_ROWS_BLOCK bytes of sorted rows each
# (24,576 x 8 x 7680 in bfloat16 is 3.02 GB of rows and 6.04 GB of what
# comes back: twelve blocks of 2,048 tokens, 0.25 + 0.5 GB alive).
_SORTED_ROWS_WHOLE = 1 << 30
_SORTED_ROWS_BLOCK = 1 << 28


def token_blocks(n: int, k: int, hidden: int, itemsize: int) -> int:
    """In how many equal blocks of tokens ``dropless_expert_mlp`` runs
    ``n`` tokens of ``k`` choices: 1 up to ``_SORTED_ROWS_WHOLE`` bytes
    of sorted rows, else the fewest that divide ``n`` and keep a
    block's rows within ``_SORTED_ROWS_BLOCK``. Static shapes in, one
    integer out."""
    row_bytes = k * hidden * itemsize
    if n * row_bytes <= _SORTED_ROWS_WHOLE:
        return 1
    return next(b for b in range(2, n + 1)
                if n % b == 0 and (n // b) * row_bytes <= _SORTED_ROWS_BLOCK)


# the widest K / N tile taken where 1,024 does not divide the width
_TILE_CAP = 1152


def _width_tile(width: int) -> int:
    """The K or N tile of a ``width``: 1,024 (the whole width below it),
    which megablox masks the remainder of. Where that remainder leaves
    over an eighth of the tiles' span empty (2,304 = 2.25 tiles: a
    quarter of the three; 7,680 = 7.5 tiles: a sixteenth of the eight,
    which stays as it is), the widest multiple of 128 up to
    ``_TILE_CAP`` that divides the width (2,304: 1,152)."""
    if width <= 1024:
        return width
    span = -(-width // 1024) * 1024
    if 8 * (span - width) <= span:
        return 1024
    return max((t for t in range(128, _TILE_CAP + 1, 128)
                if width % t == 0), default=1024)


def _gmm_tiling(m: int, k: int, n: int) -> Tuple[int, int, int]:
    """(tm, tk, tn) of the megablox kernel for an [m, k] x [G, k, n]
    product. A decode step has at most a few rows a group, so its row
    tile is the whole (padded) M, or 128 rows where that would pass
    ``_STREAMING_ROW_TILE``, and the K/N tiles are as large as
    VMEM takes: the call is weight streaming. A prefill call has
    hundreds of rows a group and takes the square-ish MXU tiles."""
    tm = min(_ROW_TILE, -(-m // 128) * 128)
    if _STREAMING_ROW_TILE < tm and m <= _DECODE_ROWS:
        tm = 128
    return tm, _width_tile(k), _width_tile(n)


def ragged_matmul(rows, weights, group_sizes, *, out_dtype=None):
    """The XLA form: ``jax.lax.ragged_dot``."""
    return jax.lax.ragged_dot(
        rows, weights, group_sizes.astype(jnp.int32),
        preferred_element_type=jnp.float32).astype(out_dtype or rows.dtype)


def pallas_matmul(rows, weights, group_sizes, *, out_dtype=None,
                  tiling: Optional[Tuple[int, int, int]] = None,
                  interpret: bool = False):
    """The Pallas form: megablox ``gmm`` under ``tiling`` (default: from
    the shapes). ``interpret`` runs the kernel off the chip (the tests'
    value check of a tiling)."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    out_dtype = out_dtype or rows.dtype
    group_sizes = group_sizes.astype(jnp.int32)
    m, k = rows.shape
    n = weights.shape[-1]
    tm, tk, tn = tiling or _gmm_tiling(m, k, n)
    padded = -(-m // tm) * tm
    if padded != m:
        rows = jnp.pad(rows, ((0, padded - m), (0, 0)))
    with jax.named_scope("moe_grouped_matmul"):
        out = gmm(rows, weights, group_sizes,
                  preferred_element_type=out_dtype, tiling=(tm, tk, tn),
                  interpret=interpret)
    # row tiles no group covers are never visited, so never written
    return out[:m]


def grouped_matmul(rows: jax.Array, weights: jax.Array,
                   group_sizes: jax.Array, *,
                   out_dtype: Any = None) -> jax.Array:
    """``rows [M, K]`` (sorted by group) x ``weights [G, K, N]`` ->
    ``[M, N]``; float32 accumulation, result in ``out_dtype`` (default:
    the rows' dtype)."""
    form = pallas_matmul if _pallas_available() else ragged_matmul
    return form(rows, weights, group_sizes, out_dtype=out_dtype)


def dropless_expert_mlp(
    x: jax.Array,
    gate_idx: jax.Array,
    gate_w: jax.Array,
    gate_proj: jax.Array,
    up_proj: jax.Array,
    down_proj: jax.Array,
    *,
    live: Optional[jax.Array] = None,
    held: Optional[jax.Array] = None,
    layer: Optional[jax.Array] = None,
    compute_dtype: Any = None,
    matmul=None,
) -> Tuple[jax.Array, jax.Array]:
    """Every (token, choice) through its expert's SwiGLU, none dropped.

    x [N, H]; gate_idx / gate_w [N, k] (expert ids and the weights their
    outputs are summed under); gate/up_proj [E, H, I], down_proj
    [E, I, H]; ``live`` [N] bool marks the tokens that exist (a serving
    step's inactive slots and padding positions do not): a dead token's
    rows are sorted past every group, cost no expert work, count in no
    load and come back as zeros. ``held`` [N, k] bool marks the choices
    whose expert is one of the E held here (a chip's share of an expert
    layer: ``gate_idx`` counts from the first held expert, and an id
    outside [0, E) of a choice not held is never read): a choice held
    elsewhere is sorted past every group like a dead token's rows and
    adds zeros, and the weights of the held ones stay as they are given.
    With ``layer`` (an int32 scalar) the
    projections are whole layer stacks, [L, E, H, I] and [L, E, I, H],
    and that layer's experts are used: the stack goes to the grouped
    matmul as [L E, ...] with every other layer's groups empty, so a
    layer scan that closes over the stack never copies a layer's 0.8 GB
    of experts out of it (a Pallas call takes no fused slice; PERF.md,
    PR 27). ``matmul`` replaces ``grouped_matmul``
    (``tools/bench_dropless_moe.py`` times the forms through it).
    Past ``_SORTED_ROWS_WHOLE`` bytes of sorted rows the tokens run in
    ``token_blocks`` equal blocks, one after the other.
    Returns (y [N, H] in the compute dtype, rows per expert [E] int32).
    """
    cdt = compute_dtype or x.dtype
    blocks = token_blocks(x.shape[0], gate_idx.shape[-1], x.shape[1],
                          jnp.dtype(cdt).itemsize)
    if blocks > 1:
        # dropless still: every block routes all its rows; only the
        # sorted copies are bounded (``token_blocks``)
        def one(block):
            rows, idx, w, alive, here = block
            return dropless_expert_mlp(
                rows, idx, w, gate_proj, up_proj, down_proj, live=alive,
                held=here, layer=layer, compute_dtype=compute_dtype,
                matmul=matmul)

        def cut(a):
            return None if a is None else a.reshape(
                (blocks, a.shape[0] // blocks) + a.shape[1:])

        y, sizes = jax.lax.map(
            one, tuple(cut(a) for a in (x, gate_idx, gate_w, live, held)))
        return y.reshape(x.shape[0], -1), jnp.sum(sizes, axis=0)
    matmul = matmul or grouped_matmul
    n, hid = x.shape
    k = gate_idx.shape[-1]
    e = gate_proj.shape[-3]
    with jax.named_scope("moe.sort"):
        ids = gate_idx.reshape(-1).astype(jnp.int32)
        computed = None if live is None else jnp.repeat(live, k)
        if held is not None:
            computed = (held.reshape(-1) if computed is None
                        else computed & held.reshape(-1))
        if computed is not None:
            ids = jnp.where(computed, ids, e)                # e: no group
        # stable: an expert's rows keep token order
        order = jnp.argsort(ids, stable=True)
        group_sizes = jnp.sum(
            ids[:, None] == jnp.arange(e, dtype=jnp.int32)[None, :],
            axis=0, dtype=jnp.int32)
        rows = x.astype(cdt)[order // k]                     # [N k, H]
    with jax.named_scope("moe.experts"):
        sizes = group_sizes
        if layer is not None:
            stack = gate_proj.shape[0]
            sizes = jax.lax.dynamic_update_slice(
                jnp.zeros(stack * e, jnp.int32), group_sizes, (layer * e,))
            gate_proj, up_proj, down_proj = (
                w.reshape((stack * e,) + w.shape[2:])
                for w in (gate_proj, up_proj, down_proj))
        gate = matmul(rows, gate_proj.astype(cdt), sizes)
        up = matmul(rows, up_proj.astype(cdt), sizes)
        out = matmul(swiglu(gate, up), down_proj.astype(cdt), sizes)
    with jax.named_scope("moe.combine"):
        inverse = jnp.zeros(n * k, jnp.int32).at[order].set(
            jnp.arange(n * k, dtype=jnp.int32))
        back = out[inverse].reshape(n, k, hid).astype(jnp.float32)
        if computed is not None:
            # rows of no group: what a form left in them (NaN, perhaps)
            # is selected away, never multiplied
            back = jnp.where(computed.reshape(n, k, 1), back, 0)
        y = jnp.sum(back * gate_w[..., None].astype(jnp.float32), axis=1)
    return y.astype(cdt), group_sizes
