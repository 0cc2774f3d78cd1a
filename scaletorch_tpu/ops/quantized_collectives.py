"""Int8 block-scaled gradient all-reduce — the quantized DCN edge.

Training gradients are the one tensor stream that crosses the slow
(DCN, inter-host) edge of the mesh every step, and they tolerate
aggressive quantization: following EQuARX (PAPERS.md — quantized
all-reduce inside XLA at a block granularity) and "The Big Send-off"
(bandwidth-optimal DCN collectives), this module implements the
all-reduce itself in int8 wire format with fp32 accumulation:

    quantize (per-block absmax scales)
      -> reduce-scatter as int8 + scales (one tiled all_to_all)
      -> dequantize + SUM IN FP32 (each rank reduces its owned chunk)
      -> re-quantize the reduced chunk
      -> all-gather as int8 + scales
      -> dequantize

Wire bytes per rank for N fp32 gradient elements over an n-rank axis:
plain fp32 all-reduce moves 2·N·(n-1)/n·4 bytes; this path moves
2·N·(n-1)/n·1 + 2·(N/block)·4 — a 4x reduction at the default
block=256 (scale overhead 1.6%). Accuracy: absmax int8 per block bounds
the element error by absmax/254 per quantization, applied twice
(scatter + gather legs); measured grad cosine similarity vs the fp32
path is >= 0.999 on real train steps (tests/ops/test_quantized_collectives.py).

The reduction itself is deterministic: chunk boundaries depend only on
(axis size, block size) and the fp32 accumulation sums source ranks in
index order (a single ``jnp.sum`` over the rank dim), so results are
bit-identical across runs and across host/process layouts of the same
logical mesh.

Everything is built from ``shard_map``-level collectives
(``all_to_all``/``all_gather``); the
per-axis selectability lives one level up: parallel/spmd.py keeps the
ICI-cheap axes (cp/ep/tp) in fp32 and routes only the configured
bandwidth-bound axis (default ``dp``) through here.
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp

# The Varying -> Invariant all-gather. jax 0.9.0 has it only under
# ``jax._src``: the public ``jax.lax.all_gather`` offers to="varying" or
# to="reduced", and "reduced" values have no reshape rule. No other
# collective both moves all-gather bytes and TYPES its result as
# identical on every member of the axis.
from jax._src.lax.parallel import all_gather_invariant

DEFAULT_BLOCK_SIZE = 256
_QMAX = 127.0  # symmetric int8

GRAD_ALLREDUCE_DTYPES = ("fp32", "bf16", "int8")


def quantize_blockwise(
    x: jax.Array, block_size: int = DEFAULT_BLOCK_SIZE
) -> Tuple[jax.Array, jax.Array]:
    """[M] fp32 (M % block_size == 0) -> (int8 [M/B, B], fp32 scales [M/B]).

    Symmetric per-block absmax: scale = absmax/127, q = round(x/scale).
    An all-zero block gets scale 1.0 (not 0) so dequantization never
    divides/multiplies by zero-derived garbage.
    """
    if x.ndim != 1 or x.shape[0] % block_size:
        raise ValueError(
            f"quantize_blockwise wants 1-D input padded to a multiple of "
            f"block_size={block_size}, got shape {x.shape}"
        )
    blocks = x.astype(jnp.float32).reshape(-1, block_size)
    absmax = jnp.max(jnp.abs(blocks), axis=-1)
    scales = jnp.where(absmax > 0, absmax / _QMAX, 1.0)
    q = jnp.clip(jnp.round(blocks / scales[:, None]), -_QMAX, _QMAX)
    return q.astype(jnp.int8), scales


def dequantize_blockwise(q: jax.Array, scales: jax.Array) -> jax.Array:
    """(int8 [..., nB, B], fp32 [..., nB]) -> fp32 [..., nB*B]."""
    deq = q.astype(jnp.float32) * scales[..., None]
    return deq.reshape(*q.shape[:-2], q.shape[-2] * q.shape[-1])


def _padded_len(n: int, ranks: int, block_size: int) -> int:
    unit = ranks * block_size
    return -(-n // unit) * unit


def quantized_pmean(
    x: jax.Array,
    axis: str,
    *,
    block_size: int = DEFAULT_BLOCK_SIZE,
    mean: bool = True,
) -> jax.Array:
    """Block-scaled int8 all-reduce(-mean) of ``x`` over mesh axis
    ``axis``. Call inside ``shard_map``; any shape/dtype in, fp32 out
    (same shape). The wire format is int8 everywhere; accumulation is
    fp32 (module docstring).
    """
    n = jax.lax.axis_size(axis)
    orig_shape = x.shape
    flat = x.astype(jnp.float32).ravel()
    padded = _padded_len(flat.shape[0], n, block_size)
    if padded != flat.shape[0]:
        pad = jnp.zeros(padded - flat.shape[0], jnp.float32)
        # fresh zeros are axis-invariant while ``x`` varies over the
        # mesh — align them or the concatenate is ill-typed
        pad = jax.lax.pvary(pad, tuple(jax.typeof(flat).vma))
        flat = jnp.concatenate([flat, pad])
    chunk = padded // n  # per-rank owned chunk, a multiple of block_size

    # leg 1 — reduce-scatter in int8: quantize all n chunks, tiled
    # all_to_all hands rank r every rank's chunk r.
    q, s = quantize_blockwise(flat, block_size)      # [padded/B, B], [padded/B]
    q = q.reshape(n, chunk // block_size, block_size)
    s = s.reshape(n, chunk // block_size)
    q = jax.lax.all_to_all(q, axis, split_axis=0, concat_axis=0, tiled=True)
    s = jax.lax.all_to_all(s, axis, split_axis=0, concat_axis=0, tiled=True)

    # fp32 accumulation of the owned chunk, source ranks in index order
    # (deterministic); mean divides here, while still in fp32.
    owned = jnp.sum(dequantize_blockwise(q, s), axis=0)  # [chunk]
    if mean:
        owned = owned / n

    # leg 2 — all-gather in int8: requantize the reduced chunk once,
    # circulate, dequantize. The gathered value is identical on every
    # member of ``axis`` and the invariant gather says so in the VMA
    # type (a plain all_gather is typed varying), which is what the
    # surrounding step's out_specs expect of a reduced gradient.
    q2, s2 = quantize_blockwise(owned, block_size)
    q2 = all_gather_invariant(q2, axis, axis=0, tiled=True)
    s2 = all_gather_invariant(s2, axis, axis=0, tiled=True)
    out = dequantize_blockwise(q2, s2)
    return out[: _size(orig_shape)].reshape(orig_shape)


def _size(shape) -> int:
    size = 1
    for d in shape:
        size *= int(d)
    return size


def reduced_pmean(x: jax.Array, axis: str, dtype: str,
                  *, block_size: int = DEFAULT_BLOCK_SIZE) -> jax.Array:
    """The per-dtype mean-reduction over one mesh axis: 'fp32' is a plain
    ``pmean``, 'bf16' halves the wire bytes by casting around the pmean,
    'int8' is the block-scaled path above. fp32 result either way."""
    if dtype == "fp32":
        return jax.lax.pmean(x.astype(jnp.float32), axis)
    if dtype == "bf16":
        return jax.lax.pmean(
            x.astype(jnp.bfloat16), axis).astype(jnp.float32)
    if dtype == "int8":
        return quantized_pmean(x, axis, block_size=block_size)
    raise ValueError(
        f"grad_allreduce_dtype must be one of {GRAD_ALLREDUCE_DTYPES}, "
        f"got {dtype!r}"
    )


# The HLO collective parser grew up here as this module's attestation
# backend but is analysis infrastructure shared by the byte-attestation
# test, tools/aot_cp_crossover.py and the deep-tier comm-budget gate;
# it now lives in analysis/hlo.py and is re-exported for back-compat.
from scaletorch_tpu.analysis.hlo import (  # noqa: E402,F401
    collective_wire_bytes,
)


def quantized_pmean_tree(
    grads: Any,
    axis: str,
    *,
    dtype: str = "int8",
    block_size: int = DEFAULT_BLOCK_SIZE,
) -> Any:
    """Mean-reduce a whole gradient tree over ``axis`` with ONE fused
    collective pair: leaves are raveled into a single fp32 vector (the
    bucketed-all-reduce layout, flattened to exactly one bucket — XLA
    pays per-collective latency once, not per leaf), reduced, and split
    back. fp32/bf16 fall back to per-leaf pmeans (XLA already fuses
    same-dtype pmeans; concatenation would only add copies)."""
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    if not leaves:
        return grads
    if dtype != "int8":
        return jax.tree_util.tree_unflatten(
            treedef,
            [reduced_pmean(g, axis, dtype, block_size=block_size)
             for g in leaves],
        )
    def _pad_to_block(v: jax.Array) -> jax.Array:
        # Per-leaf padding to a block boundary: without it a
        # small-magnitude leaf (norm scales, ~1e-4) sharing an absmax
        # block with a large-magnitude neighbor's tail (~1e-1) would
        # quantize to all-zeros — invisible in aggregate cosine metrics,
        # fatal for that parameter. Costs < block_size elements per leaf.
        rem = -v.shape[0] % block_size
        if not rem:
            return v
        pad = jax.lax.pvary(
            jnp.zeros(rem, jnp.float32), tuple(jax.typeof(v).vma))
        return jnp.concatenate([v, pad])

    segs = [_pad_to_block(g.astype(jnp.float32).ravel()) for g in leaves]
    red = quantized_pmean(
        jnp.concatenate(segs), axis, block_size=block_size)
    out, off = [], 0
    for g, seg in zip(leaves, segs):
        size = _size(g.shape)
        out.append(red[off: off + size].reshape(g.shape))
        off += seg.shape[0]
    return jax.tree_util.tree_unflatten(treedef, out)
