"""One decode step of a Mamba-2 layer's state, every slot in one pass.

The state of a layer is ``f32[slots, N, C]``: ``N = mamba_d_state`` rows
of ``C = heads x head channels`` with the CHANNELS ON THE LANES
(``models/granite_moe_hybrid.py``: a head's ``[P, N]`` matrix
transposed and the heads side by side, so that what is one number a
channel, the decay ``a`` and the drive ``dt x``, is a row vector, and
what is one number a state row, ``B`` and ``C``, a column). For a slot:

    S <- a * (S if keep else 0) + B dx^T          [N, C]
    y = C^T S                                     [C]

The step is bound by reading and writing the state (2 x 268 MB a layer
at 64 slots of [128, 8192]); written in XLA the sum over ``N`` and the
write of ``S`` are two fusions, each of which reads the state (three
passes, found in the compiled step: PERF.md, PR 61). Here a grid step
holds one ``[N, block]`` tile of one slot: read once, written once in
place (the whole ``[layers, slots, N, C]`` buffer is aliased to the
result and only ``layer``'s tiles are visited), ``y`` summed over the
tile's rows before it leaves VMEM. Five vector operations an element,
no matrix unit, no transcendental: the exponentials are per head and
come in ``a``.

``a`` and ``dx`` ride one ``[slots, 3, C]`` operand with ``keep`` (1 or
0: a slot at position 0 starts from an empty state whatever its buffer
holds, a NaN too); ``B`` and ``C`` arrive as ``[slots, N, 1]`` columns,
which the chip stores 128 lanes wide (4 MB each at 64 slots: 1.5 % of
the state's traffic). A slot that is not written is told ``a = 1, dx =
0, keep = 1`` by the caller and keeps its state bit for bit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
# channels of one grid step, the largest that divides C: a [128, 2048]
# float32 tile is 1 MB, 4 MB with its result and both double-buffered
_BLOCKS = (2048, 1024, 512, 256, 128)
F32 = jnp.float32


def kernel_serves(n_state: int, channels: int) -> bool:
    """Whether the kernel takes a state of ``[n_state, channels]`` a
    slot: whole vector registers."""
    return n_state % SUBLANES == 0 and channels % LANES == 0


def _ssd_update_kernel(vec_ref, b_ref, c_ref, s_ref, y_ref, o_ref):
    a, dx, keep = vec_ref[0:1, :], vec_ref[1:2, :], vec_ref[2:3, :]
    old = jnp.where(keep > 0.0, s_ref[...], 0.0)
    new = a * old + b_ref[...] * dx
    o_ref[...] = new
    y_ref[...] = jnp.sum(new * c_ref[...], axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("layer", "interpret"))
def ssd_state_update(state: jax.Array, a: jax.Array, dx: jax.Array,
                     keep: jax.Array, bm: jax.Array, cm: jax.Array, *,
                     layer: int, interpret: bool = False):
    """``state`` ``f32[layers, slots, N, C]`` whole, of which ``layer``
    is advanced one token: ``a``, ``dx`` ``[slots, C]``, ``keep``
    ``[slots]`` bool, ``bm``, ``cm`` ``[slots, N]`` -> (y ``[slots, C]``
    float32, the state with ``layer`` written in place: donate it)."""
    layers, slots, n_state, channels = state.shape
    if not kernel_serves(n_state, channels):
        raise ValueError(
            f"ssd_state_update takes a state of [8k, {LANES}m] a slot, got "
            f"[{n_state}, {channels}]")
    if not 0 <= layer < layers:
        raise ValueError(f"layer {layer} of {layers}")
    block = next(b for b in _BLOCKS if channels % b == 0)
    vec = jnp.stack(
        [a.astype(F32), dx.astype(F32),
         jnp.broadcast_to(keep.astype(F32)[:, None], a.shape)], axis=1)
    rows = pl.BlockSpec((None, 3, block), lambda i, j: (i, 0, j))
    column = pl.BlockSpec((None, n_state, 1), lambda i, j: (i, 0, 0))
    tile = pl.BlockSpec((None, None, n_state, block),
                        lambda i, j: (layer, i, 0, j))
    y, new_state = pl.pallas_call(
        _ssd_update_kernel,
        grid=(slots, channels // block),
        in_specs=[rows, column, column, tile],
        out_specs=[pl.BlockSpec((None, 1, block), lambda i, j: (i, 0, j)),
                   tile],
        out_shape=[jax.ShapeDtypeStruct((slots, 1, channels), F32),
                   jax.ShapeDtypeStruct(state.shape, F32)],
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=32 * 2 ** 20),
        interpret=interpret,
        name="ssd_state_update",
    )(vec, bm.astype(F32)[:, :, None], cm.astype(F32)[:, :, None],
      state.astype(F32))
    return y[:, 0], new_state
