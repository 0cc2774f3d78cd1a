"""Pallas TPU kernel for the selective scan of a state-space layer.

Mamba-1's recurrence is elementwise in (channel c, state index n):

    S_t[n, c] = exp(dt_t[c] A[n, c]) S_{t-1}[n, c] + dt_t[c] u_t[c] B_t[n]
    y_t[c]    = sum_n S_t[n, c] C_t[n]

so a prompt of S rows is S dependent steps over ``N x channels`` numbers
and no matrix product at all: one ``exp`` (EUP) and a handful of float32
multiplies and adds (VPU) per state element and token. Written as array
code the operands ``exp(dt A)`` and ``dt B u`` are ``f32[rows, channels,
N]``, 8 GB each at 8 x 3,072 rows of 5,120 channels; here they exist one
vector register at a time.

**Layout.** One grid step owns 1,024 channels, and inside the kernel a
token's 1,024 values of ``dt`` / ``u`` / ``y`` are ONE ``[8, 128]``
float32 vector register (8 rows of 128 channels), the state of the
block N registers (16), ``A`` N more: the whole recurrence of a block
lives in registers for a chunk of ``block_t`` tokens, the loop carry of a
``fori_loop``. ``B_t[n]`` and ``C_t[n]`` are the same for every channel:
scalars, read from SMEM and splat, so the sum over n is N multiply-adds
of whole registers and needs no reduction across sublanes or lanes. The
state and ``A`` are held in that view, ``[N, R, 128]`` with ``channels =
R * 128`` (``models/jamba.py``: the cache keeps the state so). ``u``,
``dt`` and ``y`` stay ``[S, channels]`` as the projections around the
call produce and read them: a block is ``[block_t, 1024]``, and a
token's row ``[1, 1024]`` is re-laid to ``[8, 128]`` in registers (and
``y`` back). Viewed ``[S, R, 128]`` in HBM instead, XLA re-lays all
three arrays around every call, since an (8, 128)-tiled ``[.., 5120]``
is no bitcast of ``[.., 40, 128]``: 4.6 ms a layer at 8 x 3,072 rows, as
much as the kernel itself (PERF.md, PR 47).

**Grid** ``(sequences, R / 8, S / block_t)``: the last axis is the
sequential one. The state's output block keeps the same index along it,
so it stays in VMEM from the first chunk of a (sequence, channel block)
to the last and is the accumulator: it is set from the incoming state at
chunk 0 and written back to HBM once. A row with ``dt = 0`` is the
identity (``exp(0) = 1``, ``dt B u = 0``): how a caller masks padding.

HBM traffic of a call: ``u``, ``dt`` in and ``y`` out once (12 bytes a
row and channel), B and C once per channel block, the state in and out
once. The kernel is bound by the VPU / EUP, not by that traffic
(benchmarks/costs/jamba.md).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
# channels of one grid step: one vector register a token
_BLOCK_C = SUBLANES * LANES
# tokens of one grid step: three [block_t, 1024] float32 blocks
# (u, dt, y), double-buffered, are 6 x block_t x 4 KB of VMEM (3 MB at
# 128) and two [block_t, N] blocks of scalars 16 KB of SMEM
BLOCK_T = 128
F32 = jnp.float32


def kernel_serves(rows: int, lanes: int) -> bool:
    """Whether the kernel takes a channel view ``[rows, lanes]``: whole
    vector registers, 8 sublanes of 128 lanes."""
    return lanes == LANES and rows % SUBLANES == 0


def _ssm_scan_kernel(b_ref, c_ref, u_ref, dt_ref, a_ref, s0_ref, y_ref,
                     s_ref, *, block_t: int, n_state: int):
    @pl.when(pl.program_id(2) == 0)
    def _first_chunk():
        s_ref[...] = s0_ref[...]

    a = tuple(a_ref[n] for n in range(n_state))
    register = (SUBLANES, LANES)

    def token(t, state):
        row = pl.ds(t, 1)
        dt = dt_ref[row, :].reshape(register)
        drive = dt * u_ref[row, :].reshape(register)
        y = jnp.zeros_like(dt)
        new = []
        for n in range(n_state):
            s = jnp.exp(dt * a[n]) * state[n] + drive * b_ref[t, n]
            y = y + s * c_ref[t, n]
            new.append(s)
        y_ref[row, :] = y.reshape(1, _BLOCK_C)
        return tuple(new)

    state = jax.lax.fori_loop(
        0, block_t, token, tuple(s_ref[n] for n in range(n_state)))
    for n in range(n_state):
        s_ref[n] = state[n]


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def ssm_scan_fwd(u: jax.Array, dt: jax.Array, a: jax.Array, bm: jax.Array,
                 cm: jax.Array, state: jax.Array, *, block_t: int = BLOCK_T,
                 interpret: bool = False):
    """The selective scan of S rows from ``state``: u, dt ``[B, S,
    channels]``, a ``[N, R, 128]`` (negative; ``channels = R * 128``),
    bm, cm ``[B, S, N]``, state ``[B, N, R, 128]``, all float32 -> (y
    ``[B, S, channels]`` without the ``D u`` skip, the state after row
    S - 1). S of any length: padded here with rows of ``dt = 0``."""
    b, s, channels = u.shape
    n_state, rows, lanes = a.shape
    if not kernel_serves(rows, lanes) or channels != rows * lanes:
        raise ValueError(
            f"ssm_scan_fwd takes channels as [8k, {LANES}], got "
            f"[{rows}, {lanes}] for {channels} channels")
    block_t = min(block_t, -(-s // SUBLANES) * SUBLANES)
    pad = -s % block_t
    if pad:
        u, dt, bm, cm = (
            jnp.pad(x, [(0, 0), (0, pad), (0, 0)]) for x in (u, dt, bm, cm))
    steps = (s + pad) // block_t

    tokens = pl.BlockSpec((None, block_t, _BLOCK_C),
                          lambda b_, r, j: (b_, j, r))
    scalars = pl.BlockSpec((None, block_t, n_state),
                           lambda b_, r, j: (b_, j, 0),
                           memory_space=pltpu.SMEM)
    held = pl.BlockSpec((None, n_state, SUBLANES, LANES),
                        lambda b_, r, j: (b_, 0, r, 0))
    y, new_state = pl.pallas_call(
        functools.partial(_ssm_scan_kernel, block_t=block_t,
                          n_state=n_state),
        grid=(b, rows // SUBLANES, steps),
        in_specs=[
            scalars, scalars, tokens, tokens,
            pl.BlockSpec((n_state, SUBLANES, LANES),
                         lambda b_, r, j: (0, r, 0)),
            held,
        ],
        out_specs=[tokens, held],
        out_shape=[jax.ShapeDtypeStruct(u.shape, F32),
                   jax.ShapeDtypeStruct(state.shape, F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssm_scan_fwd",
    )(bm.astype(F32), cm.astype(F32), u.astype(F32), dt.astype(F32),
      a.astype(F32), state.astype(F32))
    return y[:, :s], new_state
