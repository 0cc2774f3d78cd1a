"""Grouped per-expert SwiGLU MLP — Pallas TPU kernel with slot skipping.

The role of the reference's ``npu_grouped_matmul`` fused expert compute
(reference models/npu_patch.py:94-131): each expert applies its own
gate/up/down projection to its dispatched token slots. The XLA path
(parallel/expert_parallel.moe_mlp) runs one batched einsum over ALL
[E, G, C] capacity slots — MXU-dense but paying full price for padding:
capacity dispatch fills each (expert, group) block's slots as a PREFIX
(position-in-expert is a running count, expert_parallel.top_k_routing),
so slots beyond the fill count are zeros that still burn FLOPs.

This kernel walks (expert, group, slot-tile, intermediate-tile) and
**predicates whole slot-tiles off when the (e, g) fill count ends before
them** — the flash kernel's causal-skip idea applied to expert load. At
capacity factor c and balanced routing ~1 - 1/c of slot FLOPs are
padding (20% at c=1.25); under imbalance the skip grows to whatever the
cold experts leave empty.

The backward is two kernels with the same slot skip — a dx kernel
(reduction over I innermost) and a dW kernel (reduction over (group,
slot-tile) innermost), mirroring flash attention's dq/dkv split: every
output's reduction axes must be the innermost grid dims so its scratch
accumulator survives the sweep. Numerics: fp32 accumulation, bf16 MXU
feeds; ``masked_grouped_mlp`` is the dense XLA reference (and the
off-TPU execution path).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from scaletorch_tpu.models.layers import swiglu


def _struct(shape, dtype, like):
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def _pick_block(n: int, preferred: int) -> int:
    b = min(preferred, n)
    while n % b:
        b //= 2
    return max(b, 1)


def _semantics(*dims):
    """'p' = parallel grid dim, 'a' = arbitrary (sequential reduction dim
    carrying a scratch accumulator) — see ops/pallas/flash.py."""
    m = {"p": pltpu.PARALLEL, "a": pltpu.ARBITRARY}
    return pltpu.CompilerParams(
        dimension_semantics=tuple(m[d] for d in dims))


def _kernel(count_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref, acc_sc,
            *, bc, bi, ni):
    c_t = pl.program_id(2)  # slot tile within the (e, g) block
    i_t = pl.program_id(3)  # intermediate tile (reduction over I)

    @pl.when(i_t == 0)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc)

    # this (e, g) block's fill count arrives as its own [1,1,1,1] block
    # (static indexing — dynamic SMEM-table lookups trip shard_map's
    # varying-axes checker in interpret mode)
    count = count_ref[0, 0, 0, 0]
    # whole slot-tile beyond this (expert, group)'s filled prefix -> skip
    @pl.when(c_t * bc < count)
    def _block():
        x = x_ref[0, 0]        # [bc, H]
        g = jax.lax.dot_general(
            x, wg_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bc, bi]
        u = jax.lax.dot_general(
            x, wu_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        h = swiglu(g, u).astype(x.dtype)
        acc_sc[:] += jax.lax.dot_general(
            h, wd_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bc, H]

    @pl.when(i_t == ni - 1)
    def _finalize():
        # zero the partial tile's rows past the fill count (their inputs
        # are zeros anyway, but swiglu(0,0) @ wd is exactly 0 only in
        # exact arithmetic — make it structural)
        row = c_t * bc + jax.lax.broadcasted_iota(
            jnp.int32, acc_sc.shape, 0)
        o_ref[0, 0] = jnp.where(row < count, acc_sc[:], 0.0).astype(o_ref.dtype)


def _block_grads(x, wg, wu, wd, do):
    """Shared per-tile backward math: recompute gate/up/silu in fp32 and
    return (s, dg, du) for the dx and dW kernels.

    s  = silu(g)·u (the down-projection input)
    dS = dO · Wd^T;  du = dS·silu(g);  dg = dS·u·silu'(g)
    with silu'(g) = σ(g)·(1 + g·(1 − σ(g))).
    """
    g = jax.lax.dot_general(
        x, wg, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    u = jax.lax.dot_general(
        x, wu, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    sig = jax.nn.sigmoid(g)
    silu = g * sig
    s = silu * u
    ds = jax.lax.dot_general(
        do, wd, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    du = ds * silu
    dg = ds * u * (sig * (1.0 + g * (1.0 - sig)))
    return s, dg, du


def _dx_kernel(count_ref, x_ref, wg_ref, wu_ref, wd_ref, do_ref, dx_ref,
               acc_sc, *, bc, bi, ni):
    c_t = pl.program_id(2)
    i_t = pl.program_id(3)

    @pl.when(i_t == 0)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc)

    count = count_ref[0, 0, 0, 0]

    @pl.when(c_t * bc < count)
    def _block():
        x = x_ref[0, 0]
        _, dg, du = _block_grads(x, wg_ref[0], wu_ref[0], wd_ref[0],
                                 do_ref[0, 0])
        acc_sc[:] += jax.lax.dot_general(
            dg.astype(x.dtype), wg_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_sc[:] += jax.lax.dot_general(
            du.astype(x.dtype), wu_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(i_t == ni - 1)
    def _finalize():
        row = c_t * bc + jax.lax.broadcasted_iota(jnp.int32, acc_sc.shape, 0)
        dx_ref[0, 0] = jnp.where(row < count, acc_sc[:], 0.0).astype(
            dx_ref.dtype)


def _dw_kernel(counts_ref, x_ref, wg_ref, wu_ref, wd_ref, do_ref,
               dwg_ref, dwu_ref, dwd_ref, dwg_sc, dwu_sc, dwd_sc,
               *, bc, bi, ng, nc):
    g_t = pl.program_id(2)
    c_t = pl.program_id(3)

    @pl.when((g_t == 0) & (c_t == 0))
    def _init():
        dwg_sc[:] = jnp.zeros_like(dwg_sc)
        dwu_sc[:] = jnp.zeros_like(dwu_sc)
        dwd_sc[:] = jnp.zeros_like(dwd_sc)

    count = counts_ref[0, 0, 0, 0]

    @pl.when(c_t * bc < count)
    def _block():
        x = x_ref[0, 0]
        do = do_ref[0, 0]
        # mask the covering tile's rows past the fill count: upstream
        # cotangents of structurally-zero outputs must not train weights
        # (parity with masked_grouped_mlp's where-mask VJP)
        row = c_t * bc + jax.lax.broadcasted_iota(jnp.int32, do.shape, 0)
        do = jnp.where(row < count, do, 0.0)
        s, dg, du = _block_grads(x, wg_ref[0], wu_ref[0], wd_ref[0], do)
        dwg_sc[:] += jax.lax.dot_general(
            x, dg.astype(x.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dwu_sc[:] += jax.lax.dot_general(
            x, du.astype(x.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dwd_sc[:] += jax.lax.dot_general(
            s.astype(x.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when((g_t == ng - 1) & (c_t == nc - 1))
    def _finalize():
        dwg_ref[0] = dwg_sc[:].astype(dwg_ref.dtype)
        dwu_ref[0] = dwu_sc[:].astype(dwu_ref.dtype)
        dwd_ref[0] = dwd_sc[:].astype(dwd_ref.dtype)


def _backward(x, counts, wg, wu, wd, do, bc, bi, interpret):
    """Slot-skipping backward: a dx kernel (reduction over I innermost)
    and a dW kernel (reduction over (group, slot-tile) innermost) — the
    same two-kernel split flash attention's backward uses, because each
    output's reduction axes must be the innermost grid dims."""
    e, g, c, h = x.shape
    i_dim = wg.shape[-1]
    nc, ni = c // bc, i_dim // bi
    counts4 = counts.reshape(e, g, 1, 1)

    dx = pl.pallas_call(
        functools.partial(_dx_kernel, bc=bc, bi=bi, ni=ni),
        grid=(e, g, nc, ni),
        compiler_params=_semantics("p", "p", "p", "a"),
        in_specs=[
            pl.BlockSpec((1, 1, 1, 1), lambda e_, g_, c_, i_: (e_, g_, 0, 0)),
            pl.BlockSpec((1, 1, bc, h), lambda e_, g_, c_, i_: (e_, g_, c_, 0)),
            pl.BlockSpec((1, h, bi), lambda e_, g_, c_, i_: (e_, 0, i_)),
            pl.BlockSpec((1, h, bi), lambda e_, g_, c_, i_: (e_, 0, i_)),
            pl.BlockSpec((1, bi, h), lambda e_, g_, c_, i_: (e_, i_, 0)),
            pl.BlockSpec((1, 1, bc, h), lambda e_, g_, c_, i_: (e_, g_, c_, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bc, h),
                               lambda e_, g_, c_, i_: (e_, g_, c_, 0)),
        out_shape=_struct((e, g, c, h), x.dtype, x),
        scratch_shapes=[pltpu.VMEM((bc, h), jnp.float32)],
        interpret=interpret,
    )(counts4, x, wg, wu, wd, do)

    dwg, dwu, dwd = pl.pallas_call(
        functools.partial(_dw_kernel, bc=bc, bi=bi, ng=g, nc=nc),
        grid=(e, i_dim // bi, g, nc),
        compiler_params=_semantics("p", "p", "a", "a"),
        in_specs=[
            pl.BlockSpec((1, 1, 1, 1), lambda e_, i_, g_, c_: (e_, g_, 0, 0)),
            pl.BlockSpec((1, 1, bc, h), lambda e_, i_, g_, c_: (e_, g_, c_, 0)),
            pl.BlockSpec((1, h, bi), lambda e_, i_, g_, c_: (e_, 0, i_)),
            pl.BlockSpec((1, h, bi), lambda e_, i_, g_, c_: (e_, 0, i_)),
            pl.BlockSpec((1, bi, h), lambda e_, i_, g_, c_: (e_, i_, 0)),
            pl.BlockSpec((1, 1, bc, h), lambda e_, i_, g_, c_: (e_, g_, c_, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, h, bi), lambda e_, i_, g_, c_: (e_, 0, i_)),
            pl.BlockSpec((1, h, bi), lambda e_, i_, g_, c_: (e_, 0, i_)),
            pl.BlockSpec((1, bi, h), lambda e_, i_, g_, c_: (e_, i_, 0)),
        ],
        out_shape=[
            _struct(wg.shape, wg.dtype, wg),
            _struct(wu.shape, wu.dtype, wu),
            _struct(wd.shape, wd.dtype, wd),
        ],
        scratch_shapes=[
            pltpu.VMEM((h, bi), jnp.float32),
            pltpu.VMEM((h, bi), jnp.float32),
            pltpu.VMEM((bi, h), jnp.float32),
        ],
        interpret=interpret,
    )(counts4, x, wg, wu, wd, do)
    return dx, dwg, dwu, dwd


def _forward(x, counts, wg, wu, wd, bc, bi, interpret):
    e, g, c, h = x.shape
    i_dim = wg.shape[-1]
    nc, ni = c // bc, i_dim // bi
    grid = (e, g, nc, ni)
    counts4 = counts.reshape(e, g, 1, 1)
    return pl.pallas_call(
        functools.partial(_kernel, bc=bc, bi=bi, ni=ni),
        grid=grid,
        compiler_params=_semantics("p", "p", "p", "a"),
        in_specs=[
            pl.BlockSpec((1, 1, 1, 1), lambda e_, g_, c_, i_: (e_, g_, 0, 0)),
            pl.BlockSpec((1, 1, bc, h), lambda e_, g_, c_, i_: (e_, g_, c_, 0)),
            pl.BlockSpec((1, h, bi), lambda e_, g_, c_, i_: (e_, 0, i_)),
            pl.BlockSpec((1, h, bi), lambda e_, g_, c_, i_: (e_, 0, i_)),
            pl.BlockSpec((1, bi, h), lambda e_, g_, c_, i_: (e_, i_, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bc, h),
                               lambda e_, g_, c_, i_: (e_, g_, c_, 0)),
        out_shape=_struct((e, g, c, h), x.dtype, x),
        scratch_shapes=[pltpu.VMEM((bc, h), jnp.float32)],
        interpret=interpret,
    )(counts4, x, wg, wu, wd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def grouped_swiglu_mlp(x, counts, wg, wu, wd, bc=256, bi=512,
                       interpret=False):
    """x: [E, G, C, H] capacity slots (prefix-filled per (e, g));
    counts: [E, G] int32 fill counts; wg/wu: [E, H, I]; wd: [E, I, H].
    Returns [E, G, C, H]; rows at or past the fill count are zero."""
    if pl is None:
        raise RuntimeError(
            "the grouped-MLP kernel needs jax.experimental.pallas; this "
            "jax build lacks it — use masked_grouped_mlp"
        )
    bc = _pick_block(x.shape[2], bc)
    bi = _pick_block(wg.shape[-1], bi)
    return _forward(x, counts, wg, wu, wd, bc, bi, interpret)


def masked_grouped_mlp(x, counts, wg, wu, wd):
    """The dense numeric reference AND the non-TPU execution path:
    einsum with the past-count rows structurally zeroed (exactly the
    kernel's output; its autodiff is what the Pallas backward kernels
    are parity-tested against). Interpret-mode pallas inside a
    large sharded program trips a JAX closed_call lowering-cache bug, so
    off-TPU callers take this path while the kernel itself is validated
    by interpret-mode parity tests and Mosaic AOT compilation."""
    e, g, c, h = x.shape
    mask = (jnp.arange(c)[None, None, :] < counts[..., None])[..., None]
    x = jnp.where(mask, x, 0)
    gate = jnp.einsum("egch,ehi->egci", x, wg)
    up = jnp.einsum("egch,ehi->egci", x, wu)
    out = jnp.einsum("egci,eih->egch", swiglu(gate, up), wd)
    return jnp.where(mask, out, 0)


def _fwd(x, counts, wg, wu, wd, bc, bi, interpret):
    out = grouped_swiglu_mlp(x, counts, wg, wu, wd, bc, bi, interpret)
    return out, (x, counts, wg, wu, wd)


def _bwd(bc, bi, interpret, res, g_out):
    x, counts, wg, wu, wd = res
    bc = _pick_block(x.shape[2], bc)
    bi = _pick_block(wg.shape[-1], bi)
    dx, dwg, dwu, dwd = _backward(x, counts, wg, wu, wd, g_out, bc, bi,
                                  interpret)
    return dx, None, dwg, dwu, dwd


grouped_swiglu_mlp.defvjp(_fwd, _bwd)


def slot_fill_counts(dispatch: jax.Array) -> jax.Array:
    """[G, N, E, C] (or [N, E, C]) dispatch one-hots -> [E, G] int32 fill
    counts (capacity dispatch fills slots as a prefix, so the count IS
    the number of occupied slots)."""
    if dispatch.ndim == 3:
        dispatch = dispatch[None]
    return jnp.sum(dispatch, axis=(1, 3)).astype(jnp.int32).T  # [E, G]
