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
  * **Causal block skip** — for query block i, key blocks j > i are
    skipped: their compute is predicated off with ``pl.when`` and their
    index maps are clamped to an already-resident block so no DMA is
    issued for them. This is the reference ring-attention causal-skip
    idea (context_parallel.py:154-171) applied at tile granularity.
  * **vma-aware** — output ShapeDtypeStructs carry the varying-mesh-axes
    of their inputs, so the kernel composes with ``jax.shard_map``'s
    vma checking (the spmd train step runs everything inside shard_map).
  * fp32 accumulators and LSE; bf16 MXU feeds.

Backward follows FlashAttention-2: delta = rowsum(dO * O) precomputed in
XLA, then a dq kernel (grid over query blocks, reducing key blocks) and a
dkv kernel (grid over key blocks, reducing query blocks AND the n_rep
grouped query heads).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

def _resolve_blocks(block_q, block_kv):
    """None -> the SCALETORCH_TPU_FLASH_BLOCK_Q/KV env registry values
    (tools/optimize_mfu.py --flash-blocks sweeps these on the real chip).
    Resolved HERE so every entry point — the attention backend, the ring
    attention's forward/backward composition — honours the tuned tiles."""
    if block_q is None or block_kv is None:
        from scaletorch_tpu.env import get_env

        block_q = block_q or get_env("SCALETORCH_TPU_FLASH_BLOCK_Q")
        block_kv = block_kv or get_env("SCALETORCH_TPU_FLASH_BLOCK_KV")
    return block_q, block_kv


_NEG_INF = -1e30  # large-negative instead of -inf: keeps masked rows NaN-free


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
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_sc, m_sc, l_sc,
                *, scale, causal, bq, bkv):
    i = pl.program_id(2)  # query block
    j = pl.program_id(3)  # key block
    nj = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc)
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    # query block i attends key block j iff j*bkv <= i*bq + bq - 1
    needed = (j * bkv <= i * bq + bq - 1) if causal else (j >= 0)

    @pl.when(needed)
    def _block():
        q = q_ref[0, 0]  # [bq, D]
        k = k_ref[0, 0]  # [bkv, D]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [bq, bkv]
        if causal:
            # only the blocks straddling the diagonal need the triangle mask
            row = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
            col = j * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
            s = jnp.where(row >= col, s, _NEG_INF)
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
        lse_ref[0, 0] = (m_sc[:, 0] + jnp.log(l[:, 0]))[None, :]


def _semantics(*dims):
    """Mosaic grid dimension semantics: 'p' = parallel (no cross-iteration
    carry — megacore-partitionable on 2-core chips), 'a' = arbitrary (the
    sequential reduction dims that carry scratch accumulators). Declaring
    them lets Mosaic schedule DMAs/compute across iterations instead of
    assuming every dim may carry state."""
    m = {"p": pltpu.PARALLEL, "a": pltpu.ARBITRARY}
    return pltpu.CompilerParams(
        dimension_semantics=tuple(m[d] for d in dims))


def _flash_forward(q, k, v, causal, scale, bq, bkv, interpret):
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    n_rep = hq // hkv
    nq, nkv = sq // bq, skv // bkv

    def clamp_j(i, j):
        # causal: key blocks beyond the last one visible to query block i
        # are skipped; point their DMA at the last visible block (already
        # resident) so no bandwidth is spent on them. The bound is in KEY
        # block units: last visible key row is i*bq + bq - 1.
        return jnp.minimum(j, (i * bq + bq - 1) // bkv) if causal else j

    grid = (b, hq, nq, nkv)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal, bq=bq, bkv=bkv),
        grid=grid,
        compiler_params=_semantics("p", "p", "p", "a"),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bkv, d),
                         lambda b_, h, i, j: (b_, h // n_rep, clamp_j(i, j), 0)),
            pl.BlockSpec((1, 1, bkv, d),
                         lambda b_, h, i, j: (b_, h // n_rep, clamp_j(i, j), 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, 1, bq), lambda b_, h, i, j: (b_, h, 0, i)),
        ],
        out_shape=[
            _struct((b, hq, sq, d), q.dtype, q),
            _struct((b, hq, 1, sq), jnp.float32, q),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)
    return out, lse[:, :, 0, :]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_sc,
               *, scale, causal, bq, bkv):
    i = pl.program_id(2)
    j = pl.program_id(3)
    nj = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        dq_sc[:] = jnp.zeros_like(dq_sc)

    needed = (j * bkv <= i * bq + bq - 1) if causal else (j >= 0)

    @pl.when(needed)
    def _block():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]      # [1, bq]
        delta = delta_ref[0, 0]  # [1, bq]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if causal:
            row = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
            col = j * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
            s = jnp.where(row >= col, s, _NEG_INF)
        p = jnp.exp(s - lse[0][:, None])  # [bq, bkv]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta[0][:, None]) * scale
        dq_sc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == nj - 1)
    def _finalize():
        dq_ref[0, 0] = dq_sc[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_sc, dv_sc, *, scale, causal, bq, bkv):
    jj = pl.program_id(2)  # key block
    r = pl.program_id(3)   # grouped query head within this kv head
    i = pl.program_id(4)   # query block
    nr = pl.num_programs(3)
    ni = pl.num_programs(4)

    @pl.when((r == 0) & (i == 0))
    def _init():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    # key block jj receives gradient from query blocks i >= jj
    needed = (i * bq + bq - 1 >= jj * bkv) if causal else (i >= 0)

    @pl.when(needed)
    def _block():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if causal:
            row = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
            col = jj * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
            s = jnp.where(row >= col, s, _NEG_INF)
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

    @pl.when((r == nr - 1) & (i == ni - 1))
    def _finalize():
        dk_ref[0, 0] = dk_sc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_sc[:].astype(dv_ref.dtype)


def _flash_backward(q, k, v, out, lse, g, causal, scale, bq, bkv, interpret):
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    n_rep = hq // hkv
    nq, nkv = sq // bq, skv // bkv

    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    lse4 = lse[:, :, None, :]      # [B, Hq, 1, S]
    delta4 = delta[:, :, None, :]

    def clamp_j(i, j):
        # same key-block-unit bound as the forward
        return jnp.minimum(j, (i * bq + bq - 1) // bkv) if causal else j

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal, bq=bq, bkv=bkv),
        grid=(b, hq, nq, nkv),
        compiler_params=_semantics("p", "p", "p", "a"),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bkv, d),
                         lambda b_, h, i, j: (b_, h // n_rep, clamp_j(i, j), 0)),
            pl.BlockSpec((1, 1, bkv, d),
                         lambda b_, h, i, j: (b_, h // n_rep, clamp_j(i, j), 0)),
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, 1, bq), lambda b_, h, i, j: (b_, h, 0, i)),
            pl.BlockSpec((1, 1, 1, bq), lambda b_, h, i, j: (b_, h, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b_, h, i, j: (b_, h, i, 0)),
        out_shape=_struct((b, hq, sq, d), q.dtype, q),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        name="flash_dq",
    )(q, k, v, g, lse4, delta4)

    def clamp_i(jj, i):
        # key block jj only receives gradient from query blocks whose last
        # row reaches its first key row jj*bkv — bound in QUERY block units
        return jnp.maximum(i, (jj * bkv) // bq) if causal else i

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal, bq=bq, bkv=bkv),
        grid=(b, hkv, nkv, n_rep, nq),
        compiler_params=_semantics("p", "p", "p", "a", "a"),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d),
                         lambda b_, hk, jj, r, i: (b_, hk * n_rep + r,
                                                   clamp_i(jj, i), 0)),
            pl.BlockSpec((1, 1, bkv, d), lambda b_, hk, jj, r, i: (b_, hk, jj, 0)),
            pl.BlockSpec((1, 1, bkv, d), lambda b_, hk, jj, r, i: (b_, hk, jj, 0)),
            pl.BlockSpec((1, 1, bq, d),
                         lambda b_, hk, jj, r, i: (b_, hk * n_rep + r,
                                                   clamp_i(jj, i), 0)),
            pl.BlockSpec((1, 1, 1, bq),
                         lambda b_, hk, jj, r, i: (b_, hk * n_rep + r, 0,
                                                   clamp_i(jj, i))),
            pl.BlockSpec((1, 1, 1, bq),
                         lambda b_, hk, jj, r, i: (b_, hk * n_rep + r, 0,
                                                   clamp_i(jj, i))),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bkv, d), lambda b_, hk, jj, r, i: (b_, hk, jj, 0)),
            pl.BlockSpec((1, 1, bkv, d), lambda b_, hk, jj, r, i: (b_, hk, jj, 0)),
        ],
        out_shape=[
            _struct((b, hkv, skv, d), k.dtype, k),
            _struct((b, hkv, skv, d), v.dtype, v),
        ],
        scratch_shapes=[
            pltpu.VMEM((bkv, d), jnp.float32),
            pltpu.VMEM((bkv, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_dkv",
    )(q, k, v, g, lse4, delta4)
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
):
    """Raw kernel forward returning ``(out, lse)``.

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
    return _flash_forward(q, k, v, causal, scale, bq, bkv, interpret)


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
