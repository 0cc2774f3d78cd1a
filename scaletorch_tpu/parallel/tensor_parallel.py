"""Tensor parallelism: Megatron-style column/row/vocab-parallel ops.

Parity with reference scaletorch/parallel/tensor_parallel/
(tensor_parallel.py:147-507 layers, tp_comms.py:64-360 autograd comms),
re-designed for shard_map:

  * The reference surgically replaces nn.Linear modules and pairs them
    with hand-written autograd Functions (f/g: CopyToModelParallelRegion /
    ReduceFromModelParallelRegion / GatherFromModelParallelRegion, plus
    LinearWithAsyncAllReduce overlapping the grad-input all-reduce with
    the weight-grad matmul).
  * Here each layer is a pure function over **locally-sharded** operands
    executed inside ``shard_map``. JAX's varying-axis machinery derives
    the transpose collectives automatically (the VJP of a replicated->
    varying broadcast is exactly the reference's g-function all-reduce),
    and XLA's latency-hiding scheduler overlaps the backward all-reduce
    with the weight-gradient matmul — the async-overlap the reference
    implements by hand in LinearWithAsyncAllReduce (tp_comms.py:229-320).

Weight layouts are [in, out] (einsum-friendly), sharded per
``llama_param_specs``: column-parallel weights split the output dim over
'tp', row-parallel split the input dim, the embedding splits the vocab
rows (VocabParallelEmbedding parity, tensor_parallel.py:375-507).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def axis_rank(axis: str) -> jax.Array:
    return jax.lax.axis_index(axis)


def axis_size(axis: str) -> int:
    return jax.lax.axis_size(axis)


# ---- f/g region functions (tp_comms.py parity) ------------------------------
def pvary_missing(x: jax.Array, axes) -> jax.Array:
    """Mark ``x`` as varying over any of ``axes`` it isn't already varying
    over (shard_map VMA bookkeeping); no-op outside shard_map. The
    transpose of this broadcast is a psum — exactly the reference's
    g-function gradient all-reduce (tp_comms.py:64-114) — so replicated
    operands used inside a shard_map get correctly summed gradients."""
    if isinstance(axes, str):
        axes = (axes,)
    vma = jax.typeof(x).vma
    missing = tuple(a for a in axes if a not in vma)
    return jax.lax.pvary(x, missing) if missing else x


def copy_to_tensor_parallel_region(x: jax.Array, axis: str = "tp") -> jax.Array:
    """Identity forward / all-reduce backward (reference tp_comms.py:64-114).

    In shard_map terms: mark a replicated activation as varying over the tp
    axis so its cotangent is psum'd. ``jax.lax.pvary``'s transpose IS the
    g-function all-reduce. Idempotent on already-varying inputs.
    """
    return pvary_missing(x, axis)


def reduce_from_tensor_parallel_region(x: jax.Array, axis: str = "tp") -> jax.Array:
    """All-reduce forward / identity backward (reference tp_comms.py:117-166):
    shard_map's VMA typing gives ``psum`` the replicated-cotangent
    (collective-free) backward."""
    return jax.lax.psum(x, axis)


def gather_from_tensor_parallel_region(x: jax.Array, axis: str = "tp") -> jax.Array:
    """All-gather last dim forward / split backward (tp_comms.py:169-226)."""
    return jax.lax.all_gather(x, axis, axis=x.ndim - 1, tiled=True)


# ---- parallel layers --------------------------------------------------------
def column_parallel_linear(
    x: jax.Array,
    w_local: jax.Array,
    *,
    axis: str = "tp",
    gather_output: bool = False,
) -> jax.Array:
    """y_local = x @ W[:, shard] (reference ColumnParallelLinear,
    tensor_parallel.py:147-261). ``x`` replicated over tp, output sharded
    on the last dim (or gathered when gather_output)."""
    y = copy_to_tensor_parallel_region(x, axis) @ pvary_missing(w_local, axis)
    if gather_output:
        y = gather_from_tensor_parallel_region(y, axis)
    return y


def row_parallel_linear(
    x_local: jax.Array,
    w_local: jax.Array,
    *,
    axis: str = "tp",
    sequence_parallel: bool = False,
    seq_dim: int = 1,
) -> jax.Array:
    """y = sum_over_tp(x_local @ W[shard, :]) (reference RowParallelLinear,
    tensor_parallel.py:264-372). With sequence_parallel the sum is a
    reduce-scatter along the sequence dim instead of an all-reduce
    (reference :354-359)."""
    partial = pvary_missing(x_local, axis) @ pvary_missing(w_local, axis)
    if sequence_parallel:
        return jax.lax.psum_scatter(partial, axis, scatter_dimension=seq_dim,
                                    tiled=True)
    return reduce_from_tensor_parallel_region(partial, axis)


def vocab_parallel_embedding(
    ids: jax.Array,
    table_local: jax.Array,
    *,
    axis: str = "tp",
    reduce: str = "sum",
) -> jax.Array:
    """Row-sharded embedding lookup with OOV masking + all-reduce
    (reference VocabParallelEmbedding, tensor_parallel.py:375-507).

    ids: global token ids [B, S]; table_local: [V/tp, H].
    ``reduce='none'`` returns the per-shard partial sums so the caller can
    fuse the reduction with another collective (the SP path completes it
    with a sequence reduce-scatter instead — models/llama.py).
    """
    vocab_local = table_local.shape[0]
    offset = axis_rank(axis) * vocab_local
    in_shard = (ids >= offset) & (ids < offset + vocab_local)
    local_ids = jnp.where(in_shard, ids - offset, 0)
    emb = jnp.take(table_local, local_ids, axis=0)
    emb = jnp.where(in_shard[..., None], emb, 0)
    if reduce == "none":
        return emb
    return jax.lax.psum(emb, axis)


def _vocab_parallel_token_stats(
    logits_local: jax.Array,
    targets: jax.Array,
    axis: Optional[str],
    ignore_index: int,
) -> tuple[jax.Array, jax.Array]:
    """Shared Megatron vocab-parallel CE core: (nll_sum, token_count), fp32.

    logsumexp and the gold-logit lookup are computed on the local vocab
    shard and psum'd (axis=None skips the collectives — single-device
    semantics). The max shift is gradient-free, and pmax has no
    differentiation rule, so stop_gradient both silences autodiff and
    states the math. Used by both the unfused and the chunk-fused loss so
    the numerically delicate parts exist exactly once.
    """
    logits32 = logits_local.astype(jnp.float32)
    vocab_local = logits32.shape[-1]
    offset = axis_rank(axis) * vocab_local if axis is not None else 0

    local_max = jax.lax.stop_gradient(jnp.max(logits32, axis=-1))
    global_max = jax.lax.pmax(local_max, axis) if axis else local_max
    sumexp = jnp.sum(jnp.exp(logits32 - global_max[..., None]), axis=-1)
    if axis:
        sumexp = jax.lax.psum(sumexp, axis)
    logz = global_max + jnp.log(sumexp)

    mask = targets != ignore_index
    safe_t = jnp.where(mask, targets, 0)
    in_shard = (safe_t >= offset) & (safe_t < offset + vocab_local)
    local_t = jnp.where(in_shard, safe_t - offset, 0)
    gold = jnp.take_along_axis(logits32, local_t[..., None], axis=-1)[..., 0]
    gold = jnp.where(in_shard, gold, 0.0)
    if axis:
        gold = jax.lax.psum(gold, axis)
    nll = (logz - gold) * mask
    return jnp.sum(nll), jnp.sum(mask).astype(jnp.float32)


def vocab_parallel_cross_entropy(
    logits_local: jax.Array,
    targets: jax.Array,
    *,
    axis: str = "tp",
    ignore_index: int = -100,
) -> jax.Array:
    """Cross entropy over vocab-sharded logits without gathering them.

    The TPU-native replacement for gathering final_proj outputs
    (reference uses gather_output=True on the final ColumnParallelLinear,
    tensor_parallel.py:107-143): the [B, S, V] logits never materialise
    unsharded — the standard Megatron vocab-parallel loss.
    """
    nll_sum, count = _vocab_parallel_token_stats(
        logits_local, targets, axis, ignore_index
    )
    return nll_sum / jnp.maximum(count, 1.0)


def fused_vocab_parallel_cross_entropy(
    hidden: jax.Array,
    head_local: jax.Array,
    targets: jax.Array,
    *,
    axis: Optional[str] = "tp",
    chunk_size: int = 1024,
    ignore_index: int = -100,
) -> jax.Array:
    """LM-head matmul + vocab-parallel CE fused over sequence chunks.

    Full logits [B, S, V] never materialise: each chunk computes its
    [B, C, V/tp] logits, reduces them to (nll_sum, count), and the chunk
    body is rematerialised in the backward (``jax.checkpoint``) so only
    the [B, C, H] hidden chunk is saved — the difference between fitting
    and OOM at large vocab (151k × 8k seq fp32 logits alone is ~5 GB).

    hidden: [B, S, H]; head_local: [H, V/tp] (or [H, V] with axis=None);
    targets: [B, S] global ids.
    """
    b, s, h = hidden.shape
    chunk = min(chunk_size, s)
    nc = -(-s // chunk)  # ceil: tail chunk may be smaller, memory bound holds

    def chunk_stats(x_chunk, t_chunk):
        return _vocab_parallel_token_stats(
            x_chunk @ head_local, t_chunk, axis, ignore_index
        )

    if nc == 1:
        nll_sum, count = chunk_stats(hidden, targets)
        return nll_sum / jnp.maximum(count, 1.0)

    # Static Python loop (nc is small): sidesteps scan-carry vma matching
    # inside shard_map, and XLA still schedules the chunks sequentially so
    # only one chunk's logits are live at a time.
    ckpt_stats = jax.checkpoint(chunk_stats)
    nll_sum = count = None
    for c in range(nc):
        x_c = hidden[:, c * chunk:(c + 1) * chunk, :]
        t_c = targets[:, c * chunk:(c + 1) * chunk]
        n, m = ckpt_stats(x_c, t_c)
        nll_sum = n if nll_sum is None else nll_sum + n
        count = m if count is None else count + m
    return nll_sum / jnp.maximum(count, 1.0)


# ---- sharding rules ---------------------------------------------------------
def validate_tp_divisibility(cfg, tp: int) -> None:
    """Reference apply_tensor_parallel's implicit requirements
    (tensor_parallel.py:107-143): every split dim divisible by tp."""
    checks = {
        "num_attention_heads": cfg.num_attention_heads,
        "num_key_value_heads": cfg.num_key_value_heads,
        "intermediate_size": cfg.intermediate_size,
        "vocab_size": cfg.vocab_size,
    }
    for name, value in checks.items():
        if value % tp != 0:
            raise ValueError(f"{name}={value} not divisible by tp={tp}")


def llama_param_specs(
    cfg, *, tp_axis: Optional[str] = "tp", pp_axis: Optional[str] = None
) -> dict:
    """PartitionSpec pytree for Llama/Qwen3 params — the declarative
    equivalent of the reference's module-replacement map
    (tensor_parallel.py:25,107-143):
      q/k/v/gate/up -> column (output dim over tp)
      o/down        -> row (input dim over tp)
      embedding     -> vocab rows over tp; lm_head -> vocab cols over tp
      norms         -> replicated

    With ``pp_axis``, the stacked layer axis (leading dim of every layers
    leaf) is sharded over pp — the SPMD equivalent of the reference's
    per-stage layer ownership (pipeline_parallel.py:83-178); embed/norm/
    head stay replicated over pp (stage gating happens in the schedule).
    """
    t, pstg = tp_axis, pp_axis
    layers = {
        "input_layernorm": P(pstg, None),
        "q_proj": P(pstg, None, t),
        "k_proj": P(pstg, None, t),
        "v_proj": P(pstg, None, t),
        "o_proj": P(pstg, t, None),
        "post_attention_layernorm": P(pstg, None),
        "gate_proj": P(pstg, None, t),
        "up_proj": P(pstg, None, t),
        "down_proj": P(pstg, t, None),
    }
    if cfg.qk_norm:
        layers["q_norm"] = P(pstg, None)
        layers["k_norm"] = P(pstg, None)
    specs = {
        "embed_tokens": P(t, None),
        "layers": layers,
        "norm": P(None),
    }
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = P(None, t)
    return specs
