"""The GSPMD training step: grad accumulation, clipping, update, metrics.

Parity with reference scaletorch/trainer/train_step.py:14-136 (non-PP
path): per-microbatch forward/backward under grad accumulation with a
single gradient synchronisation (the ``no_sync`` contract,
data_parallel.py:46-68), loss scaled by 1/accum, clip-by-global-norm, then
the optimizer step.

SCOPE vs parallel/spmd.py: this is the *declarative* step — plain jit
with sharding-annotation-driven parallelism. It serves (a) the FSDP path
(parallel/fsdp.py places params sharded and XLA inserts the
gathers/reduce-scatters), (b) single-device training, and (c) the
single-device golden half of the parallel test suite. The production
tp/pp/cp/ep Trainer path is the explicit shard_map step in
parallel/spmd.py — model-parallel collectives cannot be expressed as
placement alone.

TPU-native shape: the whole optimizer step is ONE jitted function; grad
accumulation is a ``lax.scan`` over the leading microbatch axis, so
activation memory stays at one microbatch while XLA fuses the accumulation
adds. Buffers are donated (params/opt_state update in place in HBM).
Under a data-sharded mesh, gradients are psum'd by XLA as part of the
backward; the scan keeps accumulation local so the reduction cost is paid
once per step, matching the reference's bucketed-overlap design intent
(bucketing itself is subsumed by XLA fusion — SURVEY.md §2.2).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import optax

from scaletorch_tpu.models.layers import cross_entropy_loss

Batch = Dict[str, jax.Array]  # input_ids/target_ids: [accum, micro_bs, seq]


def make_loss_fn(forward: Callable, cfg, *, attention_backend: str,
                 gradient_checkpointing: bool) -> Callable:
    """loss(params, microbatch) -> scalar fp32.

    MoE forwards carry a router aux loss that MUST join the objective
    (reference train_step adds model.get_aux_loss(); the spmd step and the
    pipeline path both do) — forwards exposing ``return_moe_stats`` are
    asked for it and the coefficient-scaled sum is added to the CE.
    """
    import inspect

    wants_aux = "return_moe_stats" in inspect.signature(forward).parameters

    def loss_fn(params, mb: Batch) -> jax.Array:
        out = forward(
            params,
            mb["input_ids"],
            cfg,
            positions=mb.get("position_ids"),
            attention_backend=attention_backend,
            gradient_checkpointing=gradient_checkpointing,
            **({"return_moe_stats": True} if wants_aux else {}),
        )
        if wants_aux:
            logits, aux = out[0], out[1]
        else:
            logits, aux = out, 0.0
        return cross_entropy_loss(logits, mb["target_ids"]) + aux

    return loss_fn


def accumulate_gradients(
    loss_fn: Callable, params: Any, batch: Batch, *, pvary_axes=None
) -> Tuple[jax.Array, Any]:
    """Mean loss + mean grads over the leading accumulation axis via scan.

    ``pvary_axes``: when running inside a ``shard_map`` over those mesh
    axes (the quantized-allreduce step), params and the scan carry are
    marked varying first so the VMA bookkeeping lines up; identity
    outside shard_map."""
    accum = jax.tree_util.tree_leaves(batch)[0].shape[0]
    if pvary_axes:
        from scaletorch_tpu.parallel.tensor_parallel import pvary_missing
    else:
        def pvary_missing(x, _axes):
            return x
    params = jax.tree.map(lambda x: pvary_missing(x, pvary_axes), params)

    def micro_step(carry, mb):
        grads_acc, loss_acc = carry
        loss, grads = jax.value_and_grad(loss_fn)(params, mb)
        grads_acc = jax.tree.map(jnp.add, grads_acc, grads)
        return (grads_acc, loss_acc + loss), None

    zeros = jax.tree.map(
        lambda p: pvary_missing(jnp.zeros(p.shape, jnp.float32), pvary_axes),
        params,
    )
    l0 = pvary_missing(jnp.float32(0.0), pvary_axes)
    (grads, loss_sum), _ = jax.lax.scan(micro_step, (zeros, l0), batch)
    scale = 1.0 / accum
    grads = jax.tree.map(lambda g: g * scale, grads)
    return loss_sum * scale, grads


def guarded_update(
    optimizer: optax.GradientTransformation,
    params: Any,
    opt_state: Any,
    grads: Any,
    ok: jax.Array,
) -> Tuple[Any, Any, jax.Array]:
    """Apply the optimizer update only when ``ok`` (a traced scalar bool)
    holds; otherwise params and the optimizer's FLOAT state (moments,
    factored statistics) keep their previous values so NaN/Inf never
    pollutes them. Integer state leaves — the step counters driving
    lr/weight-decay schedules — advance regardless: a skipped batch still
    consumes a global step, and freezing the count (what
    ``optax.apply_if_finite`` does) would silently desync every schedule
    from the trainer's ``global_step`` by one step per rejection. Returns
    ``(params, opt_state, update_skipped)`` where ``update_skipped`` is
    1.0 on a rejected step.

    Shared by the declarative step below and the SPMD shard_map step
    (parallel/spmd.py) so both reject non-finite updates identically.
    """
    updates, new_opt_state = optimizer.update(grads, opt_state, params)
    new_params = optax.apply_updates(params, updates)
    select = lambda n, o: jnp.where(ok, n, o)  # noqa: E731
    params = jax.tree.map(select, new_params, params)
    opt_state = jax.tree.map(
        lambda n, o: n if jnp.issubdtype(n.dtype, jnp.integer) else select(n, o),
        new_opt_state, opt_state,
    )
    return params, opt_state, 1.0 - ok.astype(jnp.float32)


def make_train_step(
    forward: Callable,
    cfg,
    optimizer: optax.GradientTransformation,
    *,
    attention_backend: str = "sdpa",
    gradient_checkpointing: bool = False,
    donate: bool = True,
    mesh=None,
    data_spec=None,
    nonfinite_guard: bool = True,
    grad_allreduce_dtype: str = "fp32",
    grad_allreduce_block_size: int = 256,
) -> Callable:
    """Build the jitted step: (params, opt_state, batch) ->
    (params, opt_state, metrics).

    ``mesh``/``data_spec`` optionally pin GSPMD shardings: batch leaves get
    ``data_spec`` (e.g. P(None, 'dp', None)), params/opt-state shardings are
    taken from their current placement.

    ``nonfinite_guard`` (the divergence sentinel's in-step half,
    resilience layer): a step whose loss or global grad norm is NaN/Inf
    leaves params and optimizer state untouched and reports
    ``update_skipped=1`` in the metrics, so one poisoned batch cannot
    destroy the run between checkpoints.

    ``grad_allreduce_dtype`` ('fp32' | 'bf16' | 'int8'): wire format of
    the data-parallel gradient mean. fp32 keeps this the fully
    declarative step (XLA derives the reduction from shardings). bf16 /
    int8 need the reduction to be an *explicit* collective, so the
    grad computation is wrapped in a ``shard_map`` over ``data_spec``'s
    axes with params REPLICATED — the plain-DP regime. The FSDP caller
    (params sharded over the data axis) must keep fp32: quantizing
    GSPMD's derived reduce-scatters is the SPMD path's job
    (parallel/spmd.py), not this step's.
    """
    loss_fn = make_loss_fn(
        forward,
        cfg,
        attention_backend=attention_backend,
        gradient_checkpointing=gradient_checkpointing,
    )

    if grad_allreduce_dtype not in ("fp32", "bf16", "int8"):
        raise ValueError(
            "grad_allreduce_dtype must be 'fp32', 'bf16' or 'int8', got "
            f"{grad_allreduce_dtype!r}"
        )
    if grad_allreduce_dtype != "fp32":
        if mesh is None or data_spec is None:
            raise ValueError(
                "grad_allreduce_dtype="
                f"{grad_allreduce_dtype!r} needs mesh + data_spec: the "
                "quantized mean is an explicit collective over the data "
                "axes (with fp32 there is no explicit reduction to "
                "quantize)"
            )
        return _make_quantized_dp_step(
            loss_fn, optimizer, mesh, data_spec,
            dtype=grad_allreduce_dtype,
            block_size=grad_allreduce_block_size,
            donate=donate, nonfinite_guard=nonfinite_guard,
        )

    def train_step(params, opt_state, batch):
        loss, grads = accumulate_gradients(loss_fn, params, batch)
        grad_norm = optax.global_norm(grads)
        # Param-dtype grads into the optimizer so bf16 master params keep
        # bf16 moments (same contract as the SPMD step, parallel/spmd.py).
        grads = jax.tree.map(lambda g, w: g.astype(w.dtype), grads, params)
        metrics = {"loss": loss, "grad_norm": grad_norm}
        if nonfinite_guard:
            ok = jnp.isfinite(loss) & jnp.isfinite(grad_norm)
            params, opt_state, skipped = guarded_update(
                optimizer, params, opt_state, grads, ok
            )
            metrics["update_skipped"] = skipped
        else:
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, metrics

    donate_argnums = (0, 1) if donate else ()
    if mesh is not None and data_spec is not None:
        from jax.sharding import NamedSharding

        batch_sharding = NamedSharding(mesh, data_spec)
        return jax.jit(
            train_step,
            donate_argnums=donate_argnums,
            in_shardings=(None, None, batch_sharding),
        )
    return jax.jit(train_step, donate_argnums=donate_argnums)


def _make_quantized_dp_step(
    loss_fn: Callable,
    optimizer: optax.GradientTransformation,
    mesh,
    data_spec,
    *,
    dtype: str,
    block_size: int,
    donate: bool,
    nonfinite_guard: bool,
) -> Callable:
    """The bf16/int8 variant of the declarative step: grad accumulation
    runs per data shard inside a ``shard_map`` (params replicated, batch
    per ``data_spec``) and the single per-step gradient synchronisation is
    the explicit quantized mean (ops/quantized_collectives.py) instead of
    XLA's derived fp32 all-reduce. Optimizer update, clipping semantics
    and the non-finite guard are identical to the fp32 step and run on
    the replicated (post-reduction) gradients outside the shard_map.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from scaletorch_tpu.ops.quantized_collectives import (
        quantized_pmean_tree,
    )
    from scaletorch_tpu.parallel.spmd import spec_axes

    axes = spec_axes(data_spec)
    if not axes:
        raise ValueError(
            f"data_spec {data_spec} names no mesh axes — nothing to "
            "reduce over"
        )

    def local_grads(p, batch):
        loss, grads = accumulate_gradients(
            loss_fn, p, batch, pvary_axes=axes)
        # THE gradient synchronisation, in the quantized wire format; its
        # all-gather leg leaves every rank with the identical fp32 mean.
        grads = quantized_pmean_tree(
            grads, axes if len(axes) > 1 else axes[0],
            dtype=dtype, block_size=block_size,
        )
        return jax.lax.pmean(loss, axes), grads

    sharded_grads = jax.shard_map(
        local_grads,
        mesh=mesh,
        in_specs=(P(), data_spec),
        out_specs=(P(), P()),
    )

    def train_step(params, opt_state, batch):
        loss, grads = sharded_grads(params, batch)
        grad_norm = optax.global_norm(grads)
        grads = jax.tree.map(lambda g, w: g.astype(w.dtype), grads, params)
        metrics = {"loss": loss, "grad_norm": grad_norm}
        if nonfinite_guard:
            ok = jnp.isfinite(loss) & jnp.isfinite(grad_norm)
            params, opt_state, skipped = guarded_update(
                optimizer, params, opt_state, grads, ok
            )
            metrics["update_skipped"] = skipped
        else:
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, metrics

    batch_sharding = NamedSharding(mesh, data_spec)
    return jax.jit(
        train_step,
        donate_argnums=(0, 1) if donate else (),
        in_shardings=(None, None, batch_sharding),
    )


def audit_entry(
    grad_allreduce_dtype: str = "int8", donate: bool = True
) -> Dict[str, Any]:
    """Deep-tier audit target (analysis/jaxpr_audit.py): the declarative
    step's quantized-DP variant on a pure dp=8 virtual CPU mesh.

    Contract (see parallel/spmd.audit_entry for the semantics of each
    field): the single per-step gradient synchronisation carries int8 on
    the dp axis (``quantized_axis`` is the attested contract, not echoed
    from the arguments), params/opt-state donation survives lowering,
    and the per-shard accumulation scan stays collective-free over dp.
    """
    import jax.random as jrandom
    from jax.sharding import PartitionSpec as P

    from scaletorch_tpu.models import llama
    from scaletorch_tpu.parallel.mesh import MeshManager

    model_cfg = llama.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=256,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    mm = MeshManager(dp=8)
    tx = optax.sgd(0.1)
    step_fn = make_train_step(
        llama.forward, model_cfg, tx,
        mesh=mm.mesh, data_spec=P(None, "dp", None),
        donate=donate, grad_allreduce_dtype=grad_allreduce_dtype,
    )
    params = jax.eval_shape(
        lambda: llama.init_params(jrandom.PRNGKey(0), model_cfg))
    oshape = jax.eval_shape(tx.init, params)
    seq = 64
    batch = {
        "input_ids": jax.ShapeDtypeStruct((2, 8, seq), jnp.int32),
        "target_ids": jax.ShapeDtypeStruct((2, 8, seq), jnp.int32),
    }
    param_mb = sum(
        l.size * l.dtype.itemsize for l in jax.tree_util.tree_leaves(params)
    ) / 1e6
    return {
        "name": "declarative_train_step",
        "file": "scaletorch_tpu/trainer/train_step.py",
        "fn": step_fn,
        "args": (params, oshape, batch),
        "min_devices": 8,
        "quantized_axis": ("dp", "int8"),
        # pinned contract, not echoed from ``donate`` (see
        # parallel/spmd.audit_entry)
        "expect_donation": True,
        "hoisted_axes": ("dp",),
        "max_collective_result_mb": max(1.0, 4.0 * param_mb),
        # memory-tier contract (analysis/memory.py): see
        # parallel/spmd.audit_entry for field semantics
        "compute_dtype": "fp32",
        "donated_min_mb": round(0.9 * param_mb, 4),
    }


def make_eval_step(forward: Callable, cfg, *, attention_backend: str = "sdpa"):
    loss_fn = make_loss_fn(
        forward, cfg, attention_backend=attention_backend,
        gradient_checkpointing=False,
    )

    @jax.jit
    def eval_step(params, batch):
        # batch: [micro_bs, seq] (no accumulation axis)
        return loss_fn(params, batch)

    return eval_step
