"""The unified SPMD training step over the 5D mesh.

This is the load-bearing composition point: one ``shard_map`` over the
full ``(dp, pp, cp, ep, tp)`` mesh wraps loss, backward, gradient
reduction, clipping and the optimizer update — the role the reference
splits across DataParallelBucket hooks, tp autograd functions, and the
trainer loop (SURVEY.md §3.3):

  * DP/CP: batch (and sequence) sharded; gradients ``pmean``'d over the
    fused ``(dp, cp)`` group once per step — the reference's bucketed
    overlapped all-reduce on cp_dp_group (bucket.py:58-77,
    data_parallel.py:100-128). Accumulation over microbatches stays
    local (``no_sync`` contract); XLA's latency-hiding scheduler overlaps
    the reduction with the backward epilogue.
  * TP/SP: the model runs its tensor-parallel path (models/llama.py) with
    params arriving pre-sharded per llama_param_specs; the loss is
    computed vocab-parallel so full logits never materialise.
  * Gradient clipping uses the *global* norm: tp-sharded leaves contribute
    their shard's square-sum exactly once via a psum over tp, replicated
    leaves once with no psum — matching the reference's clip_grad_norm_
    over the full parameter set (train_step.py:122-136).

PP/EP join this composition in their own modules (pipeline_parallel /
expert_parallel) — the spmd step accepts a stage-local forward for PP.
"""

from __future__ import annotations


from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from scaletorch_tpu.env import get_env
from scaletorch_tpu.parallel.mesh import DATA_AXES, MeshManager
from scaletorch_tpu.parallel.tensor_parallel import (
    fused_vocab_parallel_cross_entropy,
    llama_param_specs,
)


def opt_state_specs(tx: optax.GradientTransformation, params: Any, param_specs: Any):
    """PartitionSpec tree for the optimizer state: params-like leaves (mu,
    nu, ...) inherit the param's spec, scalars are replicated. Optimizers
    with non-param-shaped state (factored stats) publish their own layout
    via a ``state_specs`` attribute (trainer/factored.py)."""
    if hasattr(tx, "state_specs"):
        return tx.state_specs(params)
    state_shape = jax.eval_shape(tx.init, params)
    return optax.tree_map_params(
        tx,
        lambda _, spec: spec,
        state_shape,
        param_specs,
        transform_non_params=lambda _: P(),
    )


def spec_axes(spec) -> Tuple[str, ...]:
    """Flattened mesh-axis names a PartitionSpec shards over (tuples in a
    spec entry — e.g. P(('dp','ep'), None) — are expanded)."""
    names: list = []
    for entry in spec:
        if entry is None:
            continue
        if isinstance(entry, (tuple, list)):
            names.extend(a for a in entry if a)
        else:
            names.append(entry)
    return tuple(names)


def leaf_spec_list(params: Any, p_specs: Any) -> list:
    """Per-leaf PartitionSpec, aligned with ``tree_leaves(params)``.

    Static (spec-derived) leaf metadata rather than ``jax.typeof(...).vma``
    reflection: the specs are ground truth for how each leaf is sharded.

    Unlike shard_map's in_specs, which also accepts pytree PREFIXES,
    this alignment needs one PartitionSpec per param leaf — a prefix (or
    a bare None entry, which tree_leaves silently drops) would misalign
    every zip over the flattened trees, so it is rejected loudly."""
    spec_leaves = jax.tree_util.tree_leaves(
        p_specs, is_leaf=lambda x: isinstance(x, P)
    )
    n_params = len(jax.tree_util.tree_leaves(params))
    if len(spec_leaves) != n_params:
        raise ValueError(
            f"param_specs must carry exactly one PartitionSpec per param "
            f"leaf (got {len(spec_leaves)} specs for {n_params} leaves); "
            "pytree-prefix specs and None entries are not supported here "
            "— expand them with jax.tree.map(lambda _, s: s, params, "
            "specs) first"
        )
    return spec_leaves


def _leaf_sqsum_partitioned(
    grads: Any,
    shard_axes: Tuple[str, ...] = ("tp", "pp"),
    leaf_axes: Optional[list] = None,
) -> jax.Array:
    """Global sum of squares over a gradient tree whose leaves are a mix of
    model-sharded (varying over tp and/or pp) and replicated arrays.
    Each leaf's partial square-sum is psum'd over exactly the shard axes it
    varies over, so every element is counted once. ``leaf_axes`` (aligned
    with tree_leaves) supplies each leaf's sharded axes statically; when
    omitted they are read from the VMA type."""
    groups: Dict[Tuple[str, ...], jax.Array] = {}
    leaves = jax.tree_util.tree_leaves(grads)
    if leaf_axes is None:
        leaf_axes = [
            tuple(a for a in shard_axes
                  if a in jax.typeof(g).vma)
            for g in leaves
        ]
    for g, axes in zip(leaves, leaf_axes):
        s = jnp.sum(jnp.square(g.astype(jnp.float32)))
        axes = tuple(a for a in shard_axes if a in axes)
        groups[axes] = groups.get(axes, jnp.float32(0.0)) + s
    total = jnp.float32(0.0)
    for axes, s in groups.items():
        total = total + (jax.lax.psum(s, axes) if axes else s)
    return total


def global_grad_norm(
    grads: Any,
    shard_axes: Tuple[str, ...] = ("tp", "pp"),
    leaf_axes: Optional[list] = None,
):
    if isinstance(shard_axes, str):  # tolerate single-axis callers
        shard_axes = (shard_axes,)
    return jnp.sqrt(_leaf_sqsum_partitioned(grads, shard_axes, leaf_axes))


def clip_by_global_norm(
    grads: Any,
    max_norm: float,
    shard_axes: Tuple[str, ...] = ("tp", "pp"),
    leaf_axes: Optional[list] = None,
):
    """Returns (clipped_grads, pre_clip_norm)."""
    if isinstance(shard_axes, str):
        shard_axes = (shard_axes,)
    norm = global_grad_norm(grads, shard_axes, leaf_axes)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    return jax.tree.map(lambda g: g * scale, grads), norm


def batch_specs(with_cp: bool = True) -> Dict[str, P]:
    """Sharding of the host-global step batch [accum, dp*ep*micro, seq].

    The batch dim shards over BOTH dp and ep: expert parallelism feeds
    each ep rank distinct tokens and exchanges them by expert ownership
    (the reference reads per-dp-rank data and all-to-alls over ep,
    ep_comms.py:41-133 — here ep is simply one more data dim). With
    ep == 1 this degenerates to pure dp sharding.
    """
    seq_axis = "cp" if with_cp else None
    return {
        "input_ids": P(None, ("dp", "ep"), seq_axis),
        "target_ids": P(None, ("dp", "ep"), seq_axis),
        "position_ids": P(None, seq_axis),
    }


def _build_losses(
    mm: MeshManager,
    model_forward: Callable,
    model_cfg,
    *,
    attention_backend: str,
    gradient_checkpointing: bool,
    remat_policy: str,
    sequence_parallel: bool,
    head_weight_fn: Callable,
    custom_param_specs: bool,
    model_kwargs: Optional[Dict[str, Any]],
    model_family: str,
    pp_schedule: str,
    cp_layout: str = "contiguous",
    custom_pipeline_loss: Optional[Callable] = None,
    custom_pipeline_has_aux: bool = False,
    pp_vpp: int = 1,
) -> Tuple[Callable, Optional[Callable], bool]:
    """(loss_fn, pipe_loss, pipe_has_aux) — the per-microbatch loss for the
    non-PP path and, when mm.pp > 1, the pipeline loss. Shared by the
    train step and the eval step so both compute the identical objective."""
    if attention_backend == "ring":
        # explicit-layout registry alias: the layout's masking schedule
        # must be traced into THIS step (ops/ring_attention.py), never
        # left to the process-global env default — another Trainer in the
        # same process may have set it to the other layout
        attention_backend = f"ring_{cp_layout}"

    def loss_fn(p, mb):
        out = model_forward(
            p,
            mb["input_ids"],
            model_cfg,
            positions=mb["position_ids"],
            attention_backend=attention_backend,
            gradient_checkpointing=gradient_checkpointing,
            remat_policy=remat_policy,
            tp_axis="tp",
            sequence_parallel=sequence_parallel,
            return_hidden=True,
            **(model_kwargs or {}),
        )
        # MoE forwards return (hidden, scaled_aux_loss[, stats]) — add the
        # aux to the CE (reference train_step adds model.get_aux_loss());
        # stats (expert load / drop rates) ride along as has_aux extras so
        # the operator sees routing health per step (VERDICT r1 weak #5).
        if isinstance(out, tuple):
            hidden, aux = out[0], out[1]
            extras = out[2] if len(out) == 3 else {}
        else:
            hidden, aux, extras = out, 0.0, {}
        # Head + CE fused over sequence chunks: full [B, S, V] logits never
        # materialise (vocab-parallel over tp AND chunk-rematerialised).
        with jax.named_scope("lm_head_loss"):
            head = head_weight_fn(p, model_cfg, "tp")
            ce = fused_vocab_parallel_cross_entropy(
                hidden, head, mb["target_ids"], axis="tp",
                chunk_size=int(get_env("SCALETORCH_TPU_CE_CHUNK") or 1024),
            )
        return ce + aux, extras

    if mm.pp == 1:
        return loss_fn, None, False

    if pp_schedule not in ("afab", "memory_chunked", "1f1b", "interleaved"):
        raise ValueError(
            "pp_schedule must be 'afab', 'interleaved' or 'memory_chunked' "
            f"(alias '1f1b'), got {pp_schedule}"
        )
    vpp = pp_vpp if pp_schedule == "interleaved" else 1
    if custom_pipeline_loss is not None:
        # Custom model families run PP through the public protocol: build
        # a ``(params, batch) -> loss`` with pipeline_spmd_loss over your
        # own embed_fn/stage_fn/loss_fn (see pipeline_parallel.py
        # docstring) and hand it in here.
        if pp_schedule == "interleaved":
            # The engine cannot be applied to an opaque loss — the caller
            # builds the interleaved variant themselves; silently running
            # their afab-contract loss against interleaved-order params
            # would train a scrambled model.
            raise ValueError(
                "pp_schedule='interleaved' does not apply to a "
                "custom_pipeline_loss: build the custom loss on "
                "pipeline_parallel.pipeline_interleaved_loss (embed_fn/"
                "chunk_fn/loss_fn) and pass pp_schedule='afab' — the "
                "schedule lives inside the custom loss"
            )
        return loss_fn, custom_pipeline_loss, custom_pipeline_has_aux
    if model_family == "qwen3_moe":
        # PP x EP: each stage's MoE layers run the ep all-to-all inside
        # stage compute; live-tick aux losses ride the pipeline carry
        # (pipeline_parallel.make_moe_pipeline_loss).
        from scaletorch_tpu.parallel.pipeline_parallel import (
            make_moe_pipeline_loss,
        )

        pipe_loss = make_moe_pipeline_loss(
            mm, model_cfg,
            attention_backend=attention_backend,
            gradient_checkpointing=gradient_checkpointing,
            remat_policy=remat_policy,
            sequence_parallel=sequence_parallel,
            head_weight_fn=head_weight_fn,
            vpp=vpp,
        )
        return loss_fn, pipe_loss, True
    if custom_param_specs:
        # The built-in PP path composes Llama/Qwen3 pipeline pieces (embed
        # / decoder_stack / final_hidden) over the pp-sharded stacked
        # layer axis; a custom params tree would be silently trained
        # against the wrong computation. Custom families opt in by
        # passing ``custom_pipeline_loss`` (the pipeline_spmd_loss
        # protocol) handled above.
        raise NotImplementedError(
            "pp > 1 with a custom params tree needs a custom_pipeline_loss: "
            "build one with pipeline_parallel.pipeline_spmd_loss over your "
            "embed_fn/stage_fn/loss_fn and pass it to make_spmd_train_step"
        )
    from scaletorch_tpu.parallel.pipeline_parallel import (
        make_llama_pipeline_loss,
    )

    pipe_loss = make_llama_pipeline_loss(
        mm, model_cfg,
        attention_backend=attention_backend,
        gradient_checkpointing=gradient_checkpointing,
        remat_policy=remat_policy,
        sequence_parallel=sequence_parallel,
        head_weight_fn=head_weight_fn,
        vpp=vpp,
    )
    return loss_fn, pipe_loss, False


def make_spmd_eval_step(
    mm: MeshManager,
    model_forward: Callable,
    model_cfg,
    *,
    attention_backend: str = "sdpa",
    sequence_parallel: bool = False,
    head_weight_fn: Optional[Callable] = None,
    param_specs: Any = None,
    model_kwargs: Optional[Dict[str, Any]] = None,
    model_family: str = "llama",
    cp_layout: str = "contiguous",
    pp_schedule: str = "afab",
    pp_vpp: int = 1,
) -> Tuple[Callable, Any]:
    """Jitted validation step ``(params, batch) -> loss`` over the same 5D
    mesh and loss form as the train step, minus backward/update — the
    Trainer's validation loop (role of reference make_eval_step +
    trainer eval leg). Returns (eval_fn, param_specs).

    ``pp_schedule``/``pp_vpp`` must match the TRAIN step when the engine is
    'interleaved': the layer shard arrives in interleaved storage order, so
    an afab eval pipeline would stack the wrong layers per stage."""
    use_pp = mm.pp > 1
    p_specs = (
        param_specs
        if param_specs is not None
        else llama_param_specs(
            model_cfg, tp_axis="tp", pp_axis="pp" if use_pp else None
        )
    )
    if head_weight_fn is None:
        from scaletorch_tpu.models.llama import lm_head_weight as head_weight_fn

    loss_fn, pipe_loss, pipe_has_aux = _build_losses(
        mm, model_forward, model_cfg,
        attention_backend=attention_backend,
        gradient_checkpointing=False,  # no backward: nothing to remat
        remat_policy="nothing_saveable",
        sequence_parallel=sequence_parallel,
        head_weight_fn=head_weight_fn,
        custom_param_specs=param_specs is not None,
        model_kwargs=model_kwargs,
        model_family=model_family,
        # memory_chunked is a train-side accumulation strategy; eval always
        # runs one pipeline pass, so only 'interleaved' changes the graph.
        pp_schedule="interleaved" if pp_schedule == "interleaved" else "afab",
        cp_layout=cp_layout,
        pp_vpp=pp_vpp,
    )
    all_axes = DATA_AXES + ("ep",) + (("tp", "pp") if use_pp else ("tp",))

    def eval_step(p, batch):
        from scaletorch_tpu.parallel.tensor_parallel import pvary_missing

        p_v = jax.tree.map(lambda x: pvary_missing(x, all_axes), p)
        if use_pp:
            out = pipe_loss(p_v, batch)
            loss = out[0] if pipe_has_aux else out
            loss = pvary_missing(loss, all_axes)
        else:
            accum = jax.tree_util.tree_leaves(batch)[0].shape[0]

            def micro(acc, mb):
                loss, _ = loss_fn(p_v, mb)
                return acc + pvary_missing(loss, all_axes), None

            loss_sum, _ = jax.lax.scan(
                micro, jax.lax.pvary(jnp.float32(0.0), all_axes), batch
            )
            loss = loss_sum / accum
        return jax.lax.pmean(loss, all_axes)

    sharded = jax.shard_map(
        eval_step,
        mesh=mm.mesh,
        in_specs=(p_specs, batch_specs()),
        out_specs=P(),
    )
    return jax.jit(sharded), p_specs


def make_spmd_train_step(
    mm: MeshManager,
    model_forward: Callable,
    model_cfg,
    tx: optax.GradientTransformation,
    params: Any,
    *,
    attention_backend: str = "sdpa",
    gradient_checkpointing: bool = False,
    remat_policy: str = "nothing_saveable",
    sequence_parallel: bool = False,
    max_grad_norm: float = 0.0,
    donate: bool = True,
    head_weight_fn: Optional[Callable] = None,
    param_specs: Any = None,
    pp_schedule: str = "afab",
    model_kwargs: Optional[Dict[str, Any]] = None,
    model_family: str = "llama",
    cp_layout: str = "contiguous",
    custom_pipeline_loss: Optional[Callable] = None,
    custom_pipeline_has_aux: bool = False,
    pp_vpp: int = 1,
    nonfinite_guard: bool = True,
    grad_allreduce_dtype: str = "fp32",
    grad_allreduce_axis: str = "dp",
    grad_allreduce_block_size: int = 256,
) -> Tuple[Callable, Any, Any]:
    """Build the jitted 5D train step.

    Returns ``(step_fn, param_specs, opt_specs)``; the caller shards
    params/opt_state with the returned specs (device_put with
    NamedSharding) and feeds host-global batches.

    ``tx`` must NOT include a clip transform — clipping is done here with
    the tensor-parallel-correct global norm (pass include_clip=False to
    create_optimizer).

    Model contract: ``model_forward`` must accept ``return_hidden=True``
    (returns [B, S, H] pre-head hidden states) and ``head_weight_fn(params,
    model_cfg, tp_axis)`` must return the [H, V/tp] head weight — defaults
    to the Llama/Qwen3 accessors; pass both (plus ``param_specs``) for
    other model families.

    With ``mm.pp > 1`` the microbatch loop becomes the SPMD
    collective-permute pipeline (parallel/pipeline_parallel.py);
    ``pp_schedule`` selects 'afab' or 'memory_chunked' (programmatic alias
    '1f1b' — reference pp_engine, config.py:155-173) — the accum dim of
    the batch is the microbatch dim.

    ``nonfinite_guard``: reject the update (params and optimizer state
    keep their previous values) when loss or global grad norm is
    NaN/Inf, reporting ``update_skipped`` in the metrics. Both scalars
    are already all-reduced here, so every shard takes the same branch —
    the rejection is mesh-consistent by construction (the resilience
    layer's in-step half; host-side policy lives in
    scaletorch_tpu/resilience.py).

    ``grad_allreduce_dtype`` ('fp32' | 'bf16' | 'int8'): wire format of
    the gradient mean over ``grad_allreduce_axis`` (default 'dp' — the
    axis that crosses DCN on multi-host meshes). The other data axes
    (cp, ep) and the model-axis psums stay fp32: they ride ICI, where
    bandwidth is not the binding constraint. 'int8' is the block-scaled
    quantized all-reduce (ops/quantized_collectives.py, ~4x fewer bytes);
    'bf16' halves the bytes with a plain cast. The reduction over the
    quantized axis runs LAST, on gradients that are already cp/ep-meaned
    and tp/pp-complete, so the quantization error is applied exactly
    once to the final value.
    """
    use_pp = mm.pp > 1
    if (use_pp and custom_pipeline_loss is None
            and isinstance(params, dict) and "layers" in params):
        # The stacked layer axis must shard evenly over pp. For uneven
        # layer counts the caller pads first (the Trainer does this
        # automatically) — catching it here gives a clear error instead
        # of a shard_map divisibility failure deep in tracing.
        from scaletorch_tpu.parallel.pipeline_parallel import (
            padded_stage_counts,
        )

        lead = jax.tree_util.tree_leaves(params["layers"])[0].shape[0]
        if pp_schedule == "interleaved":
            # No padding support: the engine needs L % (pp*vpp) == 0 and a
            # uniformly stacked tree, checked here AND by the param
            # interleave (interleave_stacked_params in the Trainer).
            from scaletorch_tpu.parallel.pipeline_parallel import (
                validate_interleaved_divisibility,
            )

            validate_interleaved_divisibility(
                model_cfg.num_hidden_layers, mm.pp, pp_vpp)
            if lead != model_cfg.num_hidden_layers:
                # chunk_fn's basic slicing would CLIP a mis-sized axis
                # silently (wrong layers, no error) — catch it here like
                # the afab branch catches its padding mismatch.
                raise ValueError(
                    f"interleaved pipeline needs the stacked layer axis == "
                    f"num_hidden_layers={model_cfg.num_hidden_layers}, got "
                    f"{lead}; unpad/deinterleave first, then "
                    f"interleave_stacked_params(layers, "
                    f"{model_cfg.num_hidden_layers}, {mm.pp}, {pp_vpp})"
                )
        else:
            _, slots = padded_stage_counts(model_cfg.num_hidden_layers, mm.pp)
            if lead != slots * mm.pp:
                raise ValueError(
                    f"stacked layer axis has {lead} slots but pp={mm.pp} with "
                    f"num_hidden_layers={model_cfg.num_hidden_layers} needs "
                    f"{slots * mm.pp}; pad uneven layer counts first with "
                    f"pipeline_parallel.pad_stacked_params(params['layers'], "
                    f"{model_cfg.num_hidden_layers}, {mm.pp})"
                )
    p_specs = (
        param_specs
        if param_specs is not None
        else llama_param_specs(
            model_cfg, tp_axis="tp", pp_axis="pp" if use_pp else None
        )
    )
    o_specs = opt_state_specs(tx, params, p_specs)
    b_specs = batch_specs()

    if head_weight_fn is None:
        from scaletorch_tpu.models.llama import lm_head_weight as head_weight_fn

    loss_fn, pipe_loss, pipe_has_aux = _build_losses(
        mm, model_forward, model_cfg,
        attention_backend=attention_backend,
        gradient_checkpointing=gradient_checkpointing,
        remat_policy=remat_policy,
        sequence_parallel=sequence_parallel,
        head_weight_fn=head_weight_fn,
        custom_param_specs=param_specs is not None,
        model_kwargs=model_kwargs,
        model_family=model_family,
        pp_schedule=pp_schedule,
        cp_layout=cp_layout,
        custom_pipeline_loss=custom_pipeline_loss,
        custom_pipeline_has_aux=custom_pipeline_has_aux,
        pp_vpp=pp_vpp,
    )

    # 'ep' is always a data axis for the batch (batch_specs shards rows
    # over ("dp","ep")), so it is always in the pvary set — even at ep=1
    # the vma bookkeeping must line up.
    all_axes = DATA_AXES + ("ep",) + (("tp", "pp") if use_pp else ("tp",))

    # Static per-leaf sharding metadata from the specs (not from VMA
    # reflection — leaf_spec_list docstring): which model axes each leaf
    # is sharded over drives the reduction below and the global norm.
    shard_axes = ("tp", "pp") if use_pp else ("tp",)
    leaf_shard_axes = [
        spec_axes(s) for s in leaf_spec_list(params, p_specs)
    ]
    # Per leaf: the model axes it is NOT sharded over — its gradient
    # shards are partial sums needing a psum over exactly those axes.
    rep_axes = [
        tuple(a for a in shard_axes if a not in ax) for ax in leaf_shard_axes
    ]
    # Expert-sharded leaves (varying over ep): their backward
    # all-to-all already summed every ep rank's loss contribution, so
    # they take a 1/ep scale instead of the data-axis pmean over ep.
    ep_sharded = ["ep" in ax for ax in leaf_shard_axes]

    if grad_allreduce_dtype not in ("fp32", "bf16", "int8"):
        raise ValueError(
            "grad_allreduce_dtype must be 'fp32', 'bf16' or 'int8', got "
            f"{grad_allreduce_dtype!r}"
        )
    if grad_allreduce_axis not in DATA_AXES:
        raise ValueError(
            f"grad_allreduce_axis must be one of {DATA_AXES} (the "
            f"gradient-mean group), got {grad_allreduce_axis!r}"
        )
    # Quantizing a size-1 axis would pay two quantization errors to move
    # zero bytes; silently run the fp32 path instead.
    quant_dtype = (
        grad_allreduce_dtype
        if mm.axis_size(grad_allreduce_axis) > 1 else "fp32"
    )

    def step(p, opt_state, batch):
        accum = jax.tree_util.tree_leaves(batch)[0].shape[0]

        # Broadcast every leaf to varying over (dp, cp, tp[, pp]) BEFORE
        # the microbatch loop. Differentiating w.r.t. these pre-varied
        # params keeps every backward collective-free (the broadcast's psum
        # transpose would otherwise fire per microbatch), so accumulation
        # is purely local and the reduction below runs ONCE per step —
        # the no_sync + single-bucket-flush contract
        # (reference data_parallel.py:46-68, bucket.py:58-77).
        from scaletorch_tpu.parallel.tensor_parallel import pvary_missing

        p_v = jax.tree.map(lambda x: pvary_missing(x, all_axes), p)

        zeros = jax.tree.map(
            lambda x: jax.lax.pvary(
                jnp.zeros(x.shape, jnp.float32),
                tuple(jax.typeof(x).vma),
            ),
            p_v,
        )

        extras = {}

        def pipe_value_and_grad(p, mb):
            """(loss, extras, grads) for one pipeline pass, aux-aware."""
            if pipe_has_aux:
                (l, ex), g = jax.value_and_grad(pipe_loss, has_aux=True)(p, mb)
            else:
                l, g = jax.value_and_grad(pipe_loss)(p, mb)
                ex = {}
            l = pvary_missing(l, all_axes)
            ex = {k: pvary_missing(v, all_axes) for k, v in ex.items()}
            return l, ex, g

        if use_pp and pp_schedule in ("afab", "interleaved"):
            # One pipeline over all microbatches; autodiff yields the
            # mirrored backward pipeline (all-forward-all-backward; the
            # interleaved engine differentiates its circular tick loop the
            # same way, with the bubble cut ~vpp x —
            # pipeline_parallel.interleaved_tick_schedule).
            # NOTE on schedule accounting (VERDICT r1 weak #3): in SPMD
            # every stage ticks in lockstep, so this fwd+bwd pipeline costs
            # (M + pp - 1) forward ticks + (M + pp - 1) backward ticks —
            # the same (pp-1)/(M+pp-1) bubble fraction as textbook 1F1B
            # (interleaving F and B ticks cannot hide bubbles when idle
            # SPMD stages burn the tick anyway; a manual interleaved
            # schedule would cost M + 2(pp-1) combined ticks, i.e. MORE).
            # 1F1B's remaining advantage is memory, which the chunked
            # schedule below provides.
            loss, extras, grads = pipe_value_and_grad(p_v, batch)
        elif use_pp:
            # 1F1B-equivalent MEMORY: chunk microbatches into groups of pp
            # and accumulate grads chunk-by-chunk, bounding in-flight
            # activations at O(pp) like 1F1B's steady state (reference
            # pipeline_parallel.py:457-671) at the price of a (pp-1)-tick
            # bubble per chunk instead of per step — bubble fraction
            # 2(pp-1)/(accum/nchunks...) vs afab's (pp-1)/(accum+pp-1).
            # Pick 'afab' unless boundary-activation memory is the binding
            # constraint (scripts/benchmark_comprehensive.py measures both).
            chunk = mm.pp
            # accum need not divide pp: full chunks run under the scan and
            # a shorter remainder pipeline pass (rem < pp microbatches,
            # just a bigger bubble) covers the tail — the reference 1F1B
            # handles any M >= 1 the same way (pipeline_parallel.py:457-671).
            # Every pass returns a mean over ITS microbatches, so passes
            # are recombined weighted by their microbatch counts.
            nfull, rem = divmod(accum, chunk)
            from scaletorch_tpu.parallel.pipeline_parallel import (
                MOE_PIPELINE_STATS,
            )

            zero_l = jax.lax.pvary(jnp.float32(0.0), all_axes)
            extras0 = (
                {k: zero_l for k in MOE_PIPELINE_STATS}
                if pipe_has_aux else {}
            )

            def chunk_step(carry, mb):
                g_acc, l_acc, e_acc = carry
                loss, ex, grads = pipe_value_and_grad(p_v, mb)
                e_acc = {k: e_acc[k] + ex[k] for k in e_acc}
                return (
                    (jax.tree.map(jnp.add, g_acc, grads), l_acc + loss, e_acc),
                    None,
                )

            if nfull:
                batch_c = jax.tree.map(
                    lambda x: x[:nfull * chunk].reshape(
                        (nfull, chunk) + x.shape[1:]), batch
                )
                (g_sum, l_sum, e_sum), _ = jax.lax.scan(
                    chunk_step, (zeros, zero_l, extras0), batch_c
                )
            else:
                g_sum, l_sum, e_sum = zeros, zero_l, extras0
            # per-microbatch totals: each full chunk's mean covers `chunk`
            # microbatches
            grads = jax.tree.map(lambda g: g * chunk, g_sum)
            loss = l_sum * chunk
            extras = {k: v * chunk for k, v in e_sum.items()}
            if rem:
                batch_r = jax.tree.map(lambda x: x[nfull * chunk:], batch)
                l_r, e_r, g_r = pipe_value_and_grad(p_v, batch_r)
                grads = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32) * rem, grads, g_r)
                loss = loss + l_r * rem
                extras = {k: extras[k] + e_r[k] * rem for k in extras}
            grads = jax.tree.map(lambda g: g / accum, grads)
            loss = loss / accum
            extras = {k: v / accum for k, v in extras.items()}
        elif accum == 1:
            # No accumulation: differentiate the single microbatch directly.
            # The scan below would carry an fp32 zeros tree (a full extra
            # gradient copy — 2.4 GB at 0.6B) through a one-trip loop;
            # accum is static under jit, so this branch is free.
            mb = jax.tree.map(lambda x: jnp.squeeze(x, 0), batch)
            (loss, extras), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                p_v, mb
            )
            loss = pvary_missing(loss, all_axes)
            extras = {k: pvary_missing(v, all_axes) for k, v in extras.items()}
        else:

            def micro_step(carry, mb):
                g_acc, l_acc = carry
                (loss, ex), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                    p_v, mb
                )
                return (
                    (jax.tree.map(jnp.add, g_acc, grads), l_acc + loss),
                    ex,
                )

            (grads, loss_sum), extras_mb = jax.lax.scan(
                micro_step, (zeros, jax.lax.pvary(jnp.float32(0.0), all_axes)), batch
            )
            grads = jax.tree.map(lambda g: g / accum, grads)
            loss = loss_sum / accum
            extras = jax.tree.map(lambda v: jnp.mean(v, axis=0), extras_mb)

        # fp32 gradient contract for EVERY path: the scan paths accumulate
        # into fp32 zeros already, but the afab pipeline and the accum==1
        # fast path hand back cotangents in param dtype — with bf16 master
        # params that would run the reduction, global-norm, and clipping
        # below in bf16. Promote once here (no-op when already fp32).
        grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)

        # THE gradient reduction: mean over the fused data group (cp_dp_group
        # parity), plus a sum over tp/pp for model-replicated leaves whose
        # shards each contributed a partial gradient (the reference
        # g-function all-reduce, folded into the same single reduction
        # point; pp-replicated leaves — embed/norm/head — are psum'd over
        # pp because only their owning stage produced a nonzero grad).
        #
        # With a non-fp32 grad_allreduce_dtype the mean SPLITS: the
        # ICI-cheap axes reduce per-leaf in fp32 first, then the
        # bandwidth-bound grad_allreduce_axis (DCN on multi-host) reduces
        # LAST over the whole tree in the quantized wire format — one
        # fused collective pair per vma-homogeneous leaf group
        # (ops/quantized_collectives.py).
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        data_axes_full = DATA_AXES + ("ep",)
        q_axis = grad_allreduce_axis
        reduced = []
        for g, axes, is_ep in zip(leaves, rep_axes, ep_sharded):
            if is_ep:
                fp_axes = tuple(
                    a for a in DATA_AXES
                    if quant_dtype == "fp32" or a != q_axis)
                if fp_axes:
                    g = jax.lax.pmean(g, fp_axes)
                g = g / mm.ep
            else:
                fp_axes = tuple(
                    a for a in data_axes_full
                    if quant_dtype == "fp32" or a != q_axis)
                g = jax.lax.pmean(g, fp_axes)
            if axes:
                g = jax.lax.psum(g, axes)
            reduced.append(g)
        if quant_dtype != "fp32":
            from scaletorch_tpu.ops.quantized_collectives import (
                quantized_pmean_tree,
            )

            # Group leaves by their (static) model-axis sharding so each
            # fused flatten+concat mixes only vma-identical arrays, then
            # run the quantized mean over q_axis per group.
            by_sig: Dict[Tuple[str, ...], list] = {}
            for i, ax in enumerate(leaf_shard_axes):
                by_sig.setdefault(tuple(sorted(ax)), []).append(i)
            for sig, idxs in by_sig.items():
                group = [reduced[i] for i in idxs]
                group = quantized_pmean_tree(
                    group, q_axis, dtype=quant_dtype,
                    block_size=grad_allreduce_block_size,
                )
                for i, g in zip(idxs, group):
                    reduced[i] = g
        grads = jax.tree_util.tree_unflatten(treedef, reduced)
        loss = jax.lax.pmean(loss, all_axes)
        extras = jax.tree.map(
            lambda v: jax.lax.pmean(pvary_missing(v, all_axes), all_axes),
            extras,
        )

        norm_axes = shard_axes + ("ep",)
        if max_grad_norm and max_grad_norm > 0:
            grads, grad_norm = clip_by_global_norm(
                grads, max_grad_norm, norm_axes, leaf_shard_axes)
        else:
            grad_norm = global_grad_norm(grads, norm_axes, leaf_shard_axes)

        # Hand the optimizer param-dtype gradients: reduction + clipping
        # above ran in fp32 regardless, but bf16 master params (torch-parity
        # param_dtype) need bf16 moments — fp32 grads would silently promote
        # mu/nu to fp32 on the first update and break buffer donation.
        grads = jax.tree.map(lambda g, w: g.astype(w.dtype), grads, p)
        metrics = {"loss": loss, "grad_norm": grad_norm, **extras}
        with jax.named_scope("optimizer"):
            if nonfinite_guard:
                from scaletorch_tpu.trainer.train_step import guarded_update

                ok = jnp.isfinite(loss) & jnp.isfinite(grad_norm)
                p, opt_state, skipped = guarded_update(
                    tx, p, opt_state, grads, ok
                )
                metrics["update_skipped"] = skipped
            else:
                updates, opt_state = tx.update(grads, opt_state, p)
                p = optax.apply_updates(p, updates)
        return p, opt_state, metrics

    sharded = jax.shard_map(
        step,
        mesh=mm.mesh,
        in_specs=(p_specs, o_specs, b_specs),
        out_specs=(p_specs, o_specs, P()),
    )
    donate_argnums = (0, 1) if donate else ()
    return (
        jax.jit(sharded, donate_argnums=donate_argnums),
        p_specs,
        o_specs,
    )


def audit_entry(
    grad_allreduce_dtype: str = "int8", donate: bool = True
) -> Dict[str, Any]:
    """Deep-tier audit target (analysis/jaxpr_audit.py): the REAL SPMD
    train step, built tiny on the (dp2, cp2, tp2) virtual CPU mesh with
    the int8 gradient all-reduce configured on the dp edge.

    The returned contract pins the invariants the compiled artifact must
    keep: the dp edge carries int8 wire (``quantized_axis`` is the
    attested contract, deliberately NOT derived from the arguments — a
    config drift to fp32 must FAIL the audit, not relax it), donation
    survives lowering, no dp collective hides inside the accumulation
    scan (the no_sync/single-flush design), and no collective result
    exceeds a few times the parameter footprint (the silently-replicated
    -intermediate signature). ``grad_allreduce_dtype``/``donate`` exist
    so tests can inject exactly those regressions.
    """
    import jax.random as jrandom

    from scaletorch_tpu.models import llama

    model_cfg = llama.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=256,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    mm = MeshManager(dp=2, cp=2, tp=2)
    params = jax.eval_shape(
        lambda: llama.init_params(jrandom.PRNGKey(0), model_cfg))
    tx = optax.sgd(0.1)
    step_fn, _, _ = make_spmd_train_step(
        mm, llama.forward, model_cfg, tx, params,
        max_grad_norm=1.0, donate=donate,
        grad_allreduce_dtype=grad_allreduce_dtype, grad_allreduce_axis="dp",
    )
    seq = 128
    batch = {
        "input_ids": jax.ShapeDtypeStruct((2, 2, seq), jnp.int32),
        "target_ids": jax.ShapeDtypeStruct((2, 2, seq), jnp.int32),
        "position_ids": jax.ShapeDtypeStruct((2, seq), jnp.int32),
    }
    oshape = jax.eval_shape(tx.init, params)
    param_mb = sum(
        l.size * l.dtype.itemsize for l in jax.tree_util.tree_leaves(params)
    ) / 1e6
    return {
        "name": "spmd_train_step",
        "file": "scaletorch_tpu/parallel/spmd.py",
        "fn": step_fn,
        "args": (params, oshape, batch),
        "min_devices": 8,
        "quantized_axis": ("dp", "int8"),
        # like quantized_axis, the attested contract — NOT echoed from
        # the ``donate`` argument, so building with donate=False is the
        # injected regression the audit must catch
        "expect_donation": True,
        "hoisted_axes": ("dp",),
        "max_collective_result_mb": max(1.0, 4.0 * param_mb),
        # memory-tier contract (analysis/memory.py): donated params must
        # actually alias outputs (ST1002 — bytes, not just presence like
        # ST702). memory_analysis() accounts PER DEVICE and this mesh
        # shards params over tp=2, so the floor is ~half the global
        # param bytes (0.45 = 0.9 slack x the 1/2 tp shard).
        "compute_dtype": "fp32",
        "donated_min_mb": round(0.45 * param_mb, 4),
    }


def shard_params(mm: MeshManager, params: Any, p_specs: Any) -> Any:
    """Distribute a host param tree to its mesh shardings. Multi-process
    safe: every process holds the same host tree (same init seed / same
    checkpoint) and contributes only its addressable shards."""
    from scaletorch_tpu.dist import put_global

    shardings = jax.tree.map(
        lambda s: NamedSharding(mm.mesh, s), p_specs,
        is_leaf=lambda x: isinstance(x, P),
    )
    return jax.tree.map(put_global, params, shardings)
