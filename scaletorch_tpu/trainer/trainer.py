"""Training orchestration: config -> mesh -> model -> data -> loop.

The counterpart of reference train.py:55-453 (main + _run_training_loop)
and trainer/model_builder.py:33-184 (create_model), reshaped for SPMD:
one process drives all devices; parallelism comes from the mesh + sharding
of the jitted step rather than per-rank module surgery.
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict, Optional

import jax
import numpy as np
from jax.sharding import NamedSharding

from scaletorch_tpu.config import ScaleTorchTPUArguments
from scaletorch_tpu.models.families import FAMILIES, build_model_config
from scaletorch_tpu.models.registry import resolve_attention_backend
from scaletorch_tpu.parallel.mesh import MeshManager, setup_mesh_manager
from scaletorch_tpu.telemetry.spans import span
from scaletorch_tpu.trainer.metrics import MetricsLogger
from scaletorch_tpu.trainer.optimizer import create_optimizer
from scaletorch_tpu.utils.device import device_report
from scaletorch_tpu.utils.logger import get_logger
from scaletorch_tpu.utils.misc import get_num_params, set_all_seed, to_readable_format


def build_dataloader(cfg: ScaleTorchTPUArguments, model_cfg,
                     fault_injector=None):
    if cfg.synthetic_data or not cfg.dataset_name:
        from scaletorch_tpu.data.dataloader import SyntheticDataLoader

        return SyntheticDataLoader(
            vocab_size=min(model_cfg.vocab_size,
                           cfg.synthetic_vocab_size or model_cfg.vocab_size),
            sequence_length=cfg.sequence_length,
            micro_batch_size=cfg.micro_batch_size,
            gradient_accumulation_steps=cfg.gradient_accumulation_steps,
            data_parallel_size=cfg.data_parallel_size * cfg.expert_parallel_size,
            seed=cfg.seed,
        )
    from scaletorch_tpu.data.dataloader import MicroBatchDataLoader
    from scaletorch_tpu.data.dataset import DatasetProcessor, chunks_to_array

    proc = DatasetProcessor(
        cfg.tokenizer_name_or_path or cfg.model_name_or_path,
        cfg.sequence_length,
        cfg.tokenize_strategy,
        cfg.dataset_text_key,
        cfg.num_proc,
    )
    tokens = chunks_to_array(proc.process(cfg.dataset_name))
    return MicroBatchDataLoader(
        tokens,
        micro_batch_size=cfg.micro_batch_size,
        gradient_accumulation_steps=cfg.gradient_accumulation_steps,
        data_parallel_size=cfg.data_parallel_size * cfg.expert_parallel_size,
        seed=cfg.seed,
        read_retries=cfg.data_read_retries,
        retry_base_delay=cfg.data_retry_base_delay,
        max_skipped_batches=cfg.data_max_skipped_batches,
        fault_injector=fault_injector,
    )


def validate_layer_storage(
    saved: str,
    current: str,
    *,
    pp_engine: str,
    pp_virtual_stages: int,
) -> None:
    """Refuse a resume whose stacked-layer STORAGE order differs from the
    checkpoint's. The interleaved engine permutes the layer axis with
    unchanged shapes, so no shape check can catch a cross-engine resume —
    only this metadata can. Checkpoints predating the field trained in
    model order, so the 'model_order' default makes them refuse an
    interleaved resume."""
    if saved != current:
        raise ValueError(
            f"checkpoint stores layers in {saved!r} order but "
            f"this run uses {current!r} "
            f"(pp_engine={pp_engine}, "
            f"pp_virtual_stages={pp_virtual_stages}): resume "
            "with the original engine settings, or convert the "
            "checkpoint offline with tools/convert_layer_storage.py"
        )


class Trainer:
    """End-to-end training driver (reference train.py main + loop)."""

    def __init__(self, cfg: ScaleTorchTPUArguments):
        row = FAMILIES.get(cfg.model_type)
        if row is not None and row.untrained:
            raise NotImplementedError(
                f"the trainer has no step for model_type {cfg.model_type!r}"
                f": {row.untrained}")
        self.cfg = cfg
        self.logger = get_logger(log_file=cfg.log_file,
                                 log_format=cfg.log_format)
        if cfg.verbose:
            import logging

            self.logger.setLevel(logging.DEBUG)
        # Multi-host bootstrap BEFORE the first backend touch — after this,
        # jax.devices() spans every host and the rest of the trainer is
        # multi-process-agnostic (reference init_dist call site,
        # train.py:70-76).
        from scaletorch_tpu.dist import init_distributed

        init_distributed(
            cfg.distributed_launcher,
            coordinator_address=cfg.coordinator_address,
            num_processes=cfg.num_processes,
            process_id=cfg.process_id,
        )
        # The logger was configured before the backend was up and may have
        # guessed rank 0 (e.g. flags-only env launcher): correct the
        # non-main-process gating now that the true index is known.
        if jax.process_index() != 0:
            import logging

            self.logger.setLevel(logging.ERROR)
        if cfg.verbose:
            # AFTER init_distributed: get_system_info touches jax.devices(),
            # and any backend touch before jax.distributed.initialize would
            # pin this process to its local devices only (dist.py:100-110).
            from scaletorch_tpu.utils.env_info import log_system_info

            log_system_info(self.logger)
        cfg.validate_world_size(len(jax.devices()))
        self.mm: MeshManager = setup_mesh_manager(**cfg.mesh_kwargs())
        self.model_cfg = build_model_config(cfg)
        # Resolved virtual-stage count: cfg.pp_virtual_stages, with the 0
        # sentinel (auto) resolved into a Trainer ATTRIBUTE — never back
        # into cfg, which the caller may reuse for another model whose
        # layer count resolves differently.
        self._pp_vpp = cfg.pp_virtual_stages
        if (cfg.pipeline_parallel_size > 1
                and cfg.pp_engine == "interleaved"
                and cfg.pp_virtual_stages == 0):
            from scaletorch_tpu.parallel.pipeline_parallel import (
                suggest_virtual_stages,
            )

            num_layers = self.model_cfg.num_hidden_layers
            pp = cfg.pipeline_parallel_size
            self._pp_vpp = suggest_virtual_stages(num_layers, pp)
            if self._pp_vpp < 2:
                if num_layers % pp:
                    raise ValueError(
                        f"pp_engine='interleaved' cannot apply: "
                        f"num_hidden_layers={num_layers} is not divisible "
                        f"by pp={pp} (no pp_virtual_stages value can fix "
                        "this) — use pp_engine='afab', which pads uneven "
                        "layer counts"
                    )
                raise ValueError(
                    f"pp_virtual_stages=0 (auto) found no virtual-stage "
                    f"count: per-rank layer count {num_layers // pp} has "
                    "no divisor in [2, 4] — set pp_virtual_stages "
                    f"explicitly (any divisor of {num_layers // pp} >= 2) "
                    "or use pp_engine='afab'"
                )
            self.logger.info(
                f"pp_virtual_stages auto-resolved to {self._pp_vpp}")
        if cfg.context_parallel_size > 1 and cfg.attention_backend == "auto":
            # Topology-aware CP auto-selection (parallel/cp_select.py): the
            # hand-tuned ring/zigzag/ulysses table computed from the real
            # mesh (DCN hops along the cp axis), the model's head geometry
            # and the sequence length, attested by AOT_CP_CROSSOVER.json.
            from scaletorch_tpu.parallel.cp_select import resolve_cp_backend

            choice = resolve_cp_backend(
                "auto",
                self.mm.mesh,
                cp=cfg.context_parallel_size,
                num_q_heads=self.model_cfg.num_attention_heads,
                num_kv_heads=self.model_cfg.num_key_value_heads,
                seq_len=cfg.sequence_length,
                layout=cfg.cp_layout,
            )
            self.attention_backend = choice.backend
            self.logger.info(
                f"cp backend auto-selected: {choice.backend} "
                f"(layout {choice.layout}) — {choice.reason}"
            )
        else:
            self.attention_backend = resolve_attention_backend(
                cfg.attention_backend,
                context_parallel=cfg.context_parallel_size > 1,
            )
        if (cfg.context_parallel_size > 1
                and self.attention_backend not in ("ring", "ulysses")):
            # A full-sequence backend on cp-sharded activations would silently
            # compute block-diagonal attention.
            raise ValueError(
                f"context_parallel_size={cfg.context_parallel_size} requires a "
                f"CP-aware attention backend ('ring' or 'ulysses'), got "
                f"{self.attention_backend!r}"
            )
        # CP sequence layout: the ring backend reads the env toggle at trace
        # time (model code calls backends without layout kwargs), and
        # _device_batch applies the matching host-side token permutation.
        # Ulysses owns whole heads, so its causal work is balanced in the
        # contiguous layout already — no permutation.
        self._zigzag_cp = (
            cfg.context_parallel_size > 1 and cfg.cp_layout == "zigzag"
            and self.attention_backend == "ring"
        )
        if (self._zigzag_cp
                and cfg.sequence_length % (2 * cfg.context_parallel_size)):
            # The config-time check defers this for attention_backend
            # 'auto' (it cannot know the resolver's verdict); now that
            # the backend is settled as ring+zigzag, enforce it with the
            # same remedy message.
            raise ValueError(
                f"cp_layout='zigzag' needs sequence_length "
                f"{cfg.sequence_length} divisible by 2*cp "
                f"({2 * cfg.context_parallel_size}); use cp_layout="
                f"'contiguous' for odd stripe splits"
            )
        if (cfg.context_parallel_size > 1 and cfg.cp_layout == "zigzag"
                and self.attention_backend == "ulysses"):
            self.logger.info(
                "cp_layout='zigzag' has no effect with the ulysses backend "
                "(head ownership balances causal work); using the "
                "contiguous sequence layout"
            )
        # NOTE: no process-global SCALETORCH_TPU_CP_LAYOUT write here — the
        # spmd step pins the layout at trace time via the ring_zigzag /
        # ring_contiguous registry aliases (parallel/spmd.py), so a second
        # Trainer in the same process can use the other layout safely. The
        # env toggle remains only as the default for direct 'ring' backend
        # calls outside a Trainer.

        from scaletorch_tpu.parallel.spmd import batch_specs, shard_params
        from scaletorch_tpu.parallel.tensor_parallel import (
            llama_param_specs,
            validate_tp_divisibility,
        )

        if cfg.tensor_parallel_size > 1:
            validate_tp_divisibility(self.model_cfg, cfg.tensor_parallel_size)

        # the routing families that train (build_model_config and the
        # refusal above let no other through)
        is_moe = row.counts_routing
        init_fn, fwd_fn = row.module.init_params, row.module.forward
        param_specs = model_kwargs = head_weight_fn = None
        if is_moe:
            from scaletorch_tpu.models import qwen3_moe
            from scaletorch_tpu.parallel.expert_parallel import (
                validate_ep_divisibility,
            )

            if cfg.expert_parallel_size > 1:
                validate_ep_divisibility(self.model_cfg, cfg.expert_parallel_size)
            param_specs = qwen3_moe.qwen3_moe_param_specs(
                self.model_cfg,
                tp_axis="tp",
                ep_axis="ep" if cfg.expert_parallel_size > 1 else None,
                pp_axis="pp" if cfg.pipeline_parallel_size > 1 else None,
            )
            model_kwargs = {
                "ep_axis": "ep" if cfg.expert_parallel_size > 1 else None,
                "return_moe_stats": True,
            }
            head_weight_fn = qwen3_moe.lm_head_weight

        def layout_specs():
            """The parameter layout, as HF loading and Adafactor read it
            (``param_specs=None`` is the step's own Llama default)."""
            if param_specs is not None:
                return param_specs
            return llama_param_specs(
                self.model_cfg, tp_axis="tp",
                pp_axis="pp" if cfg.pipeline_parallel_size > 1 else None)

        key = set_all_seed(cfg.seed)
        if cfg.load_pretrained_weights:
            if not cfg.model_name_or_path:
                raise ValueError(
                    "load_pretrained_weights requires model_name_or_path"
                )
            from jax.sharding import PartitionSpec

            from scaletorch_tpu.utils.hf_interop import load_hf_params

            # Streamed load straight into the mesh shardings: each process
            # reads only the checkpoint slices its shards need, one layer
            # at a time — host memory stays bounded by one layer even for
            # 30B-class models (reference per-stage/per-rank subset
            # loading, checkpoint.py:265-423).
            load_shardings = jax.tree.map(
                lambda s: NamedSharding(self.mm.mesh, s),
                layout_specs(),
                is_leaf=lambda x: isinstance(x, PartitionSpec),
            )
            params_host = load_hf_params(
                cfg.model_name_or_path, self.model_cfg,
                shardings=load_shardings,
            )
        else:
            # local_devices: under multi-process, jax.devices()[0] may belong
            # to another host and its arrays would be unreadable here.
            with jax.default_device(jax.local_devices()[0]):
                params_host = init_fn(key, self.model_cfg)

        if (cfg.pipeline_parallel_size > 1
                and self.model_cfg.num_hidden_layers
                % cfg.pipeline_parallel_size):
            # Uneven PP: pad the stacked layer axis so it shards evenly;
            # the pipeline stage compute masks the padding slots out
            # (pipeline_parallel.pad_stacked_params / decoder_stack
            # active_layers). Reference parity: ragged per-stage layer
            # counts, pipeline_parallel.py:83-133.
            from scaletorch_tpu.parallel.pipeline_parallel import (
                pad_stacked_params,
            )

            params_host = dict(params_host)
            params_host["layers"] = pad_stacked_params(
                params_host["layers"],
                self.model_cfg.num_hidden_layers,
                cfg.pipeline_parallel_size,
            )

        if (cfg.pipeline_parallel_size > 1
                and cfg.pp_engine == "interleaved"):
            # Virtual-stage engine: permute the stacked layer axis into
            # rank-major interleaved order so the plain pp-sharding hands
            # each rank its vpp chunks. HF/export callers must invert with
            # deinterleave_stacked_params (same contract as uneven-PP
            # padding above).
            from scaletorch_tpu.parallel.pipeline_parallel import (
                interleave_stacked_params,
            )

            params_host = dict(params_host)
            params_host["layers"] = interleave_stacked_params(
                params_host["layers"],
                self.model_cfg.num_hidden_layers,
                cfg.pipeline_parallel_size,
                self._pp_vpp,
            )

        # clip-free optimizer: the SPMD step applies TP-correct clipping.
        # Adafactor additionally needs the param layout + mesh sizes so its
        # factored statistics reduce across sharded dims (trainer/factored.py).
        if cfg.optimizer_name.lower() == "adafactor":
            self.tx, self.schedule = create_optimizer(
                cfg, include_clip=False, param_specs=layout_specs(),
                axis_sizes=dict(self.mm.mesh.shape),
            )
        else:
            self.tx, self.schedule = create_optimizer(cfg, include_clip=False)

        # Model-family pieces the elastic remesh path needs to REBUILD the
        # jitted step against a new mesh long after __init__'s locals are
        # gone (cheap references, no arrays).
        self._spmd_pieces = dict(
            fwd_fn=fwd_fn,
            param_specs=param_specs,
            model_kwargs=model_kwargs,
            head_weight_fn=head_weight_fn,
            model_family="qwen3_moe" if is_moe else "llama",
        )
        self.step_fn, p_specs, o_specs = self._make_step_fn(params_host)
        self.params = shard_params(self.mm, params_host, p_specs)
        self.opt_state = shard_params(self.mm, self.tx.init(params_host), o_specs)

        # Host-side resilience: divergence sentinel (policy over anomalous
        # losses), fault injector (config/env drills), preemption handler
        # (installed for the duration of train()). The device-side half is
        # the nonfinite_guard traced into step_fn above. Built BEFORE the
        # loader so the loader's corrupt-shard injection hook can bind the
        # same injector. On multi-process runs every control decision is
        # coordinated: host 0 forms it from the all-gathered per-host
        # observations and broadcasts, so no host ever enters (or skips) a
        # cross-host collective unilaterally.
        from scaletorch_tpu.resilience import ResilienceManager
        from scaletorch_tpu.resilience_distributed import CoordinatedResilience

        self.resilience = ResilienceManager.from_config(cfg)
        self.coordinator = CoordinatedResilience.from_config(
            cfg, self.resilience)
        self._watchdog = None

        self.loader = build_dataloader(
            cfg, self.model_cfg, fault_injector=self.resilience.injector)
        # batch leaves: [accum, dp*micro, seq] with batch over dp, seq over cp
        self._batch_shardings = {
            k: NamedSharding(self.mm.mesh, spec) for k, spec in batch_specs().items()
        }

        n_params = get_num_params(self.params)
        # MoE MFU counts active params per token (reference active-param
        # MFU, README.md:131).
        mfu_params = (
            self.model_cfg.num_active_params() if is_moe else n_params
        )
        self.metrics = MetricsLogger(
            num_params=mfu_params,
            num_layers=self.model_cfg.num_hidden_layers,
            num_heads=self.model_cfg.num_attention_heads,
            head_dim=self.model_cfg.actual_head_dim,
            seq_len=cfg.sequence_length,
            tokens_per_step=self.loader.tokens_per_step,
            num_chips=len(jax.devices()),
            log_frequency=cfg.log_frequency,
        )
        # Unified telemetry (scaletorch_tpu/telemetry/): span tracing,
        # JSONL export, anomaly-triggered profiling, SIGUSR1 snapshots —
        # all off (every component None, one branch per site) unless
        # --telemetry_dir / SCALETORCH_TPU_TELEMETRY_DIR is set. The
        # straggler detector is independent of the directory: it rides
        # the coordinator's existing per-step gather (zero collectives
        # of its own) whenever the run is multi-host coordinated.
        from scaletorch_tpu.telemetry import StragglerDetector, Telemetry

        self.telemetry = Telemetry.from_config(
            cfg, process_index=jax.process_index())
        self._tracer = self.telemetry.tracer
        self.metrics.exporter = self.telemetry.exporter
        self._last_data_fetch_s = 0.0
        if self.telemetry.snapshotter is not None:
            # install the SIGUSR1 handler NOW, not at train(): the
            # startup log invites the operator to poke the pid, and an
            # unhandled SIGUSR1 during the setup/compile window would
            # kill the run (default disposition is terminate). Uninstall
            # happens in close() via telemetry.close().
            self.telemetry.snapshotter.install(self._live_snapshot)
        if cfg.straggler_factor and self.coordinator.coordinated:
            # multi-host only: a single process has no fleet to compare,
            # and an unattached detector keeps straggler_counters() == {}
            # so solo runs' records carry no vestigial straggler fields
            self.coordinator.straggler = StragglerDetector(
                factor=cfg.straggler_factor,
                patience=cfg.straggler_patience,
                log_frequency=cfg.log_frequency,
                tracer=self._tracer,
            )
        # Elastic fleet membership (--elastic): the epoch state machine
        # that lets survivors of a host loss agree a smaller fleet and
        # continue from the latest checkpoint instead of tearing the run
        # down (resilience_distributed.ElasticCoordinator; train()'s
        # remesh-and-resume outer loop owns what a transition means).
        self.elastic = None
        self._elastic_fleet_hosts = jax.process_count()
        if getattr(cfg, "elastic", False):
            from scaletorch_tpu.resilience_distributed import (
                ElasticCoordinator,
            )

            self.elastic = ElasticCoordinator.from_config(
                cfg,
                rank=jax.process_index(),
                num_hosts=jax.process_count(),
                exporter=self.telemetry.exporter,
            )
        self.logger.info(
            f"model={cfg.model_type} params={to_readable_format(n_params)} "
            f"mesh={self.mm} backend={self.attention_backend} "
            f"dtype={cfg.dtype} gc={cfg.gradient_checkpointing}"
        )
        # Per-device memory BEFORE step 1, while the unsharded init copy
        # (params_host + its optimizer state, built on the first local
        # device above) is still alive: that device's peak against the
        # others is what the init path costs. Rides the performance log.
        self._device_report_init = device_report(
            arrays=(self.params, self.opt_state))
        self.global_step = 0
        self.tokens_seen = 0
        self.preempted = False
        self.emergency_checkpoint_saved = False
        # Stream-position skew: normally the loader position IS
        # global_step, but a sentinel rollback fast-forwards the stream
        # PAST the anomalous region while global_step moves back to the
        # checkpoint — the delta must persist through later checkpoints
        # or a restart would replay the very batch that diverged.
        # _saved_loader_position tracks what the newest on-disk
        # checkpoint stores, so the emergency-save shortcut can tell a
        # truly-covered boundary from a stale pre-rollback save.
        self._loader_skew = 0
        self._saved_loader_position = None
        self._wandb_logged_step = 0
        self._train_iter = None
        self._ckpt_mgr = None
        self._eval_fn = None
        self._eval_loader = None
        self._eval_batches = None
        self._eval_iter = None
        if cfg.eval_frequency:
            from scaletorch_tpu.parallel.spmd import make_spmd_eval_step

            self._eval_fn, _ = make_spmd_eval_step(
                self.mm, fwd_fn, self.model_cfg,
                attention_backend=self.attention_backend,
                sequence_parallel=cfg.sequence_parallel,
                head_weight_fn=head_weight_fn,
                param_specs=param_specs,
                model_kwargs=model_kwargs,
                model_family="qwen3_moe" if is_moe else "llama",
                cp_layout=cfg.cp_layout,
                pp_schedule=cfg.pp_engine,
                pp_vpp=self._pp_vpp,
            )
            self._eval_loader = self._build_eval_loader()

        self._wandb = None
        if cfg.wandb_project and jax.process_index() == 0:
            try:
                import dataclasses as _dc

                import wandb

                self._wandb = wandb.init(
                    project=cfg.wandb_project,
                    name=cfg.wandb_run_name,
                    config=_dc.asdict(cfg),
                )
            except Exception as exc:  # wandb not baked into the image
                self.logger.warning(f"wandb requested but unavailable: {exc!r}")

    @property
    def checkpoint_manager(self):
        if self._ckpt_mgr is None:
            from scaletorch_tpu.utils.checkpoint import CheckpointManager

            self._ckpt_mgr = CheckpointManager(
                self.cfg.checkpoint_dir,
                keep_n=self.cfg.keep_n_checkpoints,
                async_save=self.cfg.async_checkpointing,
                retries=self.cfg.checkpoint_retries,
                retry_base_delay=self.cfg.checkpoint_retry_base_delay,
                fault_injector=self.resilience.injector,
                # multi-process: retry/fallback decisions ride the same
                # coordination bus as the trainer's control decisions
                decision_bus=(self.coordinator.bus
                              if self.coordinator.coordinated else None),
                verify=self.cfg.checkpoint_verify,
            )
        return self._ckpt_mgr

    def _build_eval_loader(self):
        """Validation stream: eval_dataset_name when given; a disjoint-seed
        synthetic stream for synthetic runs; else None (eval skipped, with
        a warning — the concat-chunk train pipeline has no held-out split).
        Both paths reuse build_dataloader so eval batches always match the
        train batch contract."""
        import dataclasses as _dc

        cfg = self.cfg
        if cfg.eval_dataset_name:
            eval_cfg = _dc.replace(
                cfg, dataset_name=cfg.eval_dataset_name, synthetic_data=False
            )
            return build_dataloader(eval_cfg, self.model_cfg)
        if cfg.synthetic_data or not cfg.dataset_name:
            # disjoint seed from the train stream
            eval_cfg = _dc.replace(cfg, seed=cfg.seed + 104729)
            return build_dataloader(eval_cfg, self.model_cfg)
        self.logger.warning(
            "eval_frequency set but no eval_dataset_name; validation skipped"
        )
        return None

    def evaluate(self, num_batches: Optional[int] = None) -> Optional[float]:
        """Mean validation loss over a FIXED set of ``num_batches``
        (cfg.eval_steps) batches — cached on first call so successive
        validations score the same data and val_loss deltas measure
        learning, not sampling noise."""
        if self._eval_fn is None or self._eval_loader is None:
            return None
        num_batches = num_batches or self.cfg.eval_steps
        if self._eval_batches is None:
            self._eval_batches = []
        if len(self._eval_batches) < num_batches:
            # EXTEND the cached set from ONE persistent iterator rather
            # than rebuilding: a rebuild re-draws the cached prefix
            # (synthetic loaders share a mutable rng; file-backed loaders
            # restart their epoch permutation on re-iteration), breaking
            # the fixed-eval-set contract for earlier val_loss readings
            # either by drift or by duplication. A single live iterator
            # keeps the prefix bit-identical and serves fresh batches for
            # the extension under both semantics.
            if self._eval_iter is None:
                self._eval_iter = iter(self._eval_loader)
            self._eval_batches.extend(
                next(self._eval_iter)
                for _ in range(num_batches - len(self._eval_batches))
            )
        total = 0.0
        for batch in self._eval_batches[:num_batches]:
            total += float(self._eval_fn(self.params, self._device_batch(batch)))
        return total / max(num_batches, 1)

    def _device_batch(self, batch: Dict[str, np.ndarray]) -> Dict[str, jax.Array]:
        # put_global: device_put single-process; per-process addressable
        # shards of the (deterministic, identical) host batch multi-process.
        from scaletorch_tpu.dist import put_global

        if self._zigzag_cp:
            # Zigzag CP: permute the token order so the contiguous 'cp'
            # sequence sharding hands each ring rank its stripe pair
            # (parallel/zigzag.py); position_ids ride along, keeping RoPE
            # and the loss layout-transparent.
            from scaletorch_tpu.parallel.zigzag import zigzag_batch

            batch = zigzag_batch(batch, self.cfg.context_parallel_size)
        return {
            k: put_global(np.asarray(v), self._batch_shardings[k])
            for k, v in batch.items()
        }

    def step(self, batch: Optional[Dict[str, np.ndarray]] = None) -> Dict[str, Any]:
        """Run ONE optimizer step and return its raw metrics dict.

        The public per-step entry point for custom loops (examples,
        benchmark harnesses — the reference exposes the same granularity
        as train_step(model, batch, ...), train_step.py:47-136): draws the
        next loader batch when ``batch`` is None (one persistent iterator,
        so successive calls continue the stream), moves it to the mesh,
        applies the jitted SPMD step and advances the step/token counters.
        Metrics logging, eval and checkpoint cadence stay in ``train`` —
        this method is just the step.
        """
        self._last_data_fetch_s = 0.0
        if batch is None:
            if self._train_iter is None:
                self._train_iter = iter(self.loader)
            self._beat("data_fetch")
            t_fetch = time.perf_counter()
            batch = next(self._train_iter)
            # host-side fetch time: rides the coordination gather so the
            # straggler detector can tell input starvation from compute
            self._last_data_fetch_s = time.perf_counter() - t_fetch
        with span("train_step.place", self._tracer):
            dev_batch = self._device_batch(batch)
        self._beat("step_dispatch")
        with span("train_step.dispatch", self._tracer):
            self.params, self.opt_state, m = self.step_fn(
                self.params, self.opt_state, dev_batch
            )
        self.global_step += 1
        # count the batch actually trained on (a caller-supplied batch may
        # differ from the loader's nominal shape), and the HOST-GLOBAL
        # batch at that — every process sees the same global arrays.
        self.tokens_seen += int(np.prod(np.shape(batch["input_ids"])))
        return m

    def train(self, num_steps: Optional[int] = None) -> Dict[str, Any]:
        """Run the training loop.

        ``num_steps`` runs exactly that many MORE optimizer steps (the
        benchmark/example contract); the default runs to the absolute
        ``cfg.total_train_steps`` target, so a run resumed from step k
        continues to the same final step as an uninterrupted one instead
        of appending a whole fresh budget.

        Fault tolerance per step boundary: preemption requests (SIGTERM/
        SIGINT while ``handle_preemption``) trigger an emergency
        checkpoint and a clean early return with ``self.preempted`` set;
        anomalous losses go through the divergence sentinel's configured
        policy (skip / rollback-to-last-good / abort).
        """
        if num_steps is None:
            target_step = max(self.cfg.total_train_steps, self.global_step)
        else:
            target_step = self.global_step + num_steps
        last = {}
        self.preempted = False
        if self.cfg.handle_preemption:
            # Every host installs the handler; on multi-process runs the
            # stop flag is agreed at each step boundary
            # (CoordinatedResilience.should_stop), so one host's SIGTERM
            # becomes a COLLECTIVE emergency save — no host enters
            # orbax's cross-process collective without its peers. With
            # coordination explicitly opted OUT, a one-sided emergency
            # save would wedge the pod, so those runs keep the PR-1
            # behaviour: no in-process handler, resume from the last
            # periodic checkpoint via the external scheduler.
            if jax.process_count() > 1 and not self.coordinator.coordinated:
                self.logger.warning(
                    "handle_preemption with --ft_coordinate false on a "
                    "multi-process run: skipping in-process SIGTERM "
                    "handling (a one-sided emergency save would desync "
                    "orbax's cross-host collectives); restarts resume "
                    "from the last periodic checkpoint"
                )
            else:
                self.resilience.install_preemption_handler()
        from scaletorch_tpu.resilience import TrainingDivergedError
        from scaletorch_tpu.resilience_distributed import (
            HangWatchdog,
            PeerLostError,
            hang_timeout_from_config,
        )

        hang_timeout = hang_timeout_from_config(self.cfg)
        if hang_timeout > 0 and self._watchdog is None:
            self._watchdog = HangWatchdog(
                hang_timeout,
                crash_report=self._watchdog_crash_report,
                exit_fn=self._watchdog_exit,
            ).start()
        if self.telemetry.snapshotter is not None:
            # SIGUSR1 -> live snapshot (span tail + ring buffer + thread
            # stacks) without stopping the run. Normally armed since
            # __init__; idempotent re-install covers harnesses that bind
            # train() onto a foreign trainer object.
            self.telemetry.snapshotter.install(self._live_snapshot)
        profiler = self.telemetry.profiler
        if self.elastic is not None and self.elastic.needs_join:
            # relaunched replacement host: park at the rejoin barrier
            # until a grow epoch admits us, then restore onto the
            # fleet's latest checkpoint before entering lockstep
            self._elastic_join()
        try:
            # Remesh-and-resume outer loop: a PeerLostError from any
            # epoch-bus collective means a host died or hung past the
            # deadline — the survivors agree a shrink epoch, restore
            # from the latest checkpoint onto the smaller topology, and
            # re-enter the inner loop still aiming at the same absolute
            # target_step. Non-elastic runs take one pass and the error
            # (if any) propagates as before.
            while True:
                try:
                    while self.global_step < target_step:
                        self._beat("step_boundary")
                        if self.elastic is not None:
                            self.elastic.beat(self.global_step)
                        t_boundary = time.perf_counter()
                        # telemetry drill: an injected stall here inflates
                        # the ABOUT-TO-RUN step's wall time (global_step +
                        # 1 = the step this iteration performs) so the
                        # slow-step detector fires on exactly the
                        # configured step
                        self.resilience.injector.maybe_slow_step(
                            self.global_step + 1)
                        if profiler is not None:
                            profiler.before_step(self.global_step + 1)
                        if self.coordinator.should_stop():
                            self._emergency_checkpoint()
                            self.preempted = True
                            break
                        m = self.step()
                        step_time = time.perf_counter() - t_boundary
                        anomaly_step = self.global_step
                        m, action = self.coordinator.after_step(
                            anomaly_step, m,
                            rollback=lambda: self._rollback_to_last_good(
                                anomaly_step),
                            # positions ride the decision gather: a
                            # host-local skip of an unreadable region must
                            # abort loudly, not silently train on
                            # mismatched batches
                            position=self._stream_position(),
                            # per-host timings ride the SAME gather — the
                            # straggler layer adds zero collectives
                            telemetry={
                                "step_time": step_time,
                                "data_fetch_time": self._last_data_fetch_s,
                            },
                        )
                        if profiler is not None:
                            profiler.after_step(anomaly_step, step_time)
                        if action == "rollback":
                            # global_step has moved back to the restored
                            # checkpoint; the anomalous step's metrics
                            # would be logged against the wrong step —
                            # drop them.
                            continue
                        last = self.metrics.log_step(
                            self.global_step,
                            loss=m["loss"],
                            # optax evaluates schedule(count) BEFORE
                            # incrementing, so the update just applied
                            # used count = global_step - 1.
                            lr=float(self.schedule(self.global_step - 1)),
                            grad_norm=m["grad_norm"],
                            extras={
                                **{k: v for k, v in m.items()
                                   if k not in ("loss", "grad_norm")},
                                **self.resilience.counters(),
                                **self.coordinator.straggler_counters(),
                            },
                        )
                        if (
                            self.cfg.eval_frequency
                            and self.global_step % self.cfg.eval_frequency
                            == 0
                        ):
                            val = self.evaluate()
                            if val is not None:
                                self.logger.info(
                                    f"step {self.global_step:>6} | "
                                    f"val_loss {val:.4f}"
                                )
                                last = {**last, "val_loss": val}
                        if (last and self._wandb is not None
                                and self.global_step
                                > self._wandb_logged_step):
                            # after a rollback the step counter regresses;
                            # wandb rejects non-monotonic steps and would
                            # silently drop the whole recovery region —
                            # resume logging once the counter passes its
                            # high-water mark
                            self._wandb.log(last, step=self.global_step)
                            self._wandb_logged_step = self.global_step
                        if (
                            self.cfg.save_frequency
                            and self.cfg.checkpoint_dir
                            and self.global_step % self.cfg.save_frequency
                            == 0
                        ):
                            self.save_checkpoint()
                            # checkpoint boundary = the only scale-up
                            # point: parked/relaunched hosts are admitted
                            # here, where the state they must restore is
                            # freshly on disk
                            self._maybe_elastic_grow()
                    break
                except PeerLostError as exc:
                    if self.elastic is None:
                        raise
                    self._elastic_recover(exc)
        except TrainingDivergedError as exc:
            # every abort path leaves a post-mortem on disk — diagnosis
            # must not depend on scrollback
            self._write_crash_report(str(exc))
            raise
        finally:
            if self._watchdog is not None:
                self._watchdog.stop()
                self._watchdog = None
            self.resilience.uninstall_preemption_handler()
            if profiler is not None:
                profiler.close()  # stop an in-flight capture window
            # the SIGUSR1 handler stays armed between train() calls —
            # a poke while idle must dump, not kill; close() uninstalls
            if self._tracer is not None:
                # train() may be called again (benchmark contract): end
                # the open phase and flush, but keep the tracer live
                self._tracer.end_phase()
            self.telemetry.flush()
        if self._ckpt_mgr is not None:
            self._ckpt_mgr.wait()  # drain any in-flight async save
        if self.cfg.performance_log_dir:
            # every process dumps its own history (reference writes
            # performance_logs_<rank>_<ts>.json per rank, train.py:439-443)
            import os

            path = self.metrics.save_json(
                os.path.join(
                    self.cfg.performance_log_dir,
                    f"performance_log_proc{jax.process_index()}"
                    f"_step{self.global_step}.json",
                ),
                extra={
                    "attention_backend": self.attention_backend,
                    "mesh": dict(self.mm.mesh.shape),
                    "devices_before_first_step": self._device_report_init,
                    "devices_after_last_step": device_report(
                        arrays=(self.params, self.opt_state)),
                },
            )
            self.logger.info(f"performance log written to {path}")
        return last

    def close(self) -> None:
        """Release external resources (wandb run, async checkpoint pool,
        telemetry artifacts — the trace file becomes valid JSON here)."""
        if self._wandb is not None:
            self._wandb.finish()
            self._wandb = None
        if self._ckpt_mgr is not None:
            self._ckpt_mgr.wait()
        self.telemetry.close()

    def _layer_storage(self) -> str:
        """Identity of the stacked-layer STORAGE order this run trains in.
        The interleaved engine permutes the layer axis with unchanged
        shapes, so a resume across engines cannot be caught by any shape
        check — this string is saved with every checkpoint and validated
        on load."""
        cfg = self.cfg
        if (cfg.pipeline_parallel_size > 1
                and cfg.pp_engine == "interleaved"):
            return (f"interleaved_pp{cfg.pipeline_parallel_size}"
                    f"_vpp{self._pp_vpp}")
        return "model_order"

    def _beat(self, phase: str) -> None:
        """Feed the hang watchdog AND the span tracer's phase track —
        liveness and tracing share one phase vocabulary (step_boundary /
        data_fetch / step_dispatch / checkpoint / emergency_checkpoint),
        so a watchdog crash report and a Perfetto timeline name the same
        sites; the tracer's phase is a profiler annotation too, so an
        ``AnomalyProfiler`` window shows it. No-op (one branch each)
        when neither is armed."""
        if self._watchdog is not None:
            self._watchdog.beat(self.global_step, phase)
        if self._tracer is not None:
            self._tracer.phase(phase, step=self.global_step)

    def _agree_all(self, flag: bool) -> bool:
        """True iff every host holds True (identity single-process). Any
        branch whose arms execute DIFFERENT collective sequences must be
        taken from an agreed flag, never per-host local state."""
        if self.coordinator.coordinated:
            return self.coordinator.bus.agree_all(flag)
        return bool(flag)

    def _agree_any(self, flag: bool) -> bool:
        if self.coordinator.coordinated:
            return self.coordinator.bus.agree_any(flag)
        return bool(flag)

    def _stream_position(self) -> int:
        """Absolute data-stream position covered so far. Loaders that
        track their own position (advance-before-yield, skipped-region
        accounting) are authoritative; the skew mirror keeps the
        emergency-save staleness check coherent either way."""
        position = getattr(self.loader, "position", None)
        if position is None:
            return self.global_step + self._loader_skew
        self._loader_skew = position - self.global_step
        return position

    def _write_crash_report(self, reason: str,
                            thread_stacks=None) -> str:
        from scaletorch_tpu.resilience_distributed import write_crash_report

        return write_crash_report(
            reason,
            self.global_step,
            directory=self.cfg.crash_report_dir,
            config=self.cfg,
            monitor_records=self.metrics.ring_buffer(),
            last_metrics=self.metrics.history[-5:],
            counters=self.resilience.counters(),
            thread_stacks=thread_stacks,
            span_tail=self.telemetry.span_tail(),
            process_index=(self.coordinator.bus.process_index
                           if self.coordinator.coordinated
                           else jax.process_index()),
        )

    def _live_snapshot(self) -> Dict[str, Any]:
        """SIGUSR1 payload (telemetry.LiveSnapshotter): the same
        diagnostics a crash report carries, taken from a LIVE run."""
        return {
            "step": self.global_step,
            "tokens_seen": self.tokens_seen,
            "span_tail": self.telemetry.span_tail(),
            "monitor_records": self.metrics.ring_buffer(64),
            "last_metrics": self.metrics.history[-5:],
            "counters": {**self.resilience.counters(),
                         **self.coordinator.straggler_counters()},
        }

    def _watchdog_crash_report(self, info: dict) -> str:
        """HangWatchdog callback: persist the post-mortem (thread stacks
        + monitor ring buffer + config fingerprint) before the exit."""
        return self._write_crash_report(
            info["reason"], thread_stacks=info.get("thread_stacks"),
        )

    # separate hook so hermetic tests can record the exit instead of
    # killing the test process; os._exit (not sys.exit) because a thread
    # wedged in a dead collective would never unwind a SystemExit
    _watchdog_exit = staticmethod(os._exit)

    def save_checkpoint(self) -> bool:
        self._beat("checkpoint")
        position = self._stream_position()
        with span("checkpoint_save", self._tracer, step=self.global_step):
            saved = self.checkpoint_manager.save(
                step=self.global_step,
                params=self.params,
                opt_state=self.opt_state,
                extra={"tokens_seen": self.tokens_seen,
                       "loader_position": position,
                       # step size in SAMPLES: lets a resume under a
                       # different dp degree (elastic remesh) translate
                       # the position so consumed batches stay retired
                       "samples_per_step": getattr(
                           self.loader, "samples_per_step", None),
                       "layer_storage": self._layer_storage()},
            )
        if saved:
            self._saved_loader_position = position
        return saved

    def load_checkpoint(self, required: bool = False, *,
                        target_mesh=None) -> bool:
        """Restore the newest readable checkpoint; returns whether one was
        restored. ``required`` (--resume must) raises instead of training
        from scratch when nothing restores. ``target_mesh`` reshards the
        restore onto a DIFFERENT mesh than the live arrays' (the elastic
        remesh path, where self.params still live on the pre-shrink
        topology)."""
        restored = self.checkpoint_manager.load_latest(
            params=self.params, opt_state=self.opt_state,
            target_mesh=target_mesh,
        )
        if restored is None:
            if required:
                raise FileNotFoundError(
                    f"--resume must: no restorable checkpoint in "
                    f"{self.cfg.checkpoint_dir}"
                )
            self.logger.warning(
                f"resume requested but no checkpoint found in "
                f"{self.cfg.checkpoint_dir}; training from scratch"
            )
            return False
        validate_layer_storage(
            restored["extra"].get("layer_storage", "model_order"),
            self._layer_storage(),
            pp_engine=self.cfg.pp_engine,
            pp_virtual_stages=self._pp_vpp,
        )
        self.params = restored["params"]
        self.opt_state = restored["opt_state"]
        self.global_step = restored["step"]
        self.tokens_seen = restored["extra"].get("tokens_seen", 0)
        # Fast-forward the data stream so resumed training continues the
        # dataset walk instead of replaying it (sampler epoch parity).
        # loader_position may be AHEAD of global_step when a sentinel
        # rollback skipped an anomalous region before this save —
        # restoring the skew keeps the bad batch retired across restarts.
        # A live step() iterator predates set_state and would keep
        # yielding from the old position — drop it so the next step()
        # re-iterates.
        position = restored["extra"].get("loader_position", self.global_step)
        saved_spp = restored["extra"].get("samples_per_step")
        cur_spp = getattr(self.loader, "samples_per_step", None)
        if saved_spp and cur_spp and int(saved_spp) != int(cur_spp):
            # the checkpoint was written under a different dp degree
            # (elastic remesh): its position counts OLD-geometry steps —
            # translate by sample count so every consumed batch stays
            # retired exactly once
            from scaletorch_tpu.data.dataloader import remap_loader_position

            position = remap_loader_position(
                position,
                old_samples_per_step=int(saved_spp),
                new_samples_per_step=int(cur_spp),
            )
        self._loader_skew = position - self.global_step
        self._saved_loader_position = position
        if hasattr(self.loader, "set_state"):
            self.loader.set_state(position)
        self._train_iter = None
        self.logger.info(f"resumed from step {self.global_step}")
        return True

    def _rollback_to_last_good(self, anomaly_step: int) -> bool:
        """Divergence-sentinel rollback: restore the last good checkpoint
        and fast-forward the data stream PAST the anomalous region, so
        the retrained steps see fresh data instead of replaying the batch
        that diverged. Returns False (caller downgrades to skip) when no
        checkpoint is restorable."""
        if not self.cfg.checkpoint_dir:
            return False
        # Drain any in-flight async save FIRST: a just-dispatched save
        # (not yet visible to latest_step) would otherwise finalize after
        # the restore and resurface as a stale newest checkpoint carrying
        # the pre-rollback loader position.
        # Agree BEFORE any host can return early: a host whose directory
        # listing transiently shows nothing (list-after-write lag, racing
        # retention sweep) must not skip the restore collectives its
        # peers are about to enter — either every host rolls back or
        # every host downgrades to skip.
        self.checkpoint_manager.wait()
        if not self._agree_all(
                self.checkpoint_manager.latest_step() is not None):
            return False
        self.logger.warning(
            f"divergence at step {anomaly_step}: rolling back to the last "
            "good checkpoint and fast-forwarding the data stream"
        )
        # The anomalous batch's TRUE stream position accounts for skew
        # accumulated by earlier rollbacks AND unreadable regions the
        # loader already skipped — capture it before load_checkpoint
        # overwrites the skew from the checkpoint.
        bad_position = self._stream_position()
        if not self.load_checkpoint():
            return False
        # fast-forward PAST the bad region and remember the skew so later
        # checkpoints persist the retired batches (neither a restart nor
        # a second rollback may replay a batch that diverged)
        self._loader_skew = bad_position - self.global_step
        if hasattr(self.loader, "set_state"):
            self.loader.set_state(bad_position)
            self._train_iter = None
        return True

    def _make_step_fn(self, params_template):
        """Build (or, after an elastic remesh, REBUILD) the jitted SPMD
        train step against the CURRENT ``self.mm``. ``params_template``
        only needs shapes/dtypes (ShapeDtypeStructs work — opt-state
        spec derivation goes through eval_shape), so the remesh path can
        rebuild without materialising host params."""
        from scaletorch_tpu.parallel.spmd import make_spmd_train_step

        cfg = self.cfg
        pieces = self._spmd_pieces
        return make_spmd_train_step(
            self.mm,
            pieces["fwd_fn"],
            self.model_cfg,
            self.tx,
            params_template,
            attention_backend=self.attention_backend,
            gradient_checkpointing=cfg.gradient_checkpointing,
            remat_policy=cfg.remat_policy,
            sequence_parallel=cfg.sequence_parallel,
            max_grad_norm=cfg.max_grad_norm,
            donate=cfg.donate_params,
            pp_schedule=cfg.pp_engine,
            pp_vpp=self._pp_vpp,
            cp_layout=cfg.cp_layout,
            param_specs=pieces["param_specs"],
            model_kwargs=pieces["model_kwargs"],
            head_weight_fn=pieces["head_weight_fn"],
            model_family=pieces["model_family"],
            nonfinite_guard=cfg.nonfinite_guard,
            grad_allreduce_dtype=cfg.grad_allreduce_dtype,
            grad_allreduce_axis=cfg.grad_allreduce_axis,
            grad_allreduce_block_size=cfg.grad_allreduce_block_size,
        )

    # ---- elastic continuation (resilience_distributed.ElasticCoordinator)

    def _elastic_join(self) -> None:
        """Relaunched replacement host: block at the rejoin barrier until
        a grow epoch admits this rank, then take the SAME restore path
        the incumbent members take at that boundary — so the rejoiner
        enters lockstep holding bit-identical state."""
        view = self.elastic.join(self.global_step)
        self._elastic_apply_view(view)

    def _elastic_recover(self, exc) -> None:
        """A collective broke (host died or hung past the deadline): run
        the membership recovery protocol — store-based, no collectives
        over the broken bus — and move onto the epoch it agrees."""
        self.logger.warning(
            f"elastic recovery at step {self.global_step}: {exc!r}"
        )
        view = self.elastic.on_peer_lost(self.global_step, exc=exc)
        self._elastic_apply_view(view)

    def _maybe_elastic_grow(self) -> None:
        """Checkpoint-boundary scale-up: host 0 reads the rejoin mailbox
        and the decision rides the epoch bus, so every member admits the
        same joiners at the same boundary (or nobody does)."""
        if self.elastic is None:
            return
        view = self.elastic.maybe_grow(self.global_step)
        if view is not None:
            self._elastic_apply_view(view)

    def _elastic_apply_view(self, view) -> None:
        """Move this trainer onto an adopted membership epoch: retire the
        old epoch's checkpoint manager (its decision bus is dead or
        renumbered), rebind the coordinator onto the new epoch's bus,
        rebuild the topology for the agreed host count, and restore from
        the latest checkpoint. The restore is deliberately UNIFORM —
        members that never lost a step restore too — which keeps the
        collective sequence identical on every host and makes the
        post-transition trajectory a pure function of the checkpoint
        (the bit-identical-continuation contract the elastic drills
        pin)."""
        if self._ckpt_mgr is not None:
            # collective-free teardown: the old bus cannot carry the
            # coordinated wait anymore
            self._ckpt_mgr.detach()
            self._ckpt_mgr = None
        self.coordinator.rebind_bus(self.elastic.bus)
        target_mesh = None
        rebuild = getattr(self, "_elastic_rebuild_topology", None)
        if callable(rebuild):
            # real trainer: remesh + re-jit + loader geometry; toy
            # harnesses (threaded-host drills) run without device state
            rebuild(view)
            target_mesh = self.mm.mesh
        self.load_checkpoint(required=True, target_mesh=target_mesh)
        self.elastic.pending_bootstrap = False

    def _elastic_rebuild_topology(self, view) -> None:
        """Rebuild mesh + jitted step + loader geometry for the agreed
        host count. The dp axis absorbs the whole change
        (parallel/mesh.elastic_mesh_kwargs); an un-shrinkable geometry
        or a JAX runtime that has not renumbered onto the surviving
        devices aborts loudly to the fleet-restart fallback."""
        import math

        from scaletorch_tpu.parallel.mesh import (
            MeshShrinkError,
            elastic_mesh_kwargs,
        )
        from scaletorch_tpu.parallel.spmd import batch_specs
        from scaletorch_tpu.resilience_distributed import ElasticRemeshError

        try:
            kwargs = elastic_mesh_kwargs(
                self.cfg.mesh_kwargs(),
                hosts_before=self._elastic_fleet_hosts,
                hosts_after=view.num_hosts,
            )
        except MeshShrinkError as exc:
            raise ElasticRemeshError(str(exc)) from exc
        shape = tuple(kwargs[a] for a in ("dp", "pp", "cp", "ep", "tp"))
        if shape == self.mm.shape:
            return  # remesh-in-place (spurious loss: everyone answered)
        world = math.prod(shape)
        devices = jax.devices()
        if world != len(devices):
            raise ElasticRemeshError(
                f"elastic remesh to {view.num_hosts} host(s) needs "
                f"{world} devices but the JAX runtime exposes "
                f"{len(devices)} — the runtime did not renumber after "
                "the membership change; falling back to a fleet restart"
            )
        self.mm = setup_mesh_manager(**kwargs)
        template = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), self.params)
        self.step_fn, _, _ = self._make_step_fn(template)
        self._batch_shardings = {
            k: NamedSharding(self.mm.mesh, spec)
            for k, spec in batch_specs().items()
        }
        if hasattr(self.loader, "set_data_parallel_size"):
            self.loader.set_data_parallel_size(
                kwargs["dp"] * self.cfg.expert_parallel_size)
        self._train_iter = None

    def _emergency_checkpoint(self) -> bool:
        """Preemption-safe shutdown: synchronously persist the current
        state at the step boundary (reference graceful-abort role,
        train.py:257-268 — here with a real checkpoint). Returns whether
        this step's state is actually on disk (also recorded as
        ``self.emergency_checkpoint_saved`` for the entry point's exit
        message)."""
        sig = (self.resilience.preemption.signum
               if self.resilience.preemption is not None else None)
        if not self.cfg.checkpoint_dir:
            self.logger.warning(
                f"preemption requested (signal {sig}) but no "
                "checkpoint_dir is configured: exiting without a "
                "checkpoint"
            )
            self.emergency_checkpoint_saved = False
            return False
        # Multi-host: every host must be saving the SAME step — a
        # mismatch means the lockstep invariant broke and entering the
        # collective save would wedge, so fail loudly instead.
        self.coordinator.verify_agreement(
            "emergency_checkpoint_step", self.global_step)
        self._beat("emergency_checkpoint")
        # Every branch below is taken from an AGREED flag: a per-host
        # directory-listing race (list-after-write lag) must not send
        # hosts down arms with different collective sequences — same
        # treatment as the rollback path above.
        if self._agree_all(
                self.checkpoint_manager.latest_step() == self.global_step
                and self._saved_loader_position
                == self._stream_position()):
            # the save cadence already covered this boundary — same step
            # AND same loader position (a rollback can change the skew
            # after the step was saved, making the on-disk checkpoint
            # stale even at a matching step number). The save may still
            # be an in-flight async write: drain it and RE-CHECK the
            # directory before trusting it (wait() swallows async
            # failures by degrading to sync).
            self.checkpoint_manager.wait()
            if self._agree_all(self.checkpoint_manager.latest_step()
                               == self.global_step):
                self.logger.warning(
                    f"preemption requested (signal {sig}): step "
                    f"{self.global_step} is already checkpointed; exiting"
                )
                self.emergency_checkpoint_saved = True
                return True
            # the in-flight save failed — fall through to a fresh save
        if self._agree_any(self.checkpoint_manager.latest_step()
                           == self.global_step):
            # same step number but STALE content (e.g. the loader skew
            # changed after a rollback): orbax silently skips same-step
            # saves, so the stale one must be deleted to be replaced.
            # Shared directory: exactly one host performs the delete.
            if (not self.coordinator.coordinated
                    or self.coordinator.bus.is_main):
                try:
                    self.checkpoint_manager.delete(self.global_step)
                except Exception as exc:
                    self.logger.error(
                        f"could not replace stale checkpoint at step "
                        f"{self.global_step}: {exc!r}"
                    )
            if self.coordinator.coordinated:
                # every host must SEE the retirement before saving:
                # orbax's monotonic should_save on a host whose listing
                # still shows the step would silently no-op while its
                # peers enter the real save collective (bounded wait —
                # a failed delete falls through to the save attempt,
                # whose agreed outcome handles the skip symmetrically)
                for _ in range(50):
                    if self._agree_all(
                            self.checkpoint_manager.latest_step()
                            != self.global_step):
                        break
                    time.sleep(0.1)
        self.logger.warning(
            f"preemption requested (signal {sig}): writing emergency "
            f"checkpoint at step {self.global_step}"
        )
        saved = self.save_checkpoint()
        self.checkpoint_manager.wait()
        # wait() may have degraded async->sync after a pool failure; the
        # directory listing is the ground truth for "is my step on disk"
        # — and the verdict must be fleet-wide, not per-host
        saved = self._agree_all(saved and (
            self.checkpoint_manager.latest_step() == self.global_step))
        self.emergency_checkpoint_saved = saved
        return saved
