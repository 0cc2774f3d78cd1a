#!/usr/bin/env python
"""Training entry point.

TPU-native counterpart of reference train.py:55-453: parse composed
dataclass args, set up the device mesh, build model/optimizer/data, run
the training loop with metrics + checkpointing.

Examples:
  # single chip, synthetic data
  python train.py --model_type llama --hidden_size 512 --num_hidden_layers 8 \
      --synthetic_data true --total_train_steps 20

  # 8 virtual CPU devices, DP8 (tests/multi-chip dry runs)
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python train.py --data_parallel_size 8 --synthetic_data true ...
"""

from __future__ import annotations

import os
import sys


def main(argv=None) -> int:
    from scaletorch_tpu.env import configure_compile_cache

    configure_compile_cache()
    from scaletorch_tpu.config import parse_args
    from scaletorch_tpu.resilience import TrainingDivergedError
    from scaletorch_tpu.resilience_distributed import (
        DIVERGED_EXIT_CODE,
        WATCHDOG_EXIT_CODE,
        ElasticRemeshError,
    )
    from scaletorch_tpu.trainer.trainer import Trainer
    from scaletorch_tpu.utils.logger import get_logger

    cfg = parse_args(argv)
    trainer = Trainer(cfg)
    if trainer.telemetry.enabled:
        # the operator contract up front: where the artifacts land and
        # how to poke a live run (docs/observability.md)
        get_logger().info(
            f"telemetry enabled -> {trainer.telemetry.directory} "
            "(Chrome trace + JSONL event stream; kill -USR1 "
            f"{os.getpid()} dumps a live snapshot)"
        )
    # --resume auto: a restarted (e.g. preempted-and-rescheduled) job picks
    # up from the newest readable checkpoint and trains to the SAME
    # total_train_steps target; with no checkpoint yet it starts from
    # scratch. --resume must fails fast instead of silently restarting.
    if cfg.resume != "off" and cfg.checkpoint_dir:
        trainer.load_checkpoint(required=cfg.resume == "must")
    try:
        last = trainer.train()
        if trainer.preempted:
            # exit cleanly either way so the scheduler sees a graceful
            # shutdown, but be truthful about what survived
            if trainer.emergency_checkpoint_saved:
                get_logger().warning(
                    f"preempted at step {trainer.global_step}; emergency "
                    "checkpoint saved — restart with --resume auto to "
                    "continue"
                )
            else:
                get_logger().error(
                    f"preempted at step {trainer.global_step} and NO "
                    "emergency checkpoint could be written — a restart "
                    "resumes from the last periodic save (or scratch)"
                )
            return 0
        # final save BEFORE close() so the async dispatch is drained by
        # close()'s wait — otherwise the process could exit mid-write
        if cfg.checkpoint_dir and cfg.save_frequency:
            trainer.save_checkpoint()
    except TrainingDivergedError as exc:
        # the trainer already wrote results/crash_report_step<N>.json;
        # exit with the documented code so launchers/schedulers can tell
        # "diverged, needs a human" from "preempted, just restart"
        # (docs/fault_tolerance.md exit-code contract; the hang watchdog
        # exits 43 directly from its monitor thread)
        get_logger().error(f"training aborted: {exc}")
        return DIVERGED_EXIT_CODE
    except ElasticRemeshError as exc:
        # the elastic coordinator could not continue (un-shrinkable
        # geometry, min-hosts floor, membership store unreachable):
        # restart-family exit — the launcher's fleet-wide restart is the
        # fallback, never a human (42 stays reserved for divergence)
        get_logger().error(f"elastic continuation impossible: {exc}")
        return WATCHDOG_EXIT_CODE
    except KeyboardInterrupt:
        get_logger().warning("interrupted; exiting")
        return 130
    finally:
        # drain in-flight async checkpoint saves + finish wandb even on
        # interrupt/error (reference aborts with cleanup, train.py:257-268)
        trainer.close()
    get_logger().info(f"done: {last}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
