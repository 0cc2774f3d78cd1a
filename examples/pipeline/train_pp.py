#!/usr/bin/env python
"""Pipeline-parallel training demo — the three SPMD schedules side by side.

A capability tour of the pipeline tier (parallel/pipeline_parallel.py):
the same tiny Llama trains over a pp-sharded layer stack under the
chosen schedule, and the script prints the schedule's exact tick
accounting before training so the trade is visible up front:

  * ``afab``            one fwd+bwd pipeline over all M microbatches —
                        bubble (pp-1)/(M+pp-1), O(M) boundary carries.
  * ``interleaved``     V virtual stages per rank on a circular ring —
                        bubble cut ~V x (needs L %% (pp*V) == 0).
  * ``memory_chunked``  1F1B's O(pp) boundary memory, a bubble per
                        chunk (reference-compat alias: ``1f1b``).

Run on any mesh:

    JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/pipeline/train_pp.py --engine interleaved --vpp 2
"""

from __future__ import annotations

import argparse
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)


def main(argv=None) -> float:
    ap = argparse.ArgumentParser()
    ap.add_argument("--engine", default="interleaved",
                    choices=["afab", "interleaved", "memory_chunked", "1f1b"])
    ap.add_argument("--pp", type=int, default=2)
    ap.add_argument("--vpp", type=int, default=2,
                    help="virtual stages per rank (interleaved only)")
    ap.add_argument("--accum", type=int, default=4,
                    help="microbatches per step (the pipeline's M)")
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    args = ap.parse_args(argv)

    import jax

    from scaletorch_tpu.config import ScaleTorchTPUArguments
    from scaletorch_tpu.parallel.pipeline_parallel import (
        interleaved_tick_schedule,
    )
    from scaletorch_tpu.trainer.trainer import Trainer

    n_dev = len(jax.devices())
    vpp = args.vpp if args.engine == "interleaved" else 1
    m = args.accum
    if args.engine == "interleaved":
        acct = interleaved_tick_schedule(m, args.pp, vpp)
        print(f"interleaved pp={args.pp} vpp={vpp} M={m}: "
              f"{acct['ticks']} chunk-ticks, bubble "
              f"{acct['bubble_fraction']:.1%} (afab: "
              f"{acct['afab_bubble_fraction']:.1%}), predicted step time "
              f"{acct['relative_step_time']:.3f}x afab's")
    else:
        print(f"{args.engine} pp={args.pp} M={m}: "
              f"{m + args.pp - 1} stage-ticks fwd, bubble "
              f"{(args.pp - 1) / (m + args.pp - 1):.1%}")

    cfg = ScaleTorchTPUArguments(
        model_type="llama", hidden_size=64, intermediate_size=128,
        num_hidden_layers=args.pp * max(vpp, 2),  # divides pp*vpp
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        vocab_size=256, sequence_length=args.seq,
        max_position_embeddings=2 * args.seq,
        pipeline_parallel_size=args.pp,
        data_parallel_size=max(n_dev // args.pp, 1),
        pp_engine=args.engine, pp_virtual_stages=vpp,
        micro_batch_size=1, gradient_accumulation_steps=args.accum,
        synthetic_data=True, total_train_steps=args.steps, dtype="float32",
        learning_rate=1e-3, warmup_steps=0,
        donate_params=False, log_frequency=max(args.steps // 4, 1),
    )
    trainer = Trainer(cfg)
    try:
        first = last = None
        for _ in range(args.steps):
            m_out = trainer.step()  # public per-step API
            last = float(m_out["loss"])
            if first is None:
                first = last
        print(f"trained {args.steps} steps ({cfg.pp_engine}): "
              f"loss {first:.4f} -> {last:.4f}")
        return last
    finally:
        trainer.close()


if __name__ == "__main__":
    main()
