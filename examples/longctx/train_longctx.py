#!/usr/bin/env python
"""Long-context training demo — context parallelism over the cp axis.

The capability tour the reference demonstrates with its CP benchmark
rows (BASELINE.md: CP2-DP4 at seq 4096, CP4-DP2 at seq 8192): sequences
longer than one chip wants to attend over are sharded across the ``cp``
mesh axis and attention runs distributed, via either

  * ``--strategy ring``     — zigzag-striped ring attention (default):
    K/V blocks circulate the ring and every rank does equal causal work;
  * ``--strategy ulysses``  — all-to-all head scatter: each rank runs one
    full-sequence flash attention over a head subset (cp must divide the
    KV head count).

The loss is IDENTICAL to single-device attention (golden-tested in
tests/parallel/test_context_parallel.py, tests/ops/test_ulysses.py);
what CP buys is memory headroom and parallel attention FLOPs, so the
max trainable sequence scales with cp. Run on any mesh:

    # 8 virtual CPU devices: seq 2048 across cp=4
    JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/longctx/train_longctx.py --cp 4 --seq 2048

    python examples/longctx/train_longctx.py --cp 2 --strategy ulysses
"""

from __future__ import annotations

import argparse
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)


def main(argv=None) -> float:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cp", type=int, default=4)
    ap.add_argument("--dp", type=int, default=0,
                    help="0 = fill the remaining devices")
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--strategy", choices=["ring", "ulysses"], default="ring")
    ap.add_argument("--layout", choices=["zigzag", "contiguous"],
                    default="zigzag", help="ring sequence layout")
    args = ap.parse_args(argv)

    import jax

    from scaletorch_tpu.config import ScaleTorchTPUArguments
    from scaletorch_tpu.trainer.trainer import Trainer

    n_dev = len(jax.devices())
    dp = args.dp or max(n_dev // args.cp, 1)
    cfg = ScaleTorchTPUArguments(
        model_type="llama", hidden_size=64, intermediate_size=128,
        # 4 KV heads so the default --cp 4 works for ulysses too
        # (cp must divide the KV head count for the head-scatter path)
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        vocab_size=256, sequence_length=args.seq,
        max_position_embeddings=2 * args.seq,
        context_parallel_size=args.cp, data_parallel_size=dp,
        cp_layout=args.layout,
        attention_backend=args.strategy,
        # per-rank batch of 1: per-chip work stays fixed as the mesh
        # grows (micro_batch_size is PER dp rank; global = micro * dp)
        micro_batch_size=1, synthetic_data=True,
        total_train_steps=args.steps, dtype="float32",
        donate_params=False, log_frequency=max(args.steps // 4, 1),
    )
    trainer = Trainer(cfg)
    print(f"devices={n_dev} cp={args.cp} dp={dp} seq={args.seq} "
          f"strategy={args.strategy}"
          + (f" layout={args.layout}" if args.strategy == "ring" else ""))
    try:
        it = iter(trainer.loader)
        first = last = None
        for step in range(args.steps):
            batch = trainer._device_batch(next(it))
            trainer.params, trainer.opt_state, m = trainer.step_fn(
                trainer.params, trainer.opt_state, batch)
            last = float(m["loss"])
            if first is None:
                first = last
        tokens = args.steps * trainer.loader.tokens_per_step
        print(f"trained {args.steps} steps ({tokens} tokens at seq "
              f"{args.seq}): loss {first:.4f} -> {last:.4f}")
        return last
    finally:
        trainer.close()


if __name__ == "__main__":
    main()
