#!/usr/bin/env python
"""Mixture-of-Experts training demo — expert parallelism over the ep axis.

The capability tour of the reference's MoE rows (BASELINE.md 30B-A3B;
model_qwen3_moe.py): a Qwen3-MoE trains with its experts sharded across
the ``ep`` mesh axis and tokens moved by the capacity dispatch, with the
round-4 knobs exposed:

  * ``--dispatch einsum|index|auto`` — token-movement form. The one-hot
    einsums are 62% of step FLOPs at E=128/top-8 (AOT_30B_A3B.json); the
    index form moves exactly the O(N·k·H) routed rows. Identical math.
  * ``--sparse-step N`` / ``--dense-layers i j`` — interleaved
    dense/sparse architectures (HF ``decoder_sparse_step`` /
    ``mlp_only_layers``): dense layers run the plain SwiGLU MLP, sparse
    layers the routed experts, as contiguous segment scans.

Routing health (dropped token fraction, expert load CV) prints with the
step metrics — the operator-facing signal that the router is balanced.
Run on any mesh:

    # 8 virtual CPU devices: E=8 over ep=2, every layer sparse
    JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/moe/train_moe.py --ep 2

    # interleaved: layers 1,3 sparse / 0,2 dense, index-form dispatch
    python examples/moe/train_moe.py --ep 2 --sparse-step 2 --dispatch index
"""

from __future__ import annotations

import argparse
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)


def main(argv=None) -> float:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ep", type=int, default=2)
    ap.add_argument("--dp", type=int, default=0,
                    help="0 = fill the remaining devices")
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--experts", type=int, default=8)
    ap.add_argument("--dispatch", choices=["auto", "einsum", "index"],
                    default="auto")
    ap.add_argument("--sparse-step", type=int, default=1,
                    help="layer i is sparse iff (i+1) %% this == 0")
    ap.add_argument("--dense-layers", type=int, nargs="*", default=[],
                    help="layer indices forced dense (mlp_only_layers)")
    args = ap.parse_args(argv)

    import jax

    from scaletorch_tpu.config import ScaleTorchTPUArguments
    from scaletorch_tpu.trainer.trainer import Trainer

    n_dev = len(jax.devices())
    dp = args.dp or max(n_dev // args.ep, 1)
    cfg = ScaleTorchTPUArguments(
        model_type="qwen3_moe", hidden_size=64, intermediate_size=128,
        moe_intermediate_size=64, num_hidden_layers=4,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        num_experts=args.experts, num_experts_per_tok=2,
        # generous capacity for the demo: an untrained router is unbalanced
        # and the default 1.25 factor drops ~1/3 of tokens at init, which
        # drowns the first steps' learning signal
        moe_capacity_factor=2.0,
        moe_dispatch=args.dispatch,
        decoder_sparse_step=args.sparse_step,
        mlp_only_layers=args.dense_layers or None,
        vocab_size=256, sequence_length=args.seq,
        max_position_embeddings=2 * args.seq,
        expert_parallel_size=args.ep, data_parallel_size=dp,
        micro_batch_size=1, synthetic_data=True,
        total_train_steps=args.steps, dtype="float32",
        # demo-sized LR: the model is tiny and the run is seconds long
        learning_rate=1e-3, warmup_steps=0,
        donate_params=False, log_frequency=max(args.steps // 4, 1),
    )
    trainer = Trainer(cfg)
    layout = trainer.model_cfg.sparse_layout()
    print(f"devices={n_dev} ep={args.ep} dp={dp} experts={args.experts} "
          f"dispatch={trainer.model_cfg.resolved_moe_dispatch()} "
          f"sparse_layers={[i for i, s in enumerate(layout) if s]}")
    try:
        first = last = None
        drop = None
        for step in range(args.steps):
            m = trainer.step()  # public per-step API (draws from the loader)
            last = float(m["loss"])
            drop = float(m["moe_dropped_fraction"])
            if first is None:
                first = last
        print(f"trained {args.steps} steps: loss {first:.4f} -> {last:.4f} "
              f"(final dropped_fraction {drop:.2%})")
        return last
    finally:
        trainer.close()


if __name__ == "__main__":
    main()
