#!/usr/bin/env python
"""FSDP training demo — parameter + optimizer-state sharding on a mesh.

Counterpart of reference examples/FSDP2/fsdp2_main.py (toy Transformer,
``fully_shard`` over a 1-D device mesh, mixed precision, checkpoint
save/resume): the TPU version places each parameter sharded over the
``fsdp`` axis (parallel/fsdp.py) and lets the XLA SPMD partitioner issue
the just-in-time all-gathers and gradient reduce-scatters that FSDP2
performs with imperative hooks. Run on any mesh:

    # 8 virtual CPU devices
    JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/fsdp/train_fsdp.py --steps 10

    python examples/fsdp/train_fsdp.py --mixed-precision   # bf16 params
    python examples/fsdp/train_fsdp.py --checkpoint-dir /tmp/fsdp_ckpt
    # second run with the same --checkpoint-dir resumes (reference
    # fsdp2_main.py's save-then-load flow)
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)


def main(argv=None) -> float:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--rows", type=int, default=8,
                    help="global batch rows (sharded over the fsdp axis)")
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--mixed-precision", action="store_true",
                    help="bf16 params + bf16 compute "
                         "(reference fsdp2_main.py --mixed-precision)")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="save/resume dir; a second run resumes from it")
    ap.add_argument("--log_interval", type=int, default=1)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from scaletorch_tpu.config import ScaleTorchTPUArguments
    from scaletorch_tpu.models.llama import LlamaConfig, forward, init_params
    from scaletorch_tpu.parallel.fsdp import setup_fsdp
    from scaletorch_tpu.trainer.optimizer import create_optimizer

    dtype = jnp.bfloat16 if args.mixed_precision else jnp.float32
    cfg = LlamaConfig(
        vocab_size=512, hidden_size=128, intermediate_size=256,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        head_dim=32, max_position_embeddings=max(64, args.seq),
        dtype=dtype, param_dtype=dtype,
    )

    # Peek at the checkpoint BEFORE building the optimizer: the restored
    # adam count is cumulative, so the LR schedule's horizon must cover
    # resumed + new steps or resumed training runs at the decayed floor.
    start_step = 0
    ckpt = None
    if args.checkpoint_dir:
        import orbax.checkpoint as ocp

        ckpt = ocp.CheckpointManager(os.path.abspath(args.checkpoint_dir))
        start_step = ckpt.latest_step() or 0

    targs = ScaleTorchTPUArguments(
        total_train_steps=start_step + args.steps,
        learning_rate=args.lr, warmup_steps=2, max_grad_norm=1.0,
    )
    tx, _ = create_optimizer(targs, include_clip=True)

    params_host = init_params(jax.random.key(0), cfg)
    step_fn, params, opt_state, mesh = setup_fsdp(forward, cfg, params_host, tx)
    n_dev = mesh.shape["fsdp"]
    if args.rows % n_dev:
        raise SystemExit(f"--rows {args.rows} must divide over {n_dev} devices")

    if ckpt is not None and start_step:
        import orbax.checkpoint as ocp

        # Restore INTO the current mesh's shardings (abstract template):
        # resuming on a different topology re-shards instead of replaying
        # the saved placement from the sharding file.
        template = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
            {"params": params, "opt_state": opt_state},
        )
        restored = ckpt.restore(
            start_step, args=ocp.args.StandardRestore(template)
        )
        # Belt and braces: orbax honours the template for arrays but can
        # leave rank-0 leaves on a single device — re-place everything.
        restored = jax.tree.map(
            lambda x, t: jax.device_put(x, t.sharding), restored, template
        )
        params, opt_state = restored["params"], restored["opt_state"]
        print(f"resumed from step {start_step} in {args.checkpoint_dir}")

    # parameter memory actually sharded: report per-device bytes
    total = sum(p.size * p.dtype.itemsize for p in jax.tree.leaves(params))
    local = sum(
        p.addressable_shards[0].data.size * p.dtype.itemsize
        for p in jax.tree.leaves(params)
    )
    print(f"devices={n_dev} param_bytes total={total/1e6:.1f}MB "
          f"per-device={local/1e6:.1f}MB (x{total/max(local,1):.1f} saving)")

    rng = np.random.default_rng(start_step)
    loss = float("nan")
    for step in range(start_step, start_step + args.steps):
        ids = rng.integers(0, cfg.vocab_size, (1, args.rows, args.seq + 1))
        batch = {
            "input_ids": jnp.asarray(ids[:, :, :-1], jnp.int32),
            "target_ids": jnp.asarray(ids[:, :, 1:], jnp.int32),
        }
        params, opt_state, m = step_fn(params, opt_state, batch)
        loss = float(m["loss"])
        if (step + 1) % args.log_interval == 0:
            print(f"step {step + 1:>4} | loss {loss:.4f} "
                  f"| gnorm {float(m['grad_norm']):.3f}")

    if ckpt is not None:
        import orbax.checkpoint as ocp

        ckpt.save(
            start_step + args.steps,
            args=ocp.args.StandardSave({"params": params,
                                        "opt_state": opt_state}),
        )
        ckpt.wait_until_finished()
        print(f"saved step {start_step + args.steps} to {args.checkpoint_dir}")
    return loss


if __name__ == "__main__":
    main()
