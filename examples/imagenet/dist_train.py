#!/usr/bin/env python
"""Distributed ImageNet-style training — ResNet over a data-parallel mesh.

Counterpart of reference examples/torch_examples/imagenet/dist_train.py
(the classic DDP script: resnet18 default, SGD+momentum, StepLR decay
x0.1 every 30 epochs, top-1/top-5 accuracy, best-checkpoint save,
resume). TPU rendition: the batch is sharded over a 1-D `dp` mesh with
NamedSharding and XLA handles the gradient all-reduce; BatchNorm
statistics reduce over the GLOBAL batch (sync-BN — torch's
SyncBatchNorm rather than DDP's local default, models/resnet.py), so
training dynamics are independent of the device count.

Data: an ImageFolder-style directory of per-class .npy arrays if --data
is given, else a deterministic synthetic stand-in (fixed class
prototypes + noise) so the example is hermetic offline.

    JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/imagenet/dist_train.py --arch resnet18 \
        --image-size 64 --num-classes 10 --epochs 2
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)


def synthetic_images(n, num_classes, size, seed=0):
    """Fixed per-class prototypes + noise (learnable, hermetic)."""
    protos = np.random.default_rng(4321).uniform(
        0, 1, (num_classes, size, size, 3)).astype(np.float32)
    rng = np.random.default_rng(seed)
    y = rng.integers(0, num_classes, n).astype(np.int32)
    x = protos[y] + rng.normal(0, 0.35, (n, size, size, 3)).astype(np.float32)
    return np.clip(x, 0, 1), y


def load_folder(data_dir, size):
    """Minimal ImageFolder: data_dir/<class>/*.npy arrays [H, W, 3]."""
    classes = sorted(
        d for d in os.listdir(data_dir)
        if os.path.isdir(os.path.join(data_dir, d))
    )
    xs, ys = [], []
    for ci, cname in enumerate(classes):
        cdir = os.path.join(data_dir, cname)
        for f in sorted(os.listdir(cdir)):
            if f.endswith(".npy"):
                arr = np.load(os.path.join(cdir, f)).astype(np.float32)
                if arr.shape[:2] != (size, size):
                    raise SystemExit(
                        f"{f}: expected {size}x{size}, got {arr.shape[:2]}; "
                        "resize offline (no image libs in this example)")
                xs.append(arr)
                ys.append(ci)
    if not xs:
        raise SystemExit(f"no .npy files under {data_dir}")
    x, y = np.stack(xs), np.asarray(ys, np.int32)
    # deterministic shuffle BEFORE the train/val split: the folder walk is
    # class-ordered, so an unshuffled tail split would make the val set a
    # single class that training never saw
    perm = np.random.default_rng(0).permutation(len(x))
    return x[perm], y[perm], classes


def topk_correct(logits, labels, ks=(1, 5)):
    import jax.numpy as jnp

    order = jnp.argsort(logits, axis=-1)[:, ::-1]
    out = []
    for k in ks:
        kk = min(k, logits.shape[-1])
        out.append(jnp.any(order[:, :kk] == labels[:, None], axis=-1).sum())
    return out


def main(argv=None) -> float:
    ap = argparse.ArgumentParser(description="ResNet ImageNet-style training")
    ap.add_argument("--data", default=None, help="ImageFolder-style dir of "
                    "per-class .npy arrays; synthetic when omitted")
    ap.add_argument("-a", "--arch", default="resnet18",
                    choices=["resnet18", "resnet34"])
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("-b", "--batch-size", type=int, default=64,
                    help="GLOBAL batch (sharded over the dp mesh)")
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--momentum", type=float, default=0.9)
    ap.add_argument("--wd", type=float, default=1e-4)
    ap.add_argument("--lr-step-epochs", type=int, default=30,
                    help="StepLR: decay x0.1 every N epochs (reference)")
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--num-classes", type=int, default=1000)
    ap.add_argument("--train-samples", type=int, default=2048)
    ap.add_argument("--val-samples", type=int, default=512)
    ap.add_argument("--width", type=int, default=64)
    ap.add_argument("--bn-momentum", type=float, default=0.1,
                    help="running-stat EMA rate; raise for short runs so "
                         "eval-mode BN converges quickly")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--print-freq", type=int, default=10)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from scaletorch_tpu.models.resnet import ResNetConfig, forward, init_params

    if args.data:
        x_all, y_all, classes = load_folder(args.data, args.image_size)
        args.num_classes = len(classes)
        split = int(0.9 * len(x_all))
        tx_, ty_ = x_all[:split], y_all[:split]
        vx_, vy_ = x_all[split:], y_all[split:]
    else:
        tx_, ty_ = synthetic_images(
            args.train_samples, args.num_classes, args.image_size)
        vx_, vy_ = synthetic_images(
            args.val_samples, args.num_classes, args.image_size, seed=1)

    cfg = ResNetConfig(
        depth=int(args.arch.replace("resnet", "")),
        num_classes=args.num_classes, width=args.width,
        image_size=args.image_size, bn_momentum=args.bn_momentum,
    )
    params, bn_state = init_params(jax.random.key(0), cfg)
    n_params = sum(p.size for p in jax.tree.leaves(params))

    devs = jax.devices()
    mesh = Mesh(np.asarray(devs), ("dp",))
    n_dev = len(devs)
    if args.batch_size % n_dev:
        raise SystemExit(f"--batch-size {args.batch_size} must divide over "
                         f"{n_dev} devices")
    if len(tx_) < args.batch_size:
        raise SystemExit(f"train set ({len(tx_)}) smaller than the global "
                         f"batch ({args.batch_size}); lower --batch-size")
    print(f"=> {args.arch}: {n_params / 1e6:.2f}M params, "
          f"{n_dev}-way data parallel, global batch {args.batch_size}")

    steps_per_epoch = max(len(tx_) // args.batch_size, 1)
    # StepLR x0.1 every lr_step_epochs (reference dist_train.py StepLR)
    schedule = optax.exponential_decay(
        args.lr, transition_steps=args.lr_step_epochs * steps_per_epoch,
        decay_rate=0.1, staircase=True,
    )
    tx = optax.chain(
        optax.add_decayed_weights(args.wd),
        optax.sgd(schedule, momentum=args.momentum),
    )
    opt_state = tx.init(params)

    batch_sh = NamedSharding(mesh, P("dp"))

    @jax.jit
    def train_step(params, bn_state, opt_state, images, labels):
        def loss_fn(p, s):
            logits, new_s = forward(p, s, images, cfg, train=True)
            ce = optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), labels).mean()
            return ce, (new_s, logits)

        (loss, (bn_state2, logits)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, bn_state)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        t1, t5 = topk_correct(logits, labels)
        return params, bn_state2, opt_state, loss, t1, t5

    @jax.jit
    def eval_step(params, bn_state, images, labels):
        logits, _ = forward(params, bn_state, images, cfg, train=False)
        ce = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), labels).mean()
        t1, t5 = topk_correct(logits, labels)
        return ce, t1, t5

    def put(x):
        return jax.device_put(x, batch_sh)

    best_acc1, last_loss = 0.0, float("nan")
    rng = np.random.default_rng(0)
    for epoch in range(args.epochs):
        order = rng.permutation(len(tx_))
        t0, seen, c1 = time.time(), 0, 0
        for it in range(steps_per_epoch):
            idx = order[it * args.batch_size:(it + 1) * args.batch_size]
            params, bn_state, opt_state, loss, t1, t5 = train_step(
                params, bn_state, opt_state,
                put(jnp.asarray(tx_[idx])), put(jnp.asarray(ty_[idx])))
            last_loss = float(loss)
            seen += len(idx)
            c1 += int(t1)
            if (it + 1) % args.print_freq == 0 or it == steps_per_epoch - 1:
                ips = seen / (time.time() - t0)
                print(f"Epoch [{epoch}][{it + 1}/{steps_per_epoch}] "
                      f"loss {last_loss:.4f} acc@1 {100 * c1 / seen:.2f}% "
                      f"({ips:.0f} img/s)")

        # validation (reference validate(): top-1/top-5 over the val set).
        # Batches must divide over the mesh; trim to a device multiple and
        # report how many samples were actually scored.
        vtot, v1, v5, vloss = 0, 0, 0, 0.0
        vbs = args.batch_size
        usable = (len(vx_) // n_dev) * n_dev
        it0 = 0
        while it0 < usable:
            n = min(vbs, usable - it0)
            n = (n // n_dev) * n_dev
            sl = slice(it0, it0 + n)
            it0 += n
            ce, t1, t5 = eval_step(params, bn_state,
                                   put(jnp.asarray(vx_[sl])),
                                   put(jnp.asarray(vy_[sl])))
            vtot += n; v1 += int(t1); v5 += int(t5)
            vloss += float(ce) * n
        acc1 = 100 * v1 / max(vtot, 1)
        dropped = len(vx_) - usable
        print(f" * Val acc@1 {acc1:.2f}% acc@5 {100 * v5 / max(vtot, 1):.2f}% "
              f"loss {vloss / max(vtot, 1):.4f} ({vtot} samples"
              + (f", {dropped} dropped to fit the mesh)" if dropped else ")"))

        if args.checkpoint_dir and acc1 >= best_acc1:
            import pickle

            os.makedirs(args.checkpoint_dir, exist_ok=True)
            host = jax.tree.map(np.asarray, {"params": params,
                                             "bn_state": bn_state,
                                             "epoch": epoch, "acc1": acc1})
            with open(os.path.join(args.checkpoint_dir, "model_best.pkl"),
                      "wb") as f:
                pickle.dump(host, f)
            print(f"=> saved best (acc@1 {acc1:.2f}%)")
        best_acc1 = max(best_acc1, acc1)
    return best_acc1


if __name__ == "__main__":
    main()
