#!/usr/bin/env python
"""Device-mesh parallelism walkthrough — the TPU/JAX rendition of the
reference's examples/device_mesh tier (device_mesh_api.py, dtensor_demo,
tensor_parallel_demo, sequence_parallel_demo, fsdp_dp_demo, fsdp_tp_demo,
manual_process_group).

Where torch builds each strategy from process groups + DTensor placements
+ module wrappers, JAX has exactly two primitives and everything below is
a composition of them:

  * ``NamedSharding(mesh, PartitionSpec(...))`` — declarative placement;
    the XLA SPMD partitioner inserts the collectives (DTensor's role).
  * ``jax.shard_map`` — per-device programs with explicit collectives
    (the manual process-group role).

Run (8 virtual devices):
    JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/device_mesh/mesh_demos.py
"""

from __future__ import annotations

import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)


def demo_mesh_api():
    """2-D mesh construction (reference device_mesh_api.py:1-30 and
    manual_process_group.py roles — axis names replace group handles)."""
    import jax
    from jax.sharding import Mesh

    devs = np.asarray(jax.devices()[:8]).reshape(2, 4)
    mesh = Mesh(devs, ("replicate", "shard"))
    print(f"[mesh-api] mesh axes {dict(mesh.shape)} "
          f"(2 replicate x 4 shard, no process groups needed)")
    return mesh


def demo_dtensor_placements(mesh):
    """Shard / Replicate / partial placements (reference dtensor_demo):
    in JAX each is a PartitionSpec, conversions are device_put."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    x = jnp.arange(32.0).reshape(8, 4)
    sharded = jax.device_put(x, NamedSharding(mesh, P("shard", None)))
    replicated = jax.device_put(x, NamedSharding(mesh, P()))
    print(f"[dtensor] Shard(0): {sharded.sharding.spec}, per-device "
          f"{sharded.addressable_shards[0].data.shape}; Replicate(): "
          f"{replicated.sharding.spec}, per-device "
          f"{replicated.addressable_shards[0].data.shape}")
    # 'partial' (pending-reduction) values live inside shard_map as
    # un-psummed accumulators — see demo_tensor_parallel's local matmuls.
    resharded = jax.device_put(replicated, NamedSharding(mesh, P(None, "shard")))
    print(f"[dtensor] redistribute -> {resharded.sharding.spec}, per-device "
          f"{resharded.addressable_shards[0].data.shape}")


def demo_tensor_parallel():
    """Megatron TP MLP: column-shard W1, row-shard W2, ONE all-reduce
    (reference tensor_parallel_demo.py) — via the framework's own ops."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from scaletorch_tpu.parallel.tensor_parallel import (
        column_parallel_linear,
        pvary_missing,
        row_parallel_linear,
    )

    mesh = Mesh(np.asarray(jax.devices()[:4]), ("tp",))
    h, f = 32, 64
    x = jnp.ones((2, 8, h))
    w1 = 0.02 * jnp.arange(h * f, dtype=jnp.float32).reshape(h, f) / (h * f)
    w2 = w1.T / 10.0

    def tp_mlp(x, w1, w2):
        x = pvary_missing(x, ("tp",))
        hidden = column_parallel_linear(x, w1, axis="tp")     # no comm
        return row_parallel_linear(hidden, w2, axis="tp")     # one psum

    out = jax.shard_map(
        tp_mlp, mesh=mesh,
        in_specs=(P(), P(None, "tp"), P("tp", None)), out_specs=P(),
    )(x, w1, w2)
    ref = (x @ w1) @ w2
    ok = bool(jnp.allclose(out, ref, atol=1e-5))
    assert ok, "tensor-parallel MLP diverged from single-device reference"
    print(f"[tp] col+row parallel MLP matches single-device: "
          f"{ok} (one all-reduce total)")


def demo_sequence_parallel():
    """SP: ranks hold different sequence shards; all-gather in, reduce-
    scatter out (reference sequence_parallel_demo.py)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from scaletorch_tpu.parallel.sequence_parallel import (
        all_gather_sequence,
        reduce_scatter_sequence,
    )

    mesh = Mesh(np.asarray(jax.devices()[:4]), ("tp",))
    x = jnp.arange(4 * 16 * 8, dtype=jnp.float32).reshape(1, 64, 8)

    def sp_block(x_shard, w):
        full = all_gather_sequence(x_shard, axis="tp")        # [1, 64, 8]
        # In real SP this matmul is row-parallel, so each rank holds a
        # PARTIAL result; the reduce-scatter both sums the partials and
        # re-shards the sequence. Emulate the partial with w/4.
        y = full @ (w / 4.0)
        return reduce_scatter_sequence(y, axis="tp")          # [1, 16, 8]

    w = jnp.eye(8) * 2.0
    w_v = jax.shard_map(
        lambda x, w: sp_block(x, jax.lax.pvary(w, ("tp",))),
        mesh=mesh, in_specs=(P(None, "tp", None), P()),
        out_specs=P(None, "tp", None),
    )(x, w)
    ok = bool(jnp.allclose(w_v, x * 2.0, atol=1e-5))
    assert ok, "sequence-parallel round-trip diverged"
    print(f"[sp] gather->compute->reduce-scatter round-trips the sequence: "
          f"{ok} (per-rank seq {x.shape[1] // 4})")


def demo_fsdp_dp():
    """HSDP: FSDP sharding inside fast-link groups, DP replication across
    them (reference fsdp_dp_demo.py) — one PartitionSpec, zero wrappers."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = np.asarray(jax.devices()[:8]).reshape(2, 4)
    mesh = Mesh(devs, ("dp_replicate", "dp_shard"))
    w = jnp.zeros((1024, 64))
    placed = jax.device_put(w, NamedSharding(mesh, P("dp_shard", None)))
    shard = placed.addressable_shards[0].data.shape
    print(f"[hsdp] param {w.shape} -> per-device {shard}: sharded 4-way "
          f"inside each replica group, replicated across the 2 groups")


def demo_fsdp_tp():
    """FSDP x TP 2-D parallelism (reference fsdp_tp_demo.py): shard
    storage over 'fsdp', shard computation over 'tp'."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = np.asarray(jax.devices()[:8]).reshape(2, 4)
    mesh = Mesh(devs, ("fsdp", "tp"))
    w_col = jnp.zeros((64, 512))   # column-parallel weight
    placed = jax.device_put(w_col, NamedSharding(mesh, P("fsdp", "tp")))
    print(f"[fsdp+tp] weight {w_col.shape} -> per-device "
          f"{placed.addressable_shards[0].data.shape}: tp splits the "
          f"compute dim, fsdp splits storage of each tp shard; XLA "
          f"all-gathers over 'fsdp' just-in-time")


def main():
    import jax

    if len(jax.devices()) < 8:
        raise SystemExit(
            f"these demos need >= 8 devices, have {len(jax.devices())}. "
            "Run with: JAX_PLATFORMS=cpu "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8"
        )
    mesh = demo_mesh_api()
    demo_dtensor_placements(mesh)
    demo_tensor_parallel()
    demo_sequence_parallel()
    demo_fsdp_dp()
    demo_fsdp_tp()
    print("all device-mesh demos passed")


if __name__ == "__main__":
    main()
