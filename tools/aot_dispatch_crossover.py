#!/usr/bin/env python
"""AOT cost-analysis sweep of the einsum-vs-index MoE dispatch crossover.

VERDICT r4 weak #3: the ``auto`` dispatch mode's E>16 threshold was a
guess. This tool replaces the guess with compiler truth: for each expert
count it AOT-compiles the REAL train step (local libtpu, v5e target, no
chip needed) in both dispatch forms and records XLA's own cost analysis
(total step FLOPs) plus the compiled temp-HBM. The crossover is the
smallest E where the index form's compiled FLOPs drop below the
einsum form's.

This is compile-time evidence, not wall-clock — scatter/gather can be
memory-bound where einsum is MXU-bound, so the on-chip A/B
(``python bench.py`` phase 3.5 / tools/bench_moe_dispatch.py) remains
the final word. Until a chip is reachable, the compiled-FLOP crossover
is the best available setting for ``resolve_moe_dispatch``.

Usage:
    python tools/aot_dispatch_crossover.py \
        [--experts 4 8 16 32 64] [--top-k 2] [--out AOT_DISPATCH_CROSSOVER.json]

Model shape: a 2-layer slice of the moe-mid geometry (hidden 1024,
expert FFN 384, seq 4096) — per-layer dispatch cost scales linearly in
depth, so 2 layers compile fast while preserving the FLOP *ratio*.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

_CHILD_ENV = "_SCALETORCH_TPU_XOVER_CHILD"


def _compile_point(num_experts: int, top_k: int, mode: str, seq: int) -> dict:
    """Child-side: lower + compile one (E, mode) point, return cost rows."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies

    from scaletorch_tpu.config import ScaleTorchTPUArguments
    from scaletorch_tpu.models import qwen3_moe
    from scaletorch_tpu.parallel.mesh import MeshManager
    from scaletorch_tpu.parallel.spmd import make_spmd_train_step
    from scaletorch_tpu.trainer.optimizer import create_optimizer
    from scaletorch_tpu.trainer.trainer import build_model_config

    cfg = ScaleTorchTPUArguments(
        model_type="qwen3_moe", vocab_size=32768, hidden_size=1024,
        intermediate_size=3072, moe_intermediate_size=384,
        num_hidden_layers=2, num_attention_heads=16, num_key_value_heads=4,
        head_dim=64, rope_theta=1e6, max_position_embeddings=2 * seq,
        num_experts=num_experts, num_experts_per_tok=top_k,
        moe_dispatch=mode, sequence_length=seq, micro_batch_size=1,
        gradient_checkpointing=True, synthetic_data=True,
        dtype="bfloat16", max_grad_norm=1.0,
    )
    model_cfg = build_model_config(cfg)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2x1")
    mm = MeshManager(devices=list(topo.devices[:1]))
    params = jax.eval_shape(
        lambda: qwen3_moe.init_params(jax.random.key(0), model_cfg))
    specs = qwen3_moe.qwen3_moe_param_specs(model_cfg, tp_axis="tp")
    tx, _ = create_optimizer(cfg, include_clip=False)
    step_fn, _, _ = make_spmd_train_step(
        mm, qwen3_moe.forward, model_cfg, tx, params,
        gradient_checkpointing=True, max_grad_norm=1.0,
        param_specs=specs, model_family="qwen3_moe",
    )
    batch = {
        "input_ids": jax.ShapeDtypeStruct((1, 1, seq), jnp.int32),
        "target_ids": jax.ShapeDtypeStruct((1, 1, seq), jnp.int32),
        "position_ids": jax.ShapeDtypeStruct((1, seq), jnp.int32),
    }
    compiled = step_fn.lower(params, jax.eval_shape(tx.init, params),
                             batch).compile()
    m = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    return {
        "num_experts": num_experts, "top_k": top_k, "mode": mode,
        "step_tflops": round((cost.get("flops") or 0) / 1e12, 3),
        "temp_gb": round(m.temp_size_in_bytes / 1e9, 3),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--experts", nargs="*", type=int,
                    default=[4, 8, 16, 32, 64])
    ap.add_argument("--top-k", type=int, default=2)
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--out", default="AOT_DISPATCH_CROSSOVER.json")
    args = ap.parse_args()

    if os.environ.get(_CHILD_ENV):
        e, k, mode, seq = os.environ[_CHILD_ENV].split(":")
        print(json.dumps(_compile_point(int(e), int(k), mode, int(seq))))
        return

    # the aot_memory.py recipe: local libtpu compiles for v5e with no
    # device attached (one child per point bounds compile-state growth)
    env = dict(os.environ, TPU_WORKER_HOSTNAMES="localhost",
               TPU_SKIP_MDS_QUERY="1")
    rows = []
    for e in args.experts:
        for mode in ("einsum", "index"):
            env[_CHILD_ENV] = f"{e}:{args.top_k}:{mode}:{args.seq}"
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__)],
                env=env, capture_output=True, text=True, timeout=2400,
                cwd=REPO,
            )
            line = [ln for ln in proc.stdout.splitlines()
                    if ln.startswith("{")]
            if proc.returncode != 0 or not line:
                rows.append({"num_experts": e, "mode": mode,
                             "error": proc.stderr.strip()[-300:]})
            else:
                rows.append(json.loads(line[-1]))
            print(json.dumps(rows[-1]), flush=True)

    # the crossover: smallest E where index compiles fewer FLOPs
    by_e: dict = {}
    for r in rows:
        if "error" not in r:
            by_e.setdefault(r["num_experts"], {})[r["mode"]] = r
    crossover = None
    for e in sorted(by_e):
        pair = by_e[e]
        if ("einsum" in pair and "index" in pair
                and pair["index"]["step_tflops"] < pair["einsum"]["step_tflops"]):
            crossover = e
            break
    out = {
        "top_k": args.top_k, "seq": args.seq, "rows": rows,
        "compiled_flops_crossover_experts": crossover,
        "note": ("index wins (fewer compiled step FLOPs) from this expert "
                 "count on; wall-clock confirmation: bench.py phase 3.5"),
    }
    print(json.dumps({"crossover": crossover}))
    with open(os.path.join(REPO, args.out), "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
