#!/usr/bin/env python
"""AOT HBM analyzer: compile a train-step for a target TPU gen with NO
device attached and report the compiler's exact memory accounting.

TPU-native counterpart of the reference's trial-and-error OOM probing
(scripts/benchmark_comprehensive.py catches torch.cuda OOM at runtime;
tools/optimize_mfu.py re-runs variants until one fits): XLA knows the
peak HBM of a compiled program before it ever touches a chip, so memory
feasibility is a compile-time query. Uses the local ``libtpu`` AOT
plugin via ``jax.experimental.topologies`` — works on a CPU-only box.

Usage:
    python tools/aot_memory.py --model qwen3-0.6b --seq 2048 --bs 2
    python tools/aot_memory.py --model qwen3-0.6b --seq 8192 --gc \\
        --policies nothing_saveable dots_saveable save_attn
    python tools/aot_memory.py --model qwen3-1.7b --seq 2048 --sweep-gc

Prints one JSON line per variant: argument/temp/output/alias bytes,
estimated peak HBM, and fits_hbm for the generation's per-chip HBM.
The accounting itself (argument/temp/alias/peak math) is shared with
the jaxlint memory tier (``scaletorch_tpu/analysis/memory.py``), which
gates the same numbers for the audit manifest in CI against
``tools/hbm_budget.json``; this tool keeps the libtpu AOT topology
path so the numbers come out for a real TPU generation.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# Per-chip HBM by generation (utils/device.py carries FLOPS; memory here).
HBM_GB = {"v5e": 16, "v6e": 32, "v5p": 95, "v4": 32}


def _aot_session_env() -> None:
    """Before jax is imported: libtpu must come up with no TPU and no
    metadata server, and — the compile target being a topology, not a
    local device — the platform test cannot see the TPU, so the Pallas
    kernels are asked for explicitly (the one use of FORCE_PALLAS)."""
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
    os.environ.setdefault("SCALETORCH_TPU_FORCE_PALLAS", "1")


def build_lowered(model: str, *, seq: int, micro_bs: int, grad_accum: int,
                  gc: bool, remat_policy: str, gen: str,
                  param_dtype: str = "float32", optimizer: str = "adamw",
                  dp: int = 1, tp: int = 1, cp: int = 1, pp: int = 1,
                  ep: int = 1, sp: bool = False, pp_engine: str = "afab",
                  pp_vpp: int = 1, moe_dispatch: str = "auto"):
    """Lower the real SPMD train step against an AOT TPU topology —
    single chip by default, or a multi-chip mesh factoring (dp/tp/cp/pp/
    ep over the 4-chip v5e host topology): Mosaic kernel compilation for
    sharded shapes and collective lowering onto ICI are validated without
    any hardware attached."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies

    from scaletorch_tpu.benchmark import make_bench_args
    from scaletorch_tpu.models import llama, qwen3_moe
    from scaletorch_tpu.models.registry import resolve_attention_backend
    from scaletorch_tpu.parallel.mesh import MeshManager
    from scaletorch_tpu.parallel.spmd import make_spmd_train_step
    from scaletorch_tpu.trainer.optimizer import create_optimizer
    from scaletorch_tpu.trainer.trainer import build_model_config

    world = dp * tp * cp * pp * ep
    # smallest AOT topology that holds the mesh (v5e slices are 2D grids;
    # 4 chips = one host, 8/16 = multi-host slices — ICI collective
    # lowering is validated either way)
    for shape, n in (("2x2x1", 4), ("2x4x1", 8), ("4x4x1", 16),
                     ("4x8x1", 32)):
        if world <= n:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name=f"{gen}:{shape}")
            break
    else:
        raise ValueError(f"mesh {world} devices > largest AOT topology (32)")
    cfg = make_bench_args(model, seq=seq, micro_bs=micro_bs,
                          grad_accum=grad_accum, gc=gc,
                          remat_policy=remat_policy,
                          dp=dp, tp=tp, cp=cp, pp=pp, ep=ep, sp=sp,
                          pp_engine=pp_engine,
                          extra={"param_dtype": param_dtype,
                                 "optimizer_name": optimizer,
                                 "moe_dispatch": moe_dispatch,
                                 "pp_virtual_stages": pp_vpp})
    model_cfg = build_model_config(cfg)
    mm = MeshManager(devices=list(topo.devices[:world]),
                     dp=dp, pp=pp, cp=cp, ep=ep, tp=tp)

    is_moe = cfg.model_type == "qwen3_moe"
    mod = qwen3_moe if is_moe else llama
    params = jax.eval_shape(lambda: mod.init_params(jax.random.key(0), model_cfg))
    if pp > 1 and model_cfg.num_hidden_layers % pp:
        # Mirror the Trainer's uneven-PP padding so the HBM estimate
        # covers the padded slots the real run carries.
        from scaletorch_tpu.parallel.pipeline_parallel import pad_stacked_params

        params = dict(params, layers=jax.eval_shape(
            lambda t: pad_stacked_params(
                t, model_cfg.num_hidden_layers, pp),
            params["layers"],
        ))
    moe_specs = (qwen3_moe.qwen3_moe_param_specs(
        model_cfg, tp_axis="tp",
        ep_axis="ep" if ep > 1 else None,
        pp_axis="pp" if pp > 1 else None) if is_moe else None)
    if cfg.optimizer_name.lower() == "adafactor":
        from scaletorch_tpu.parallel.tensor_parallel import llama_param_specs

        tx, _ = create_optimizer(
            cfg, include_clip=False,
            param_specs=(moe_specs if is_moe else llama_param_specs(
                model_cfg, tp_axis="tp",
                pp_axis="pp" if pp > 1 else None)),
            axis_sizes=dict(mm.mesh.shape),
        )
    else:
        tx, _ = create_optimizer(cfg, include_clip=False)

    step_fn, p_specs, o_specs = make_spmd_train_step(
        mm, mod.forward, model_cfg, tx, params,
        attention_backend=resolve_attention_backend(
            cfg.attention_backend, context_parallel=cp > 1),
        gradient_checkpointing=gc,
        remat_policy=remat_policy,
        sequence_parallel=sp,
        max_grad_norm=cfg.max_grad_norm,
        param_specs=moe_specs,
        model_kwargs={"ep_axis": "ep" if ep > 1 else None} if is_moe else None,
        model_family="qwen3_moe" if is_moe else "llama",
        pp_schedule=cfg.pp_engine,
        pp_vpp=pp_vpp,
        cp_layout=cfg.cp_layout,
    )
    opt_state = jax.eval_shape(tx.init, params)
    rows = micro_bs * dp * ep
    batch = {
        "input_ids": jax.ShapeDtypeStruct(
            (grad_accum, rows, seq), jnp.int32),
        "target_ids": jax.ShapeDtypeStruct(
            (grad_accum, rows, seq), jnp.int32),
        "position_ids": jax.ShapeDtypeStruct((grad_accum, seq), jnp.int32),
    }
    return step_fn.lower(params, opt_state, batch)


def analyze(args_ns, *, gc: bool, remat_policy: str) -> dict:
    from scaletorch_tpu.analysis.memory import accounting_from_compiled

    lowered = build_lowered(
        args_ns.model, seq=args_ns.seq, micro_bs=args_ns.bs,
        grad_accum=args_ns.accum, gc=gc, remat_policy=remat_policy,
        gen=args_ns.gen, param_dtype=args_ns.param_dtype,
        optimizer=args_ns.optimizer,
        dp=args_ns.dp, tp=args_ns.tp, cp=args_ns.cp, pp=args_ns.pp,
        ep=args_ns.ep, sp=args_ns.sp, pp_engine=args_ns.pp_engine,
        pp_vpp=args_ns.pp_vpp, moe_dispatch=args_ns.moe_dispatch)
    # XLA:TPU enforces the HBM budget at compile time (RESOURCE_EXHAUSTED
    # on overflow), so a successful compile IS the fit verdict — the
    # caller's except path records the failure. The size fields below are
    # reported for composition analysis, not re-judged against a budget
    # (donated-argument aliasing makes any client-side sum double-count).
    # The argument/temp/alias/peak math is the SAME accounting the
    # jaxlint memory tier gates on (analysis/memory.py) — one
    # implementation, two consumers.
    compiled = lowered.compile()
    acct = accounting_from_compiled(compiled)
    if acct is None:
        raise RuntimeError(
            "compiled.memory_analysis() reported nothing for the AOT "
            "TPU target — libtpu too old for memory accounting?"
        )
    try:
        cost = compiled.cost_analysis() or {}
        flops = cost.get("flops")
    except Exception:  # noqa: BLE001 — cost analysis is best-effort
        flops = None
    return {
        **({"step_tflops": round(flops / 1e12, 2)} if flops else {}),
        "model": args_ns.model, "seq": args_ns.seq, "bs": args_ns.bs,
        "accum": args_ns.accum, "gc": gc, "remat_policy": remat_policy,
        "gen": args_ns.gen, "param_dtype": args_ns.param_dtype,
        **{ax: getattr(args_ns, ax) for ax in ("dp", "tp", "cp", "pp", "ep")
           if getattr(args_ns, ax) > 1},
        **({"sp": True} if args_ns.sp else {}),
        **({"pp_engine": args_ns.pp_engine} if args_ns.pp > 1 else {}),
        **({"moe_dispatch": args_ns.moe_dispatch}
           if args_ns.moe_dispatch != "auto" else {}),
        "argument_gb": round(acct.argument_bytes / 1e9, 3),
        "temp_gb": round(acct.temp_bytes / 1e9, 3),
        "output_gb": round(acct.output_bytes / 1e9, 3),
        "alias_gb": round(acct.alias_bytes / 1e9, 3),
        "code_mb": round(acct.generated_code_bytes / 1e6, 1),
        "upper_bound_gb": round(acct.peak_bytes / 1e9, 3),
        "fits_hbm": True,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="qwen3-0.6b")
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--bs", type=int, default=1)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--gc", action="store_true")
    ap.add_argument("--gen", default="v5e", choices=sorted(HBM_GB))
    ap.add_argument("--param-dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--optimizer", default="adamw")
    for ax in ("dp", "tp", "cp", "pp", "ep"):
        ap.add_argument(f"--{ax}", type=int, default=1)
    ap.add_argument("--sp", action="store_true", help="sequence parallel")
    ap.add_argument("--pp-engine", default="afab",
                    choices=["afab", "memory_chunked", "1f1b", "interleaved"],
                    help="pipeline schedule to analyze (afab is the "
                         "config/train.py default; memory_chunked (alias 1f1b) is the O(pp)-memory "
                         "chunked schedule; interleaved is the virtual-stage "
                         "circular pipeline — pair with --pp-vpp)")
    ap.add_argument("--pp-vpp", type=int, default=1,
                    help="virtual stages per rank (pp_engine=interleaved); "
                         "the vpp x tick-carry memory shows up in temp_gb")
    ap.add_argument("--moe-dispatch", default="auto",
                    choices=["auto", "einsum", "index"],
                    help="capacity-dispatch token movement (MoE models)")
    ap.add_argument("--policies", nargs="*", default=None,
                    help="remat policies to compare (implies --gc)")
    ap.add_argument("--sweep-gc", action="store_true",
                    help="compare gc off vs on")
    args_ns = ap.parse_args()

    _aot_session_env()

    variants = []
    if args_ns.policies:
        variants = [(True, p) for p in args_ns.policies]
    elif args_ns.sweep_gc:
        variants = [(False, "nothing_saveable"), (True, "nothing_saveable")]
    else:
        variants = [(args_ns.gc, "nothing_saveable")]

    for gc, policy in variants:
        try:
            row = analyze(args_ns, gc=gc, remat_policy=policy)
        except Exception as e:  # noqa: BLE001 — per-variant isolation
            row = {"model": args_ns.model, "gc": gc, "remat_policy": policy,
                   "error": repr(e)[:300]}
            if "RESOURCE_EXHAUSTED" in row["error"]:
                row["fits_hbm"] = False
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
