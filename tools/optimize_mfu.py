#!/usr/bin/env python
"""Search training knobs for the best MFU on the current chip.

Counterpart of reference tools/optimize_mfu.py (tries gc/compile/batch
variants and reports the winner). The TPU knobs that matter here:
remat policy (what GC saves), gradient checkpointing on/off, and
micro-batch size. Each variant runs in-process with warmup; OOM variants
are recorded and skipped.

Usage:
    python tools/optimize_mfu.py --model qwen3-0.6b --seq 8192
    python tools/optimize_mfu.py --policies nothing_saveable dots_saveable
"""

from __future__ import annotations

import argparse
import gc as _gc
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

_OOM = ("RESOURCE_EXHAUSTED", "Out of memory", "out of memory")


# jax.Device.device_kind -> the generation names tools/aot_memory.py takes
_GEN_BY_KIND = {
    "TPU v4": "v4",
    "TPU v5 lite": "v5e", "TPU v5e": "v5e",
    "TPU v5": "v5p", "TPU v5p": "v5p",
    "TPU v6 lite": "v6e", "TPU v6e": "v6e",
}


def _detect_gen(explicit: str | None) -> str:
    """Chip generation for the prefilter's HBM budget — the fit verdict
    must be judged against the chip the sweep will RUN on (dots_saveable
    at seq 16384 overflows a 16GB v5e but fits a 32GB v6e). A device it
    does not know is an error, never a default generation."""
    if explicit:
        return explicit
    import jax

    kind = jax.local_devices()[0].device_kind
    if kind not in _GEN_BY_KIND:
        raise SystemExit(
            f"cannot tell the TPU generation of device_kind {kind!r}; "
            "pass --aot-gen")
    return _GEN_BY_KIND[kind]


def _aot_prefilter(args, variants):
    """Compile-time HBM verdict per variant via tools/aot_memory.py. The
    children only compile, so they are pinned to JAX_PLATFORMS=cpu: this
    parent holds the chip, and a chip belongs to one process. One
    subprocess per (micro_bs, gc) group: aot_memory takes every remat
    policy in a single invocation, so the JAX-import/lowering startup is
    paid per group, not per variant. Returns (kept_variants,
    dropped_labels); inconclusive compiles fail OPEN (kept) so an AOT
    infra problem never eats a real measurement."""
    gen = _detect_gen(args.aot_gen)

    def _run_knobs(shape):
        """The non-policy knobs that change the compiled memory picture.
        Pulled from the variant shape (extra{} carries config-level keys)
        so the prefilter compiles EXACTLY what the sweep will run — a
        future sweep knob (accum, optimizer, master-param dtype) must not
        silently diverge the fit verdict (ADVICE r4)."""
        extra = shape.get("extra") or {}
        return (
            shape.get("micro_bs", 1),
            bool(shape.get("gc")),
            shape.get("grad_accum", 1),
            extra.get("optimizer_name", "adamw"),
            extra.get("param_dtype", "float32"),
        )

    groups: dict = {}
    for label, shape in variants:
        groups.setdefault(_run_knobs(shape), []).append((label, shape))

    kept, dropped = [], []
    for (bs, gc, accum, optimizer, param_dtype), members in groups.items():
        cmd = [sys.executable, os.path.join(REPO, "tools", "aot_memory.py"),
               "--model", args.model, "--seq", str(args.seq),
               "--bs", str(bs), "--gen", gen,
               "--accum", str(accum), "--optimizer", optimizer,
               "--param-dtype", param_dtype]
        if gc:
            policies = []
            for _, shape in members:
                p = shape.get("remat_policy", "nothing_saveable")
                if p not in policies:
                    policies.append(p)
            cmd += ["--gc", "--policies", *policies]
        fit_by_policy: dict = {}
        try:
            proc = subprocess.run(
                cmd, capture_output=True, text=True, timeout=2400,
                cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"))
            for line in proc.stdout.splitlines():
                line = line.strip()
                if not line.startswith("{"):
                    continue
                row = json.loads(line)
                fit_by_policy[row.get("remat_policy")] = row.get(
                    "fits_hbm", True)
        except Exception as e:  # noqa: BLE001 — prefilter is best-effort
            print(f"aot-prefilter inconclusive for bs={bs} gc={gc} "
                  f"({repr(e)[:80]}); keeping its variants", flush=True)
        for label, shape in members:
            pol = shape.get("remat_policy", "nothing_saveable")
            if fit_by_policy.get(pol, True):
                kept.append((label, shape))
            else:
                dropped.append(label)
    # preserve the caller's sweep order
    order = {label: i for i, (label, _) in enumerate(variants)}
    kept.sort(key=lambda kv: order[kv[0]])
    return kept, dropped


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="qwen3-0.6b")
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--policies", nargs="*", default=[
        "nothing_saveable", "dots_saveable", "save_attn",
    ])
    ap.add_argument("--batch_sizes", nargs="*", type=int, default=[1, 2])
    ap.add_argument("--try_no_gc", action="store_true",
                    help="also try gradient_checkpointing off")
    ap.add_argument("--out", default=None, help="write results JSON here")
    ap.add_argument("--aot-prefilter", action="store_true",
                    help="AOT-compile each variant first (local libtpu, no "
                         "chip) and drop the ones XLA says cannot fit HBM — "
                         "no chip time is burned on known-OOM rows (e.g. "
                         "dots_saveable at seq 16384, AOT_SEQ16K.json)")
    ap.add_argument("--aot-gen", default=None,
                    choices=["v5e", "v6e", "v5p", "v4"],
                    help="chip generation for the prefilter's HBM budget; "
                         "default: detect from the attached device")
    ap.add_argument("--flash-blocks", nargs="*", default=None,
                    metavar="BQxBKV",
                    help="also run the best gc/batch point with all three "
                         "flash kernels at one uniform pair, e.g. 512x512 "
                         "1024x1024 (sets SCALETORCH_TPU_FLASH_BLOCK_Q/KV "
                         "per run: they override flash.flash_blocks, the "
                         "rule that picks a pair a kernel from the "
                         "shapes when they are unset)")
    args = ap.parse_args()

    # Validate BEFORE the expensive sweeps: a typo'd spec must not crash
    # the run after minutes of completed benchmarks.
    flash_blocks = []
    for spec in args.flash_blocks or []:
        try:
            bq, bkv = (int(x) for x in spec.lower().split("x"))
        except ValueError:
            raise SystemExit(
                f"--flash-blocks entry {spec!r} is not BQxBKV (e.g. 512x512)"
            )
        flash_blocks.append((bq, bkv))

    from scaletorch_tpu.benchmark import benchmark_config, make_bench_args
    from scaletorch_tpu.utils.device import require_tpu

    require_tpu("tools/optimize_mfu.py")
    variants = []
    if args.try_no_gc:
        for bs in args.batch_sizes:
            variants.append((f"no-gc_bs{bs}", dict(gc=False, micro_bs=bs)))
    for policy in args.policies:
        for bs in args.batch_sizes:
            variants.append((
                f"gc-{policy}_bs{bs}",
                dict(gc=True, remat_policy=policy, micro_bs=bs),
            ))

    results = []
    if args.aot_prefilter:
        variants, dropped = _aot_prefilter(args, variants)
        for label in dropped:
            results.append({"label": label, "error": "AOT_NO_FIT"})
            print(f"{label:<28} AOT_NO_FIT (skipped — compile-time OOM)",
                  flush=True)

    for label, shape in variants:
        cfg = make_bench_args(args.model, seq=args.seq, **shape)
        try:
            r = benchmark_config(cfg, warmup=args.warmup, steps=args.steps)
            results.append({"label": label, **r})
            print(f"{label:<28} MFU {r['mfu']:6.2f}%  "
                  f"tok/s {r['tokens_per_second']:>10,.0f}", flush=True)
        except Exception as e:  # noqa: BLE001 — record and continue
            status = "OOM" if any(m in repr(e) for m in _OOM) else "FAILED"
            results.append({"label": label, "error": status})
            print(f"{label:<28} {status}", flush=True)
            _gc.collect()

    ok = [r for r in results if "mfu" in r]
    if ok and flash_blocks:
        # Tile-size sweep on the winning shape: the kernels read the env
        # registry at trace time (set, it overrides their rule), so each
        # variant re-jits with its tiles.
        best_label = max(ok, key=lambda r: r["mfu"])["label"]
        best_shape = next(v for label, v in variants if label == best_label)
        for bq, bkv in flash_blocks:
            os.environ["SCALETORCH_TPU_FLASH_BLOCK_Q"] = str(bq)
            os.environ["SCALETORCH_TPU_FLASH_BLOCK_KV"] = str(bkv)
            label = f"flash_{bq}x{bkv}"
            try:
                cfg = make_bench_args(args.model, seq=args.seq, **best_shape)
                r = benchmark_config(cfg, warmup=args.warmup, steps=args.steps)
                results.append({"label": label, **r})
                print(f"{label:<28} MFU {r['mfu']:6.2f}%  "
                      f"tok/s {r['tokens_per_second']:>10,.0f}", flush=True)
            except Exception as e:  # noqa: BLE001
                status = "OOM" if any(m in repr(e) for m in _OOM) else "FAILED"
                results.append({"label": label, "error": status})
                print(f"{label:<28} {status}", flush=True)
                _gc.collect()
        for v in ("SCALETORCH_TPU_FLASH_BLOCK_Q", "SCALETORCH_TPU_FLASH_BLOCK_KV"):
            os.environ.pop(v, None)
        ok = [r for r in results if "mfu" in r]
    if ok:
        best = max(ok, key=lambda r: r["mfu"])
        print(f"\nbest: {best['label']} at {best['mfu']}% MFU "
              f"({best['tokens_per_second']:,.0f} tok/s)")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print(f"results written to {args.out}")


if __name__ == "__main__":
    main()
