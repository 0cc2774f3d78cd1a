#!/usr/bin/env python
"""Profile actual MFU with an analytic FLOPs breakdown + optional XLA trace.

Counterpart of reference tools/profile_mfu.py: print the per-component
FLOPs/token budget (linear / attention / embed+head), measure the real
train step with and without gradient checkpointing, and report achieved
TFLOP/s + MFU against the chip's peak. ``--trace DIR`` additionally
captures a ``jax.profiler`` trace of the steady-state steps for
tensorboard/xprof (the per-op timeline the reference gets from
torch_npu profiling).

Usage:
    python tools/profile_mfu.py --model qwen3-0.6b --seq 8192
    python tools/profile_mfu.py --model qwen3-0.6b --trace /tmp/xprof
"""

from __future__ import annotations

import argparse
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def flops_breakdown(p, seq: int) -> dict:
    """FLOPs/token by component (reference profile_mfu.py:60-82)."""
    h, l_ = p["hidden_size"], p["num_hidden_layers"]
    heads = p["num_attention_heads"]
    kv = p.get("num_key_value_heads", heads)
    hd = p.get("head_dim") or h // heads
    inter = p["intermediate_size"]
    v = p["vocab_size"]
    linear = 2 * l_ * (
        h * heads * hd + 2 * h * kv * hd + heads * hd * h + 3 * h * inter
    )
    attn = 2 * 2 * heads * hd * seq * l_
    embed = 2 * 2 * v * h
    fwd = linear + attn + embed
    return {
        "linear": linear, "attention": attn, "embed_head": embed,
        "forward": fwd, "train_3x": 3 * fwd,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="qwen3-0.6b")
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--bs", type=int, default=1)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--trace", default=None,
                    help="write a jax.profiler trace of the timed steps here")
    ap.add_argument("--skip_no_gc", action="store_true",
                    help="only measure the GC variant (small-HBM chips)")
    args = ap.parse_args()

    from scaletorch_tpu.benchmark import benchmark_config, make_bench_args
    from scaletorch_tpu.models.presets import preset
    from scaletorch_tpu.utils.device import (
        get_device_kind,
        get_theoretical_flops,
        require_tpu,
    )

    require_tpu("tools/profile_mfu.py")
    p = preset(args.model)
    br = flops_breakdown(p, args.seq)
    print(f"model={args.model} seq={args.seq} bs={args.bs}")
    print("FLOPs/token breakdown:")
    for k in ("linear", "attention", "embed_head", "forward", "train_3x"):
        print(f"  {k:<10} {br[k] / 1e9:8.2f} GFLOPs")
    peak = get_theoretical_flops()
    print(f"device: {get_device_kind()}  peak bf16 {peak / 1e12:.0f} TFLOP/s")

    variants = [("gc", True)] if args.skip_no_gc else [
        ("no-gc", False), ("gc", True),
    ]
    for label, gc in variants:
        cfg = make_bench_args(args.model, seq=args.seq, micro_bs=args.bs, gc=gc)
        try:
            if args.trace and gc:
                import jax

                os.makedirs(args.trace, exist_ok=True)
                with jax.profiler.trace(args.trace):
                    r = benchmark_config(cfg, warmup=args.warmup,
                                         steps=args.steps)
                print(f"trace written to {args.trace}")
            else:
                r = benchmark_config(cfg, warmup=args.warmup, steps=args.steps)
        except Exception as e:  # noqa: BLE001 — report, continue variants
            print(f"[{label}] FAILED: {repr(e)[:200]}")
            continue
        achieved = r["tokens_per_second"] * br["train_3x"] / 1e12
        print(f"[{label}] step {r['step_time_s'] * 1e3:.1f}ms | "
              f"tok/s {r['tokens_per_second']:,.0f} | "
              f"achieved {achieved:.1f} TFLOP/s | MFU {r['mfu']:.1f}%"
              + (f" | mem {r['memory_gb']}GB" if r["memory_gb"] else ""))


if __name__ == "__main__":
    main()
