#!/usr/bin/env python
"""Measure the context-parallel strategies against each other.

One command produces the ring-contiguous vs ring-zigzag vs Ulysses
step-time comparison at a given geometry (the measurement VERDICT r2 #4
asks for — it needs cp > 1, i.e. cp*dp TPU chips). A timing tool: it
refuses to run without a TPU (serial CPU device emulation hides the
very load imbalance zigzag fixes).

    python tools/bench_cp_compare.py --cp 2 --dp 2 --seq 8192   # 4 chips

Output: one JSON object with per-strategy step_time/tokens-per-second
and the zigzag:contiguous / ulysses:contiguous speedups.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="qwen3-0.6b")
    ap.add_argument("--cp", type=int, default=4)
    ap.add_argument("--dp", type=int, default=2)
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--gc", action="store_true")
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    import jax

    from scaletorch_tpu.benchmark import benchmark_config, make_bench_args
    from scaletorch_tpu.utils.device import require_tpu

    require_tpu("tools/bench_cp_compare.py")

    strategies = {
        "ring_contiguous": {"attention_backend": "ring",
                            "cp_layout": "contiguous"},
        "ring_zigzag": {"attention_backend": "ring", "cp_layout": "zigzag"},
        "ulysses": {"attention_backend": "ulysses"},
    }
    results = {}
    for name, extra in strategies.items():
        cfg = make_bench_args(
            args.model, seq=args.seq, cp=args.cp, dp=args.dp, gc=args.gc,
            extra=extra,
        )
        try:
            r = benchmark_config(cfg, warmup=args.warmup, steps=args.steps)
            results[name] = {k: r[k] for k in
                             ("step_time_s", "tokens_per_second", "loss")}
        except Exception as e:  # noqa: BLE001 — e.g. ulysses kv-head cap
            results[name] = {"error": repr(e)[:200]}
        print(f"{name}: {results[name]}", flush=True)

    base = results.get("ring_contiguous", {}).get("step_time_s")
    out = {
        "geometry": {"model": args.model, "cp": args.cp, "dp": args.dp,
                     "seq": args.seq, "gc": args.gc,
                     "device": jax.devices()[0].device_kind},
        **results,
    }
    if base:
        for name in ("ring_zigzag", "ulysses"):
            st = results.get(name, {}).get("step_time_s")
            if st:
                out[f"{name}_speedup_vs_contiguous"] = round(base / st, 3)
    print(json.dumps(out, indent=1))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    if all("error" in results[s] for s in strategies):
        sys.exit(1)  # a fully-failed run must not look like a measurement


if __name__ == "__main__":
    main()
