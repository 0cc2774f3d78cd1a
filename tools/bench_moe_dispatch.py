#!/usr/bin/env python
"""Measure the MoE dispatch implementations against each other.

One command produces the einsum (GShard one-hot) vs index (scatter/
gather) step-time comparison for a MoE config on whatever device is
present. The AOT cost analysis already shows the one-hot einsums are
62% of step FLOPs at E=128/top-8 (AOT_30B_A3B.json, 2.65x compiled-FLOP
reduction); this is the matching WALL-CLOCK measurement for a real chip.
A timing tool: it refuses to run without a TPU.

    python tools/bench_moe_dispatch.py --model moe-mid --seq 4096

Output: one JSON object with per-mode step_time/tokens-per-second and
the index:einsum speedup.
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
    ap.add_argument("--model", default="moe-mid",
                    help="MoE preset (moe-mid = v5e-sized 30B-A3B shape "
                         "family)")
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--bs", type=int, default=1)
    ap.add_argument("--ep", type=int, default=1)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--gc", action="store_true")
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    import jax

    from scaletorch_tpu.benchmark import benchmark_config, make_bench_args
    from scaletorch_tpu.utils.device import require_tpu

    require_tpu("tools/bench_moe_dispatch.py")

    results = {}
    for mode in ("einsum", "index"):
        cfg = make_bench_args(
            args.model, seq=args.seq, micro_bs=args.bs, ep=args.ep,
            dp=args.dp, gc=args.gc,
            extra={"moe_dispatch": mode},
        )
        try:
            r = benchmark_config(cfg, warmup=args.warmup, steps=args.steps)
            results[mode] = {k: r[k] for k in
                             ("step_time_s", "tokens_per_second", "loss")}
        except Exception as e:  # noqa: BLE001 — e.g. OOM at large shapes
            results[mode] = {"error": repr(e)[:200]}
        print(f"{mode}: {results[mode]}", flush=True)

    out = {
        "geometry": {"model": args.model, "seq": args.seq, "bs": args.bs,
                     "ep": args.ep, "dp": args.dp, "gc": args.gc,
                     "device": jax.devices()[0].device_kind},
        **results,
    }
    base = results.get("einsum", {}).get("step_time_s")
    st = results.get("index", {}).get("step_time_s")
    if base and st:
        out["index_speedup_vs_einsum"] = round(base / st, 3)
    print(json.dumps(out, indent=1))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    if all("error" in results[m] for m in ("einsum", "index")):
        sys.exit(1)  # a fully-failed run must not look like a measurement


if __name__ == "__main__":
    main()
