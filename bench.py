"""One training row of the reference's single-chip table, on the chip.

    BENCH_ROW=<label> python bench.py      # default: the headline row

Runs the row in this process (a chip belongs to one process) through
``scaletorch_tpu.benchmark.benchmark_config`` and prints ONE JSON line
naming the device it ran on. There is no fallback of any kind: without
a TPU it exits non-zero with a one-line reason, and a row that fails —
a kernel that does not compile, an out-of-memory — raises; it is never
re-run on another attention path or with other memory settings and
reported as if it were the row.

The headline row is Qwen3-0.6B, seq 8192, micro-batch 1, gradient
checkpointing, bf16 (reference README.md:31: 9,834 tok/s / 39.0% MFU on
one Ascend 910B). ``vs_baseline`` is our MFU over the reference's MFU at
the identical model/sequence configuration — the hardware-normalised
comparison. The benchmark proper (cells, traffic mixes, the ledger) is
a later PR's; this file is only the row runner.

Knobs: BENCH_WARMUP_STEPS (3), BENCH_STEPS (10), BENCH_REMAT_POLICY.
"""

from __future__ import annotations

import json
import os
import sys

HEADLINE = "qwen3-0.6b_seq8192_bs1_gc"

# The reference's published single-chip table (BASELINE.md §Single-NPU;
# reference README.md:30-36 + scripts/run_npu.sh:20-24 sweep rows).
# label -> (model, run-shape kwargs, baseline MFU %, baseline tok/s)
SINGLE_CHIP_ROWS = {
    "qwen3-0.6b_seq2048_bs2": ("qwen3-0.6b", dict(seq=2048, micro_bs=2), 22.5, 9731),
    HEADLINE: ("qwen3-0.6b", dict(seq=8192, gc=True), 39.0, 9834),
    "qwen3-0.6b_seq16384_bs1_gc": ("qwen3-0.6b", dict(seq=16384, gc=True), 56.0, 9079),
    # Same reference row, the AOT-planned recipe (AOT_SEQ16K.json
    # on_chip_plan): bf16 master + save_attn keeps the flash kernel's
    # (out, lse) so GC backward skips the flash-forward recompute.
    "qwen3-0.6b_seq16384_bf16_save_attn": (
        "qwen3-0.6b",
        dict(seq=16384, gc=True, remat_policy="save_attn",
             extra={"param_dtype": "bfloat16"}),
        56.0, 9079),
    # 1.7B/4B rows store master weights + adam moments in bf16 — exactly
    # what the reference's torch bf16 AdamW stores (tensor.to(bf16) model,
    # exp_avg/exp_avg_sq in param dtype). fp32 master state for 1.7B is
    # 19.2 GB before activations (tools/aot_memory.py) — it only exists on
    # the reference's 64 GB chips, not a 16 GB v5e.
    "qwen3-1.7b_seq2048_bs1": (
        "qwen3-1.7b", dict(seq=2048, extra={"param_dtype": "bfloat16"}),
        24.9, 4685),
    "qwen3-1.7b_seq8192_bs1_gc": (
        "qwen3-1.7b", dict(seq=8192, gc=True, extra={"param_dtype": "bfloat16"}),
        51.5, 7396),
    # 4B AdamW state alone is 22.5 GB even in bf16 — beyond ANY single
    # 16 GB chip. Adafactor (sharding-aware, trainer/factored.py) is the
    # idiomatic TPU answer: same model FLOPs, factored second moments.
    "qwen3-4b_seq2048_bs1_gc": (
        "qwen3-4b", dict(seq=2048, gc=True, extra={
            "param_dtype": "bfloat16", "optimizer_name": "adafactor"}),
        28.4, 2415),
    # 910-sweep rows (scripts/run_npu.sh:20-24)
    "qwen3-0.6b_seq16384_sweep": ("qwen3-0.6b", dict(seq=16384, gc=True), 60.1, 9700),
    "qwen3-0.6b_seq2048_bs4_ga2": (
        "qwen3-0.6b", dict(seq=2048, micro_bs=4, grad_accum=2), 43.9, 19000,
    ),
}


def run_row(label: str, warmup: int, steps: int) -> dict:
    """Measure one row. Raises on any failure, ``NoTpuError`` first."""
    from scaletorch_tpu.benchmark import benchmark_config, make_bench_args

    model, shape, base_mfu, base_tok_s = SINGLE_CHIP_ROWS[label]
    shape = dict(shape)
    shape.setdefault("remat_policy", os.environ.get(
        "BENCH_REMAT_POLICY", "nothing_saveable"))
    cfg = make_bench_args(model, **shape)
    r = benchmark_config(cfg, warmup=warmup, steps=steps)
    if r["mfu"] > 100.0:
        # more work than the chip can do means the timed region ended
        # before the work did
        raise RuntimeError(
            f"implausible MFU {r['mfu']}% for {label}: timing barrier violated"
        )
    return {
        "metric": f"{label}_single_chip_mfu",
        "value": r["mfu"],
        "unit": "% MFU",
        "vs_baseline": round(r["mfu"] / base_mfu, 3),
        "tokens_per_second": r["tokens_per_second"],
        "baseline_mfu": base_mfu,
        "baseline_tokens_per_second": base_tok_s,
        "memory_gb": r["memory_gb"],
        "platform": r["platform"],
        "device_kind": r["device_kind"],
        "num_chips": r["num_chips"],
        "attention_backend": r["attention_backend"],
        # Echo every training-recipe deviation so cross-commit bench JSON
        # diffs show WHAT changed, not just that the number moved.
        **{k: v for k, v in shape.get("extra", {}).items()
           if k in ("param_dtype", "optimizer_name")},
        **({"remat_policy": shape["remat_policy"]}
           if shape["remat_policy"] != "nothing_saveable" else {}),
    }


def main() -> int:
    from scaletorch_tpu.env import configure_compile_cache
    from scaletorch_tpu.utils.device import NoTpuError

    configure_compile_cache()
    label = os.environ.get("BENCH_ROW") or HEADLINE
    if label not in SINGLE_CHIP_ROWS:
        raise KeyError(
            f"BENCH_ROW {label!r} unknown; rows: {', '.join(SINGLE_CHIP_ROWS)}"
        )
    # stdout carries ONLY the result line: the framework logger's
    # streams move to stderr
    import logging

    from scaletorch_tpu.utils.logger import get_logger

    for h in get_logger().handlers:
        if isinstance(h, logging.StreamHandler):
            h.setStream(sys.stderr)
    try:
        row = run_row(label,
                      int(os.environ.get("BENCH_WARMUP_STEPS", 3)),
                      int(os.environ.get("BENCH_STEPS", 10)))
    except NoTpuError as exc:
        print(f"bench.py: {exc}", file=sys.stderr)
        return 1
    print(json.dumps(row))
    return 0


if __name__ == "__main__":
    sys.exit(main())
