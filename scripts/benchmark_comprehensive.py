#!/usr/bin/env python
"""Comprehensive parallelism benchmark sweep — DP/TP/PP/CP/SP/EP and combos.

TPU-native counterpart of reference ``scripts/benchmark_comprehensive.py``
(:54-174 config table, :337-470 subprocess runner with per-config
OOM/error capture, :527-591 incremental results JSON + summary tables).
Differences by design:

* the reference launches ``torchrun --nproc_per_node=N``; here every
  config is ONE process driving all chips (SPMD), so the subprocess is
  just ``python train.py`` with parallel-size flags.
* two tiers instead of one: ``--tier correctness`` runs the full combo
  matrix with downscaled models on the 8-virtual-CPU mesh (the system
  test the reference gets from its smoke scripts), ``--tier perf`` runs
  the reference's published model/shape rows on real chips.
* per-config metrics come from the trainer's performance-log JSON
  (``--performance_log_dir``, reference monitor.py save_stats role), not
  stdout scraping; stdout is only the error channel.

Usage:
    python scripts/benchmark_comprehensive.py                   # correctness, CPU
    python scripts/benchmark_comprehensive.py --tier perf       # real chips
    python scripts/benchmark_comprehensive.py --filter CP --steps 8
Results stream into ``benchmark_results.json`` after every config.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)  # runnable from any cwd
WARMUP_STEPS = 2

# ---------------------------------------------------------------------------
# Config tables: (label, model, tp, pp, dp, cp, ep, bs, ga, seq, gc, sp, engine)
# Mirrors the reference CONFIGS tuple layout (benchmark_comprehensive.py:55)
# with an extra ep column (the reference sweeps EP in run_npu.sh instead).
#
# READING THE CORRECTNESS TABLE: on the virtual CPU mesh the SIGNAL is
# the loss column (every config must land on the same objective) and the
# OK/FAIL status. tokens_per_sec and wall_s are recorded for the
# hardware tier only — on a timeshared CPU host they vary by integer
# factors with machine load and must not be used to rank configs.
# ---------------------------------------------------------------------------

# fmt: off
CORRECTNESS_CONFIGS = [
    # --- pure DP ---
    ("tiny-DP8",             "dense-tiny", 1, 1, 8, 1, 1, 2, 2, 256, False, False, "memory_chunked"),
    # --- TP ---
    ("tiny-TP2-DP4",         "dense-tiny", 2, 1, 4, 1, 1, 2, 1, 256, False, False, "memory_chunked"),
    ("tiny-TP4-DP2",         "dense-tiny", 4, 1, 2, 1, 1, 2, 1, 256, False, False, "memory_chunked"),
    # --- PP (both schedules) ---
    ("tiny-PP2-DP4",         "dense-tiny", 1, 2, 4, 1, 1, 2, 2, 256, False, False, "memory_chunked"),
    ("tiny-PP4-DP2-afab",    "dense-tiny", 1, 4, 2, 1, 1, 2, 4, 256, False, False, "afab"),
    ("tiny-PP4-DP2-1f1b",    "dense-tiny", 1, 4, 2, 1, 1, 2, 4, 256, False, False, "memory_chunked"),
    ("tiny-PP2-VPP2-DP4",    "dense-tiny", 1, 2, 4, 1, 1, 2, 4, 256, False, False, "interleaved",
     {"pp_virtual_stages": 2}),  # virtual-stage circular pipeline (L=4 = pp*vpp)
    ("tiny-PP2-VPP2-CP2-GC", "dense-tiny", 1, 2, 2, 2, 1, 1, 2, 512, True, False, "interleaved",
     {"pp_virtual_stages": 2}),  # interleaved x ring-attention composition
    # --- CP (ring runs the zigzag layout by default; ulysses = the
    # all-to-all head-scatter strategy) ---
    ("tiny-CP2-DP4",         "dense-tiny", 1, 1, 4, 2, 1, 1, 1, 512, False, False, "memory_chunked"),
    ("tiny-CP4-DP2-GC",      "dense-tiny", 1, 1, 2, 4, 1, 1, 1, 1024, True, False, "memory_chunked"),
    ("tiny-CP2-DP4-ulysses", "dense-tiny", 1, 1, 4, 2, 1, 1, 1, 512, False, False, "memory_chunked",
     {"attention_backend": "ulysses"}),
    # --- SP ---
    ("tiny-SP-TP2-DP4",      "dense-tiny", 2, 1, 4, 1, 1, 2, 1, 256, False, True,  "memory_chunked"),
    # --- mixed dense ---
    ("tiny-TP2-PP2-DP2-GC",  "dense-tiny", 2, 2, 2, 1, 1, 2, 2, 256, True,  False, "memory_chunked"),
    ("tiny-TP2-CP2-DP2",     "dense-tiny", 2, 1, 2, 2, 1, 1, 1, 512, False, False, "memory_chunked"),
    ("tiny-SP-TP2-CP2-DP2",  "dense-tiny", 2, 1, 2, 2, 1, 1, 1, 512, False, True,  "memory_chunked"),
    ("tiny-TP2-PP2-CP2-GC",  "dense-tiny", 2, 2, 1, 2, 1, 1, 2, 512, True,  False, "memory_chunked"),
    # --- MoE / EP ---
    ("moe-DP8",              "moe-tiny",   1, 1, 8, 1, 1, 2, 1, 256, False, False, "memory_chunked"),
    ("moe-EP2-DP4",          "moe-tiny",   1, 1, 4, 1, 2, 1, 1, 256, False, False, "memory_chunked"),
    ("moe-EP4-DP2",          "moe-tiny",   1, 1, 2, 1, 4, 1, 1, 256, False, False, "memory_chunked"),
    ("moe-EP2-TP2-DP2",      "moe-tiny",   2, 1, 2, 1, 2, 1, 1, 256, False, False, "memory_chunked"),
    # auto now resolves to index everywhere (AOT_DISPATCH_CROSSOVER.json),
    # so the base moe rows attest the index path; this row keeps the
    # einsum form attested.
    ("moe-EP2-DP4-einsum",   "moe-tiny",   1, 1, 4, 1, 2, 1, 1, 256, False, False, "memory_chunked",
     {"moe_dispatch": "einsum"}),
    ("moe-interleaved-EP2-DP4", "moe-tiny", 1, 1, 4, 1, 2, 1, 1, 256, False, False, "memory_chunked",
     {"decoder_sparse_step": 2}),  # layers 1,3 sparse / 0,2 dense
    ("moe-EP2-CP2-DP2",      "moe-tiny",   1, 1, 2, 2, 2, 1, 1, 512, False, False, "memory_chunked"),
    ("moe-EP2-TP2-CP2-GC",   "moe-tiny",   2, 1, 1, 2, 2, 1, 1, 512, True,  False, "memory_chunked"),
    # --- PP x EP (MoE pipeline; VERDICT r1 missing #8) ---
    ("moe-PP2-EP2-DP2",      "moe-tiny",   1, 2, 2, 1, 2, 1, 2, 256, False, False, "afab"),
    ("moe-PP2-VPP2-EP2-DP2", "moe-tiny",   1, 2, 2, 1, 2, 1, 2, 256, False, False, "interleaved",
     {"pp_virtual_stages": 2}),  # expert all-to-all inside switch chunks
    ("moe-PP2-EP2-TP2-1f1b", "moe-tiny",   2, 2, 1, 1, 2, 1, 2, 256, False, False, "memory_chunked"),
]

# The reference's published 8-chip rows (BASELINE.md §8-NPU) + single-chip
# rows; run on a real pod/chip. World size must equal available devices.
# The optional trailing dict carries training-recipe extras (param_dtype /
# optimizer_name) — the SAME memory recipes bench.py's single-chip rows
# use (bench.py SINGLE_CHIP_ROWS): 1.7B needs bf16 master weights and 4B
# needs Adafactor to fit a 16 GB chip; without them this table OOMs where
# bench.py's rows run, and the two tables silently disagree.
PERF_CONFIGS = [
    ("0.6B-single",          "qwen3-0.6b", 1, 1, 1, 1, 1, 1, 1, 8192,  True,  False, "memory_chunked"),
    ("0.6B-seq16k-single",   "qwen3-0.6b", 1, 1, 1, 1, 1, 1, 1, 16384, True,  False, "memory_chunked"),
    ("0.6B-DP8",             "qwen3-0.6b", 1, 1, 8, 1, 1, 2, 2, 2048,  False, False, "memory_chunked"),
    ("0.6B-CP2-DP4",         "qwen3-0.6b", 1, 1, 4, 2, 1, 1, 1, 4096,  False, False, "memory_chunked"),
    ("1.7B-DP8-GC",          "qwen3-1.7b", 1, 1, 8, 1, 1, 1, 2, 2048,  True,  False, "memory_chunked",
     {"param_dtype": "bfloat16"}),
    ("1.7B-CP4-DP2-GC",      "qwen3-1.7b", 1, 1, 2, 4, 1, 1, 1, 8192,  True,  False, "memory_chunked",
     {"param_dtype": "bfloat16"}),
    ("4B-CP2-DP4-GC",        "qwen3-4b",   1, 1, 4, 2, 1, 1, 1, 4096,  True,  False, "memory_chunked",
     {"param_dtype": "bfloat16", "optimizer_name": "adafactor"}),
    ("8B-TP2-CP2-DP2-GC",    "qwen3-8b",   2, 1, 2, 2, 1, 1, 1, 4096,  True,  False, "memory_chunked",
     {"param_dtype": "bfloat16", "optimizer_name": "adafactor"}),
    ("14B-TP4-CP2-GC",       "qwen3-14b",  4, 1, 1, 2, 1, 1, 1, 4096,  True,  False, "memory_chunked",
     {"param_dtype": "bfloat16", "optimizer_name": "adafactor"}),
    ("32B-TP8-SEQ4K-GC",     "qwen3-32b",  8, 1, 1, 1, 1, 1, 1, 4096,  True,  False, "memory_chunked",
     {"param_dtype": "bfloat16", "optimizer_name": "adafactor"}),
    ("30B-A3B-EP2-TP4",      "qwen3-30b-a3b", 4, 1, 1, 1, 2, 1, 1, 4096, False, False, "memory_chunked",
     {"param_dtype": "bfloat16", "optimizer_name": "adafactor"}),
]
# fmt: on


def build_cmd(cfg, steps, perf_dir):
    (label, model, tp, pp, dp, cp, ep, bs, ga, seq, gc, sp, engine) = cfg[:13]
    extra = cfg[13] if len(cfg) > 13 else {}
    from scaletorch_tpu.models.presets import preset

    cmd = [sys.executable, os.path.join(REPO, "train.py")]
    for k, v in preset(model).items():
        cmd += [f"--{k}", str(v)]
    cmd += [
        "--tensor_parallel_size", str(tp),
        "--pipeline_parallel_size", str(pp),
        "--data_parallel_size", str(dp),
        "--context_parallel_size", str(cp),
        "--expert_parallel_size", str(ep),
        "--pp_engine", engine,
        "--micro_batch_size", str(bs),
        "--gradient_accumulation_steps", str(ga),
        "--sequence_length", str(seq),
        "--gradient_checkpointing", str(gc),
        "--sequence_parallel", str(sp),
        "--synthetic_data", "true",
        "--total_train_steps", str(steps),
        "--max_grad_norm", "1.0",
        "--seed", "42",
        "--log_frequency", "1",
        "--performance_log_dir", perf_dir,
    ]
    for k, v in extra.items():
        cmd += [f"--{k}", str(v)]
    return cmd


def world_size(cfg) -> int:
    _, _, tp, pp, dp, cp, ep, *_ = cfg
    return tp * pp * dp * cp * ep


def load_perf_json(perf_dir, warmup):
    """Read the trainer's dumped metrics history (MetricsLogger.save_json).

    Files are named ``performance_log_proc{P}_step{S}.json``; pick process
    0's latest step deterministically — a lexicographic sort would grab an
    arbitrary process on multi-process runs (metrics are replicated, but
    the choice should not depend on process count)."""
    def _key(name):
        m = re.search(r"proc(\d+)_step(\d+)", name)
        # max() picks: lowest process index, then its highest step;
        # unparseable names lose to any real dump
        return (-(10 ** 9), 0) if not m else (-int(m.group(1)), int(m.group(2)))

    files = [f for f in os.listdir(perf_dir) if f.endswith(".json")]
    if not files:
        return None
    with open(os.path.join(perf_dir, max(files, key=_key))) as f:
        data = json.load(f)
    steady = [r for r in data.get("records", [])
              if r.get("step", 0) > warmup]
    if not steady:
        return None
    out = {"loss": round(steady[-1]["loss"], 4)}
    # rates and MFU are in the records only when the run was on a TPU
    # (trainer/metrics.py): the CPU correctness tier reports the loss
    rated = [r for r in steady if "tokens_per_second" in r]
    if rated:
        n = len(rated)
        out["tokens_per_sec"] = round(
            sum(r["tokens_per_second"] for r in rated) / n)
        out["mfu"] = round(sum(r["mfu"] for r in rated) / n, 2)
    mems = [r["peak_memory_gb"] for r in steady if "peak_memory_gb" in r]
    if mems:
        out["memory_gb"] = round(max(mems), 2)
    return out


_ERR_PATTERNS = ("error", "oom", "out of memory", "killed", "resource_exhausted")


def run_config(cfg, steps, device, timeout):
    label, model = cfg[0], cfg[1]
    nchips = world_size(cfg)
    import tempfile

    with tempfile.TemporaryDirectory(prefix=f"bench_{label}_") as perf_dir:
        cmd = build_cmd(cfg, steps, perf_dir)
        env = dict(os.environ)
        if device == "cpu":
            env.update(
                JAX_PLATFORMS="cpu",
                XLA_FLAGS=f"--xla_force_host_platform_device_count={nchips}",
            )
        print(f"[{label}] {model} world={nchips} ...", flush=True)
        t0 = time.time()
        try:
            proc = subprocess.run(
                cmd, capture_output=True, text=True, timeout=timeout,
                cwd=REPO, env=env,
            )
        except subprocess.TimeoutExpired:
            return {"label": label, "model": model, "status": "TIMEOUT",
                    "wall_s": round(time.time() - t0, 1)}
        wall = round(time.time() - t0, 1)
        if proc.returncode != 0:
            out = proc.stdout + proc.stderr
            err_lines = [ln for ln in out.splitlines()
                         if any(p in ln.lower() for p in _ERR_PATTERNS)]
            if err_lines:
                msg = err_lines[-1]
            else:
                tail = out.strip().splitlines()
                msg = tail[-1] if tail else ""
            return {
                "label": label, "model": model,
                "status": f"FAILED rc={proc.returncode}",
                "error": msg[:300],
                "wall_s": wall,
            }
        metrics = load_perf_json(perf_dir, WARMUP_STEPS) or {}
        return {"label": label, "model": model, "status": "OK",
                "world": nchips, "wall_s": wall, **metrics}


def print_table(results):
    ok = [r for r in results if r.get("status") == "OK"]
    if ok:
        print("\n| Config | Model | World | Loss | Tok/s | MFU | Mem(GB) | Wall(s) |")
        print("|---|---|---|---|---|---|---|---|")
        for r in ok:
            print(f"| {r['label']} | {r['model']} | {r.get('world', '')} "
                  f"| {r.get('loss', '')} | {r.get('tokens_per_sec', '')} "
                  f"| {r.get('mfu', '')} | {r.get('memory_gb', '')} "
                  f"| {r['wall_s']} |")
    failed = [r for r in results if r.get("status") != "OK"]
    for r in failed:
        print(f"FAILED: {r['label']}: {r['status']} {r.get('error', '')}")
    print(f"\n{len(ok)} OK / {len(failed)} failed / {len(results)} total")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tier", choices=["correctness", "perf"], default="correctness")
    ap.add_argument("--device", choices=["cpu", "native"], default=None,
                    help="cpu = virtual 8-device CPU mesh (default for "
                         "correctness); native = whatever jax sees")
    ap.add_argument("--filter", default=None, help="regex on config label")
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--timeout", type=int, default=900)
    ap.add_argument("--max-world", type=int, default=None,
                    help="skip configs needing more devices (perf tier)")
    ap.add_argument("--out", default="benchmark_results.json")
    args = ap.parse_args()

    configs = CORRECTNESS_CONFIGS if args.tier == "correctness" else PERF_CONFIGS
    device = args.device or ("cpu" if args.tier == "correctness" else "native")
    if args.filter:
        configs = [c for c in configs if re.search(args.filter, c[0])]
    if args.max_world:
        configs = [c for c in configs if world_size(c) <= args.max_world]

    results = []
    for cfg in configs:
        r = run_config(cfg, args.steps, device, args.timeout)
        results.append(r)
        status = r["status"] if r["status"] != "OK" else (
            f"OK loss={r.get('loss')}"
            + (f" tok/s={r['tokens_per_sec']} mfu={r['mfu']}%"
               if "mfu" in r else ""))
        print(f"  -> {status} ({r['wall_s']}s)", flush=True)
        with open(args.out, "w") as f:  # incremental: survive any crash
            json.dump(results, f, indent=1)

    print_table(results)
    sys.exit(1 if any(r["status"] != "OK" for r in results) else 0)


if __name__ == "__main__":
    main()
