#!/usr/bin/env python3
"""Run one cell many times, one process a run, and keep every run's line.

    python3 benchmarks/tools/run_many.py --out chiprun_out/x.jsonl \
        --workload <cell> --seeds 11,12 [--roots .checkouts/a,.checkouts/b] \
        [--rates 1.4,2.0,...] [--seconds 40] [--trace 0] [--warm <seed>]

A builder's tool, never run by the driver: the knee ladder of the chat
cell (``--rates``: for each seed the rates in order through
``--override traffic.rate_per_s``, stopping a seed after two rungs in a
row that were not sustained) and the sets of runs a bound is set from
(no ``--rates``: for each seed one run in each root in turn, so two
exports of one tree give the alternating pairs of a null comparison).
It never imports jax: each run is ``benchmarks/run.py`` in a child of
its own, in its root. One JSON object a run is appended to ``--out``:
the result line, the client view and the waits that the run logged, and
every ``[bench <seconds>s]`` stamp (where ``setup_s`` went, by phase).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
import time

# a rung of the ladder is sustained when the run is correct (it drained
# and no request failed), its backlog did not grow and the 90th
# percentile of the time to a first token stayed under three full-shape
# prefill calls (PR 41's rule, stated before its runs)
SUSTAINED_BACKLOG_S = 0.35
SUSTAINED_TTFT_P90_MS = 1500.0

STAMP = re.compile(r"^\[bench\s+([0-9.]+)s\] (.*)$")

# what a run's client view says of its gaps (``read_sets.py --gaps``
# tabulates the same keys)
GAP_KEYS = ("n_gaps", "serve_itl_p50_ms", "serve_itl_p95_ms",
            "serve_itl_p99_ms", "serve_itl_p995_ms", "itl_p999_ms",
            "itl_over_3x_median_share_pct", "itl_over_10x_median_share_pct")


def sustained(record: dict) -> bool:
    view = record.get("client_view") or {}
    growth = view.get("backlog_growth_s")
    return bool(record.get("line", {}).get("correct")
                and growth is not None and growth < SUSTAINED_BACKLOG_S
                and view.get("serve_ttft_p90_ms", 1e9)
                < SUSTAINED_TTFT_P90_MS)


def run_once(root: str, workload: str, seed: int, seconds: float,
             trace: int, rate=None) -> dict:
    command = [sys.executable, "benchmarks/run.py", "--workload", workload,
               "--seed", str(seed), "--seconds", str(seconds),
               "--trace", str(trace)]
    if rate is not None:
        command += ["--override", f"traffic.rate_per_s={rate}"]
    started = time.monotonic()
    proc = subprocess.run(command, cwd=root, capture_output=True, text=True)
    record = {"root": root, "workload": workload, "seed": seed,
              "rate": rate, "trace": trace, "rc": proc.returncode,
              "wall_s": time.monotonic() - started, "stamps": []}
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    if lines and lines[-1].startswith("{"):
        record["line"] = json.loads(lines[-1])
    for text in lines:
        found = STAMP.match(text)
        if not found:
            continue
        at, message = float(found.group(1)), found.group(2)
        for key, prefix in (("client_view", "client view: "),
                            ("waits", "waits: ")):
            if message.startswith(prefix):
                record[key] = json.loads(message[len(prefix):])
                break
        else:
            record["stamps"].append([at, message[:200]])
    if "line" not in record:
        record["stdout_end"] = proc.stdout[-2000:]
        record["stderr_end"] = proc.stderr[-2000:]
    return record


def brief(record: dict) -> str:
    line = record.get("line", {})
    view = record.get("client_view") or {}
    shown = {k: round(v["value"], 4)
             for k, v in line.get("metrics", {}).items()}
    for key in ("backlog_growth_s", "serve_ttft_p90_ms") + GAP_KEYS:
        if view.get(key) is not None:
            shown[key] = round(view[key], 4)
    return (f"{record['root']} {record['workload']} seed={record['seed']} "
            f"rate={record['rate']} rc={record['rc']} "
            f"correct={line.get('correct')} failed={line.get('failed')}/"
            f"{line.get('attempted')} bringup="
            f"{line.get('runtime_bringup_s')} wall={record['wall_s']:.0f}s "
            f"{json.dumps(shown)}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--out", required=True)
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--roots", default=".")
    p.add_argument("--rates", default="")
    p.add_argument("--seconds", type=float, default=40.0)
    p.add_argument("--trace", type=int, default=0)
    p.add_argument("--warm", type=int, default=None,
                   help="a first run on this seed in each root, kept in "
                        "the file as the run that compiles")
    args = p.parse_args(argv)
    roots = [os.path.abspath(r) for r in args.roots.split(",")]
    seeds = [int(s) for s in args.seeds.split(",")]
    rates = [float(r) for r in args.rates.split(",") if r]
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)

    def keep(record: dict, **more) -> dict:
        record.update(more)
        with open(args.out, "a") as f:
            f.write(json.dumps(record) + "\n")
        print(brief(record), flush=True)
        return record

    if args.warm is not None:
        # in EVERY root, a shared JAX_COMPILATION_CACHE_DIR or not: a
        # program's key holds its source paths, so an export's first
        # run compiles some of what its twin's already has (PR 46: a
        # second root's first run read 23.6 s of set-up for 16.4)
        for root in roots:
            keep(run_once(root, args.workload, args.warm, args.seconds,
                          args.trace, rates[0] if rates else None),
                 warm=True)
    for seed in seeds:
        if not rates:
            for root in roots:
                keep(run_once(root, args.workload, seed, args.seconds,
                              args.trace))
            continue
        failed_in_a_row = 0
        for rate in rates:
            record = run_once(roots[0], args.workload, seed, args.seconds,
                              args.trace, rate)
            keep(record, sustained=sustained(record))
            failed_in_a_row = 0 if record["sustained"] \
                else failed_in_a_row + 1
            if failed_in_a_row == 2:
                break
    return 0


if __name__ == "__main__":
    sys.exit(main())
