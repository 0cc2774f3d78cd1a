#!/usr/bin/env python3
"""Read what ``run_many.py`` kept: the spreads a bound is set from and
the table a held percentile is placed by.

    python3 benchmarks/tools/read_sets.py chiprun_out/x.jsonl [...] [--gaps]

A builder's tool, never run by the driver; it imports nothing but the
standard library, ``run_many.py``'s list of keys and
``benchmarks/lib/trace.py``'s two-edge rule. The
runs of one file are split into sets by the root they ran in (two
exports of one tree, run in turn on the same seeds, are the two sets of
six that a bound wants and the pairs of a null comparison at once); a
``--warm`` run is left out, as the driver leaves out each side's first.
For every number of the result lines' ``metrics``, and for
``runtime_bringup_s``, each set's median, quartiles
(``statistics.quantiles(n=4)``), the spread the contract measures (the
distance between the quartiles over the median) and the spread the
driver quotes in its reasons (the distance covered by the runs once the
one farthest from the median is left out). ``--gaps`` adds one row a
run of a serving cell: the gap percentiles, the two shares and whether
the 99th and the 99.5th percentile clear both of their edges
(``trace.percentile_clearance``).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import trace  # noqa: E402
from benchmarks.tools.run_many import GAP_KEYS  # noqa: E402


def spreads(values):
    """median, first and third quartile, their distance over the median
    and the range without the run farthest from the median, each as a
    share of the median too."""
    median = statistics.median(values)
    q1, _, q3 = statistics.quantiles(values, n=4)
    kept = sorted(values, key=lambda v: abs(v - median))[:-1] or values
    trimmed = max(kept) - min(kept)
    return {"n": len(values), "median": median, "q1": q1, "q3": q3,
            "iqr_pct": 100.0 * (q3 - q1) / median,
            "trimmed_range": trimmed,
            "trimmed_range_pct": 100.0 * trimmed / median,
            "min": min(values), "max": max(values)}


def numbers_of(record):
    line = record.get("line", {})
    out = {k: v["value"] for k, v in line.get("metrics", {}).items()}
    if "runtime_bringup_s" in line:
        out["runtime_bringup_s"] = line["runtime_bringup_s"]
    return out


def gap_row(record):
    view = record.get("client_view") or {}
    line = record.get("line", {})
    row = {"seed": record["seed"], "set": os.path.basename(record["root"]),
           "correct": line.get("correct"),
           "failed": f"{line.get('failed')}/{line.get('attempted')}"}
    row.update({k: view.get(k) for k in GAP_KEYS})
    if view.get("n_gaps"):
        for q in (99.0, 99.5):
            found = trace.percentile_clearance(
                view["n_gaps"], q, view["itl_over_3x_median_share_pct"],
                view["itl_over_10x_median_share_pct"])
            row[f"clear_p{q:g}"] = "".join(
                letter if found[part] else "-" for letter, part in
                (("L", "long_edge"), ("F", "full_edge"), ("S", "samples")))
    return row


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("files", nargs="+")
    p.add_argument("--gaps", action="store_true")
    args = p.parse_args(argv)
    for path in args.files:
        with open(path) as f:
            records = [json.loads(text) for text in f if text.strip()]
        measured = [r for r in records if not r.get("warm") and "line" in r]
        lost = [r for r in records if "line" not in r]
        print(f"== {path}: {len(measured)} runs, {len(lost)} without a "
              f"line, {sum(not r['line']['correct'] for r in measured)} "
              f"not correct")
        sets = {}
        for r in measured:
            sets.setdefault((r["workload"], r["root"]), []).append(r)
        for (cell, root), runs in sorted(sets.items()):
            numbers = [numbers_of(r) for r in runs]
            for name in sorted({k for found in numbers for k in found}):
                values = [found[name] for found in numbers if name in found]
                if len(values) < 2:
                    continue
                s = spreads(values)
                print(f"{cell} {os.path.basename(root)} {name}: "
                      f"n={s['n']} median={s['median']:.4f} "
                      f"q1={s['q1']:.4f} q3={s['q3']:.4f} "
                      f"iqr={s['iqr_pct']:.3f}% "
                      f"trimmed_range={s['trimmed_range']:.4f} "
                      f"({s['trimmed_range_pct']:.3f}%) "
                      f"range={s['min']:.4f}..{s['max']:.4f}")
        if args.gaps:
            for r in measured:
                row = gap_row(r)
                print(" ".join(
                    f"{k}={v:.3f}" if isinstance(v, float) else f"{k}={v}"
                    for k, v in row.items()))
    return 0


if __name__ == "__main__":
    sys.exit(main())
