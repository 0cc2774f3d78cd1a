#!/usr/bin/env python3
"""Look at one traced run by hand: which planes, lines and names the
profiler wrote, before writing a name pattern into a metric file; and
cut the small recorded traces the tests read (``benchmarks/testdata``).

    python3 benchmarks/tools/look_at_trace.py <out.json> -- \\
        --workload <cell> --seed <n> --seconds <s>

Runs the cell once with ``--trace 1`` through ``benchmarks/run.py`` in
this process, keeps the events the run's own reduction read, and writes
``{"window", "summary", "sample"}``: per plane and line the names that
took most time (count, total ms), and 2.5 s of events from the middle of
the window in the form ``lib/trace.load_recorded`` reads. Not part of a
measurement: the driver never runs it.
"""

from __future__ import annotations

import json
import os
import sys
from typing import Any, Dict, List, Sequence, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.lib import trace, tracing  # noqa: E402


def summarize(events: Sequence[trace.Event], top: int = 25) -> Dict[str, Any]:
    """Planes, their lines, and per line the names that took most time
    (count, total ms): what to read before writing a name pattern."""
    lines: Dict[Tuple[str, str], Dict[str, List[int]]] = {}
    for e in events:
        per_name = lines.setdefault((e["plane"], e["line"]), {})
        entry = per_name.setdefault(e["name"], [0, 0])
        entry[0] += 1
        entry[1] += e["dur_ns"]
    out: Dict[str, Any] = {}
    for (plane, line), per_name in sorted(lines.items()):
        ranked = sorted(per_name.items(), key=lambda kv: -kv[1][1])[:top]
        out.setdefault(plane, {})[line] = {
            "events": sum(v[0] for v in per_name.values()),
            "names": len(per_name),
            "top": [[name, count, ns / 1e6]
                    for name, (count, ns) in ranked],
        }
    return out


def sample(events: Sequence[trace.Event], window: trace.Interval,
           min_dur_ns: int = 0) -> Dict[str, Any]:
    """The events that start inside the window, earliest first, in the
    compact form ``load_recorded`` reads: a small recorded trace for the
    tests. Events shorter than ``min_dur_ns`` are left out (custom calls
    and program executions are always kept)."""
    inside = [e for e in events
              if window[0] <= e["start_ns"] < window[1]
              and (e["dur_ns"] >= min_dur_ns or e["line"] == trace.MODULES_LINE
                   or "custom-call" in e["name"])]
    inside.sort(key=lambda e: e["start_ns"])
    tables: Dict[str, Dict[str, int]] = {"plane": {}, "line": {}, "name": {}}
    rows = []
    for e in inside:
        idx = [tables[k].setdefault(e[k], len(tables[k]))
               for k in ("plane", "line", "name")]
        rows.append(idx + [e["start_ns"] - window[0], e["dur_ns"]])
    return {"planes": list(tables["plane"]), "lines": list(tables["line"]),
            "names": list(tables["name"]), "events": rows}


def main(argv: List[str]) -> int:
    if len(argv) < 3 or argv[1] != "--":
        print(__doc__, file=sys.stderr)
        return 2
    out_path, cell_args = argv[0], argv[2:]
    kept: Dict[str, Any] = {}
    read_events = tracing.Tracer.events

    def keeping(self):
        kept["events"] = read_events(self)
        return kept["events"]

    tracing.Tracer.events = keeping
    code = bench_run.main(cell_args + ["--trace", "1"])
    events = kept.get("events") or []
    if not trace.device_planes(events):
        print("[look] the run left no device trace", file=sys.stderr)
        return code or 1
    window = trace.device_window(events)
    middle = (window[0] + window[1]) // 2
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump({"window": window, "summary": summarize(events),
                   "sample": sample(events,
                                    (middle, middle + 2_500_000_000),
                                    min_dur_ns=50_000)}, f)
    return code


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
