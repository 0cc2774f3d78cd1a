"""From a profiler trace to numbers. The reduction every PR shares.

``load_xplane`` turns the ``.xplane.pb`` file jax's profiler writes into
plain events ``{"plane", "line", "name", "start_ns", "dur_ns"}``; the
rest of this file works on such lists, so the same code runs on the
small recorded trace under ``benchmarks/testdata/`` in the tests.

Device planes are those named ``/device:TPU:<n>``. On them the line
``XLA Modules`` holds one event per executed program (named after the
jitted function, e.g. ``jit_step(...)``) and ``XLA Ops`` one per HLO
operation inside it (a Pallas kernel is a custom call named after the
kernel). Host threads live on the ``/host:CPU`` plane, one line per
thread; ``jax.profiler.TraceAnnotation`` spans land there.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

Event = Dict[str, Any]
Interval = Tuple[int, int]

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


_HLO = re.compile(r"^%?(?P<instr>[\w.\-]+) = (?P<rest>.*)$", re.S)
_OPCODE = re.compile(r"\s(?P<opcode>[a-z][a-z0-9\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_KIND = re.compile(r"\bkind=(k\w+)")
_LAYOUT = re.compile(r"\{[^{}]*\}")


def short_name(name: str) -> str:
    """An ``XLA Ops`` event is named by its whole HLO instruction
    (``%fusion.3 = bf16[8,128]{1,0:T(8,128)} fusion(...), kind=kLoop,
    calls=...``, hundreds of characters). This keeps what tells
    operations apart, in a fixed order:

        <instruction> | <opcode> | <custom-call target or fusion kind> | <result shape>

    e.g. ``closed_call.12 | custom-call | tpu_custom_call |
    bf16[16,8,2,128]``. Any other name is returned as it is."""
    match = _HLO.match(name)
    if match is None:
        return name
    rest = " " + match.group("rest")
    opcode = _OPCODE.search(rest)
    if opcode is None:
        return name[:200]
    shape = _LAYOUT.sub("", rest[: opcode.start()]).strip()
    detail = _TARGET.search(rest) or _KIND.search(rest)
    return " | ".join([match.group("instr"), opcode.group("opcode"),
                       detail.group(1) if detail else "-", shape[:120]])


def load_xplane(path: str, planes: Optional[re.Pattern] = None) -> List[Event]:
    """Every event of the trace file (or of the planes that match),
    HLO instruction names shortened by ``short_name``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    events: List[Event] = []
    names: Dict[str, str] = {}
    for plane in data.planes:
        if planes is not None and not planes.search(plane.name):
            continue
        for line in plane.lines:
            for event in line.events:
                full = event.name
                if full not in names:
                    names[full] = short_name(full)
                events.append({
                    "plane": plane.name, "line": line.name,
                    "name": names[full],
                    "start_ns": int(event.start_ns),
                    "dur_ns": int(event.duration_ns),
                })
    return events


# -- selection ----------------------------------------------------------------

def device_planes(events: Iterable[Event]) -> List[str]:
    return sorted({e["plane"] for e in events
                   if DEVICE_PLANE.match(e["plane"])})


def select(events: Iterable[Event], *, plane: Optional[str] = None,
           line: Optional[str] = None,
           patterns: Optional[Sequence[str]] = None,
           exclude: Optional[Sequence[str]] = None) -> List[Event]:
    """Events of one plane / line whose name matches any of ``patterns``
    (regular expressions, searched) and none of ``exclude``."""
    want = [re.compile(p) for p in patterns] if patterns else None
    drop = [re.compile(p) for p in exclude] if exclude else []
    out = []
    for e in events:
        if plane is not None and e["plane"] != plane:
            continue
        if line is not None and e["line"] != line:
            continue
        if want is not None and not any(p.search(e["name"]) for p in want):
            continue
        if any(p.search(e["name"]) for p in drop):
            continue
        out.append(e)
    return out


def clip(events: Iterable[Event], window: Interval) -> List[Event]:
    """Events cut to the window; those outside it dropped."""
    lo, hi = window
    out = []
    for e in events:
        start = max(e["start_ns"], lo)
        end = min(e["start_ns"] + e["dur_ns"], hi)
        if end > start:
            out.append({**e, "start_ns": start, "dur_ns": end - start})
    return out


# -- interval arithmetic ------------------------------------------------------

def intervals(events: Iterable[Event]) -> List[Interval]:
    return [(e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in events]


def union(spans: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping intervals."""
    merged: List[List[int]] = []
    for start, end in sorted(spans):
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def total(spans: Iterable[Interval]) -> int:
    return sum(end - start for start, end in spans)


def subtract(spans: Sequence[Interval],
             cover: Sequence[Interval]) -> List[Interval]:
    """The parts of ``spans`` that no interval of ``cover`` overlaps.
    Both are made disjoint first."""
    cover = union(cover)
    out: List[Interval] = []
    j = 0
    for start, end in union(spans):
        cursor = start
        while j < len(cover) and cover[j][1] <= cursor:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < end:
            if cover[k][0] > cursor:
                out.append((cursor, cover[k][0]))
            cursor = max(cursor, cover[k][1])
            k += 1
        if cursor < end:
            out.append((cursor, end))
    return out


def gaps(spans: Sequence[Interval], window: Interval) -> List[Interval]:
    """The idle intervals of the window."""
    return subtract([window], spans)


# -- the trace window ---------------------------------------------------------

def device_window(events: Sequence[Event]) -> Interval:
    """First start to last end of any operation on any device plane:
    the window the device numbers are taken over. The profiler's own
    start and stop sit outside it."""
    ops = [e for e in events if DEVICE_PLANE.match(e["plane"])
           and e["line"] in (OPS_LINE, MODULES_LINE)]
    if not ops:
        raise ValueError("the trace holds no operation on a TPU plane")
    return (min(e["start_ns"] for e in ops),
            max(e["start_ns"] + e["dur_ns"] for e in ops))


def busy_by_device(events: Sequence[Event],
                   window: Interval) -> Dict[str, List[Interval]]:
    """Per device plane, the union of the intervals in which an
    operation ran (the ``XLA Ops`` line, nested events merged)."""
    out = {}
    for plane in device_planes(events):
        ops = clip(select(events, plane=plane, line=OPS_LINE), window)
        out[plane] = union(intervals(ops))
    return out


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile by the nearest-rank rule on the sorted
    values (q in 0..100): the smallest value with at least q % of the
    samples at or below it. No interpolation: a tail is a real sample."""
    if not values:
        raise ValueError("percentile of no samples")
    ordered = sorted(values)
    rank = max(1, -(-len(ordered) * q // 100))  # ceil
    return float(ordered[int(rank) - 1])


def samples_beyond(n: int, q: float) -> int:
    """How many samples lie beyond the q-th percentile of n."""
    return n - int(max(1, -(-n * q // 100)))


# the rule of benchmarks/README.md, "Which percentile": a held
# percentile is clear of an edge by this factor, with this many samples
# beyond it
CLEAR_BY = 2.5
MIN_BEYOND = 10


def percentile_clearance(n: int, q: float, long_share_pct: float,
                         full_share_pct: float = 0.0) -> Dict[str, bool]:
    """Whether the q-th percentile of ``n`` gaps reads the long
    population and nothing else: the share of long gaps is ``CLEAR_BY``
    times the tail beyond the percentile or more (``long_edge``), the
    share of the gaps of the next population up, which the percentile
    is NOT to read, is the tail over ``CLEAR_BY`` or less
    (``full_edge``), and at least ``MIN_BEYOND`` samples lie beyond it
    (``samples``); ``clear`` is all three."""
    tail_pct = 100.0 - q
    out = {"long_edge": long_share_pct >= CLEAR_BY * tail_pct,
           "full_edge": full_share_pct * CLEAR_BY <= tail_pct,
           "samples": samples_beyond(n, q) >= MIN_BEYOND}
    out["clear"] = all(out.values())
    return out


def top_operations(events: Sequence[Event], window: Interval,
                   limit: int = 10) -> List[List[Any]]:
    """[name, seconds] of the device operations that took most time,
    summed over events and averaged over devices. Control-flow wrappers
    (``while``, ``conditional``, ``call``) are left out: their children
    are listed."""
    planes = device_planes(events)
    sums: Dict[str, int] = {}
    for e in clip(select(events, line=OPS_LINE,
                         exclude=[r" \| (while|conditional|call) \| "]),
                  window):
        if DEVICE_PLANE.match(e["plane"]):
            sums[e["name"]] = sums.get(e["name"], 0) + e["dur_ns"]
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:limit]
    return [[name, ns / 1e9 / max(1, len(planes))] for name, ns in ranked]


def idle_gaps_by_host_span(events: Sequence[Event], window: Interval,
                           host_patterns: Sequence[str],
                           limit: int = 10) -> List[List[Any]]:
    """[name, seconds]: the first device's idle time, attributed to the
    host annotation (``TraceAnnotation`` span matching
    ``host_patterns``) that covers the middle of each gap; gaps under no
    span go to ``(no host span)``. Summed by name, longest first."""
    planes = device_planes(events)
    if not planes:
        return []
    busy = busy_by_device(events, window)[planes[0]]
    spans = [e for e in select(events, patterns=host_patterns)
             if not DEVICE_PLANE.match(e["plane"])]
    spans.sort(key=lambda e: e["dur_ns"])  # innermost first
    sums: Dict[str, int] = {}
    for start, end in gaps(busy, window):
        mid = (start + end) // 2
        name = "(no host span)"
        for e in spans:
            if e["start_ns"] <= mid < e["start_ns"] + e["dur_ns"]:
                name = e["name"]
                break
        sums[name] = sums.get(name, 0) + (end - start)
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:limit]
    return [[name, ns / 1e9] for name, ns in ranked]


# -- a recorded trace ---------------------------------------------------------

def load_recorded(path: str) -> List[Event]:
    """Events of a trace stored by ``benchmarks/tools/look_at_trace.py``
    (``sample``): index tables of planes, lines and names, and rows
    ``[plane, line, name, start_ns, dur_ns]``."""
    import json

    with open(path) as f:
        data = json.load(f)
    return [{"plane": data["planes"][p], "line": data["lines"][l],
             "name": data["names"][n], "start_ns": start, "dur_ns": dur}
            for p, l, n, start, dur in data["events"]]
