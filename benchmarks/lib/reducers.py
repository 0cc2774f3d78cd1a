"""The reader kinds a per-layer metric file may name.

A metric is data: ``benchmarks/metrics/<name>.json`` gives a ``reducer``
``{"kind": ..., parameters}``. Each kind below takes the run's context
and those parameters and returns a number, or ``None`` when there is
nothing to read (the harness then leaves the metric out of the line).

Context keys: ``events`` (trace events), ``window`` (ns interval),
``records`` (name -> list of dicts: ``access`` from the gateway,
``loadgen`` from the client), ``counters`` (name -> number), ``client``
(the run's client view: every number the runner took from the client's
clock, the end-to-end values among them), ``config``, ``traffic``,
``workload``, ``peaks``, ``spec`` (finds a named ``cost_module``; not
needed for the default one).

A kind in ``UNTRACED_KINDS`` reads nothing of the trace, the records or
the counters, so ``run.py`` prints its metrics for an untraced run too.
"""

from __future__ import annotations

import statistics
from typing import Any, Callable, Dict, List, Optional

from benchmarks.lib import modules, trace

Context = Dict[str, Any]


def _per_device(ctx: Context, fn: Callable[[str], float]) -> Optional[float]:
    planes = trace.device_planes(ctx.get("events") or [])
    if not planes:
        return None
    return sum(fn(p) for p in planes) / len(planes)


def _matching(ctx: Context, plane: str, params: Dict[str, Any]):
    return trace.clip(trace.select(
        ctx["events"], plane=plane, line=params.get("line", trace.OPS_LINE),
        patterns=params.get("patterns"), exclude=params.get("exclude")),
        ctx["window"])


def _window_ns(ctx: Context) -> int:
    return ctx["window"][1] - ctx["window"][0]


def share_of_window(ctx: Context, params: Dict[str, Any]) -> Optional[float]:
    """% of the window in which a matching event ran, averaged over the
    devices."""
    def one(plane):
        spans = trace.union(trace.intervals(_matching(ctx, plane, params)))
        return 100.0 * trace.total(spans) / _window_ns(ctx)
    return _per_device(ctx, one)


def idle_share(ctx: Context, params: Dict[str, Any]) -> Optional[float]:
    """100 - busy: busy is the union of every operation on the device."""
    if not trace.device_planes(ctx.get("events") or []):
        return None
    busy = trace.busy_by_device(ctx["events"], ctx["window"])
    shares = [100.0 * (1 - trace.total(b) / _window_ns(ctx))
              for b in busy.values()]
    return sum(shares) / len(shares)


def _first_device_events(ctx: Context, params: Dict[str, Any]):
    planes = trace.device_planes(ctx.get("events") or [])
    if not planes:
        return []
    return sorted(_matching(ctx, planes[0], params),
                  key=lambda e: e["start_ns"])


def median_duration(ctx: Context, params: Dict[str, Any]) -> Optional[float]:
    """Median device duration of the matching events on the first
    device, in ms."""
    found = _first_device_events(ctx, params)
    if not found:
        return None
    return statistics.median(e["dur_ns"] for e in found) / 1e6


def start_interval(ctx: Context, params: Dict[str, Any]) -> Optional[float]:
    """A percentile of the start-to-start interval between consecutive
    matching events on the first device, in ms."""
    found = _first_device_events(ctx, params)
    if len(found) < 2:
        return None
    steps = [b["start_ns"] - a["start_ns"]
             for a, b in zip(found, found[1:])]
    return trace.percentile(steps, params.get("percentile", 50)) / 1e6


def exposed_share(ctx: Context, params: Dict[str, Any]) -> Optional[float]:
    """% of the window in which a matching event (a collective) ran
    while no other operation ran on that device."""
    def one(plane):
        mine = _matching(ctx, plane, params)
        others = trace.clip(trace.select(
            ctx["events"], plane=plane,
            line=params.get("line", trace.OPS_LINE),
            patterns=params.get("against"),
            exclude=list(params["patterns"])
            + list(params.get("against_exclude", []))), ctx["window"])
        alone = trace.subtract(trace.intervals(mine),
                               trace.intervals(others))
        return 100.0 * trace.total(alone) / _window_ns(ctx)
    if not any(_matching(ctx, p, params)
               for p in trace.device_planes(ctx.get("events") or [])):
        return None
    return _per_device(ctx, one)


def _cost_args(ctx: Context, names: List[str]) -> Dict[str, Any]:
    """Arguments a cost function may ask for, each from its one home."""
    traffic, workload = ctx["traffic"], ctx["workload"]
    chips = int(workload["chips"])
    cp = int(workload.get("launch", {}).get("context_parallel_size", 1))
    available = {
        "config": lambda: ctx["config"],
        "seq_total": lambda: int(traffic["sequence_length"]),
        "seq_local": lambda: int(traffic["sequence_length"]) // cp,
        "live_tokens": lambda: ctx["counters"]["live_tokens_mean"],
        "chips": lambda: chips,
    }
    return {n: available[n]() for n in names}


def roofline_share(ctx: Context, params: Dict[str, Any]) -> Optional[float]:
    """% of a peak: what the matching kernel calls had to do (per call,
    ``cost_function`` of ``cost_module``: a path, by default
    ``benchmarks/lib/costs.py``) over their device time over the peak.
    ``terms`` lists, per kernel pattern, which entry of the cost
    function's result one call is charged and how many trace events
    make up one call."""
    planes = trace.device_planes(ctx.get("events") or [])
    if not planes:
        return None
    cost = modules.cost_function(
        ctx.get("spec"), params["cost_function"], params.get("cost_module"))(
            **_cost_args(ctx, params["cost_args"]))
    peak = float(ctx["peaks"][params["peak"]])

    def one(plane):
        required, busy_ns = 0.0, 0
        for term in params["terms"]:
            found = _matching(ctx, plane, {**params, **term})
            per_call = cost[term["charge"]] if isinstance(cost, dict) \
                else cost
            required += per_call * len(found) / term.get(
                "events_per_call", 1)
            busy_ns += sum(e["dur_ns"] for e in found)
        return (required, busy_ns)

    pairs = [one(p) for p in planes]
    if not any(ns for _, ns in pairs):
        return None
    required = sum(r for r, _ in pairs)
    seconds = sum(ns for _, ns in pairs) / 1e9
    return 100.0 * required / seconds / peak


def record_percentile(ctx: Context, params: Dict[str, Any]) -> Optional[float]:
    """A percentile of one field of the records named, scaled."""
    rows = ctx.get("records", {}).get(params["records"]) or []
    where = params.get("where", {})
    values = [r[params["field"]] for r in rows
              if r.get(params["field"]) is not None
              and all(r.get(k) == v for k, v in where.items())]
    if not values:
        return None
    return trace.percentile(values, params["percentile"]) \
        * params.get("scale", 1.0)


def counter(ctx: Context, params: Dict[str, Any]) -> Optional[float]:
    value = ctx.get("counters", {}).get(params["key"])
    return None if value is None else value * params.get("scale", 1.0)


def client_value(ctx: Context, params: Dict[str, Any]) -> Optional[float]:
    """One number of the run's client view by its ``key`` there (a
    serving run's: ``serve_cell.client_metrics``), scaled: neither a
    span nor a counter, and there with the profiler off."""
    value = (ctx.get("client") or {}).get(params["key"])
    return None if value is None else value * params.get("scale", 1.0)


UNTRACED_KINDS = ("client_value",)

KINDS: Dict[str, Callable[[Context, Dict[str, Any]], Optional[float]]] = {
    "share_of_window": share_of_window,
    "idle_share": idle_share,
    "median_duration": median_duration,
    "start_interval": start_interval,
    "exposed_share": exposed_share,
    "roofline_share": roofline_share,
    "record_percentile": record_percentile,
    "counter": counter,
    "client_value": client_value,
}


def read_metric(ctx: Context, metric: Dict[str, Any]) -> Optional[float]:
    reducer = metric["reducer"]
    return KINDS[reducer["kind"]](ctx, reducer)
