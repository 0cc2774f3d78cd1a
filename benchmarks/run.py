#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's data files by the names in ``BENCHMARK.json``, builds
the system under test through its normal entry points, warms the cell's
shapes, checks the system against the plain float32 reference on this
run's seeded weights and inputs (outside the window), measures for
``--seconds``, and prints one JSON object as the last line of stdout.
``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` takes
a profiler trace of a few seconds of the steady window and reports the
per-layer metrics.

No accelerator, fewer chips than the cell asks for, or a device that
``benchmarks/peaks.json`` does not list: non-zero exit, no result line.
``--rehearse`` walks the same control flow on whatever jax finds (the
CPU, at the toy sizes the tests use); it prints the line, names the
device it ran on, and exits 3: a rehearsal is never a measurement.
"""

from __future__ import annotations

import time

PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

REHEARSAL_EXIT = 3
REFUSED_EXIT = 2

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def log(message: str) -> None:
    """One line of the run's log, stamped with the seconds since the
    process started (``runtime_bringup_s`` + ``setup_s`` up to the
    window): where both went is read off these."""
    print(f"[bench {time.monotonic() - PROCESS_START:7.2f}s] {message}",
          flush=True)


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--root", default=None,
                   help="tree that holds BENCHMARK.json and benchmarks/ "
                        "data files (default: this checkout)")
    p.add_argument("--rehearse", action="store_true",
                   help="run the control flow without a TPU; exits 3")
    p.add_argument("--override", action="append", default=[],
                   help="traffic.<key>=<json> or workload.<key>=<json>: "
                        "change one parameter for this run (sweeps and "
                        "debugging; the driver never passes it)")
    return p.parse_args(argv)


def refuse(reason: str) -> int:
    print(f"[bench] refused: {reason}", file=sys.stderr, flush=True)
    return REFUSED_EXIT


def read_per_layer(spec, cell: str, reduce_ctx,
                   untraced_only: bool = False) -> dict:
    """The cell's per-layer metrics, each by its own reader, that found
    something to read: name -> value and unit. ``untraced_only`` keeps
    the readers that need no trace (``reducers.UNTRACED_KINDS``)."""
    from benchmarks.lib import reducers

    found = {}
    for metric in spec.per_layer(cell):
        if untraced_only and \
                metric["reducer"]["kind"] not in reducers.UNTRACED_KINDS:
            continue
        value = reducers.read_metric(reduce_ctx, metric)
        if value is not None:
            found[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return found


def report_per_layer(line, tracer, spec, args, result, counters, config,
                     traffic, workload, peaks) -> None:
    """Fills the result line of a traced run: the cell's per-layer
    metrics, the device's busy and window seconds, and the breakdown.
    Off a TPU the trace has no device plane: the readers of device
    metrics then return nothing."""
    from benchmarks.lib import trace

    events = tracer.events()
    window = (trace.device_window(events)
              if trace.device_planes(events) else None)
    line["metrics"] = read_per_layer(spec, args.workload, {
        "events": events, "window": window, "records": result["records"],
        "counters": counters, "client": result["values"], "config": config,
        "traffic": traffic, "workload": workload, "peaks": peaks or {},
        "spec": spec,
    })
    line["breakdown"] = {"device_ops": [], "idle_gaps": []}
    if window is None:
        line["device"]["window_s"] = tracer.stopped_at - tracer.started_at
        return
    busy = trace.busy_by_device(events, window)
    line["device"]["busy_s"] = sum(
        trace.total(b) for b in busy.values()) / len(busy) / 1e9
    line["device"]["window_s"] = (window[1] - window[0]) / 1e9
    line["breakdown"] = {
        "device_ops": trace.top_operations(events, window),
        "idle_gaps": trace.idle_gaps_by_host_span(
            events, window, workload.get("host_spans", [r".*"])),
    }


def main(argv=None) -> int:
    args = parse_args(argv)
    from benchmarks.lib.spec import Refused, Spec

    spec = Spec(args.root)
    workload = spec.workload(args.workload)
    config = spec.config(workload["config"])
    traffic = spec.traffic(workload["traffic"])
    for item in args.override:
        target, _, value = item.partition("=")
        group, _, key = target.partition(".")
        {"traffic": traffic, "workload": workload}[group][key] = \
            json.loads(value)
        log(f"override {group}.{key} = {value}")
    chips = int(workload["chips"])

    if args.rehearse and "jax" not in sys.modules:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count={chips}"
            ).strip()
    import jax

    from benchmarks.lib import device as device_lib
    from benchmarks.lib.tracing import Tracer

    # a rehearsal leaves no cache behind: CPU programs in the checkout's
    # cache would travel to the chip with the copy and never hit there
    cache_dir = (None if args.rehearse
                 else device_lib.configure_compile_cache(ROOT))
    try:
        devices = jax.devices()
    except RuntimeError as exc:
        return refuse(f"jax found no device: {exc}")
    # ``setup_s`` counts from here: what lies before is the runtime's
    # bring-up (importing jax, opening the chip), none of it the
    # program's, and it is printed apart as ``runtime_bringup_s``
    setup_start = time.monotonic()
    info = device_lib.describe(devices)
    log(f"platform={info['platform']} kind={info['kind']!r} "
        f"count={info['count']} jax={jax.__version__} cache={cache_dir}")
    on_tpu = info["platform"] == "tpu"
    if not on_tpu and not args.rehearse:
        return refuse(f"the benchmark measures on a TPU; jax found "
                      f"{info['platform']!r}")
    if len(devices) < chips:
        return refuse(f"cell {args.workload} needs {chips} chips, jax "
                      f"found {len(devices)}")
    if len(devices) != chips and not args.rehearse:
        return refuse(f"cell {args.workload} runs on exactly {chips} "
                      f"chips, jax found {len(devices)}")
    peaks = None
    if on_tpu:
        try:
            peaks = spec.peaks(info["kind"])
        except KeyError as exc:
            return refuse(str(exc))

    if workload["kind"] == "train":
        from benchmarks.lib import train_cell as runner
    elif workload["kind"] == "serve":
        from benchmarks.lib import serve_cell as runner
    else:
        return refuse(f"unknown cell kind {workload['kind']!r}")

    tracer = Tracer(bool(args.trace))
    ctx = {
        "spec": spec, "workload": workload, "config": config,
        "traffic": traffic, "seed": args.seed, "seconds": args.seconds,
        "tracer": tracer, "setup_start": setup_start,
        "compiles": device_lib.CompileCounter(), "log": log,
        "rehearse": args.rehearse, "root": ROOT,
    }
    try:
        result = runner.run(ctx)
        problems = list(result["problems"])
        for problem in problems:
            log(f"PROBLEM: {problem}")

        values = dict(result["values"], setup_s=result["setup_s"])
        # what a counter reader may name: the runner's counts, its
        # client-side values and the device's memory peak
        counters = dict(result["counters"], **values,
                        memory_peak_bytes=device_lib.memory_peak_bytes(
                            devices))
        out_device = dict(info,
                          memory_peak_bytes=counters["memory_peak_bytes"])
        line = {"correct": not problems,
                "attempted": int(result["attempted"]),
                "failed": int(result["failed"]),
                "metrics": {}, "device": out_device,
                "runtime_bringup_s": setup_start - PROCESS_START}
        if not args.trace:
            for metric in spec.end_to_end(args.workload):
                line["metrics"][metric["name"]] = {
                    "value": values[metric["name"]], "unit": metric["unit"]}
            # the per-layer metrics that need no trace, under a key of
            # their own: an untraced run's ``metrics`` are the
            # end-to-end ones and no other
            line["per_layer_untraced"] = read_per_layer(
                spec, args.workload, {"client": result["values"]},
                untraced_only=True)
            for note in runner.notes(ctx, result, peaks):
                log(note)
        else:
            report_per_layer(line, tracer, spec, args, result, counters,
                             config, traffic, workload, peaks)
        # what was compared, each number beside its limit: last in the line
        line.update(check=result["check"], problems=problems)
    except Refused as exc:
        return refuse(str(exc))
    finally:
        tracer.stop()
        tracer.cleanup()
    print(json.dumps(line), flush=True)
    return 0 if on_tpu else REHEARSAL_EXIT


if __name__ == "__main__":
    sys.exit(main())
