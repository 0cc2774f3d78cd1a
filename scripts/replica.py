#!/usr/bin/env python
"""One engine replica in its own process — the supervised child.

The fleet half of the serving gateway (docs/serving_gateway.md): each
replica is one ``InferenceEngine`` on an ``EngineWorker`` thread behind
the v:1 replica wire (serving/remote.py) — its OWN process, its own
GIL, its own compile cache, its own failure domain. The parent
(``scripts/serve.py --serve_replica_procs N`` via
``serving.supervisor.ReplicaSupervisor``) spawns it, reads ``READY
port=<n>`` (or ``READY uds=<path>`` with ``--uds``) from stdout, and
talks to it through a ``RemoteEngineWorker``.

Exit-code contract (docs/fault_tolerance.md):

  * 0  — clean drain: SIGTERM/SIGINT or ``POST /v1/drain``; in-flight
         requests finish streaming, then the process leaves. The
         supervisor does NOT restart it.
  * 44 — the serving stall watchdog (ARMED here by default): a wedged
         step loop — a stuck device dispatch, or the ``/v1/hang``
         drill — dumps a crash report and ``os._exit(44)``. The
         supervisor restarts with backoff.
  * anything else (SIGKILL -> -9, import error -> 1, ...) — a crash;
         restarted with backoff, flap-detected if it loops.

Model flags mirror scripts/serve.py (same ``build_model`` /
``build_engine``, same deterministic ``--preset tiny``), so a replica
process and an in-process replica build the bit-identical engine.
"""

from __future__ import annotations

import argparse
import asyncio
import os
import signal
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import serve  # noqa: E402  (scripts/serve.py: build_model/build_engine)


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--preset", default="tiny")
    p.add_argument("--model_name_or_path", default=None)
    p.add_argument("--param_seed", type=int, default=0)
    p.add_argument("--max_slots", type=int, default=4)
    p.add_argument("--max_seq", type=int, default=128)
    p.add_argument("--prefill_len", type=int, default=64)
    p.add_argument("--page_size", type=int, default=16)
    p.add_argument("--replica_id", default="r0")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0,
                   help="0 = ephemeral; the bound port rides the "
                        "READY line.")
    p.add_argument("--uds", default="",
                   help="Bind a unix-domain socket at this path instead "
                        "of TCP; READY then reads 'READY uds=<path>'.")
    p.add_argument("--watchdog_timeout_s", type=float, default=120.0,
                   help="Serving stall watchdog (exit 44); <= 0 "
                        "disarms it.")
    p.add_argument("--crash_report_dir", default="results")
    p.add_argument("--drain_timeout_s", type=float, default=30.0)
    # warm-transfer drills (donor side, fired by ReplicaServer while
    # streaming /warm; env SCALETORCH_TPU_FT_GW_WARM_* wins when present)
    p.add_argument("--ft_gw_warm_donor_crash_at", type=int, default=0,
                   help="SIGKILL this process after streaming the k-th "
                        "warm chunk.")
    p.add_argument("--ft_gw_warm_corrupt_chunk_at", type=int, default=0,
                   help="Flip bytes in the k-th warm chunk after "
                        "checksumming.")
    return p.parse_args(argv)


async def _serve(args, worker) -> None:
    from scaletorch_tpu.inference.resilience import ServingFaultInjector
    from scaletorch_tpu.serving.remote import ReplicaServer

    injector = ServingFaultInjector.from_config(args)
    server = ReplicaServer(
        worker, host=args.host, port=args.port,
        uds=args.uds or None,
        injector=injector if injector.active else None)
    await server.start()
    if args.uds:
        print(f"READY uds={args.uds}", flush=True)
    else:
        print(f"READY port={server.port}", flush=True)
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGTERM, signal.SIGINT):
        loop.add_signal_handler(sig, server.request_drain)
    await server.wait_drain()
    print("draining replica...", flush=True)
    # stop admissions but keep ticking: in-flight submit streams must
    # deliver their terminal `done` events before the loop goes away
    worker.shutdown(drain=True)
    deadline = time.monotonic() + args.drain_timeout_s
    while worker.inflight > 0 and time.monotonic() < deadline:
        await asyncio.sleep(0.02)
    await server.close()


def main(argv=None) -> int:
    args = parse_args(argv)
    from scaletorch_tpu.env import configure_compile_cache

    configure_compile_cache()
    from scaletorch_tpu.inference.resilience import make_serving_watchdog
    from scaletorch_tpu.serving.gateway import EngineWorker

    cfg, params = serve.build_model(args)
    engine = serve.build_engine(args, cfg, params)
    watchdog = None
    if args.watchdog_timeout_s > 0:
        watchdog = make_serving_watchdog(
            engine, args.watchdog_timeout_s,
            crash_report_dir=args.crash_report_dir)
        watchdog.start()
    worker = EngineWorker(engine, replica_id=args.replica_id).start()
    try:
        asyncio.run(_serve(args, worker))
    finally:
        worker.shutdown(drain=True)
        worker.join(timeout=args.drain_timeout_s)
        if watchdog is not None:
            watchdog.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
