#!/usr/bin/env python
"""Launch the serving gateway over one or more engine replicas.

The production shape is ``--model_name_or_path`` + HF safetensors; the
hermetic shape (CI's gateway-smoke, local development) is ``--preset
tiny``: a deterministic tiny Llama initialized from ``--param_seed`` so
a second process can rebuild the EXACT same model and compare streamed
tokens bit-for-bit (scripts/gateway_smoke.py does).

Prints ``READY port=<port>`` on stdout once the socket is bound.
SIGTERM/SIGINT drain gracefully — in-flight streams finish, queued
requests end ``aborted``, replicas stop at refcount-clean page pools —
and the process exits 0 (the exit-code contract's "clean drain").

Examples
--------
  # tiny deterministic model, paged cache, two replicas:
  JAX_PLATFORMS=cpu python scripts/serve.py --preset tiny \\
      --serve_replicas 2 --serve_port 8000

  # talk to it:
  curl -N -X POST http://127.0.0.1:8000/v1/generate \\
      -d '{"prompt": [1, 2, 3], "max_new_tokens": 8}'
  curl http://127.0.0.1:8000/healthz
  curl http://127.0.0.1:8000/metrics
"""

from __future__ import annotations

import argparse
import asyncio
import os
import signal
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TINY = dict(
    vocab_size=64, hidden_size=32, intermediate_size=64,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
)


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--preset", default="tiny",
                   help="'tiny' (deterministic tiny Llama from "
                        "--param_seed) or a models/presets.py name "
                        "(random init unless --model_name_or_path).")
    p.add_argument("--model_name_or_path", default=None,
                   help="HF checkpoint dir for real weights "
                        "(utils/hf_interop.load_hf_params).")
    p.add_argument("--param_seed", type=int, default=0)
    p.add_argument("--max_slots", type=int, default=4)
    p.add_argument("--max_seq", type=int, default=128)
    p.add_argument("--prefill_len", type=int, default=64)
    # does nothing: the engine has one layout. Kept because
    # benchmarks/lib/serve_cell.py passes it; goes when the harness
    # stops (ROADMAP D14)
    p.add_argument("--cache_layout", default="paged", choices=("paged",))
    p.add_argument("--page_size", type=int, default=16)
    p.add_argument("--disagg", default="",
                   help="Disaggregated prefill/decode serving "
                        "(inference/disagg.py): 'P:D' splits the "
                        "visible devices into a P-device prefill slice "
                        "and a D-device decode slice; 'auto' sizes the "
                        "split from tools/hbm_budget.json's per-phase "
                        "rows. In-process replicas only (not "
                        "--serve_replica_procs).")
    p.add_argument("--serve_host", default="127.0.0.1")
    p.add_argument("--serve_port", type=int, default=8000)
    p.add_argument("--serve_replicas", type=int, default=1)
    p.add_argument("--serve_replica_procs", type=int, default=0,
                   help="> 0: run N replicas as CHILD PROCESSES "
                        "(scripts/replica.py each) behind the replica "
                        "supervisor — independent failure domains with "
                        "auto-restart — instead of --serve_replicas "
                        "in-process worker threads. CPU only so far: "
                        "the children inherit this environment, and a "
                        "TPU chip belongs to one process, so on a chip "
                        "every child after the first fails to acquire "
                        "it. Use --serve_replicas there.")
    p.add_argument("--replica_watchdog_timeout_s", type=float,
                   default=120.0,
                   help="Each replica child's serving stall watchdog "
                        "(exit 44); <= 0 disarms it.")
    p.add_argument("--supervisor_backoff_base_s", type=float, default=0.5)
    p.add_argument("--supervisor_backoff_max_s", type=float, default=30.0)
    p.add_argument("--supervisor_flap_window_s", type=float, default=60.0)
    p.add_argument("--supervisor_flap_max_restarts", type=int, default=5)
    p.add_argument("--serve_tenants", default="",
                   help="'name:weight[:rate[:burst]],...' "
                        "(config.ServingArguments grammar)")
    p.add_argument("--serve_default_weight", type=float, default=1.0)
    p.add_argument("--serve_max_backlog", type=int, default=256)
    p.add_argument("--serve_free_page_watermark", type=float, default=0.05)
    p.add_argument("--serve_default_ttl_s", type=float, default=0.0)
    p.add_argument("--telemetry_dir", default=None,
                   help="Observability root: gateway_metrics/access/"
                        "latency_histograms JSONL (telemetry/export.py "
                        "schema), one shared Chrome trace "
                        "(serve.trace.json — gateway + every replica on "
                        "one timeline, request spans correlated by W3C "
                        "trace id), and SIGUSR1 live snapshots.")
    p.add_argument("--slo_path", default="",
                   help="tools/slo.json-grammar SLO file; /healthz then "
                        "carries a live 'slo' verdict for --slo_preset.")
    p.add_argument("--slo_preset", default="tiny",
                   help="Preset name inside --slo_path (default tiny).")
    # gateway fault drills (ServingFaultInjector.from_config reads the
    # same field names; env SCALETORCH_TPU_FT_GW_* wins when present)
    p.add_argument("--ft_gw_tenant_storm_at", type=int, default=0)
    p.add_argument("--ft_gw_tenant_storm_count", type=int, default=8)
    p.add_argument("--ft_gw_replica_down_at", type=int, default=0)
    p.add_argument("--ft_gw_replica_crash_at", type=int, default=0,
                   help="SIGKILL the replica serving the k-th dispatch "
                        "(process mode; in-process degrades to thread "
                        "death).")
    p.add_argument("--ft_gw_replica_hang_at", type=int, default=0,
                   help="Stall the replica serving the k-th dispatch "
                        "so its watchdog exits 44.")
    p.add_argument("--ft_gw_warm_donor_crash_at", type=int, default=0,
                   help="SIGKILL the warm-transfer donor after it "
                        "streams the k-th /warm chunk (process mode).")
    p.add_argument("--ft_gw_warm_corrupt_chunk_at", type=int, default=0,
                   help="Flip bytes in the k-th /warm chunk after "
                        "checksumming — the recipient must drop it and "
                        "keep the rest.")
    p.add_argument("--serve_replica_uds", default="",
                   help="Directory for per-replica unix-domain sockets: "
                        "process-mode replicas bind <dir>/<rid>.sock "
                        "instead of a TCP port (the warm-transfer wire "
                        "and dispatch both ride the socket).")
    return p.parse_args(argv)


def build_model(args):
    """(cfg, params) — deterministic for a preset + a seed. Every preset
    but 'tiny' goes through the program's one dispatch from launch
    arguments to a model (``models.families.build_model_config``, what
    ``train.py`` and the benchmark harness use), so whatever family that
    knows can be served."""
    import jax
    import jax.numpy as jnp

    if args.preset == "tiny":
        from scaletorch_tpu.models import llama

        cfg = llama.LlamaConfig(dtype=jnp.float32, **TINY)
        params = llama.init_params(jax.random.PRNGKey(args.param_seed), cfg)
        return cfg, params
    from scaletorch_tpu.config import ScaleTorchTPUArguments
    from scaletorch_tpu.models.families import build_model_config, family_of
    from scaletorch_tpu.models.presets import preset

    # serving holds the weights in the compute dtype: the fp32 master
    # copy is a training concern, and decode reads every weight per token
    cfg = build_model_config(ScaleTorchTPUArguments(
        **preset(args.preset), dtype="bfloat16", param_dtype="bfloat16"))
    if args.model_name_or_path:
        from scaletorch_tpu.utils.hf_interop import load_hf_params

        return cfg, load_hf_params(args.model_name_or_path, cfg)
    # the family's initialiser as one program: drawn piece by piece, a
    # 4 GB expert stack is held in float32 beside its bf16 cast and the
    # published sizes do not fit
    init = jax.jit(family_of(cfg).module.init_params, static_argnums=1)
    return cfg, init(jax.random.PRNGKey(args.param_seed), cfg)


def build_engine(args, cfg, params, tracer=None, device=None):
    """One engine; with ``device`` its params, KV pool and jitted steps
    live on that device alone (a one-device mesh) — how N in-process
    replicas take N chips instead of stacking on the first."""
    from scaletorch_tpu.inference import (
        DisaggregatedEngine,
        InferenceEngine,
        SamplingParams,
    )

    kw = dict(
        max_slots=args.max_slots, max_seq=args.max_seq,
        prefill_len=args.prefill_len,
        sampling=SamplingParams(temperature=0.0),
        page_size=args.page_size,
        strict_submit=False,
        tracer=tracer,
    )
    if getattr(args, "disagg", ""):
        from scaletorch_tpu.inference.disagg import parse_disagg_spec

        return DisaggregatedEngine(
            params, cfg, disagg_split=parse_disagg_spec(args.disagg),
            **kw)
    if device is not None:
        import jax
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        mesh = Mesh(np.array([device]), ("tp",))
        params = jax.device_put(
            params, NamedSharding(mesh, PartitionSpec()))
        kw["mesh"] = mesh
    engine = InferenceEngine(params, cfg, **kw)
    if len(engine.prefill_shapes) > 1:
        # every prefill program before the first request: none compiles
        # under traffic. An engine with the one full shape compiles it at
        # its first call, as it did before there was a list
        engine.warm_prefill_shapes()
    return engine


def make_replica_spawner(args):
    """``(replica_id) -> Popen`` launching scripts/replica.py with this
    serve invocation's model/engine flags — the supervisor's spawn_fn.
    stdout is piped (the supervisor reads ``READY port=``), stderr is
    inherited so replica logs land in the parent's stream."""
    import subprocess

    replica_py = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "replica.py")

    def spawn(replica_id: str):
        cmd = [sys.executable, replica_py,
               "--preset", args.preset,
               "--param_seed", str(args.param_seed),
               "--max_slots", str(args.max_slots),
               "--max_seq", str(args.max_seq),
               "--prefill_len", str(args.prefill_len),
               "--page_size", str(args.page_size),
               "--replica_id", replica_id,
               "--port", "0",
               "--watchdog_timeout_s",
               str(args.replica_watchdog_timeout_s)]
        if args.model_name_or_path:
            cmd += ["--model_name_or_path", args.model_name_or_path]
        if args.serve_replica_uds:
            cmd += ["--uds", os.path.join(args.serve_replica_uds,
                                          f"{replica_id}.sock")]
        if args.ft_gw_warm_donor_crash_at:
            cmd += ["--ft_gw_warm_donor_crash_at",
                    str(args.ft_gw_warm_donor_crash_at)]
        if args.ft_gw_warm_corrupt_chunk_at:
            cmd += ["--ft_gw_warm_corrupt_chunk_at",
                    str(args.ft_gw_warm_corrupt_chunk_at)]
        return subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True)

    return spawn


def build_replica_fleet(args, exporter=None):
    """Process mode: spawn ``--serve_replica_procs`` replica children
    under a ``ReplicaSupervisor``, each fronted by a
    ``RemoteEngineWorker``. Returns ``(workers, supervisor)``."""
    from scaletorch_tpu.serving.remote import RemoteEngineWorker
    from scaletorch_tpu.serving.supervisor import ReplicaSupervisor

    if args.serve_replica_uds:
        os.makedirs(args.serve_replica_uds, exist_ok=True)

    def worker_factory(replica_id: str, port, proc):
        # READY gave either a TCP port (int) or a UDS path (str)
        if isinstance(port, str):
            return RemoteEngineWorker(
                "127.0.0.1", 0, replica_id=replica_id, proc=proc,
                uds=port).start()
        return RemoteEngineWorker(
            "127.0.0.1", port, replica_id=replica_id, proc=proc).start()

    supervisor = ReplicaSupervisor(
        make_replica_spawner(args),
        [f"r{i}" for i in range(args.serve_replica_procs)],
        worker_factory=worker_factory,
        backoff_base_s=args.supervisor_backoff_base_s,
        backoff_max_s=args.supervisor_backoff_max_s,
        flap_window_s=args.supervisor_flap_window_s,
        flap_max_restarts=args.supervisor_flap_max_restarts,
        exporter=exporter,
    )
    workers = supervisor.start()
    return workers, supervisor


def build_gateway(args):
    from scaletorch_tpu.inference.resilience import ServingFaultInjector
    from scaletorch_tpu.serving.admission import parse_tenant_spec
    from scaletorch_tpu.serving.gateway import ServingGateway

    # ONE tracer shared by the gateway and every replica engine: the
    # asyncio thread, the EngineWorker threads and the tick loops all
    # write the same Chrome trace, so one Perfetto load shows a request
    # crossing all of them, correlated by trace id. (Process-mode
    # replicas live in other processes — the trace covers the gateway
    # side only there.)
    tracer = None
    exporter = None
    if args.telemetry_dir:
        from scaletorch_tpu.telemetry.export import TelemetryExporter
        from scaletorch_tpu.telemetry.spans import SpanTracer

        tracer = SpanTracer(
            os.path.join(args.telemetry_dir, "serve.trace.json"),
            role="serve")
        exporter = TelemetryExporter(
            os.path.join(args.telemetry_dir, "gateway_events.jsonl"))
    slo_targets = None
    if args.slo_path:
        from scaletorch_tpu.serving.slo import load_slo, preset_targets

        slo_targets = preset_targets(load_slo(args.slo_path),
                                     args.slo_preset)
    supervisor = None
    if args.serve_replica_procs > 0:
        engines, supervisor = build_replica_fleet(args, exporter=exporter)
    else:
        import jax

        cfg, params = build_model(args)
        # replica i on device i (round-robin past the device count);
        # a disaggregated engine splits the devices itself
        devices = [None] if args.disagg else jax.devices()
        engines = {
            f"r{i}": build_engine(args, cfg, params, tracer=tracer,
                                  device=devices[i % len(devices)])
            for i in range(args.serve_replicas)
        }
        del params  # each engine holds its own placed copy
    injector = ServingFaultInjector.from_config(args)
    return ServingGateway(
        engines,
        supervisor=supervisor,
        host=args.serve_host, port=args.serve_port,
        tenants=parse_tenant_spec(args.serve_tenants),
        default_weight=args.serve_default_weight,
        max_backlog=args.serve_max_backlog,
        free_page_watermark=args.serve_free_page_watermark,
        default_ttl_s=args.serve_default_ttl_s,
        injector=injector if injector.active else None,
        exporter=exporter,
        tracer=tracer,
        slo_targets=slo_targets,
    )


def make_snapshotter(args, gateway):
    """SIGUSR1 live snapshots for a RUNNING gateway (the PR 8
    LiveSnapshotter pointed at the serving process): span tail,
    per-replica engine snapshots + histogram state, gateway gauges and
    per-tenant latency histograms — without stopping anything."""
    from scaletorch_tpu.telemetry.profiling import LiveSnapshotter

    def snapshot_fn():
        payload = {
            "gateway": gateway.snapshot(),
            "slo": gateway.slo_status(),
            "tenant_histograms": gateway.hists.to_record(),
            "replicas": {
                rid: {
                    "alive": worker.alive,
                    "metrics": worker.gauges(),
                    # remote workers have no in-process engine: their
                    # histogram state lives in the child; the gauges
                    # above are the polled snapshot
                    "histograms": (
                        worker.engine.metrics.histogram_state()
                        if getattr(worker, "engine", None) is not None
                        else None),
                }
                for rid, worker in gateway.workers.items()
            },
        }
        if gateway.supervisor is not None:
            payload["supervisor"] = gateway.supervisor.status()
        if gateway.tracer is not None:
            payload["span_timeline_tail"] = gateway.tracer.tail(128)
        return payload

    return LiveSnapshotter(args.telemetry_dir, snapshot_fn)


async def _main(args) -> int:
    gateway = build_gateway(args)
    snapshotter = (make_snapshotter(args, gateway)
                   if args.telemetry_dir else None)
    if snapshotter is not None:
        snapshotter.install()
    await gateway.start()
    print(f"READY port={gateway.port}", flush=True)
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGTERM, signal.SIGINT):
        loop.add_signal_handler(sig, stop.set)
    serve = asyncio.ensure_future(gateway.serve_forever())
    await stop.wait()
    print("draining gateway...", flush=True)
    await gateway.stop(drain=True)
    if gateway.supervisor is not None:
        # the drain above already made every replica exit 0 ("drained",
        # never restarted); this reaps the children and the monitor
        await loop.run_in_executor(
            None, lambda: gateway.supervisor.stop(drain=True))
    serve.cancel()
    if snapshotter is not None:
        snapshotter.uninstall()
    if gateway.tracer is not None:
        # terminate the trace file AFTER the replicas drained (their
        # worker threads emit into it until join) so it is valid JSON
        gateway.tracer.close()
    if gateway.exporter is not None:
        gateway.exporter.close()
    return 0


def _configure_disagg_devices(args) -> None:
    """--disagg needs a multi-device platform; on the CPU simulation
    path that is the host-platform device-count XLA flag, which must be
    set BEFORE the first jax import (all jax imports here are lazy —
    the first happens inside build_model). A caller that already
    imported jax configured its own devices; respect that."""
    if not args.disagg or "jax" in sys.modules:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()


def main(argv=None) -> int:
    from scaletorch_tpu.env import configure_compile_cache

    args = parse_args(argv)
    if args.disagg:
        if args.serve_replica_procs > 0:
            raise SystemExit(
                "--disagg runs in-process replicas only; drop "
                "--serve_replica_procs")
        _configure_disagg_devices(args)
    # after the disagg device flags: this imports jax, they must precede it
    configure_compile_cache()
    return asyncio.run(_main(args))


if __name__ == "__main__":
    sys.exit(main())
