"""Per-step training metrics: tokens/s, MFU, memory — console + history.

Parity with reference scaletorch/trainer/metrics.py:23-114
(log_training_metrics): one line per logging step on the designated
process with loss / LR / grad-norm / tokens-per-second (global and
per-chip) / MFU / device memory. MFU uses the same analytic formula as
the reference (utils/misc.get_mfu) against the peak-FLOPS table. Rates
and MFU are device metrics: off a TPU the records carry the host's
``step_time`` and none of them.

Async-dispatch aware: on non-logging steps nothing is materialised — no
``float(loss)`` host sync, no memory-stats poll — so the host keeps
dispatching ahead of the device (JAX's async dispatch is the TPU
equivalent of the reference's non-blocking CUDA stream timing). Rates are
computed over the window since the previous logged step.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Optional

import jax

from scaletorch_tpu.utils.device import (
    device_memory_stats,
    get_theoretical_flops,
    is_tpu,
)
from scaletorch_tpu.utils.logger import get_logger
from scaletorch_tpu.utils.misc import get_mfu, to_readable_format

# Cumulative resilience counters (DivergenceSentinel.counters / the
# in-step update_skipped flag / the straggler detector) recognised in
# ``extras`` — forwarded into the SystemMonitor ring buffer and surfaced
# on the console line when nonzero.
ANOMALY_COUNTER_KEYS = (
    "anomalies", "nonfinite_losses", "loss_spikes", "rollbacks",
    "update_skipped", "straggler_flags",
)


@dataclass
class MetricsLogger:
    num_params: int
    num_layers: int
    num_heads: int
    head_dim: int
    seq_len: int
    tokens_per_step: int           # global tokens consumed per optimizer step
    num_chips: int = 1
    log_frequency: int = 1
    # peak FLOP/s of one chip; None = look the TPU up in the table, and
    # off a TPU stay None — no tokens/s and no MFU are recorded then
    peak_flops: Optional[float] = None
    collect_system: bool = True   # host CPU/mem + accel env per logged step
    # optional telemetry.TelemetryExporter: every logged record also
    # lands on the JSONL event stream (kind 'train_step') — the durable,
    # machine-readable twin of the console line
    exporter: Optional[object] = None
    history: list = field(default_factory=list)
    _window_start_time: Optional[float] = None
    _window_start_step: Optional[int] = None
    _monitor: Optional[object] = None

    def __post_init__(self) -> None:
        if self.peak_flops is None and is_tpu():
            self.peak_flops = get_theoretical_flops()
        if self.collect_system:
            # reference PerformanceMonitor role (utils/monitor.py:69-162):
            # host CPU/memory/load + power/temp where exposed, sampled on
            # logging steps only so the hot path stays sync-free. psutil
            # is not a hard dependency — degrade to no system telemetry
            # rather than failing every entry point at startup.
            try:
                from scaletorch_tpu.utils.monitor import SystemMonitor

                self._monitor = SystemMonitor()
            except ImportError:
                get_logger().info(
                    "psutil not available: system telemetry disabled"
                )

    def log_step(self, step: int, loss, lr: float, grad_norm,
                 extras: Optional[dict] = None) -> dict:
        """Call every step; materialises/logs only on logging steps.

        ``loss``/``grad_norm``/``extras`` values may be device scalars —
        they are converted (forcing a host sync) only when this step
        actually logs. ``extras`` carries step-specific scalars from the
        train step (e.g. MoE moe_dropped_fraction / moe_load_cv).
        """
        if step % self.log_frequency != 0:
            return {}

        now = time.perf_counter()
        record = {
            "step": step,
            "loss": float(loss),
            "lr": float(lr),
            "grad_norm": float(grad_norm),
        }
        for k, v in (extras or {}).items():
            record[k] = float(v)
        if self._window_start_time is not None:
            elapsed = now - self._window_start_time
            steps_in_window = step - self._window_start_step
            if elapsed > 0 and steps_in_window > 0:
                record["step_time"] = elapsed / steps_in_window
                if self.peak_flops is not None:
                    tok_s = self.tokens_per_step * steps_in_window / elapsed
                    record.update(
                        tokens_per_second=tok_s,
                        tokens_per_second_per_chip=tok_s / self.num_chips,
                        mfu=get_mfu(
                            tok_s,
                            self.num_params,
                            self.num_layers,
                            self.num_heads,
                            self.head_dim,
                            self.seq_len,
                            num_chips=self.num_chips,
                            peak_flops=self.peak_flops,
                        ),
                    )
        # restart the window *after* materialisation so the sync cost isn't
        # attributed to the next window
        self._window_start_time = time.perf_counter()
        self._window_start_step = step

        mem = device_memory_stats()
        if mem["bytes_in_use"]:
            record["memory_gb"] = mem["bytes_in_use"] / 1e9
            record["peak_memory_gb"] = mem["peak_bytes_in_use"] / 1e9
        if self._monitor is not None:
            # reuse the stats fetched above (no second allocator poll) and
            # skip the monitor's device_(peak_)mem_gb aliases of the
            # memory_gb/peak_memory_gb fields already written; resilience
            # counters ride into the monitor's ring buffer so a post-mortem
            # tail shows when anomalies clustered
            sys_rec = self._monitor.sample(
                step, device_stats=mem,
                counters={k: record[k] for k in ANOMALY_COUNTER_KEYS
                          if k in record},
            )
            record.update(
                (k, v) for k, v in sys_rec.items()
                if k not in ("time", "step", "device_mem_gb",
                             "device_peak_mem_gb")
            )
        self.history.append(record)
        if self.exporter is not None:
            self.exporter.emit("train_step", record)

        if jax.process_index() == 0:
            parts = [
                f"step {step:>6}",
                f"loss {record['loss']:.4f}",
                f"lr {record['lr']:.2e}",
                f"gnorm {record['grad_norm']:.3f}",
            ]
            if "tokens_per_second" in record:
                parts += [
                    f"tok/s {to_readable_format(record['tokens_per_second'])}",
                    f"tok/s/chip {to_readable_format(record['tokens_per_second_per_chip'])}",
                    f"MFU {record['mfu']:.1f}%",
                ]
            if "moe_dropped_fraction" in record:
                parts.append(f"drop {record['moe_dropped_fraction']:.2%}")
            if "moe_load_cv" in record:
                parts.append(f"load_cv {record['moe_load_cv']:.2f}")
            if record.get("update_skipped"):
                parts.append("UPDATE-SKIPPED")
            if record.get("anomalies"):
                parts.append(f"anomalies {int(record['anomalies'])}")
            if record.get("straggler_flags"):
                parts.append(
                    f"STRAGGLER host {int(record.get('straggler_host', -1))}")
            if "memory_gb" in record:
                parts.append(f"mem {record['memory_gb']:.1f}GB")
            # the structured twin of the human line: --log_format json
            # (utils/logger.JsonFormatter) emits the record dict as-is
            get_logger().info(" | ".join(parts),
                              extra={"structured_record": record})
        return record

    def ring_buffer(self, last_n: Optional[int] = None) -> list:
        """The SystemMonitor ring buffer's retained records (crash-report
        / post-mortem surface); [] when system telemetry is disabled."""
        if self._monitor is None:
            return []
        return self._monitor.tail(last_n)

    def save_json(self, path: str, extra: Optional[dict] = None) -> str:
        """Dump the full metrics history as JSON (reference
        PerformanceMonitor.save_stats, monitor.py:220-250); ``extra``
        top-level keys ride along (the trainer's per-device reports)."""
        import json
        import os

        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        summary = {}
        rates = [r["tokens_per_second"] for r in self.history
                 if "tokens_per_second" in r]
        if rates:
            summary = {
                "mean_tokens_per_second": sum(rates) / len(rates),
                "mean_mfu": sum(r["mfu"] for r in self.history
                                if "mfu" in r) / len(rates),
            }
        if self._monitor is not None:
            summary = {**summary, **self._monitor.summary()}
        with open(path, "w") as f:
            json.dump(
                {
                    "num_params": self.num_params,
                    "seq_len": self.seq_len,
                    "num_chips": self.num_chips,
                    "peak_flops": self.peak_flops,
                    "summary": summary,
                    "records": self.history,
                    **(extra or {}),
                },
                f,
                indent=1,
            )
        return path
