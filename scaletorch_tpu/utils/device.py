"""TPU device abstraction: kind probing, peak-FLOPS table, memory stats.

TPU-native counterpart of the reference's device layer
(scaletorch/utils/device.py:24-298). The reference multiplexes over
cuda/npu/mlu/musa vendor plugins; on JAX there is one backend API, so this
module keeps only the parts with behavioural weight: the **peak bf16 FLOPS
table** used for MFU accounting (reference device.py:214-231), the
"is there a chip" gate every measurement path goes through, and live
device memory statistics (reference memory_* helpers).
"""

from __future__ import annotations

from typing import Any, Optional

import jax

# Peak dense bf16 FLOP/s per chip, keyed by ``jax.Device.device_kind``
# exactly as jax reports it (both spellings jax's own
# pallas/mosaic tpu_info table lists for a generation). Source: Google
# Cloud TPU documentation, system architecture page of each generation
# ("TPU v4": 275, "TPU v5e": 197, "TPU v5p": 459, "TPU v6e": 918
# TFLOP/s bf16 per chip). A kind that is not here is an error, never a
# default: an MFU against a made-up peak is worse than no MFU.
PEAK_BF16_FLOPS: dict[str, float] = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


class NoTpuError(RuntimeError):
    """A measurement or chip-only path was asked to run without a TPU."""


def is_tpu() -> bool:
    """True when jax's default backend is a TPU."""
    return jax.default_backend() == "tpu"


def require_tpu(what: str) -> None:
    """Refuse to run ``what`` anywhere but on a TPU. Timings, rates and
    utilizations mean something only on the chip; a path that produces
    them fails here instead of falling back to the CPU."""
    if not is_tpu():
        raise NoTpuError(
            f"{what} needs a TPU: jax found platform "
            f"{jax.default_backend()!r} "
            f"({jax.devices()[0].device_kind}); it does not run on a "
            "fallback device"
        )


def get_device_kind(device: Optional[jax.Device] = None) -> str:
    device = device or jax.local_devices()[0]
    return device.device_kind


def get_theoretical_flops(device: Optional[jax.Device] = None) -> float:
    """Peak dense bf16 FLOP/s of one chip, from ``PEAK_BF16_FLOPS``.
    Raises ``ValueError`` for a ``device_kind`` the table does not list."""
    kind = get_device_kind(device)
    try:
        return PEAK_BF16_FLOPS[kind]
    except KeyError:
        raise ValueError(
            f"no peak FLOP/s on record for device_kind {kind!r}; add it "
            "to scaletorch_tpu.utils.device.PEAK_BF16_FLOPS with its "
            "source before reporting an MFU on it"
        ) from None


def device_memory_stats(device: Optional[jax.Device] = None) -> dict[str, float]:
    """Live per-device memory statistics in bytes.

    Maps the reference's memory_allocated/reserved/max_memory_* helpers onto
    jax.Device.memory_stats() (TPU backends report bytes_in_use /
    peak_bytes_in_use / bytes_limit; CPU returns {}).
    """
    device = device or jax.local_devices()[0]
    stats = device.memory_stats() or {}
    out = {
        "bytes_in_use": float(stats.get("bytes_in_use", 0)),
        "peak_bytes_in_use": float(stats.get("peak_bytes_in_use", 0)),
        "bytes_limit": float(stats.get("bytes_limit", 0)),
    }
    # allocator extras some backends export (consumed by utils/monitor.py
    # for the fragmentation stat); absent keys stay absent — optional
    for k in ("largest_free_block_bytes", "bytes_reservable_limit",
              "num_allocs", "peak_pool_bytes", "bytes_reserved",
              "peak_bytes_reserved"):
        if k in stats:
            out[k] = float(stats[k])
    return out


def device_report(devices=None, arrays: Any = None) -> list[dict]:
    """Per device (default: this process's): what it is, what its
    allocator holds, and — with ``arrays``, a pytree of jax Arrays —
    ``resident_bytes``, the bytes of their shards that live on it. A
    device whose ``bytes_in_use`` exceeds its ``resident_bytes`` of the
    training state by more than a batch holds something it should not
    (e.g. an unsharded init copy). ``peak_bytes_reserved`` is reported
    beside ``peak_bytes_in_use``: on the v5e runtime the in-use peak
    tracks array buffers only, and a step's scratch shows up as a
    reservation. Memory figures are 0 where the backend reports none
    (CPU)."""
    devices = list(devices) if devices is not None else jax.local_devices()
    resident = {d.id: 0 for d in devices}
    for leaf in jax.tree_util.tree_leaves(arrays):
        for shard in leaf.addressable_shards:
            if shard.device.id in resident:
                resident[shard.device.id] += shard.data.nbytes
    report = []
    for d in devices:
        stats = device_memory_stats(d)
        report.append({
            "id": d.id,
            "platform": d.platform,
            "kind": d.device_kind,
            "bytes_in_use": int(stats["bytes_in_use"]),
            "peak_bytes_in_use": int(stats["peak_bytes_in_use"]),
            "peak_bytes_reserved": int(stats.get("peak_bytes_reserved", 0)),
            "bytes_limit": int(stats["bytes_limit"]),
            **({"resident_bytes": resident[d.id]}
               if arrays is not None else {}),
        })
    return report


def bf16_supported() -> bool:
    """bf16 is native on every TPU generation and on CPU via XLA."""
    return True
