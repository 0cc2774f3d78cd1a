"""What the run is on, what it compiled, how much memory it took."""

from __future__ import annotations

import os
from typing import Any, Dict, List

CACHE_DIR_NAME = ".jax_cache"


def configure_compile_cache(root: str) -> str:
    """jax's persistent compile cache at ``JAX_COMPILATION_CACHE_DIR``
    where that is set (nothing is set in code then), else at the fixed
    ``<checkout>/.jax_cache`` (the directory the program's own entry
    points use, so the two agree). Every program is cached, however
    quick its compile: a run pays set-up in every later check."""
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not placed:
        placed = os.path.join(root, CACHE_DIR_NAME)
        jax.config.update("jax_compilation_cache_dir", placed)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return placed


class CompileCounter:
    """Totals of jax's compile and cache monitoring events, for the
    whole process; ``snapshot()`` before and after the window shows
    whether anything compiled inside it."""

    def __init__(self) -> None:
        import jax

        self.backend_compiles = 0
        self.backend_compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event: str, **_: Any) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def _on_duration(self, event: str, seconds: float, **_: Any) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend_compiles += 1
            self.backend_compile_s += seconds

    def snapshot(self) -> Dict[str, float]:
        return {"backend_compiles": self.backend_compiles,
                "backend_compile_s": self.backend_compile_s,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}


def memory_peak_bytes(devices: List[Any]) -> int:
    """Peak on the fullest chip: ``peak_bytes_in_use`` (arrays) plus
    ``peak_bytes_reserved`` (a program's scratch), which on this runtime
    is what a step really held (PERF.md section 7). Where the backend
    reports no reserved peak the in-use peak stands alone."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


def describe(devices: List[Any]) -> Dict[str, Any]:
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}
