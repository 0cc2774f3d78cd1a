"""The profiler around the window, and the benchmark's own host spans.

With ``--trace 0`` every method is a no-op, so the measured path is the
same code either way. Spans are ``jax.profiler.TraceAnnotation``s: they
land in the profiler's own trace, on the device events' clock, which is
how an idle gap gets the name of what the host was doing.
"""

from __future__ import annotations

import contextlib
import shutil
import tempfile
import time
from typing import List, Optional

from benchmarks.lib import trace


class Tracer:
    def __init__(self, enabled: bool) -> None:
        self.enabled = enabled
        self.log_dir: Optional[str] = None
        self.started_at: Optional[float] = None
        self.stopped_at: Optional[float] = None

    def start(self) -> None:
        if not self.enabled:
            return
        import jax

        self.log_dir = tempfile.mkdtemp(prefix="bench_trace_")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0   # no per-call python events
        options.host_tracer_level = 2     # TraceAnnotation spans
        jax.profiler.start_trace(self.log_dir, profiler_options=options)
        self.started_at = time.monotonic()

    def stop(self) -> None:
        if not self.enabled or self.log_dir is None \
                or self.stopped_at is not None:
            return
        import jax

        self.stopped_at = time.monotonic()
        jax.profiler.stop_trace()

    def span(self, name: str):
        if not self.enabled:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def step(self, name: str, number: int):
        if not self.enabled:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.StepTraceAnnotation(name, step_num=number)

    def events(self) -> List[trace.Event]:
        if not self.enabled or self.log_dir is None:
            return []
        return trace.load_xplane(trace.find_xplane(self.log_dir))

    def cleanup(self) -> None:
        if self.log_dir is None:
            return
        shutil.rmtree(self.log_dir, ignore_errors=True)
        self.log_dir = None
