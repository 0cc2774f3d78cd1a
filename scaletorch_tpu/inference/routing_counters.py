"""Routing counters of an MoE model's paged engine steps.

The jitted steps of a model that routes tokens to experts
(``decode.make_paged_*_step(..., routing_counts=True)``) take one more
argument, a small int32 accumulator, and return it with this call's
counts added. ``CountedStep`` hides that from the engine: it is called
like the plain step and gives its four results; the accumulator rides
from call to call on the device and is read only when someone asks
(``EngineMetrics.snapshot()``), or once every ``READ_EVERY`` calls to
keep the wrapping uint32 sums exact; what is read is always the result
of a step that has ended, so no reader waits for the device.

Counters (cumulative; "rows" are (token, choice) assignments of tokens
that exist: inactive slots and padding positions count nowhere):

    moe_routed_assignments   rows computed, over prefill and decode,
                             summed over the MoE layers
    moe_dropped_assignments  rows routed and not computed (dropless: 0)
    moe_expert_visits        decode steps only: experts with at least
                             one row, summed over steps and layers
    moe_peak_load_rows       prefill calls only: the fullest expert's
                             rows, summed over calls and layers
    moe_prefill_assignments  the prefill calls' share of the first
    moe_assignments_elsewhere  rows whose expert another share of the
                             layer holds (``qwen3_moe.ExpertShare``):
                             no work here and not dropped; 0 where every
                             expert is held

``snapshot`` adds ``moe_assignments_held`` (the first counter under the
name that pairs with the last: under uniform routing their ratio is
held : absent experts, and it is the first thing to look at when a
step of a share is slow or fast) and the gauge ``moe_experts_held``.
"""

from __future__ import annotations

import threading
from typing import Callable, Dict

import numpy as np

ROUTING_COUNTERS = (
    "moe_routed_assignments", "moe_dropped_assignments",
    "moe_expert_visits", "moe_peak_load_rows", "moe_prefill_assignments",
    "moe_assignments_elsewhere")

# the accumulator is uint32 and wraps; the host adds the change since
# its last reading modulo 2**32, which is exact while fewer than 2**32
# rows are counted between two readings. A prefill call of 16 x 1024
# live rows x 8 choices x 16 layers adds 2.1e6: 256 calls stay far under
READ_EVERY = 256


def step_counts(counts: Dict[str, "jax.Array"], *, prefill: bool):
    """One call's counts (``qwen3_moe.forward_cached(...,
    return_routing=True)``) in ``ROUTING_COUNTERS`` order."""
    import jax.numpy as jnp

    zero = jnp.zeros((), jnp.int32)
    return jnp.stack([
        counts["routed"], counts["dropped"],
        zero if prefill else counts["expert_visits"],
        counts["peak_load_rows"] if prefill else zero,
        counts["routed"] if prefill else zero,
        counts["elsewhere"]]).astype(jnp.uint32)


class RoutingCounters:
    """Host totals plus the accumulators that are on the device now:
    ``accumulator``, the result of the newest call, ``_previous`` and
    ``_older``, the two before it, and ``_settled``, three calls back,
    whose step has ended: the engine's decode loop runs one step ahead,
    across an admission too (step n unread, the prefill call behind it,
    step n+1 behind the call), so what it has read before it dispatches
    is the call three back at the latest (the two between may still be
    on the device). A reader never waits for a step
    in flight: the gateway reads the engine's snapshot on its event
    loop for every request it routes, and a wait there stalls every
    open stream.

    ``sharding``: where the steps' other operands are committed (the
    engine's mesh, replicated), so that the first call's accumulator is
    placed like every later one and the steps compile once."""

    def __init__(self, num_experts: int, moe_layers: int,
                 sharding=None) -> None:
        import jax

        self.num_experts, self.moe_layers = num_experts, moe_layers
        self._totals = np.zeros(len(ROUTING_COUNTERS), np.int64)
        self._read = np.zeros(len(ROUTING_COUNTERS), np.uint32)
        self._read_call = 0     # the call whose accumulator was read last
        self._lock = threading.Lock()
        self._calls = 0
        zeros = np.zeros(len(ROUTING_COUNTERS), np.uint32)
        self.accumulator = self._previous = self._older = self._settled = (
            jax.device_put(zeros, sharding) if sharding is not None
            else jax.numpy.asarray(zeros))

    def _read_device(self, accumulator, call: int) -> None:
        """Add what the accumulator that call number ``call`` returned
        holds over the last one read; an accumulator OLDER than that one
        is left alone (``snapshot`` reads the newest that is ready, the
        periodic read of ``run`` the one three calls back: read after a
        newer one, its step back added 2**32 for good, one window in
        eleven on the v5e: PERF.md, PR 51)."""
        if call <= self._read_call:
            return
        now = np.asarray(accumulator, np.uint32)
        self._totals += (now - self._read).astype(np.int64)  # mod 2**32
        self._read, self._read_call = now, call

    def run(self, step: Callable, args) -> tuple:
        """One call of a counting step: the accumulator goes in last
        and its result takes its place (engine thread)."""
        with self._lock:
            self._settled, self._older, self._previous = (
                self._older, self._previous, self.accumulator)
            *results, self.accumulator = step(*args, self._previous)
            self._calls += 1
            if self._calls % READ_EVERY == 0:
                self._read_device(self._settled, self._calls - 3)
        return tuple(results)

    def snapshot(self, decode_steps: int) -> Dict[str, float]:
        """The cumulative counters and the per-step means they give:
        experts touched per decode step and layer, and the fullest
        expert's load over the mean load in the prefill calls. Counts
        every call that has ended: the newest three as well once their
        results are ready, as they are whenever the engine is idle."""
        with self._lock:
            self._read_device(*next(
                ((acc, self._calls - back) for back, acc in enumerate(
                    (self.accumulator, self._previous, self._older))
                 if acc.is_ready()), (self._settled, self._calls - 3)))
            totals = dict(zip(ROUTING_COUNTERS, map(int, self._totals)))
        steps = decode_steps * self.moe_layers
        mean_load = totals["moe_prefill_assignments"] / self.num_experts
        return dict(
            totals,
            moe_assignments_held=totals["moe_routed_assignments"],
            moe_experts_held=self.num_experts,
            moe_experts_touched_per_step=(
                totals["moe_expert_visits"] / steps if steps else 0.0),
            moe_peak_over_mean_load=(
                totals["moe_peak_load_rows"] / mean_load
                if mean_load else 0.0))


class CountedStep:
    """A jitted paged step built with ``routing_counts=True``, called
    like the plain one: four results; attributes (``_cache_size``,
    ``lower``) are the jitted function's."""

    def __init__(self, step: Callable, counters: RoutingCounters) -> None:
        self._step, self._counters = step, counters

    def __call__(self, *args):
        return self._counters.run(self._step, args)

    def uncounted(self, *args):
        """The same call with its counts thrown away (the accumulator
        goes in and is not taken back): a warm-up's rows are no tokens,
        and the expert block reckons one row where a call has none."""
        return self._step(*args, self._counters.accumulator)[:-1]

    def __getattr__(self, item):
        return getattr(self._step, item)
