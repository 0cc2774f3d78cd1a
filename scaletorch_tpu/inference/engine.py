"""Continuous-batching inference engine over a fixed-slot batch.

The serving loop the ROADMAP's "heavy traffic" story needs, shaped for
TPU execution discipline:

  * a FIXED number of slots (the decode batch) and a FIXED maximum
    sequence length — every device buffer keeps its shape for the whole
    engine lifetime, so the decode step (inference/decode.py) compiles
    exactly once and the prefill step once a shape of
    ``prefill_shapes``, a short list fixed at construction;
  * per-slot lengths and stop state live on the HOST; between decode
    steps the engine admits queued requests into freed slots by writing
    a row of the prompt buffer each and flipping its ``write_mask``
    bit — data changes, nothing retraces. Where the cache is addressed
    by page the prefill call takes the shape of its admission: the
    listed ``(rows, length)`` of fewest positions that holds the
    admitted prompts' tails, so one short prompt does not pay for
    ``max_slots x prefill_len`` positions (``warm_prefill_shapes`` runs
    every listed shape once, before the first request: a server
    compiles nothing under traffic). Where the cache keeps state by
    slot and a prefill row NAMES its slot (the delta-rule families:
    ``decode.rows_name_slots``) the one program is one row, an
    admitted prompt a call; where a row IS its slot (jamba, the window
    rings) the one call runs every slot;
  * the decode loop runs ONE STEP AHEAD of the host: with step n on the
    device a tick dispatches step n+1, fed n's sampled tokens as the
    device array they are, and only then reads n back and emits it —
    the device never waits for the host between two steps, and the
    copy-back, the emit loop, the serving thread's inbox and the next
    dispatch all run beside a step. Lengths are known ahead, so a slot
    that ends by ``max_new_tokens`` / ``max_seq`` is switched off
    exactly; an ``eos``, a non-finite row, a cancel or a TTL is learnt
    one step late and costs one discarded row
    (``decode_slot_steps_discarded``), matched to the request that was
    bound to the slot when the step was dispatched, never to the slot's
    next tenant. The run-ahead crosses an admission: a tick that admits
    dispatches the prefill call behind the step in flight and the next
    step behind the call, the admitted slots' first tokens fed as the
    device array the call returns, before it reads anything back
    (``InferenceEngine.step``);
  * K/V lives in ONE layout: a global page pool + per-slot page tables
    (kv_cache.PagedKVCache). Admission is page-budget-aware (HBM scales
    with tokens cached, not B x S_max), a radix tree shares
    page-aligned prompt prefixes across requests (refcounted,
    copy-on-write at the page boundary), and the steps touch the pool
    through the table (the Mosaic pair of ops/pallas/paged_attention.py
    on TPU: ``paged_write`` in place and the decode kernel, the pool
    carried whole through the layer loop; the lax scatter + gather
    elsewhere; the snapshot's ``paged_pool_in_place`` says which). The
    tables are data, so the one-compile discipline survives admissions,
    prefix hits, quarantine page-clears, and frees;
  * the pool is donated through every step (written in place); with a
    mesh it is head-sharded over ``tp`` via the same axis the training
    params use (paged_kv_cache_specs), and the steps run GSPMD;
  * the weights lie where the step programs read them: when an engine
    is built, its decode step is compiled once with every parameter's
    layout left to the compiler, each leaf the program reads in another
    order of dimensions is stored once in that order as a new array
    (``decode.place_params``; the caller's arrays are not donated and
    stay as they were), and ``engine.params`` is the placed tree, which
    both jitted steps are built to read (``params_relaid_leaves`` /
    ``params_relaid_bytes`` in the snapshot; 0 / 0 on a CPU). Such a
    leaf that the decode program reads only one static layer at a time
    is stored as its layers, each a program parameter of its own
    (``params_layered_leaves`` / ``params_layered_bytes``). A new
    family needs nothing for it.

Serving-grade fault tolerance (inference/resilience.py) rides the same
discipline: every submitted request ends in exactly one terminal
``outcome`` (ok / timeout / shed / rejected / quarantined / aborted),
admission is bounded (``queue_capacity`` sheds oldest-first), per-request
TTL deadlines are checked at admission and every decode step, a slot
whose logits go non-finite is quarantined (cache lines mask-cleared, the
other slots keep serving, nothing retraces), and ``drain()`` stops
admissions and finishes the in-flight work — wired to the training
stack's ``PreemptionHandler`` for SIGTERM and to ``HangWatchdog`` via
``make_serving_watchdog`` for stalled steps.

Metrics ride the existing plumbing: ``EngineMetrics`` keeps the
counters/gauges (tokens/s, time-to-first-token, queue depth, slot
occupancy, per-outcome counters, deadline-miss/quarantine rates) and can
sample them into a ``SystemMonitor`` ring buffer (utils/monitor.py) so a
serving process's tail is diagnosable exactly like a training run's.
"""

from __future__ import annotations

import contextlib
import itertools
import time
from collections import deque
from dataclasses import dataclass, field
from typing import (
    Any,
    Callable,
    Dict,
    List,
    NamedTuple,
    Optional,
    Sequence,
    Tuple,
)

import jax
import jax.numpy as jnp
import numpy as np
from jax._src.array import ArrayImpl  # see _tokens_operand
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from scaletorch_tpu.inference.decode import (
    abstract,
    chosen_orders,
    compile_decode_for_layouts,
    counts_routing,
    load_orders,
    make_fill_slots_step,
    make_paged_decode_step,
    make_paged_prefill_step,
    orders_key,
    place_params,
    prefill_shapes,
    rows_name_slots,
    store_orders,
)
from scaletorch_tpu.inference.routing_counters import (
    ROUTING_COUNTERS,
    CountedStep,
    RoutingCounters,
)
from scaletorch_tpu.inference.kv_cache import (
    PageAllocator,
    RadixPrefixCache,
    TRASH_PAGE,
    carries_state,
    ceil_div,
    init_paged_kv_cache,
    kv_cache_bytes,
    latent_cache_bytes,
    no_prefix_reason,
    paged_kv_cache_shardings,
    recurrent_state_bytes,
    window_cache_bytes,
    window_of,
    window_ring_pages,
)
from scaletorch_tpu.inference.resilience import (
    TERMINAL_OUTCOMES,
    EngineDraining,
    ServingFaultInjector,
)
from scaletorch_tpu.inference.sampling import SamplingParams
from scaletorch_tpu.ops.pallas.paged_attention import (
    chained_first_blocks,
    in_place_pair,
)
from scaletorch_tpu.telemetry.histogram import LogHistogram
from scaletorch_tpu.telemetry.spans import (
    collection_counters,
    observe_collections,
    span,
)
from scaletorch_tpu.utils.logger import get_logger

logger = get_logger(__name__)

# The engine's three phase clocks. Every instant of the engine thread is
# booked to exactly one of them: STALL while a tick sweeps deadlines,
# admits and prefills (every stream already resident stands still for
# someone else's admission), DEVICE_WAIT while the host is blocked on
# the decode program's result, HOST for everything else: the rest of
# the tick and the time between ticks.
STALL, DEVICE_WAIT, HOST = 0, 1, 2
_PHASE_CLOCK = {
    "engine.tick.sweep": STALL,
    "engine.tick.admit": STALL,
    "engine.tick.prefill": STALL,
    "engine.tick.prefill_wait": STALL,
    "engine.tick.decode_wait": DEVICE_WAIT,
}
# A tick that took longer than this, the time since the previous tick
# ended included, is reported with its phases. A chat tick with a
# prefill is 0.85 s on a v5e; the stalls this is for were 7 and 13 s.
SLOW_TICK_S = 2.0


def _host_key(seed: int) -> np.ndarray:
    """``jax.random.PRNGKey(seed)`` of the threefry generator as two
    uint32 words, computed on the host. The jax call is a program on
    the device and a copy back, so it waits for whatever the device is
    running: an admission behind a step in flight stood still for the
    rest of that step (6.9 ms of ``engine.tick.admit`` on a v5e, and
    the step's tokens came that much late). The seed is taken as jax
    takes a Python int: wrapped to 32 bits unless ``jax_enable_x64``
    (tests/inference/test_token_release.py holds the equality)."""
    high = (seed >> 32) & 0xFFFFFFFF if jax.config.jax_enable_x64 else 0
    return np.array([high, seed & 0xFFFFFFFF], np.uint32)


@dataclass
class Request:
    """One generation request. ``eos_id`` stops the slot early;
    ``max_new_tokens`` always bounds it; the engine's ``max_seq`` caps
    prompt + generation regardless. ``deadline`` (absolute monotonic
    time, or None) retires the request with ``timeout`` wherever it is
    — queued or mid-decode — once passed. ``trace_id`` is the W3C
    trace-context id the gateway threaded in (None = untraced): it
    keys the request's lifecycle spans on the tracer's async track.
    ``admit_time`` is stamped when the request enters a slot —
    ``queue_wait_s`` on the result derives from it."""

    request_id: int
    prompt: List[int]
    max_new_tokens: int = 64
    eos_id: Optional[int] = None
    seed: int = 0
    submit_time: float = field(default_factory=time.monotonic)
    deadline: Optional[float] = None
    trace_id: Optional[str] = None
    admit_time: Optional[float] = None


@dataclass
class RequestResult:
    """The single terminal record of a request. ``outcome`` is one of
    ``TERMINAL_OUTCOMES``; ``finish_reason`` refines an ``ok`` outcome
    ('eos' | 'length' | 'max_seq') and repeats the outcome otherwise.
    Non-ok outcomes carry whatever tokens were generated before the
    fault (``tokens``) plus a human-readable ``detail``."""

    request_id: int
    prompt: List[int]
    tokens: List[int]               # generated tokens (prompt excluded)
    finish_reason: str              # 'eos' | 'length' | 'max_seq' | outcome
    outcome: str = "ok"             # one of TERMINAL_OUTCOMES
    detail: Optional[str] = None    # non-ok outcomes: what happened
    ttft_s: Optional[float] = None  # submit -> first generated token
    latency_s: Optional[float] = None
    # request-scoped latency attribution (additive; the gateway's
    # access records and per-tenant histograms read these):
    queue_wait_s: Optional[float] = None   # submit -> slot admission
    prefill_s: Optional[float] = None      # its admission's prefill wall
    # first token -> retirement (the last token of an ``ok`` request),
    # split by the engine's phase clocks; the three sum to that span.
    # None for a request that never emitted a token from a slot.
    stall_s: Optional[float] = None        # others' sweeps/admissions
    device_wait_s: Optional[float] = None  # host blocked on decode
    host_s: Optional[float] = None         # the rest, gaps between ticks too
    prefix_hit: bool = False               # radix prefix pages shared
    trace_id: Optional[str] = None


@dataclass
class EngineMetrics:
    """Serving health counters/gauges. ``snapshot()`` is flat numeric —
    ready for a MetricsLogger line or a SystemMonitor ring-buffer record
    (``monitor.sample(counters=metrics.snapshot())``) — and lands in
    serving crash reports via ``make_serving_watchdog``. The per-outcome
    counters satisfy the conservation invariant
    ``requests_submitted == sum(requests_<outcome>)`` once the engine
    is drained."""

    requests_submitted: int = 0
    requests_completed: int = 0     # ok outcomes only
    requests_admitted: int = 0      # entered a slot (prefilled)
    tokens_generated: int = 0
    prefill_calls: int = 0
    # ... of them, dispatched with a decode step still on the device
    # (behind it, the step not read first): all but the cold starts
    prefill_calls_behind_flight: int = 0
    # ... of them, with no admitted row after a prefix in the pool:
    # every prompt attended to itself in key blocks, no layer read the
    # pool (``PagedKVIO``'s ``prefix_hit``)
    prefill_calls_self_attended: int = 0
    # rows x length of the shape each prefill call ran at, and of them
    # the admitted prompts' tail tokens: run / admitted is what a call
    # pads (1 is none)
    prefill_positions_run: int = 0
    prefill_positions_admitted: int = 0
    decode_steps: int = 0           # decode steps dispatched
    # slots the paged-decode kernel walked (a slot with a live page at
    # the position its step was given; an inactive slot is given 0 and
    # is walked too), summed over the kernel's calls, and of them the
    # slots whose first block the slot before had already started
    # (``paged_attention.chained_first_blocks`` of each dispatched
    # step's positions x the layers that call the kernel; 0 where the
    # lax pair or the latent kernel reads the pool)
    paged_slot_walks: int = 0
    paged_slot_walks_chained: int = 0
    # ... of them, dispatched while the step before, or the prefill
    # call before, had not been read back (the decode loop running one
    # step ahead, across an admission too), and slot-steps
    # computed for a request that had ended by the time they were read
    # (an eos, a non-finite row of a step or of a prefill call, a cancel
    # or a TTL is learnt one step late; an end by length never is)
    decode_steps_ahead: int = 0
    decode_slot_steps_discarded: int = 0
    # tokens handed to ``on_tokens`` at the readback of the step or the
    # prefill call that made them, and tokens that waited for the next
    # dispatch or their request's result (an engine whose dispatch
    # blocks for its step): the two sum to ``tokens_generated`` while a
    # hook is attached
    tokens_handed_at_readback: int = 0
    tokens_handed_later: int = 0
    slow_ticks: int = 0             # ticks over SLOW_TICK_S (gap included)
    queue_depth: int = 0
    active_slots: int = 0
    num_slots: int = 0
    # page-pool gauges/counters: pool
    # occupancy plus the radix prefix-cache's yield — an admission whose
    # prompt head was already cached is a ``prefix_hit`` and its shared
    # tokens (never re-prefilled) accumulate in ``prefill_tokens_saved``
    pages_in_use: int = 0
    page_pool_free: int = 0
    prefix_hits: int = 0
    prefill_tokens_saved: int = 0
    # warm-rejoin accounting: ``prefix_pages`` gauges the radix tree's
    # registered page count (the donor-selection signal the gateway
    # ranks peers by); ``warm_pages_total`` counts pages this engine
    # imported from peers since boot
    prefix_pages: int = 0
    warm_pages_total: int = 0
    # which pair the two paged step programs were built with: 1 = the
    # Mosaic pair (``paged_write`` in place + the decode kernel at a
    # layer index; a TPU whose head_dim the kernels serve), 0 = the lax
    # scatter + gather
    paged_pool_in_place: int = 0
    # what the compiler asked to be moved when the engine was built:
    # the parameter leaves stored in the order of dimensions the decode
    # program chose (``decode.place_params``), and their bytes; 0 / 0
    # where the program reads them as they lie (every CPU)
    params_relaid_leaves: int = 0
    params_relaid_bytes: int = 0
    # those of them that are stacks the decode program reads ONLY one
    # static layer at a time (an unrolled forward's ``a[index]``),
    # stored as their layers and not as one stack, out of which the
    # program copied each layer every step; 0 / 0 where every stack is
    # scanned
    params_layered_leaves: int = 0
    params_layered_bytes: int = 0
    outcomes: Dict[str, int] = field(
        default_factory=lambda: {o: 0 for o in TERMINAL_OUTCOMES})
    # request-scoped latency distributions (telemetry/histogram.py):
    # one log-bucketed histogram per metric, fed on the host paths that
    # already exist (no device sync). ``snapshot()`` stays flat
    # numeric; readers wanting distributions use ``histogram_state()``
    # (live snapshots, replica aggregation).
    hist: Dict[str, LogHistogram] = field(default_factory=lambda: {
        name: LogHistogram()
        for name in ("ttft", "tpot", "queue_wait", "prefill", "e2e")})
    # an MoE model's routing counters (inference/routing_counters.py):
    # on the device between snapshots, read when one is taken
    routing: Optional[RoutingCounters] = None
    # a model with state-carrying layers (kv_cache.HybridCache): the
    # bytes of its slot-indexed buffers (0: no such model, and none of
    # these three is in the snapshot), slots a prefill call started from
    # an empty state, and decode slot-steps dispatched on a slot whose
    # state was last started by another request (0 or a fault)
    recurrent_state_bytes: int = 0
    recurrent_state_resets: int = 0
    recurrent_state_owner_mismatches: int = 0
    # a model with window-attention layers (kv_cache.WindowCache): the
    # bytes of its rings (0: no such model, and none of these six is
    # in the snapshot); times a slot's write position passed its ring's
    # end (a prompt longer than the ring has at admission); keys one
    # window layer and one full layer attended, summed over the decode
    # slot-steps dispatched (from the positions: min(p + 1, window) and
    # p + 1); decode slot-steps dispatched on a slot whose ring was
    # last started by another request (0 or a fault); pages of admitted
    # prompts' window-layer K/V that the prefill call computed and wrote
    # to TRASH because a ring keeps a prompt's newest ``ring_pages``
    # (from the prompt's length: ``max(ceil(len / page) - ring_pages,
    # 0)`` a window layer, summed over the window layers: writes that
    # nothing reads, counted so that taking them out has a number)
    window_cache_bytes: int = 0
    window_ring_wraps: int = 0
    window_keys_attended: int = 0
    full_keys_attended: int = 0
    window_slot_reuse_mismatches: int = 0
    window_pages_trashed: int = 0
    # a model with latent attention (kv_cache.LatentCache): the bytes of
    # its pool of latent rows (0: no such model, and neither is in the
    # snapshot) and the cached rows one layer's decode kernel walked,
    # summed over the decode slot-steps dispatched (from the positions:
    # p + 1)
    latent_cache_bytes: int = 0
    latent_keys_attended: int = 0
    # a model with Mamba-2 layers (a config with ``mamba_chunk_size``:
    # a state matrix a head): how many it has (0: no such model, and
    # none of these is in the snapshot); slot x layer states a decode
    # step advanced, summed over the steps dispatched (live slots x
    # layers: x the bytes of one slot's state of one layer, what the
    # updates had to read and write); chunks of ``mamba_chunk_size``
    # rows a prompt's scan ran, summed over the prefill calls (rows x
    # ceil(length / chunk) of the shape each call took x layers: a
    # padded row's chunks are run too); and ``full_keys_attended``
    # above, p + 1 a live slot and step, for its attention layers
    ssd_layers: int = 0
    ssd_state_slot_updates: int = 0
    ssd_prefill_chunks: int = 0

    def record_ttft(self, ttft_s: float) -> None:
        self.hist["ttft"].observe(ttft_s)

    def histogram_state(self) -> Dict[str, Dict]:
        """Sparse JSON form of every latency histogram (the
        ``latency_histograms`` JSONL record shape, unlabeled)."""
        return {name: h.to_dict() for name, h in self.hist.items()
                if h.count}

    def record_outcome(self, outcome: str) -> None:
        self.outcomes[outcome] += 1
        if outcome == "ok":
            self.requests_completed += 1

    def snapshot(self) -> Dict[str, float]:
        terminal = sum(self.outcomes.values())
        snap = {
            "requests_submitted": self.requests_submitted,
            "requests_completed": self.requests_completed,
            "tokens_generated": self.tokens_generated,
            "prefill_calls": self.prefill_calls,
            "prefill_calls_behind_flight": self.prefill_calls_behind_flight,
            "prefill_calls_self_attended": self.prefill_calls_self_attended,
            "prefill_positions_run": self.prefill_positions_run,
            "prefill_positions_admitted": self.prefill_positions_admitted,
            "decode_steps": self.decode_steps,
            "decode_steps_ahead": self.decode_steps_ahead,
            "decode_slot_steps_discarded": self.decode_slot_steps_discarded,
            "tokens_handed_at_readback": self.tokens_handed_at_readback,
            "tokens_handed_later": self.tokens_handed_later,
            "paged_slot_walks": self.paged_slot_walks,
            "paged_slot_walks_chained": self.paged_slot_walks_chained,
            "slow_ticks": self.slow_ticks,
            "queue_depth": self.queue_depth,
            "num_slots": self.num_slots,
            "slot_occupancy": (
                self.active_slots / self.num_slots if self.num_slots else 0.0
            ),
            "deadline_miss_rate": (
                self.outcomes["timeout"] / terminal if terminal else 0.0
            ),
            "quarantine_rate": (
                self.outcomes["quarantined"] / terminal if terminal else 0.0
            ),
            "pages_in_use": self.pages_in_use,
            "page_pool_free": self.page_pool_free,
            "prefix_hit_rate": (
                self.prefix_hits / self.requests_admitted
                if self.requests_admitted else 0.0
            ),
            "prefill_tokens_saved": self.prefill_tokens_saved,
            "prefix_pages": self.prefix_pages,
            "warm_pages_total": self.warm_pages_total,
            "paged_pool_in_place": self.paged_pool_in_place,
            "params_relaid_leaves": self.params_relaid_leaves,
            "params_relaid_bytes": self.params_relaid_bytes,
            "params_layered_leaves": self.params_layered_leaves,
            "params_layered_bytes": self.params_layered_bytes,
        }
        for outcome, count in self.outcomes.items():
            snap[f"requests_{outcome}"] = count
        if self.routing is not None:
            snap.update(self.routing.snapshot(self.decode_steps))
        if self.recurrent_state_bytes:
            snap["recurrent_state_bytes"] = self.recurrent_state_bytes
            snap["recurrent_state_resets"] = self.recurrent_state_resets
            snap["recurrent_state_owner_mismatches"] = (
                self.recurrent_state_owner_mismatches)
        if self.window_cache_bytes:
            for name in ("window_cache_bytes", "window_ring_wraps",
                         "window_keys_attended", "full_keys_attended",
                         "window_slot_reuse_mismatches",
                         "window_pages_trashed"):
                snap[name] = getattr(self, name)
        if self.latent_cache_bytes:
            snap["latent_cache_bytes"] = self.latent_cache_bytes
            snap["latent_keys_attended"] = self.latent_keys_attended
        if self.ssd_layers:
            for name in ("ssd_state_slot_updates", "ssd_prefill_chunks",
                         "full_keys_attended"):
                snap[name] = getattr(self, name)
        # what the interpreter's collector has cost this process
        # (``host_gc_*``: telemetry/spans.py, ``CollectionObserver``)
        snap.update(collection_counters())
        return snap


class _Slot:
    """Host-side state of one decode slot."""

    __slots__ = ("request", "tokens", "position", "generated",
                 "first_token_t", "last_token_t", "prefill_s", "prefix_hit",
                 "clocks_at_first")

    def __init__(self) -> None:
        self.request: Optional[Request] = None
        self.tokens: List[int] = []
        self.position = 0        # absolute position of the NEXT token to feed
        self.generated = 0
        self.first_token_t: Optional[float] = None
        self.last_token_t: Optional[float] = None  # TPOT inter-arrival
        self.prefill_s: Optional[float] = None     # its admission's prefill
        self.prefix_hit = False                    # radix pages shared
        # the engine's phase clocks when the first token was emitted
        self.clocks_at_first: Optional[Tuple[float, float, float]] = None

    @property
    def active(self) -> bool:
        return self.request is not None


class _InFlight(NamedTuple):
    """A decode step on the device whose result the host has not read:
    its number (1-based, over the engine's life), its sampled tokens
    and finite mask as the device arrays they are, the positions it
    fed, and the request each of its slots ran for: a result belongs
    to that request, never to the slot's next tenant. ``stall`` is an
    injected slow decode, slept where the host waits for this step."""

    number: int
    nxt: Any
    finite: Any
    positions: np.ndarray
    bound: List[Tuple[int, Request]]
    stall: float


class _Admission(NamedTuple):
    """The prefill calls of one tick's admission, on the device, their
    results not read by the host (one call; one an admitted prompt
    where a row names its slot): each call's first tokens ``[rows]``
    and finite mask as the device arrays they are, the admitted slots
    with the request each was bound to, the (call, row) each slot took,
    and when the first call was dispatched (``prefill_s`` runs from
    there to the readback)."""

    first: List[Any]
    finite: List[Any]
    bound: List[Tuple[int, Request]]
    row_of: Dict[int, Tuple[int, int]]
    dispatched_t: float


class _Phase:
    """One phase of a tick: a span (telemetry/spans.py) whose two ends
    are also boundaries of the engine's phase clocks. Phases follow one
    another inside ``engine.tick``; they do not nest."""

    __slots__ = ("_engine", "_name", "_t0", "_span")

    def __init__(self, engine: "InferenceEngine", name: str) -> None:
        self._engine = engine
        self._name = name

    def __enter__(self) -> None:
        engine = self._engine
        self._t0 = time.monotonic()
        engine._advance(self._t0)
        engine._open_clock = _PHASE_CLOCK.get(self._name, HOST)
        self._span = span(self._name, engine.tracer)
        self._span.__enter__()

    def __exit__(self, *exc) -> None:
        self._span.__exit__(*exc)
        engine = self._engine
        now = time.monotonic()
        engine._advance(now)
        engine._open_clock = HOST
        seconds = engine._tick_phase_s
        seconds[self._name] = seconds.get(self._name, 0.0) + now - self._t0


class InferenceEngine:
    """KV-cache decode with continuous batching.

    Parameters
    ----------
    params, cfg : the model tree and its config (any Llama-family or
        GPT-MoE config; ``resolve_forward_cached`` picks the forward).
        For sharded serving pass params already placed with their
        NamedShardings (utils/hf_interop.load_hf_params(shardings=...)
        feeds this directly). ``engine.params`` is that tree as the
        decode program reads it: a leaf the compiler asks for in
        another order of dimensions is a new array, TRANSPOSED into
        that order, or a tuple of such arrays, one a layer (only the
        engine's own steps read such a tree),
        every other leaf is the caller's own array, and nothing of the
        caller's is donated or changed (``_param_orders``).
    max_slots : decode batch size B (fixed).
    max_seq : prompt + generation cap per slot (S_max).
    prefill_len : static prompt-buffer length P_max (default
        ``max_seq``); prompts longer than this are rejected. With
        ``max_slots`` it is the largest of ``prefill_shapes``, the
        ``(rows, length)`` a prefill call may take.
    sampling : engine-wide sampling knobs (static, baked into the
        compiled steps).
    page_size : tokens per page. The cache is a global pool of
        fixed-size pages [L,n_pages,Hkv,page_size,D] plus per-slot page
        tables, and admission is PAGE-BUDGET-aware: a request is
        admitted when the pool can cover ``min(prompt + max_new_tokens,
        max_seq)`` tokens of pages (minus any radix prefix hit), not
        when a slot index frees up — HBM scales with tokens actually
        cached, and concurrency with the pool, not with ``B × S_max``.
    num_pages : pool size including the reserved TRASH page. None sizes
        the pool every slot can fill to ``max_seq`` (``max_slots *
        ceil(max_seq / page_size) + 1``); smaller pools trade
        concurrency for HBM.
    prefix_cache : keep a radix tree over page-aligned token prefixes
        so a request whose prompt head is already cached shares those
        pages (refcounted, copy-on-write at the page boundary) and
        prefills only its tail. A model with state-carrying layers
        (``kv_cache.carries_state``) is served without it whatever this
        says: a shared page holds K/V for its tokens, and nothing holds
        the recurrent state after them (no snapshots at page
        boundaries), so the export / import of prefix pages refuses
        such a model by name. So is a model with window-attention
        layers (``kv_cache.window_of``): their K/V lives by slot in a
        ring that holds a suffix. ``kv_cache.no_prefix_reason`` is the
        one place that says which models these are.
    mesh / tp_axis : optional — shard the pool's KV heads over
        ``tp_axis`` of the mesh (the page axis stays unsharded: pages
        are not slot-aligned).
    monitor : optional SystemMonitor; ``step()`` samples the metrics
        snapshot into its ring buffer every ``monitor_every`` steps.
    tracer : optional ``telemetry.SpanTracer``. A tick is cut into the
        ``engine.tick.*`` phases (docs/observability.md) as profiler
        annotations whether or not a tracer is attached; with one, each
        phase is a Chrome trace event too. The same boundaries feed the
        always-on phase clocks behind ``RequestResult.stall_s`` /
        ``device_wait_s`` / ``host_s`` and the ``slow_tick`` report.
    exporter : optional ``telemetry.TelemetryExporter``; metrics
        snapshots ride the same schema-versioned JSONL stream the
        trainer's step records use (kind ``engine_metrics``) on the
        ``monitor_every`` cadence and at drain/run exit — durable
        serving metrics, not just the in-memory ring buffer.
    queue_capacity : bounded admission — with more than this many
        requests queued, the OLDEST queued request is shed (terminal
        outcome ``shed``). 0 (default) keeps the queue unbounded.
    default_ttl_s : deadline applied to requests submitted without an
        explicit ``ttl_s`` (0 = no deadline). Expired requests end as
        ``timeout``, queued or mid-decode.
    strict_submit : True (default) preserves raise-on-invalid
        ``submit()``; False converts validation failures into a
        structured ``rejected`` terminal result so one malformed
        request cannot kill a server loop.
    forward_fn : optional override of the model's cache-aware forward
        (tests use it to simulate content-dependent poison requests).
    injector : optional ``ServingFaultInjector`` driving hermetic
        fault drills (NaN logits, slow decode, submit/deadline storms).
    preemption : optional ``resilience.PreemptionHandler``; ``run()``
        polls it each tick and responds to SIGTERM by draining.
    watchdog : optional ``HangWatchdog`` (see ``make_serving_watchdog``);
        ``step()`` beats it so a stalled tick fires the serving
        crash-report path.
    on_tokens : optional ``(slot, request_id, token_ids, emitted_t)``
        callback invoked from ``step()`` with each slot's newly sampled
        tokens — PUSH, not poll, so a streaming bridge
        (serving/gateway.py) never waits on terminal results to forward
        tokens. ``emitted_t`` is the ``time.monotonic()`` the engine
        read when the step's tokens were back on the host (one reading
        a step, the one the TPOT histogram is fed from): the first
        stamp of the delivery leg, which the consumer carries on. A
        step's tokens (or a prefill call's first tokens) are handed
        over at its readback, all of them and before any slot's
        retirement is booked: the decode loop runs one step ahead, so
        the device has the next step to run while the consumers work
        (an engine whose dispatch blocks for its step,
        ``_DISPATCH_BLOCKS``, hands them over after its next dispatch
        instead); a request that reaches its
        terminal result has its tokens handed over first. Host-side
        only: the hook sees tokens after the device->host transfer the
        engine already performs, so attaching it adds zero retraces
        (``decode_compile_count`` stays 1). Concatenating every ``token_ids`` delivered for a request
        reproduces its final ``RequestResult.tokens`` bit-exactly. A
        raising hook is logged and disarmed, never fatal to serving.

    ``on_dispatched`` (an attribute, None by default) is called with no
    argument on the ticking thread each time a decode step has been put
    on the device: the place for host work that should run beside a
    step (the serving bridge delivers the last tick's terminal results
    there).
    ``on_handed_over`` (the same kind of attribute) is called after a
    readback's tokens went to ``on_tokens``: the place to let their
    consumers run (the serving bridge gives the interpreter to its
    event loop there). An engine whose dispatch blocks never calls it.
    """

    # Whether ``_dispatch`` returns only when the step it dispatched has
    # run. Here it returns at once and the loop runs one step ahead, so
    # a readback always has a step (or nothing left to run) behind it
    # and its tokens are handed over there. An engine that blocks in its
    # dispatch (DisaggregatedEngine) reads a step with the device idle:
    # it keeps the tokens for its next dispatch, which lets go of the
    # interpreter for a whole step (``_emit``, ``_dispatch``).
    _DISPATCH_BLOCKS = False

    def __init__(
        self,
        params: Any,
        cfg: Any,
        *,
        max_slots: int = 4,
        max_seq: int = 512,
        prefill_len: Optional[int] = None,
        sampling: SamplingParams = SamplingParams(),
        cache_dtype: Any = None,
        page_size: int = 16,
        num_pages: Optional[int] = None,
        prefix_cache: bool = True,
        mesh: Any = None,
        tp_axis: str = "tp",
        donate_cache: Optional[bool] = None,
        monitor: Any = None,
        monitor_every: int = 16,
        tracer: Any = None,
        exporter: Any = None,
        queue_capacity: int = 0,
        default_ttl_s: float = 0.0,
        strict_submit: bool = True,
        forward_fn: Optional[Callable] = None,
        injector: Optional[ServingFaultInjector] = None,
        preemption: Any = None,
        watchdog: Any = None,
        on_tokens: Optional[
            Callable[[int, int, List[int], float], None]] = None,
    ) -> None:
        if max_slots < 1:
            raise ValueError(f"max_slots must be >= 1, got {max_slots}")
        if max_seq < 2:
            raise ValueError(f"max_seq must be >= 2, got {max_seq}")
        if queue_capacity < 0:
            raise ValueError(
                f"queue_capacity must be >= 0 (0 = unbounded), "
                f"got {queue_capacity}"
            )
        if default_ttl_s < 0:
            raise ValueError(
                f"default_ttl_s must be >= 0 (0 = no deadline), "
                f"got {default_ttl_s}"
            )
        self.params = params
        self.cfg = cfg
        self.max_slots = max_slots
        self.max_seq = max_seq
        self.prefill_len = prefill_len or max_seq
        if self.prefill_len > max_seq:
            raise ValueError(
                f"prefill_len {self.prefill_len} exceeds max_seq {max_seq}"
            )
        self.sampling = sampling
        self.monitor = monitor
        self.monitor_every = monitor_every
        self.tracer = tracer
        # the process's collections are timed from the first engine on,
        # and a full one is a ``host.gc.full`` span beside the tick's
        observe_collections(tracer)
        self.exporter = exporter
        self.queue_capacity = queue_capacity
        self.default_ttl_s = default_ttl_s
        self.strict_submit = strict_submit
        self.injector = injector
        self.preemption = preemption
        self.watchdog = watchdog
        self.on_tokens = on_tokens
        # (slot, request_id, token_ids, emitted_t) emitted and not yet
        # handed to on_tokens: see _release_tokens
        self._held_tokens: List[Tuple[int, int, List[int], float]] = []
        self.on_dispatched: Optional[Callable[[], None]] = None
        self.on_handed_over: Optional[Callable[[], None]] = None

        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self.page_size = page_size
        self._pages_per_slot = ceil_div(max_seq, page_size)
        if num_pages is None:
            num_pages = max_slots * self._pages_per_slot + 1
        self.num_pages = num_pages

        sharding = replicated = None
        if mesh is not None:
            sharding = paged_kv_cache_shardings(mesh, tp_axis=tp_axis)
            replicated = NamedSharding(mesh, PartitionSpec())
        # where the decode step's ``tokens`` operand goes when the pool
        # spans several devices (None: one device, ``_tokens_operand``)
        self._token_home = (
            replicated if mesh is not None and mesh.size > 1 else None)
        # state-carrying layers: a recurrent state per slot beside the
        # pool; window layers: a ring of pages per slot beside it.
        # Neither shares a prefix (class docstring)
        self._stateful = carries_state(cfg)
        self._window = window_of(cfg)
        self._by_slot = self._stateful or self._window is not None
        # whether a prefill row names its slot: a call's rows are then
        # its admitted prompts (else, by slot, a row IS its slot)
        self._rows_name_slots = rows_name_slots(cfg)
        # tokens one slot's ring holds in a window layer
        self._ring_tokens = (
            0 if self._window is None
            else page_size * window_ring_pages(self._window, page_size))
        prefix_cache = prefix_cache and no_prefix_reason(cfg) is None
        self.cache = init_paged_kv_cache(
            cfg, num_pages, page_size, dtype=cache_dtype, sharding=sharding,
            slots=max_slots)
        self.allocator = PageAllocator(num_pages)
        self.radix = (
            RadixPrefixCache(
                page_size, self.allocator.retain, self.allocator.release,
                self.allocator.refcount,
            ) if prefix_cache else None
        )
        # the request whose prefill last started each slot's state (or
        # its window layers' rings)
        self._state_owner: List[Optional[int]] = [None] * max_slots
        # per-slot page table (host copy; reaches the device as data
        # every step), the pages each slot holds a reference on
        # (shared prefix pages first, own pages after), and how many
        # leading table entries are FROZEN — shared or
        # radix-registered, so exempt from quarantine clears/pokes
        self._tables = np.full(
            (max_slots, self._pages_per_slot), TRASH_PAGE, np.int32)
        # device copy of the tables, re-uploaded only after a host
        # write (admission/retire) — the decode hot loop reads it
        # every tick and must not pay a H2D transfer per token
        self._tables_dev = None
        self._slot_pages: List[List[int]] = [[] for _ in range(max_slots)]
        self._slot_frozen = [0] * max_slots
        logger.info(
            "inference engine: %d slots over %d pages x %d tokens, "
            "pool %.1f MiB%s%s",
            max_slots, num_pages, page_size,
            kv_cache_bytes(cfg, num_pages, page_size,
                           dtype=cache_dtype) / 2**20,
            (f" + {recurrent_state_bytes(self.cache) / 2**20:.1f} MiB of "
             "recurrent state by slot" if self._stateful else "")
            + (f" + {window_cache_bytes(self.cache) / 2**20:.1f} MiB of "
               "window layers' rings by slot"
               if self._window is not None else "")
            + (", prefix cache on" if prefix_cache else ""),
            f", sharded over {mesh.axis_names}" if mesh is not None
            else "",
        )

        # a model that routes tokens to experts: its steps count what
        # they route (inference/routing_counters.py)
        routing = None
        counts = forward_fn is None and counts_routing(cfg)
        steps = dict(page_size=page_size, seq_limit=max_seq,
                     forward_fn=forward_fn, donate_cache=donate_cache,
                     routing_counts=counts)
        # the (rows, length) a prefill call may take, fewest positions
        # first. Where state, convolution tail or rings are indexed by
        # the row itself the cache keeps the one call over every slot;
        # where a row names its slot, ONE row: a second program is 3-6 s
        # of trace and lowering in such a family, and ``setup_s`` pays
        # for every listed shape (PERF.md, PRs 43 and 53)
        self.prefill_shapes = (
            ((1, self.prefill_len),) if self._rows_name_slots
            else ((max_slots, self.prefill_len),) if self._by_slot
            else prefill_shapes(max_slots, self.prefill_len))
        self._decode = make_paged_decode_step(cfg, sampling, **steps)
        orders = self._param_orders(params, steps)
        self.params, moved = place_params(params, orders)
        if orders is not None:
            self._decode = make_paged_decode_step(
                cfg, sampling, param_orders=orders, **steps)
        self._prefill = make_paged_prefill_step(
            cfg, sampling, param_orders=orders, **steps)
        logger.info(
            "inference engine: params_relaid_leaves %d, "
            "params_relaid_bytes %d (%.1f MiB): what the decode program "
            "reads in another order of dimensions, stored so once; "
            "params_layered_leaves %d, params_layered_bytes %d of them "
            "stacks it reads a static layer at a time, stored as their "
            "layers",
            moved["params_relaid_leaves"], moved["params_relaid_bytes"],
            moved["params_relaid_bytes"] / 2**20,
            moved["params_layered_leaves"], moved["params_layered_bytes"])
        if counts:
            routing = RoutingCounters(
                cfg.num_experts, len(cfg.sparse_layer_ids()),
                sharding=replicated)
            self._prefill = CountedStep(self._prefill, routing)
            self._decode = CountedStep(self._decode, routing)
        self._fill_slots = make_fill_slots_step(donate_cache=donate_cache)
        # the decode step's ``tokens`` with a prefill call's first
        # tokens written over the admitted slots' entries, on the device
        # (``_merge_tokens``); a row that has no step to run carries
        # ``max_slots`` and is dropped
        self._merge = jax.jit(
            lambda tokens, first, slot_of_row:
            tokens.at[slot_of_row].set(first, mode="drop"),
            out_shardings=self._token_home)
        for rows in {rows for rows, _ in self.prefill_shapes}:
            # a few ms a row count, before the first request: nothing
            # compiles when the first admission goes behind a step
            self._merge_tokens(
                np.zeros(max_slots, np.int32), np.zeros(rows, np.int32),
                np.full(rows, max_slots, np.int32))

        self._slots = [_Slot() for _ in range(max_slots)]
        # the decode step that is on the device, its result not yet
        # read: the loop runs one step ahead (``step()``)
        self._in_flight: Optional[_InFlight] = None
        self._queue: deque[Request] = deque()
        # the queued request the pool could not cover when it was last
        # at the head of the line: no admission is due for it until a
        # slot retires (pages come back from nowhere else)
        self._page_starved: Optional[int] = None
        self._results: Dict[int, RequestResult] = {}
        self._finished_tick: List[RequestResult] = []
        self._ids = itertools.count()
        self._base_keys = np.zeros((max_slots, 2), np.uint32)
        self._base_keys_dev = None
        self._draining = False
        self.metrics = EngineMetrics(
            num_slots=max_slots, routing=routing,
            paged_pool_in_place=int(in_place_pair(self.cache.k.shape[-1])),
            **moved,
            recurrent_state_bytes=recurrent_state_bytes(self.cache),
            window_cache_bytes=window_cache_bytes(self.cache),
            latent_cache_bytes=latent_cache_bytes(self.cache),
            ssd_layers=(cfg.num_mamba_layers
                        if hasattr(cfg, "mamba_chunk_size") else 0))
        # calls of the paged-decode kernel in one decode step: a layer
        # of the pool and a ring layer each make one (none where the lax
        # pair reads the pool, or the latent kernel a latent one)
        self._kernel_calls_a_step = (
            0 if self.metrics.latent_cache_bytes
            or not self.metrics.paged_pool_in_place
            else self.cache.k.shape[0] + (
                self.cache.wk.shape[0] if self._window is not None else 0))
        # phase clocks: cumulative seconds [STALL, DEVICE_WAIT, HOST],
        # the clock that is open, the last boundary; this tick's seconds
        # by phase name; when the previous tick ended, and whether it
        # left work behind (only then is the time until the next tick
        # part of that tick: an idle engine is not a slow one)
        self._clocks = [0.0, 0.0, 0.0]
        self._open_clock = HOST
        self._clock_t = time.monotonic()
        self._tick_phase_s: Dict[str, float] = {}
        self._tick_end_t = self._clock_t
        self._tick_left_work = False
        self._update_page_gauges()
        # progress fingerprint of the last JSONL export: an idle engine
        # polled at a cadence multiple (or a drain() straight after
        # run()) must not append duplicate records — but any outcome
        # movement (e.g. a queued request timing out on an idle tick)
        # still must
        self._exported_key = self._export_key()

    def _param_orders(self, params, steps: Dict[str, Any]):
        """The order of dimensions the compiler chooses for each leaf of
        ``params`` in the decode program at this engine's shapes
        (``decode.chosen_orders``; None: as they lie; ``steps`` is what
        the step builders are called with). Asking is a
        trace, a lowering and a compile of the decode step, 2-9 s on a
        v5e's host that no compile cache shortens, so the answer is
        kept beside the compile cache under a name made of what the
        program is built from (``decode.orders_key``) and the next
        process starts from it: set-up then costs what it did when the
        weights were taken as they came. A ``forward_fn`` is code this
        package cannot name, so its answer is not kept."""
        slots = self.max_slots

        def operand(*shape, dtype=jnp.int32):
            return jax.ShapeDtypeStruct(shape, dtype)

        operands = (
            operand(slots), operand(slots), operand(slots, dtype=jnp.bool_),
            operand(slots, self._pages_per_slot), abstract(self.cache),
            operand(slots, 2, dtype=jnp.uint32))
        if steps["routing_counts"]:
            operands += (operand(len(ROUTING_COUNTERS), dtype=jnp.uint32),)
        key = None if steps["forward_fn"] is not None else orders_key(
            self.cfg, self.sampling, sorted(steps.items()),
            jax.tree.map(lambda x: x.format, params), operands)
        found, orders = load_orders(key, params)
        if not found:
            orders = chosen_orders(params, *compile_decode_for_layouts(
                self._decode, params, operands,
                donate_cache=steps["donate_cache"]))
            store_orders(key, orders)
        return orders

    def _update_page_gauges(self) -> None:
        self.metrics.pages_in_use = self.allocator.used_count
        self.metrics.page_pool_free = self.allocator.free_count
        self.metrics.prefix_pages = (
            len(self.radix) if self.radix is not None else 0)

    def _tables_device(self):
        """The page tables as a device array, uploaded once per host
        mutation rather than once per decode tick."""
        if self._tables_dev is None:
            self._tables_dev = jnp.asarray(self._tables)
        return self._tables_dev

    def _base_keys_device(self):
        """The slots' sampling keys as a device array, uploaded once
        per admission (``_bind_slot``), like the page tables."""
        if self._base_keys_dev is None:
            self._base_keys_dev = jnp.asarray(self._base_keys)
        return self._base_keys_dev

    def _tokens_operand(self, tokens):
        """The decode step's ``tokens`` operand, from the host (numpy)
        or from the step before (its sampled tokens, on the device), in
        ONE form: a step fed either way is then one call signature
        (``decode_compile_count``) of one compiled program.

        On one device that form is what a host-built operand is to
        jit, an array on the device that is not committed to it: the
        program the steps then run is the one every other caller of
        ``_decode`` with host-built operands runs (a serving check
        before the first request, say), and nothing compiles when the
        first step is fed on the device. A jitted step's results are
        committed whenever its parameters are, and jax has no public
        way to take that mark off without a copy through the host, so
        the same buffer is wrapped again (``ArrayImpl``, internal to
        the one jax this repo runs on; tests/inference/test_run_ahead.py
        holds what is relied on). Over several devices the operand is
        committed, replicated over the pool's mesh, both ways."""
        if self._token_home is not None:
            return (tokens if isinstance(tokens, jax.Array)
                    else jax.device_put(tokens, self._token_home))
        device = next(iter(self.cache.k.sharding.device_set))
        if not isinstance(tokens, jax.Array):
            tokens = jax.device_put(tokens, device)
        return ArrayImpl(tokens.aval, SingleDeviceSharding(device),
                         tokens._arrays, committed=False, _skip_checks=True)

    def _merge_tokens(self, tokens, first, slot_of_row: np.ndarray):
        """``tokens`` (a step's sampled tokens on the device, or the
        host's) with ``first[row]`` (a prefill call's first tokens, on
        the device) at ``slot_of_row[row]``, without either leaving the
        device. Both go in in the form ``_tokens_operand`` gives, so
        that the one little program a row count compiles (at
        construction) serves every admission; ``_dispatch`` gives the
        result that form in turn, as it does any step's tokens."""
        with self.on_device():
            return self._merge(
                self._tokens_operand(tokens), self._tokens_operand(first),
                slot_of_row)

    def _request_pages(self, prompt_len: int, max_new_tokens: int) -> int:
        """Worst-case pages a request reserves: every position it can
        write — prompt plus generation, capped by ``max_seq`` (the
        engine retires at the cap before feeding past it)."""
        total = min(prompt_len + max_new_tokens, self.max_seq)
        return ceil_div(total, self.page_size)

    def _phase(self, name: str) -> _Phase:
        """One ``engine.tick.*`` phase: a span and two clock boundaries
        (spans time HOST work, never a device sync — the
        telemetry/spans.py contract)."""
        return _Phase(self, name)

    def _advance(self, now: float) -> None:
        """Book the time since the last boundary to the open clock.
        Called at every phase boundary and wherever a request reads the
        clocks (first token, retirement), which may be mid-phase."""
        self._clocks[self._open_clock] += now - self._clock_t
        self._clock_t = now

    def _req_event(self, ph: str, req: Request, name: str, **args) -> None:
        """Request-scoped async span event (``ph`` in 'b'/'e'/'n') on
        the request's trace_id track — one branch when untraced or the
        tracer is off. The lifecycle vocabulary (req.queued /
        req.admitted / req.prefill / req.decode / req.finalize) shares
        the tick loop's phase names, so one Perfetto load correlates a
        request's track with the per-thread phase spans by eye AND by
        trace_id."""
        if self.tracer is None or req.trace_id is None:
            return
        self.tracer.async_event(ph, name, req.trace_id, **args)

    def _export_key(self):
        """Progress fingerprint for JSONL export dedup (counters only —
        snapshot() itself has wall-clock-derived rates that differ on
        every call)."""
        return (
            self.metrics.decode_steps,
            self.metrics.requests_submitted,
            tuple(sorted(self.metrics.outcomes.items())),
        )

    def _export_snapshot(self) -> None:
        """Append a metrics record to the JSONL stream iff progress was
        made since the last export."""
        key = self._export_key()
        if key == self._exported_key:
            return
        self._exported_key = key
        self.exporter.emit("engine_metrics", self.metrics.snapshot())

    # ---- compile accounting (the no-retrace contract) --------------------
    @property
    def decode_compile_count(self) -> int:
        return self._decode._cache_size()

    @property
    def prefill_compile_count(self) -> int:
        return self._prefill._cache_size()

    @property
    def draining(self) -> bool:
        return self._draining

    # ---- placement --------------------------------------------------------
    @property
    def devices(self) -> List[Any]:
        """The devices the KV cache lives on, by id — the engine's
        placement: the jitted steps follow their committed operands."""
        return sorted(self.cache.k.sharding.device_set, key=lambda d: d.id)

    def on_device(self):
        """Context for the thread that ticks a ONE-device engine: the
        host-built step inputs (``jnp.asarray``) land on that device
        directly instead of on ``jax.devices()[0]`` and then hopping."""
        devices = self.devices
        if len(devices) != 1:
            return contextlib.nullcontext()
        return jax.default_device(devices[0])

    def device_report(self) -> List[Dict[str, Any]]:
        """utils/device.device_report for this engine's devices."""
        from scaletorch_tpu.utils.device import device_report

        return device_report(self.devices)

    # ---- request lifecycle ----------------------------------------------
    def submit(
        self,
        prompt: List[int],
        *,
        max_new_tokens: int = 64,
        eos_id: Optional[int] = None,
        seed: int = 0,
        ttl_s: Optional[float] = None,
        trace_id: Optional[str] = None,
    ) -> int:
        """Queue a request; returns its id. Admission happens inside
        ``step()`` when a slot frees up.

        ``ttl_s`` sets this request's deadline (None = engine
        ``default_ttl_s``; <= 0 = no deadline). ``trace_id`` (a W3C
        trace-context id, threaded in by the serving gateway) keys this
        request's lifecycle spans on the tracer's async track and rides
        the terminal result. Invalid submissions
        raise (``strict_submit=True``, the default) or end as a
        ``rejected`` terminal result; submitting into a draining engine
        raises ``EngineDraining`` / rejects the same way. A full queue
        (``queue_capacity``) sheds the OLDEST queued request to make
        room — under overload the freshest work survives, and the shed
        request gets a ``shed`` terminal result instead of silently
        rotting in an unbounded queue.
        """
        err = None
        if self._draining:
            err = "engine is draining: admissions are stopped"
        elif not prompt:
            err = "prompt must contain at least one token"
        elif len(prompt) > self.prefill_len:
            err = (
                f"prompt length {len(prompt)} exceeds the engine's static "
                f"prefill buffer ({self.prefill_len}); re-create the engine "
                "with a larger prefill_len/max_seq"
            )
        elif len(prompt) >= self.max_seq:
            err = (
                f"prompt length {len(prompt)} leaves no room to generate "
                f"within max_seq {self.max_seq}"
            )
        elif (self._request_pages(len(prompt), max_new_tokens)
                > self.allocator.capacity):
            err = (
                f"request needs {self._request_pages(len(prompt), max_new_tokens)} "
                f"pages but the pool's capacity is {self.allocator.capacity}; "
                "re-create the engine with more num_pages or cap "
                "max_new_tokens"
            )
        if err is not None and self.strict_submit:
            raise EngineDraining(err) if self._draining else ValueError(err)
        req = Request(
            request_id=next(self._ids), prompt=list(prompt),
            max_new_tokens=max_new_tokens, eos_id=eos_id, seed=seed,
            trace_id=trace_id,
        )
        ttl = self.default_ttl_s if ttl_s is None else ttl_s
        if ttl and ttl > 0:
            req.deadline = req.submit_time + ttl
        self.metrics.requests_submitted += 1
        self._req_event("b", req, "request", request_id=req.request_id)
        self._req_event("b", req, "req.queued")
        if err is not None:
            self._finalize(req, "rejected", tokens=[], detail=err,
                           now=time.monotonic())
            return req.request_id
        self._queue.append(req)
        while self.queue_capacity and len(self._queue) > self.queue_capacity:
            shed = self._queue.popleft()
            self._finalize(
                shed, "shed", tokens=[],
                detail=(f"queue exceeded capacity {self.queue_capacity}; "
                        "oldest request shed"),
                now=time.monotonic(),
            )
        self.metrics.queue_depth = len(self._queue)
        return req.request_id

    def _finalize(
        self,
        req: Request,
        outcome: str,
        *,
        tokens: List[int],
        reason: Optional[str] = None,
        detail: Optional[str] = None,
        ttft_t: Optional[float] = None,
        prefill_s: Optional[float] = None,
        prefix_hit: bool = False,
        decode_clocks: Tuple[Optional[float], ...] = (None, None, None),
        now: float,
    ) -> None:
        """Record the single terminal result of ``req``. Every request
        path funnels through here, so the conservation invariant
        (submitted == sum over outcomes) holds by construction — and so
        do the request's lifecycle-span close and its e2e-latency
        histogram observation. The stream sees every token, then the
        terminal result: the request's held tokens are handed over
        first."""
        self._release_tokens(req.request_id)
        latency = now - req.submit_time
        queue_wait = (req.admit_time - req.submit_time
                      if req.admit_time is not None else None)
        self._results[req.request_id] = RequestResult(
            request_id=req.request_id,
            prompt=req.prompt,
            tokens=tokens,
            finish_reason=reason or outcome,
            outcome=outcome,
            detail=detail,
            ttft_s=(ttft_t - req.submit_time) if ttft_t is not None else None,
            latency_s=latency,
            queue_wait_s=queue_wait,
            prefill_s=prefill_s,
            prefix_hit=prefix_hit,
            stall_s=decode_clocks[STALL],
            device_wait_s=decode_clocks[DEVICE_WAIT],
            host_s=decode_clocks[HOST],
            trace_id=req.trace_id,
        )
        if req.admit_time is not None and outcome in ("ok", "timeout"):
            # only SERVED requests feed the e2e histogram (the same
            # outcome set as serving/slo.py's LATENCY_OUTCOMES, not
            # imported — serving sits above inference): an instant
            # reject's near-zero latency and a client-cancelled slot's
            # truncated one would both drag the tail estimate down
            # exactly when overload makes served traffic slowest
            self.metrics.hist["e2e"].observe(latency)
        self._req_event(
            "e", req, "req.decode" if req.admit_time is not None
            else "req.queued")
        self._req_event("n", req, "req.finalize", outcome=outcome,
                        finish_reason=reason or outcome)
        self._req_event("e", req, "request", outcome=outcome)
        self._finished_tick.append(self._results[req.request_id])
        self.metrics.record_outcome(outcome)
        if outcome != "ok":
            logger.warning(
                "request %d -> %s%s", req.request_id, outcome,
                f" ({detail})" if detail else "",
            )

    def _retire_slot(
        self,
        i: int,
        outcome: str,
        *,
        reason: Optional[str] = None,
        detail: Optional[str] = None,
        now: float,
    ) -> None:
        """Terminal-result a slot's request (partial tokens attached)
        and free the slot."""
        slot = self._slots[i]
        req = slot.request
        decode_clocks: Tuple[Optional[float], ...] = (None, None, None)
        if slot.clocks_at_first is not None:
            self._advance(now)
            decode_clocks = tuple(
                c - c0 for c, c0 in zip(self._clocks, slot.clocks_at_first))
        self._finalize(
            req, outcome, tokens=slot.tokens[len(req.prompt):],
            reason=reason, detail=detail, ttft_t=slot.first_token_t,
            prefill_s=slot.prefill_s, prefix_hit=slot.prefix_hit,
            decode_clocks=decode_clocks, now=now,
        )
        slot.request = None
        slot.tokens = []
        slot.clocks_at_first = None
        # drop the slot's references; pages shared with live slots or
        # pinned by the radix tree survive (refcount > 1), the rest
        # return to the free list
        for p in self._slot_pages[i]:
            self.allocator.release(p)
        self._slot_pages[i] = []
        self._slot_frozen[i] = 0
        self._tables[i, :] = TRASH_PAGE
        self._tables_dev = None
        self._page_starved = None
        self._update_page_gauges()

    def _expire(self, now: float) -> None:
        """Deadline sweep: retire queued and mid-decode requests whose
        deadline has passed with a ``timeout`` terminal result. Runs at
        every tick — admission control AND each decode step see fresh
        deadline state."""
        if self._queue:
            kept: deque[Request] = deque()
            for req in self._queue:
                if req.deadline is not None and now >= req.deadline:
                    self._finalize(
                        req, "timeout", tokens=[],
                        detail="deadline exceeded before admission", now=now)
                else:
                    kept.append(req)
            self._queue = kept
            self.metrics.queue_depth = len(self._queue)
        for i, slot in enumerate(self._slots):
            if (slot.active and slot.request.deadline is not None
                    and now >= slot.request.deadline):
                self._retire_slot(
                    i, "timeout", detail="deadline exceeded mid-decode",
                    now=now)

    def _quarantine(self, indices: List[int], now: float, where: str) -> None:
        """Retire poisoned slots (non-finite logits) and mask-clear their
        pages (and their recurrent state, where the model has one) so
        the NaN K/V cannot outlive the request. The clear is
        one jitted masked fill over the whole pool — data-only, so the
        decode step's single compile survives the fault. The mask
        covers the slot's MUTABLE pages only (own pages past the frozen
        prefix): frozen pages are immutable since registration —
        written once by a healthy prefill — so the NaN cannot live there,
        and clearing them would corrupt the slots sharing them."""
        mask = np.zeros(self.num_pages, bool)
        for i in indices:
            mutable = self._slot_pages[i][self._slot_frozen[i]:]
            mask[mutable] = True
            self._retire_slot(
                i, "quarantined",
                detail=f"non-finite logits at {where}", now=now)
        self._fill(mask, indices, 0.0)

    def _fill(self, page_mask: np.ndarray, slots: List[int],
              value: float) -> None:
        """The masked fill of the cache: ``value`` into the masked pages
        and, for a model with state-carrying layers, into the recurrent
        state and convolution tail of ``slots``; for one with window
        layers, into their rings."""
        by_slot = ()
        if self._by_slot:
            slot_mask = np.zeros(self.max_slots, bool)
            slot_mask[slots] = True
            by_slot = (jnp.asarray(slot_mask),)
        self.cache = self._fill_slots(
            self.cache, jnp.asarray(page_mask),
            jnp.asarray(value, jnp.float32), *by_slot)

    def _poison_slot(self, slot_idx: int) -> None:
        """Fault injection: NaN-fill one slot's pages so its next
        decode step produces non-finite logits (same masked fill the
        quarantine clear uses — one compile serves both)."""
        active = [i for i, s in enumerate(self._slots) if s.active]
        if not active:
            logger.warning(
                "fault injection: no active slot to poison; skipping")
            return
        if slot_idx not in active:
            slot_idx = active[0]
        # NaN the slot's mutable pages only — frozen prefix pages may
        # be shared, and poisoning them would fault the neighbours
        # the drill asserts are unaffected. (With a page-aligned
        # prompt the poke surfaces from the second decode on: until
        # then the only mutable lane is overwritten fresh each step.)
        mask = np.zeros(self.num_pages, bool)
        mutable = self._slot_pages[slot_idx][self._slot_frozen[slot_idx]:]
        mask[mutable] = True
        self._fill(mask, [slot_idx], float("nan"))

    # ---- warm rejoin: peer-to-peer prefix state exchange -----------------
    #
    # A restarted replica rejoins with an empty radix tree; these three
    # methods are the engine half of warming it from a live peer. The
    # donor side (`export_prefix_map` / `export_prefix_pages`) is a pure
    # read plus a refcount-retained host copy — donor conservation is
    # untouched and the wire streams from host memory, so a slow
    # recipient can never pin (or evict) donor pool pages. The recipient
    # side (`import_prefix_pages`) allocates pool pages, writes the
    # transferred bytes through the SAME jitted fill step quarantine
    # uses (a cache-shaped value is a new argument structure of
    # `fill_slots` only — `decode_compile_count == 1` holds through
    # warming), registers the chains frozen-from-birth (the tree holds
    # the single reference, so a warmed page is evictable-at-zero like
    # any cached prefix), and releases every allocation in a `finally`
    # so an interrupted import leaves the allocator conservation oracle
    # green.

    def _refuse_prefix_exchange(self, what: str) -> None:
        """A model whose pages are no prefix (``no_prefix_reason``: a
        recurrent state that was never kept, or window layers' K/V that
        lives by slot) has none to export or import."""
        reason = no_prefix_reason(self.cfg)
        if reason is not None:
            raise NotImplementedError(
                f"{what}: {type(self.cfg).__name__} {reason}")

    def export_prefix_map(self) -> Dict[str, Any]:
        """Snapshot the radix tree for a warming peer: root-to-leaf
        token chains with their page ids, plus per-page refcount/frozen
        state. Engine-thread only (worker inbox)."""
        self._refuse_prefix_exchange("export_prefix_map")
        if self.radix is None:
            return {"page_size": self.page_size, "chains": [], "pages": {}}
        return {
            "page_size": self.page_size,
            "dtype": str(self.cache.k.dtype),
            "page_shape": ([int(self.cache.k.shape[0])]
                           + [int(d) for d in self.cache.k.shape[2:]]),
            "chains": [
                {"tokens": [int(t) for t in tokens],
                 "pages": [int(p) for p in pages]}
                for tokens, pages in self.radix.chains()],
            "pages": {
                int(p): {"refcount": self.allocator.refcount(p),
                         "frozen": True}
                for p in self.radix.registered_pages()},
            "capacity": self.allocator.capacity,
            "free": self.allocator.free_count,
        }

    def export_prefix_pages(
        self, pages: Sequence[int]
    ) -> Tuple[Dict[str, Any], Dict[int, Tuple[bytes, bytes]]]:
        """Copy the requested FROZEN pages' K/V bytes to host memory.

        Only radix-registered pages ship (anything else is mutable slot
        state); each is refcount-retained across the device->host copy
        and released immediately after, so the donor keeps serving and
        its conservation invariant never moves. Returns ``(meta,
        {page: (k_bytes, v_bytes)})``; requested pages no longer frozen
        are simply absent (the wire sends a zero-content frame)."""
        self._refuse_prefix_exchange("export_prefix_pages")
        meta: Dict[str, Any] = {
            "dtype": str(self.cache.k.dtype),
            "page_shape": ([int(self.cache.k.shape[0])]
                           + [int(d) for d in self.cache.k.shape[2:]]),
            "page_size": self.page_size,
        }
        contents: Dict[int, Tuple[bytes, bytes]] = {}
        if self.radix is None:
            return meta, contents
        frozen = set(self.radix.registered_pages())
        valid = [int(p) for p in pages if int(p) in frozen]
        if not valid:
            return meta, contents
        for p in valid:
            self.allocator.retain(p)
        try:
            idx = jnp.asarray(np.asarray(valid, np.int32))
            k_host = np.asarray(self.cache.k[:, idx])
            v_host = np.asarray(self.cache.v[:, idx])
        finally:
            for p in valid:
                self.allocator.release(p)
        for i, p in enumerate(valid):
            contents[p] = (k_host[:, i].tobytes(), v_host[:, i].tobytes())
        return meta, contents

    def import_prefix_pages(
        self,
        chains: Sequence[Tuple[Sequence[int], Sequence[int]]],
        contents: Dict[int, Tuple[bytes, bytes]],
        *,
        dtype: Optional[str],
        page_shape: Sequence[int],
        page_size: Optional[int],
    ) -> Dict[str, Any]:
        """Install transferred donor pages into this engine's pool and
        radix tree. ``chains`` holds donor ``(tokens, donor_pages)``
        paths; ``contents`` maps donor page id -> ``(k, v)`` bytes —
        a chain whose page bytes are missing (dropped chunk, snapped
        stream) keeps its valid PREFIX and sheds the tail, so a partial
        transfer still warms what arrived intact. Returns ``{"pages":
        new_radix_pages, "chains": [registered token lists]}``."""
        self._refuse_prefix_exchange("import_prefix_pages")
        result: Dict[str, Any] = {"pages": 0, "chains": []}
        if self.radix is None:
            return result
        expected_shape = tuple(
            [int(self.cache.k.shape[0])]
            + [int(d) for d in self.cache.k.shape[2:]])
        if (page_size != self.page_size
                or str(dtype) != str(self.cache.k.dtype)
                or tuple(int(d) for d in page_shape) != expected_shape):
            logger.warning(
                "warm import skipped: peer pool is incompatible "
                "(page_size=%s dtype=%s shape=%s vs local %s/%s/%s)",
                page_size, dtype, tuple(page_shape),
                self.page_size, self.cache.k.dtype, expected_shape)
            return result
        page_nbytes = int(np.prod(expected_shape)
                          * np.dtype(self.cache.k.dtype).itemsize)
        imported: Dict[int, int] = {}       # donor page -> local page
        newly_allocated: List[int] = []
        planned: List[Tuple[List[int], List[int]]] = []
        try:
            for tokens, donor_pages in chains:
                local: List[int] = []
                for dp in donor_pages:
                    dp = int(dp)
                    lp = imported.get(dp)
                    if lp is None:
                        data = contents.get(dp)
                        if (data is None or len(data[0]) != page_nbytes
                                or len(data[1]) != page_nbytes):
                            break  # chunk never arrived: keep the prefix
                        got = self.allocator.alloc(1)
                        if got is None:
                            break  # pool pressure: warm what fits
                        lp = got[0]
                        imported[dp] = lp
                        newly_allocated.append(lp)
                    local.append(lp)
                if local:
                    planned.append((
                        [int(t) for t in
                         tokens[:len(local) * self.page_size]], local))
            if imported:
                self._write_imported_pages(imported, contents)
                created = 0
                for tokens, local in planned:
                    created += self.radix.insert(tokens, local)
                self.metrics.warm_pages_total += created
                result["pages"] = created
                result["chains"] = [tokens for tokens, _ in planned]
        finally:
            # drop our allocation reference on every imported page:
            # registered ones fall to the tree's single reference
            # (frozen-from-birth, evictable at zero slot refs like any
            # cached prefix); duplicates of chunks the tree already held
            # — and everything, if the import was interrupted before
            # insert — free immediately, so the conservation oracle
            # passes after an aborted transfer
            for lp in newly_allocated:
                self.allocator.release(lp)
            self._update_page_gauges()
        return result

    def _write_imported_pages(
        self, imported: Dict[int, int],
        contents: Dict[int, Tuple[bytes, bytes]],
    ) -> None:
        """One masked fill writes every imported page's bytes into the
        pool — the same audited `fill_slots` compile quarantine rides,
        fed a cache-shaped value instead of a scalar."""
        mask = np.zeros(self.num_pages, bool)
        vk = np.zeros(self.cache.k.shape, self.cache.k.dtype)
        vv = np.zeros(self.cache.v.shape, self.cache.v.dtype)
        shape = tuple([vk.shape[0]] + list(vk.shape[2:]))
        for dp, lp in imported.items():
            kb, vb = contents[dp]
            vk[:, lp] = np.frombuffer(kb, vk.dtype).reshape(shape)
            vv[:, lp] = np.frombuffer(vb, vv.dtype).reshape(shape)
            mask[lp] = True
        self.cache = self._fill_slots(
            self.cache, jnp.asarray(mask),
            type(self.cache)(jnp.asarray(vk), jnp.asarray(vv)))

    def _bind_slot(self, i: int, req: Request) -> None:
        slot = self._slots[i]
        slot.request = req
        slot.tokens = list(req.prompt)
        slot.position = len(req.prompt)
        slot.generated = 0
        slot.first_token_t = None
        slot.last_token_t = None
        slot.prefill_s = None
        slot.prefix_hit = False
        slot.clocks_at_first = None
        req.admit_time = time.monotonic()
        self.metrics.hist["queue_wait"].observe(
            req.admit_time - req.submit_time)
        self._req_event("e", req, "req.queued")
        self._req_event("n", req, "req.admitted", slot=i)
        self._base_keys[i] = _host_key(req.seed)
        self._base_keys_dev = None
        self.metrics.requests_admitted += 1

    def _note_prefill(self, admitted: List[int], prefill_s: float) -> None:
        """Attribute one batched prefill's wall time to every request it
        admitted (they shared the call), close their ``req.prefill``
        spans and open ``req.decode`` — BEFORE any quarantine retires a
        poisoned slot, so every begun span gets its end."""
        for i in admitted:
            slot = self._slots[i]
            slot.prefill_s = prefill_s
            self.metrics.hist["prefill"].observe(prefill_s)
            self._req_event("e", slot.request, "req.prefill")
            self._req_event("b", slot.request, "req.decode")

    def _reserve_pages(self, req: Request):
        """Try to reserve the pages one request needs: radix-match its
        prompt, retain the shared prefix pages, allocate the rest
        (evicting unpinned radix leaves when the free list runs short).
        Returns (shared_tokens, page_list) or None when the pool cannot
        cover the request right now — pages free as slots retire, so the
        request just waits at the head of the queue (FIFO)."""
        plen = len(req.prompt)
        ps = self.page_size
        total_pages = self._request_pages(plen, req.max_new_tokens)
        shared = 0
        shared_pages: List[int] = []
        if self.radix is not None:
            matched, pages = self.radix.match(req.prompt)
            # never share the whole prompt: the first token samples from
            # the logits at prompt_len - 1, so at least one tail token
            # must run through prefill
            shared = min(matched, ((plen - 1) // ps) * ps)
            shared_pages = pages[: shared // ps]
            for p in shared_pages:
                self.allocator.retain(p)
        own_needed = total_pages - len(shared_pages)
        own = self.allocator.alloc(own_needed)
        if own is None and self.radix is not None:
            self.radix.evict(own_needed - self.allocator.free_count)
            own = self.allocator.alloc(own_needed)
        if own is None:
            for p in shared_pages:
                self.allocator.release(p)
            return None
        return shared, shared_pages + own

    def _admission_due(self) -> bool:
        """A queued request, a free slot for it, and no word yet that
        the pool cannot cover it: what ``step()`` asks before it
        admits. A step in flight does not hold an admission back: the
        prefill call goes behind it on the device."""
        return (bool(self._queue)
                and self._queue[0].request_id != self._page_starved
                and not all(s.active for s in self._slots))

    def _call_prefill(self, *operands, counted: bool = True):
        """One call of the prefill step on host-built ``operands``
        ``[rows, ...]`` (``_prefill_operands``), the cache donated and
        taken back: the one place an admission and
        ``warm_prefill_shapes`` call it from, so that both reach the
        same compiled program of a shape (the default device is part of
        a jitted call's signature). ``counted`` False: an MoE model's
        routing counters are left as they were."""
        step = self._prefill if counted else getattr(
            self._prefill, "uncounted", self._prefill)
        with self.on_device():
            # the operands stay numpy: the jitted call uploads them
            # itself (``_dispatch`` says what a jnp.asarray each costs),
            # and a call behind a step in flight has that step's time
            # to get itself and the next step dispatched
            first, _logits, finite, self.cache = step(
                self.params, *operands[:5], self.cache, *operands[5:])
        return first, finite

    def _prefill_operands(self, row_slots: List[int], taken, shape=None):
        """The host-built operands of one prefill call whose leading
        rows are the slots ``row_slots``, at ``shape`` or the shape of
        ``prefill_shapes`` with the fewest positions that holds them:
        tokens, tail lengths, starts, write mask, page tables, base
        keys and, where a row names its slot, the slot ids. ``taken``:
        ``{slot: (the prompt's tail, tokens shared before it)}`` of the
        admitted slots, whose rows are written; every other row is
        masked and one token long, and a row past ``row_slots`` carries
        a TRASH table (and the id past the last slot)."""
        tails = [len(taken[i][0]) for i in row_slots if i in taken]
        rows, length = shape or next(
            shape for shape in self.prefill_shapes
            if shape[0] >= len(row_slots) and shape[1] >= max(tails))
        tokens = np.zeros((rows, length), np.int32)
        tail_lens = np.ones(rows, np.int32)
        starts = np.zeros(rows, np.int32)
        write_mask = np.zeros(rows, bool)
        tables = np.full((rows, self._pages_per_slot), TRASH_PAGE, np.int32)
        tables[: len(row_slots)] = self._tables[row_slots]
        # a slot's sampling key stays its own: a request's tokens do
        # not depend on the shape that admitted it
        base_keys = np.zeros((rows, 2), np.uint32)
        base_keys[: len(row_slots)] = self._base_keys[row_slots]
        for row, i in enumerate(row_slots):
            if i in taken:
                tail, shared = taken[i]
                tokens[row, : len(tail)] = tail
                tail_lens[row] = len(tail)
                starts[row] = shared
                write_mask[row] = True
        operands = (tokens, tail_lens, starts, write_mask, tables, base_keys)
        if self._rows_name_slots:
            slot_ids = np.full(rows, self.max_slots, np.int32)
            slot_ids[: len(row_slots)] = row_slots
            operands += (slot_ids,)
        return operands

    def warm_prefill_shapes(self) -> None:
        """Run the prefill step once at every shape of
        ``prefill_shapes`` with no row admitted (every row masked, its
        table TRASH: nothing but the TRASH page is written, no counter
        moves, the donated cache comes back), so that each program
        exists before the first request: ``prefill_compile_count`` is
        ``len(prefill_shapes)`` from here on, whatever is admitted. For
        an idle engine; ``scripts/serve.py`` calls it as it builds one.
        An engine that is not warmed compiles a shape at the first
        admission that takes it. Largest first and nothing waited for:
        the device runs the full shape while the host traces the next."""
        for shape in reversed(self.prefill_shapes):
            self._call_prefill(
                *self._prefill_operands([], {}, shape), counted=False)

    def _admit(self) -> Optional[_Admission]:
        """Move queued requests into free slots while the page pool can
        cover them, and DISPATCH their prefill at the shape of
        ``prefill_shapes`` with the fewest positions that holds them:
        ONE batched call regardless of how many were admitted, a row
        of which is an admitted slot, in admission order (every slot in
        turn where the cache is by slot and a row is its slot); or,
        where a row names its slot, one call of the one-row program an
        admitted prompt, back to back. Rows past the admitted are
        padding: masked, one token, a TRASH table. Nothing is read
        back: the calls go on the device behind the step in flight, if
        there is one, which is not read first, and ``_read_admission``
        takes their results once the step after them has been
        dispatched too. None when nothing was admitted."""
        with self._phase("engine.tick.admit"):
            if not self._admission_due():
                return None
            free = [i for i, s in enumerate(self._slots) if not s.active]
            # slot: (the prompt's tail to prefill, tokens shared before it)
            taken: Dict[int, Tuple[Sequence[int], int]] = {}
            for i in free:
                if not self._queue:
                    break
                reserved = self._reserve_pages(self._queue[0])
                if reserved is None:
                    # page budget exhausted: head of the line waits
                    self._page_starved = self._queue[0].request_id
                    break
                req = self._queue.popleft()
                shared, pages = reserved
                self._bind_slot(i, req)
                self._slot_pages[i] = pages
                self._slot_frozen[i] = shared // self.page_size
                self._tables[i, :] = TRASH_PAGE
                self._tables[i, : len(pages)] = pages
                self._tables_dev = None
                taken[i] = (req.prompt[shared:], shared)
                if shared:
                    self.metrics.prefix_hits += 1
                    self.metrics.prefill_tokens_saved += shared
                    self._slots[i].prefix_hit = True
            if not taken:
                return None
            admitted = list(taken)
            calls = ([[i] for i in admitted] if self._rows_name_slots
                     else [list(range(self.max_slots))] if self._by_slot
                     else [admitted])
            row_of = {slot: (call, row) for call, row_slots in enumerate(calls)
                      for row, slot in enumerate(row_slots) if slot in taken}
            operands = [self._prefill_operands(row_slots, taken)
                        for row_slots in calls]
        t0 = time.monotonic()
        for i in admitted:
            tokens, _, starts, *_ = operands[row_of[i][0]]
            self._req_event("b", self._slots[i].request, "req.prefill",
                            prefix_hit=self._slots[i].prefix_hit,
                            rows=tokens.shape[0], length=tokens.shape[1],
                            self_attended=not starts.any())
        with self._phase("engine.tick.prefill"):
            first, finite = zip(*(self._call_prefill(*call)
                                  for call in operands))
        self.metrics.prefill_calls += len(calls)
        self.metrics.prefill_calls_self_attended += sum(
            not starts.any() for _, _, starts, *_ in operands)
        self.metrics.prefill_positions_run += sum(
            tokens.size for tokens, *_ in operands)
        self.metrics.prefill_positions_admitted += sum(
            len(tail) for tail, _ in taken.values())
        if self._by_slot:
            # the call started every admitted slot's state from zero
            # (or its rings from the prompt)
            for i in admitted:
                self._state_owner[i] = self._slots[i].request.request_id
        if self._stateful:
            self.metrics.recurrent_state_resets += len(admitted)
        if self.metrics.ssd_layers:
            self.metrics.ssd_prefill_chunks += self.metrics.ssd_layers * sum(
                tokens.shape[0] * ceil_div(tokens.shape[1],
                                           self.cfg.mamba_chunk_size)
                for tokens, *_ in operands)
        if self._window is not None:
            self.metrics.window_ring_wraps += sum(
                (self._slots[i].position - 1) // self._ring_tokens
                for i in admitted)
            ring_pages = self._ring_tokens // self.page_size
            self.metrics.window_pages_trashed += self.cache.wk.shape[0] * sum(
                max(ceil_div(len(tail), self.page_size) - ring_pages, 0)
                for tail, _ in taken.values())
        return _Admission(
            list(first), list(finite),
            [(i, self._slots[i].request) for i in admitted], row_of, t0)

    def _read_admission(self, admission: _Admission) -> None:
        """Read a dispatched prefill call back and emit its first
        tokens. A slot whose prefill logits are non-finite (poison
        prompt) is quarantined here, and the row the step behind the
        call already runs for it is thrown away when that step is read;
        the other admitted slots proceed."""
        with self._phase("engine.tick.prefill_wait"):
            # by slot, from every call's arrays in one round trip
            first, finite = (
                {i: by_call[call][row]
                 for i, (call, row) in admission.row_of.items()}
                for by_call in jax.device_get(
                    (admission.first, admission.finite)))
        with self._phase("engine.tick.emit"):
            now = time.monotonic()
            admitted = [i for i, _ in admission.bound]
            self._note_prefill(admitted, now - admission.dispatched_t)
            poisoned = [i for i in admitted if not finite[i]]
            if poisoned:
                # skip radix registration for poison prompts — their
                # pages hold non-finite K/V and must never be shared
                self._quarantine(poisoned, now, where="prefill")
            healthy = [i for i in admitted if finite[i]]
            for i in healthy:
                if self.radix is not None:
                    slot = self._slots[i]
                    plen = len(slot.request.prompt)
                    frozen = (plen // self.page_size) * self.page_size
                    if frozen:
                        n = frozen // self.page_size
                        self.radix.insert(
                            slot.request.prompt[:frozen],
                            [int(p) for p in self._tables[i, :n]],
                        )
                        # the fully-written prompt pages are immutable
                        # from here on — exempt from quarantine clears
                        # and shareable by later admissions
                        self._slot_frozen[i] = n
            self._emit([(i, int(first[i])) for i in healthy], now)
            self._update_page_gauges()
            self.metrics.queue_depth = len(self._queue)

    def _release_tokens(self, request_id: Optional[int] = None, *,
                        at_readback: bool = False) -> None:
        """Hand the held tokens to ``on_tokens``: all of them, or one
        request's. All of them ``at_readback``: ``_emit`` calls this as
        soon as a step's (or a prefill call's) tokens are recorded,
        before any retirement is booked. The consumers' writes (16
        streams' on the gateway's event loop, which shares the
        interpreter) then run while this thread has a whole step of
        slack before the device wants the next dispatch: the decode
        loop runs one step ahead (``step()``). Until it did, the tokens
        waited for the next dispatch so that those writes would not run
        between two steps with the device idle (1.7 ms of a 36 ms tick
        on a v5e, PERF.md, PR 27), and a tick that retired a slot held
        every other stream's tokens for the retirement and the page
        tables' upload (1.2-1.4 ms on one tick in ~32: the 95th
        percentile of the inter-token gaps stood on those, PERF.md,
        PR 64). An engine whose dispatch blocks for its step
        (``_DISPATCH_BLOCKS``) is still where PR 27 found this one: it
        hands all of them over after its next dispatch, and one
        request's before that request's terminal result is recorded,
        so the stream sees every token, then the result. A raising
        hook is disarmed (logged), never fatal: one bad consumer must
        not take the whole decode batch down; the batch it raised in is
        dropped with it, no token of it is held or handed over
        again."""
        if not self._held_tokens:
            return
        if request_id is None:
            held, self._held_tokens = self._held_tokens, []
        else:
            held = [h for h in self._held_tokens if h[1] == request_id]
            if not held:
                return
            self._held_tokens = [
                h for h in self._held_tokens if h[1] != request_id]
        if at_readback:
            self.metrics.tokens_handed_at_readback += len(held)
        else:
            self.metrics.tokens_handed_later += len(held)
        hook = self.on_tokens
        if hook is None:
            return
        try:
            for slot, request_id, token_ids, emitted_t in held:
                hook(slot, request_id, token_ids, emitted_t)
        except Exception:
            logger.exception("on_tokens hook raised; disarming the hook")
            self.on_tokens = None

    def _record_token(self, i: int, token: int, now: float) -> Optional[str]:
        """Book one generated token to slot i and hold it for
        ``on_tokens``; the stop condition it hits, if any."""
        slot = self._slots[i]
        req = slot.request
        slot.tokens.append(token)
        slot.generated += 1
        self.metrics.tokens_generated += 1
        if slot.first_token_t is None:
            slot.first_token_t = now
            self._advance(now)
            slot.clocks_at_first = tuple(self._clocks)
            self.metrics.record_ttft(now - req.submit_time)
        else:
            # per-token inter-arrival (TPOT): decode cadence as the
            # client experiences it, first token (prefill) excluded
            self.metrics.hist["tpot"].observe(now - slot.last_token_t)
        slot.last_token_t = now
        if self.on_tokens is not None:
            self._held_tokens.append((i, req.request_id, [token], now))
        if req.eos_id is not None and token == req.eos_id:
            return "eos"
        if slot.generated >= req.max_new_tokens:
            return "length"
        if slot.position + slot.generated >= self.max_seq:
            # continuing would feed a token at position >= max_seq —
            # past the end of the cache
            return "max_seq"
        return None

    def _emit(self, rows: List[Tuple[int, int]], now: float) -> None:
        """Emit what a step or a prefill call just read gave: one token
        for each ``(slot, token)`` of ``rows``, all stamped ``now``.
        Every token is recorded first, then they are all handed over
        (``_release_tokens``) and ``on_handed_over`` runs; only after
        that are the slots a stop condition hit retired, so no stream's
        token waits for another's result, page release or radix
        bookkeeping. A retiring stream's own last token went out with
        the rest, so ``_finalize`` finds nothing of it held and the
        stream still sees every token, then the result. An engine
        whose dispatch blocks keeps them for that dispatch."""
        ended = [(i, reason) for i, token in rows
                 if (reason := self._record_token(i, token, now)) is not None]
        if self._held_tokens and not self._DISPATCH_BLOCKS:
            self._release_tokens(at_readback=True)
            if self.on_handed_over is not None:
                self.on_handed_over()
        for i, reason in ended:
            self._retire_slot(i, "ok", reason=reason, now=now)

    def step(self) -> List[RequestResult]:
        """One engine tick: deadline sweep, admit into freed slots
        (prefill), then one decode step's tokens for the active slots —
        with the slots whose logits went non-finite quarantined instead
        of emitting. Returns every result that reached its terminal
        outcome since the PREVIOUS ``step()`` returned — including
        requests finalized between ticks (a ``shed``/``rejected``
        recorded inside ``submit()``, a ``cancel()``), so a
        push-delivery bridge sees each terminal result exactly once.

        The decode loop runs one step ahead: a tick that finds step n
        on the device dispatches step n+1, fed n's sampled tokens as
        the device array they are, and only then reads n back and
        emits it, so the device never waits for the host between two
        steps. What the host knows without the tokens it decides
        exactly (positions; a slot that ends at n by ``max_new_tokens``
        or ``max_seq`` is off in n+1); an ``eos``, a non-finite row, a
        cancel or a TTL is learnt late, and that slot's row of n+1 is
        thrown away (``decode_slot_steps_discarded``).

        The run-ahead crosses an admission. A tick that finds an
        admission due admits on the host and dispatches the prefill
        call at once, behind step n and without reading it; then step
        n+1 behind the call, for the slots of n that continue plus
        every slot just admitted, whose token is the call's first token
        as the device array it is, written over n's sampled tokens at
        the admitted slots' indices on the device (``_merge_tokens``);
        only then does it read n and emit it, read the call and emit
        the first tokens, and leave n+1 in flight. The device holds
        n -> prefill -> n+1 with no host in between. A prefill row
        that is not finite is quarantined at the readback and its row
        of n+1 thrown away, like anything else learnt late. With no
        step in flight (an idle engine) the order is the same less n.
        What goes ahead is decided here from what the engine sees: a
        step in flight, slots that continue, an admission due.

        The tick is one ``engine.tick`` span cut into ``engine.tick.*``
        phases (docs/observability.md), each also a boundary of the
        phase clocks."""
        flight, self._in_flight = self._in_flight, None
        # the decode step this tick reads
        tick = (flight.number if flight is not None
                else self.metrics.decode_steps + 1)
        tick_t0 = time.monotonic()
        if self.watchdog is not None:
            self.watchdog.beat(step=self.metrics.decode_steps,
                               phase="serve-step")
        inj = self.injector
        if inj is not None:
            storm = inj.take_submit_storm(tick) if not self._draining else 0
            for _ in range(storm):
                self.submit([1], max_new_tokens=1)
            if inj.take_deadline_storm(tick):
                past = time.monotonic() - 1.0
                for req in self._queue:
                    req.deadline = past
                for s in self._slots:
                    if s.active:
                        s.request.deadline = past
        with span("engine.tick", self.tracer, tick=tick):
            with self._phase("engine.tick.sweep"):
                self._expire(time.monotonic())
            self._tick_device(flight)
            with self._phase("engine.tick.export"):
                self.metrics.active_slots = sum(
                    s.active for s in self._slots)
                self.metrics.queue_depth = len(self._queue)
                if (
                    (self.monitor is not None or self.exporter is not None)
                    and self.metrics.decode_steps % self.monitor_every == 0
                ):
                    if self.monitor is not None:
                        self.monitor.sample(
                            counters=self.metrics.snapshot())
                    if self.exporter is not None:
                        # idle ticks keep the progress fingerprint
                        # unchanged — only movement appends to the
                        # durable stream (the ring buffer above is
                        # bounded, the file is not)
                        self._export_snapshot()
        self._close_tick(tick, tick_t0)
        finished, self._finished_tick = self._finished_tick, []
        return finished

    def _tick_device(self, flight: Optional[_InFlight]) -> None:
        """What a tick puts on the device and what it reads back, in
        the order ``step()`` describes: everything is dispatched before
        anything is read."""
        admission = self._admit()
        if admission is not None and flight is not None:
            self.metrics.prefill_calls_behind_flight += len(admission.first)
        if admission is None and flight is None:
            # slots that hold tokens and no step in flight: fed from
            # the host (an admission made by hand; nothing, when idle)
            flight = self._dispatch(None)
            if flight is None:
                return
        self._in_flight = self._dispatch(flight, admission)
        if flight is not None:
            self._read(flight)
        if admission is not None:
            self._read_admission(admission)
        self._drop_dead_flight()    # its streams may have ended here

    def _live(self, flight: _InFlight) -> List[Tuple[int, Request]]:
        """The slots of a step whose request is the one the step ran
        for: not ended since, not the slot's next tenant."""
        return [(i, req) for i, req in flight.bound
                if self._slots[i].request is req]

    def _drop_dead_flight(self) -> None:
        """Forget the step in flight if every request it runs for has
        ended: nobody waits for it, and its rows count as discarded."""
        flight = self._in_flight
        if flight is not None and not self._live(flight):
            self._read(flight)
            self._in_flight = None

    def _continues(self, i: int, req: Request) -> bool:
        """Whether slot ``i`` has a step to run after the token that is
        on its way to it (a step's or a prefill call's, not yet read):
        that token is the slot's n-th, and these are the conditions
        ``_emit`` will end it by, known ahead."""
        slot = self._slots[i]
        n = slot.generated + 1
        return n < req.max_new_tokens and slot.position + n < self.max_seq

    def _dispatch(self, before: Optional[_InFlight],
                  admission: Optional[_Admission] = None
                  ) -> Optional[_InFlight]:
        """Put the next decode step on the device. With ``before``
        None the step is fed from the host: every active slot's last
        token at its position. With ``before`` still on the device it is the
        step after it: the slots of ``before`` that cannot end at it
        by length, one position on, fed ITS sampled tokens without
        their leaving the device. With ``admission`` (a prefill call
        on the device, behind ``before`` if there is one) the step
        also runs every admitted slot that has more than one token to
        give, at its prompt's length, fed the call's first token the
        same way (``_merge_tokens``). None when no slot has a step to
        run."""
        with self._phase("engine.tick.feed"):
            positions = np.zeros(self.max_slots, np.int32)
            active = np.zeros(self.max_slots, bool)
            fresh = [] if admission is None else [
                (i, req) for i, req in admission.bound
                if self._continues(i, req)]
            if before is None:
                tokens = np.zeros(self.max_slots, np.int32)
                # (an admitted slot has no token on the host yet)
                bound = [(i, s.request) for i, s in enumerate(self._slots)
                         if s.active and s.generated]
                for i, _ in bound:
                    slot = self._slots[i]
                    # feed the last emitted token at its absolute
                    # position: the prompt occupies [0, len),
                    # generated token g sits at len + g - 1
                    tokens[i] = slot.tokens[-1]
                    positions[i] = slot.position + slot.generated - 1
            else:
                tokens = before.nxt
                bound = [(i, req) for i, req in self._live(before)
                         if self._continues(i, req)]
                for i, _ in bound:
                    positions[i] = before.positions[i] + 1
            if fresh:
                slot_of_row = [
                    np.full(first.shape[0], self.max_slots, np.int32)
                    for first in admission.first]
                for i, _ in fresh:
                    call, row = admission.row_of[i]
                    slot_of_row[call][row] = i
                    positions[i] = self._slots[i].position
                for first, slots in zip(admission.first, slot_of_row):
                    tokens = self._merge_tokens(tokens, first, slots)
                bound = sorted(bound + fresh, key=lambda b: b[0])
            if not bound:
                return None
            if self._by_slot:
                strangers = sum(self._state_owner[i] != req.request_id
                                for i, req in bound)
                if self._stateful:
                    self.metrics.recurrent_state_owner_mismatches += strangers
                else:
                    self.metrics.window_slot_reuse_mismatches += strangers
            held = [i for i, _ in bound]
            if self._window is not None:
                self._count_window_keys(positions[held])
            if self.metrics.latent_cache_bytes or self.metrics.ssd_layers:
                # p + 1 a live slot: what one layer's pool walk reads
                keys = int(positions[held].astype(np.int64).sum()) + len(
                    held)
                if self.metrics.latent_cache_bytes:
                    self.metrics.latent_keys_attended += keys
                if self.metrics.ssd_layers:
                    self.metrics.full_keys_attended += keys
                    self.metrics.ssd_state_slot_updates += (
                        len(held) * self.metrics.ssd_layers)
            active[held] = True
            if self._kernel_calls_a_step:
                walked, chained = chained_first_blocks(
                    positions, self.page_size, self._pages_per_slot)
                self.metrics.paged_slot_walks += (
                    walked * self._kernel_calls_a_step)
                self.metrics.paged_slot_walks_chained += (
                    chained * self._kernel_calls_a_step)
            # positions and active stay numpy: the jitted call uploads
            # host operands itself, without the 0.25 ms of Python a
            # jnp.asarray each costs on a v5e's host
            feed = (self._tokens_operand(tokens), positions, active,
                    self._tables_device())
            base_keys = self._base_keys_device()
        number = self.metrics.decode_steps + 1
        stall = 0.0
        if self.injector is not None:
            poison = self.injector.take_nan_logits(number)
            if poison is not None:
                self._poison_slot(poison)
            stall = self.injector.take_slow_decode(number)
        with self._phase("engine.tick.decode"):
            nxt, _logits, finite, self.cache = self._decode(
                self.params, *feed, self.cache, base_keys)
            # dropped inside a phase: freeing the logits costs 0.2 ms
            # on a v5e
            del _logits, feed, base_keys
        self.metrics.decode_steps = number
        if before is not None or admission is not None:
            self.metrics.decode_steps_ahead += 1
        with self._phase("engine.tick.decode_wait"):
            if self._DISPATCH_BLOCKS:
                # the step has run: what the last readback held goes
                # out, and its consumers run when the next dispatch
                # blocks
                self._release_tokens()
            # what the consumers of the results wake runs beside the step
            if self.on_dispatched is not None:
                self.on_dispatched()
        return _InFlight(number, nxt, finite, positions, bound, stall)

    def _count_window_keys(self, positions: np.ndarray) -> None:
        """What one dispatched decode step's slots attend, by kind of
        layer (one layer of each), and the rings that wrap at it."""
        keys = positions.astype(np.int64) + 1
        self.metrics.full_keys_attended += int(keys.sum())
        self.metrics.window_keys_attended += int(
            np.minimum(keys, self._window).sum())
        self.metrics.window_ring_wraps += int(np.sum(
            (positions > 0) & (positions % self._ring_tokens == 0)))

    def _read(self, flight: _InFlight) -> None:
        """Read a dispatched step back and emit it: one token for each
        of its slots whose request is still the one it ran for,
        quarantine for a non-finite row. The rows of requests that
        ended meanwhile are thrown away, and a step that has only such
        rows is not waited for."""
        live = self._live(flight)
        self.metrics.decode_slot_steps_discarded += (
            len(flight.bound) - len(live))
        if not live:
            return
        with self._phase("engine.tick.decode_wait"):
            if flight.stall > 0:
                # an injected slow decode is booked where a real one
                # would be: the host waiting on the step
                time.sleep(flight.stall)
            # both copies started, then both awaited: one round trip
            # to the device, not two
            nxt, finite = jax.device_get((flight.nxt, flight.finite))
        with self._phase("engine.tick.emit"):
            now = time.monotonic()
            poisoned = [i for i, _ in live if not finite[i]]
            if poisoned:
                self._quarantine(poisoned, now, where="decode")
            self._emit([(i, int(nxt[i])) for i, _ in live if finite[i]], now)

    def _close_tick(self, tick: int, tick_t0: float) -> None:
        """A tick that took over ``SLOW_TICK_S``, the time since the
        previous tick ended included when that tick left work behind,
        is counted, logged with every phase's seconds and exported as
        one ``slow_tick`` record."""
        now = time.monotonic()
        phases, self._tick_phase_s = self._tick_phase_s, {}
        gap = tick_t0 - self._tick_end_t if self._tick_left_work else 0.0
        self._tick_end_t = now
        self._tick_left_work = self.pending > 0
        wall = now - tick_t0 + gap
        if wall <= SLOW_TICK_S:
            return
        self.metrics.slow_ticks += 1
        record = {"tick": tick, "wall_s": wall, "gap_before_s": gap,
                  "phases_s": phases}
        logger.warning("slow tick: %s", record)
        if self.exporter is not None:
            self.exporter.emit("slow_tick", record)

    def tick(self) -> List[RequestResult]:
        """Single-step driving alias for ``step()`` — the vocabulary the
        serving bridge (serving/gateway.py) uses: one tick = one
        admission sweep + one decode step."""
        return self.step()

    @property
    def pending(self) -> int:
        return len(self._queue) + sum(s.active for s in self._slots)

    def cancel(self, request_id: int, *,
               detail: str = "cancelled by client") -> bool:
        """Abort one in-flight request — queued or mid-decode — with an
        ``aborted`` terminal result (partial tokens attached, pages
        released through the allocator). The serving gateway calls this
        when a client disconnects mid-stream: the slot frees for the
        next admission instead of decoding for a closed socket. Returns
        False when the id is unknown or already terminal."""
        now = time.monotonic()
        for idx, req in enumerate(self._queue):
            if req.request_id == request_id:
                del self._queue[idx]
                self._finalize(req, "aborted", tokens=[], detail=detail,
                               now=now)
                self.metrics.queue_depth = len(self._queue)
                return True
        for i, slot in enumerate(self._slots):
            if slot.active and slot.request.request_id == request_id:
                self._retire_slot(i, "aborted", detail=detail, now=now)
                self.metrics.active_slots = sum(
                    s.active for s in self._slots)
                self._drop_dead_flight()
                return True
        return False

    def stop_admissions(self) -> None:
        """Enter the draining state WITHOUT running the tick loop:
        ``submit()`` now raises ``EngineDraining`` / rejects, while
        queued and admitted requests keep flowing through ``step()``.
        The blocking ``drain()`` composes this with its own loop; a
        streaming bridge that owns the tick loop (and must keep
        delivering per-tick tokens/results during shutdown) calls this
        and keeps ticking until ``pending`` reaches zero. Idempotent."""
        self._draining = True

    def _abort_pending(self, detail: str) -> None:
        """Terminal-result every in-flight request as ``aborted``
        (partial tokens attached for admitted slots) — completed work is
        never discarded, and no slot stays active past its request's
        terminal result."""
        now = time.monotonic()
        while self._queue:
            self._finalize(self._queue.popleft(), "aborted", tokens=[],
                           detail=detail, now=now)
        for i, slot in enumerate(self._slots):
            if slot.active:
                self._retire_slot(i, "aborted", detail=detail, now=now)
        self._drop_dead_flight()
        self.metrics.queue_depth = 0
        self.metrics.active_slots = 0

    def run(self, max_steps: int = 100_000) -> Dict[int, RequestResult]:
        """Drive ``step()`` until queue and slots drain; returns all
        results by request id. On ``max_steps`` exhaustion the completed
        results are RETURNED (never discarded) and the unfinished
        requests end as ``aborted`` with their partial tokens. A pending
        preemption request (SIGTERM via the ``preemption`` handler)
        switches to ``drain()``: admissions stop, in-flight requests
        finish, and the engine returns cleanly."""
        steps = 0
        while self.pending and steps < max_steps:
            if self.preemption is not None and self.preemption.requested:
                logger.warning(
                    "preemption requested (signal %s): draining the engine",
                    self.preemption.signum,
                )
                self.drain(max_steps=max_steps - steps)
                return dict(self._results)
            self.step()
            steps += 1
        if self.pending:
            logger.warning(
                "engine did not drain within %d steps: aborting %d "
                "in-flight requests (completed results are returned)",
                max_steps, self.pending,
            )
            self._abort_pending(f"run(max_steps={max_steps}) exhausted")
        if self.exporter is not None:
            # final snapshot: a short-lived run must leave its terminal
            # counters on the durable stream even between cadence points
            # (deduped — ending exactly on a cadence step appends once)
            self._export_snapshot()
        return dict(self._results)

    def drain(
        self,
        *,
        max_steps: int = 100_000,
        finish_queued: bool = False,
    ) -> Dict[int, RequestResult]:
        """Graceful shutdown: stop admissions (``submit()`` now raises
        ``EngineDraining`` / returns ``rejected``), finish the in-flight
        (admitted) requests, and flush all results. Queued-but-never-
        admitted requests are ``aborted`` immediately unless
        ``finish_queued`` — a SIGTERM grace period has no room for
        unbounded queue depth. Anything still unfinished after
        ``max_steps`` is ``aborted`` with partials attached. Idempotent."""
        self.stop_admissions()
        if not finish_queued:
            now = time.monotonic()
            while self._queue:
                self._finalize(
                    self._queue.popleft(), "aborted", tokens=[],
                    detail="drain: not yet admitted", now=now)
            self.metrics.queue_depth = 0
        steps = 0
        while self.pending and steps < max_steps:
            self.step()
            steps += 1
        if self.pending:
            self._abort_pending(f"drain(max_steps={max_steps}) exhausted")
        if self.exporter is not None:
            self._export_snapshot()
        return dict(self._results)

    def result(self, request_id: int) -> Optional[RequestResult]:
        return self._results.get(request_id)

    def pop_result(self, request_id: int) -> Optional[RequestResult]:
        """Remove and return a terminal result (None when absent or not
        yet terminal). The engine retains every terminal record for
        ``result()``/``run()`` otherwise — unbounded over a long-running
        server's lifetime, so a serving loop should pop each result once
        it has been delivered."""
        return self._results.pop(request_id, None)
