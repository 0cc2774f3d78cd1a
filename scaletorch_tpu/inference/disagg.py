"""Disaggregated prefill/decode serving: MPMD phase slices with
page-ownership handoff.

Prefill is compute-bound, decode is bandwidth-bound — one SPMD program
over both phases wastes whichever resource the current phase doesn't
need. ``DisaggregatedEngine`` splits the device fleet into a PREFILL
slice and a DECODE slice (two ``Mesh``es over disjoint device subsets)
and runs one jitted program per phase: the paged prefill step only ever
sees prefill-slice operands, the paged decode step only decode-slice
operands, so the one-compile discipline holds on BOTH programs
(``prefill_compile_count == 1`` and ``decode_compile_count == 1``
across admissions, handoffs and quarantines — jit follows committed
operand placement, it never retraces for it).

The page is the handoff unit (PR 10) and ownership crosses slices
through TWO ``PageAllocator``s, all-or-nothing per request:

  submit -> queue -> [prefill slice] prefill pool pages, full-prompt
  prefill, FIRST token emitted -> handoff queue -> [wire] only the
  filled prompt pages move (``PageHandoffChannel`` — ``jax.device_put``
  on the CPU simulation path, the same seam an ICI transfer slots
  into) -> [decode slice] decode pool pages reserved (radix prefix
  shared pages retained, not re-transferred), contents scattered in,
  prompt prefix registered FROZEN in the decode-side radix tree,
  decode slot bound -> prefill pages released.

A request that dies mid-handoff (deadline, cancel, transport fault)
ends in exactly ONE of the six terminal outcomes and leaks zero pages
on either pool: the decode-side reservation rolls back whole and the
prefill-side pages release through the same funnel — both allocators'
``check_conservation`` stay green under randomized
admit/handoff/retire/quarantine/abort schedules (the tests' oracle).

Greedy outputs are BIT-IDENTICAL to the colocated paged engine: per
request, the forward is row-independent, the prefill computes the same
K/V from the same (tokens, positions, params), and the page copy is
bitwise — scheduling differences cannot change a token. The colocated
engine is therefore the standing parity oracle (tests, bench row,
gateway smoke).

Slice sizing reads the per-program HBM rows the memory tier pins in
``tools/hbm_budget.json`` (``paged_prefill_step`` vs
``paged_decode_step``):
``plan_slice_split`` splits the fleet proportional to per-phase peak
memory, which on the 8-virtual-device CPU mesh lands on 4+4. An
explicit ``"prefill:decode"`` spec overrides.
"""

from __future__ import annotations

import json
import os
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

# ---- spec parsing / slice planning (pure host, importable cheaply) ----

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DEFAULT_HBM_BUDGET = os.path.join(_REPO_ROOT, "tools", "hbm_budget.json")


def parse_disagg_spec(spec: Any) -> Optional[Tuple[int, int]]:
    """``"P:D"`` -> ``(P, D)`` device counts; ``""``/``"auto"`` -> None
    (budget-driven sizing via ``plan_slice_split``). The single grammar
    home for ``scripts/serve.py --disagg`` and
    ``config.ServingArguments.serve_disagg``."""
    s = str(spec).strip().lower()
    if s in ("", "auto", "none"):
        return None
    parts = s.split(":")
    err = (f"disagg spec must be 'prefill:decode' device counts "
           f"(e.g. '4:4') or 'auto', got {spec!r}")
    if len(parts) != 2:
        raise ValueError(err)
    try:
        n_p, n_d = int(parts[0]), int(parts[1])
    except ValueError:
        raise ValueError(err) from None
    if n_p < 1 or n_d < 1:
        raise ValueError(
            f"each slice needs >= 1 device, got {spec!r}")
    return n_p, n_d


def _budget_peak(entries: Dict[str, Any], *names: str) -> Optional[float]:
    for name in names:
        try:
            return float(entries[name]["peak_mb"])
        except (KeyError, ValueError, TypeError):
            continue
    return None


def plan_slice_split(
    num_devices: int,
    *,
    budget_path: Optional[str] = None,
) -> Tuple[int, int]:
    """Size the two slices from the CI-attested per-phase HBM rows:
    devices split proportional to ``peak_mb`` of the prefill-slice vs
    decode-slice programs (the ``disagg_*`` rows the manifest entries
    below pin; the colocated ``paged_prefill_step``/
    ``paged_decode_step`` rows are the fallback), each slice getting at least one device. A
    missing or unreadable budget falls back to an even split — sizing
    degrades, correctness doesn't."""
    if num_devices < 2:
        raise ValueError(
            f"disaggregation needs >= 2 devices (one per slice), "
            f"got {num_devices}")
    w_p = w_d = 1.0
    path = budget_path or DEFAULT_HBM_BUDGET
    try:
        with open(path) as f:
            entries = json.load(f)["entries"]
    except (OSError, ValueError):
        entries = {}
    w_p = _budget_peak(entries, "disagg_prefill_slice",
                       "paged_prefill_step") or 1.0
    w_d = _budget_peak(entries, "disagg_decode_slice",
                       "paged_decode_step") or 1.0
    n_p = int(round(num_devices * w_p / (w_p + w_d)))
    n_p = max(1, min(num_devices - 1, n_p))
    return n_p, num_devices - n_p


# jax-dependent imports AFTER the pure helpers: config-time callers of
# `parse_disagg_spec` go through a lazy import, everything below is the
# engine half
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from scaletorch_tpu.inference.engine import (  # noqa: E402
    EngineMetrics,
    InferenceEngine,
    Request,
)
from scaletorch_tpu.inference.kv_cache import (  # noqa: E402
    TRASH_PAGE,
    PageAllocator,
    carries_state,
    ceil_div,
    init_paged_kv_cache,
    latent_of,
    window_of,
)
from scaletorch_tpu.telemetry.histogram import LogHistogram  # noqa: E402
from scaletorch_tpu.telemetry.spans import span  # noqa: E402
from scaletorch_tpu.utils.logger import get_logger  # noqa: E402

logger = get_logger()


class HandoffError(RuntimeError):
    """A page transfer failed in flight (injected in drills; a real ICI
    transport fault on hardware). The engine converts it into exactly
    one ``aborted`` terminal result with both pools conserved."""


class PageHandoffChannel:
    """Moves filled K/V pages from the prefill slice to the decode
    slice.

    ``transfer`` gathers the source pages on the prefill slice (an
    eager device-side take — the host never sees the bytes) and commits
    them to the decode slice's placement with ``jax.device_put``. On
    the CPU simulation mesh that is a buffer copy; on hardware the SAME
    call lowers to an ICI device-to-device transfer — this seam is the
    only line that changes for a real fabric. Byte/page accounting and
    the fault-injection hook live here so drills and gauges share one
    counter set."""

    def __init__(self, dst_sharding: Optional[Any] = None) -> None:
        self.dst_sharding = dst_sharding
        self.transfers = 0
        self.pages_transferred = 0
        self.bytes_transferred = 0
        self.failures = 0
        self._fail_next = 0

    def fail_next(self, n: int = 1) -> None:
        """Drill hook: the next ``n`` transfers raise ``HandoffError``
        (the mid-handoff crash the conservation tests interleave)."""
        self._fail_next += n

    def transfer(self, src_cache, src_pages: List[int]):
        """Returns ``(k_pages, v_pages, nbytes)`` with both page blocks
        committed to ``dst_sharding`` — shape [L, n, H_kv, page, D]."""
        if self._fail_next > 0:
            self._fail_next -= 1
            self.failures += 1
            raise HandoffError("injected handoff transport fault")
        idx = jnp.asarray(np.asarray(src_pages, np.int32))
        k = src_cache.k[:, idx]
        v = src_cache.v[:, idx]
        if self.dst_sharding is not None:
            k = jax.device_put(k, self.dst_sharding)
            v = jax.device_put(v, self.dst_sharding)
        nbytes = int(k.nbytes + v.nbytes)
        self.transfers += 1
        self.pages_transferred += len(src_pages)
        self.bytes_transferred += nbytes
        return k, v, nbytes


@dataclass
class DisaggMetrics(EngineMetrics):
    """EngineMetrics plus the per-slice health the phase split creates:
    slice sizes, the prefill pool's occupancy (the decode pool rides the
    base gauges), handoff counters/bytes and per-slice busy fractions
    (host wall attributed to each slice's program since the engine
    was built). ``snapshot()`` stays flat numeric, so every key reaches
    /metrics as an ``engine_*`` gauge and JSONL consumers unchanged."""

    prefill_slice_devices: int = 0
    decode_slice_devices: int = 0
    prefill_pages_in_use: int = 0
    prefill_pool_free: int = 0
    handoffs: int = 0
    handoff_failures: int = 0
    pages_handed_off: int = 0
    handoff_bytes: int = 0
    prefill_busy_s: float = 0.0
    decode_busy_s: float = 0.0
    _started_t: float = field(default_factory=time.monotonic)

    def __post_init__(self) -> None:
        # handoff latency (prefill-done -> decode-slot bound): queueing
        # for a free slot/pages plus the wire
        self.hist["handoff"] = LogHistogram()

    def busy_fractions(self) -> Tuple[float, float]:
        dt = time.monotonic() - self._started_t
        if dt <= 0:
            return 0.0, 0.0
        return (min(1.0, self.prefill_busy_s / dt),
                min(1.0, self.decode_busy_s / dt))

    def snapshot(self) -> Dict[str, float]:
        snap = super().snapshot()
        busy_p, busy_d = self.busy_fractions()
        snap.update({
            "prefill_slice_devices": self.prefill_slice_devices,
            "decode_slice_devices": self.decode_slice_devices,
            "prefill_pages_in_use": self.prefill_pages_in_use,
            "prefill_pool_free": self.prefill_pool_free,
            "handoffs": self.handoffs,
            "handoff_failures": self.handoff_failures,
            "pages_handed_off": self.pages_handed_off,
            "handoff_bytes": self.handoff_bytes,
            "prefill_slice_busy_fraction": busy_p,
            "decode_slice_busy_fraction": busy_d,
        })
        return snap


class _PendingHandoff:
    """A request between phases: prefilled (first token already emitted
    to the stream), holding prefill-pool pages, waiting for a decode
    slot + decode-pool pages."""

    __slots__ = ("req", "pages", "first_token", "prefill_s",
                 "first_token_t", "ready_t")

    def __init__(self, req: Request, pages: List[int], first_token: int,
                 prefill_s: float, first_token_t: float,
                 ready_t: float) -> None:
        self.req = req
        self.pages = pages
        self.first_token = first_token
        self.prefill_s = prefill_s
        self.first_token_t = first_token_t
        self.ready_t = ready_t


class DisaggregatedEngine(InferenceEngine):
    """The colocated paged engine with its prefill phase lifted onto a
    separate device slice.

    The base class remains the DECODE side unchanged: pool, allocator,
    radix tree, page tables, slots, the jitted decode step and the tick
    loop — ``step()`` is inherited, only the admission hooks
    (``_tick_device`` / ``_admit`` / ``_expire`` / ``cancel`` /
    ``_abort_pending``) are reinterpreted as the phase scheduler:

      1. handoff sweep — bind prefilled requests into free decode slots
         by decode-pool budget (FIFO; all-or-nothing reservation);
      2. prefill admission — admit queued requests into the prefill
         slice by PREFILL-pool budget, one batched prefill call, first
         tokens emitted (or poison prompts quarantined) right here;
      3. second handoff sweep — a request prefilled this tick can reach
         a decode slot the same tick, matching the colocated engine's
         admit-then-decode cadence.

    Parameters beyond ``InferenceEngine``: ``devices`` (default the
    whole fleet), ``disagg_split`` (``(P, D)`` tuple, ``"P:D"`` string,
    or None = ``plan_slice_split`` over ``budget_path``),
    ``prefill_pool_pages`` (prefill-side scratch pool; default sizes
    ``max_slots`` full prompts + trash page) and ``channel`` (a
    ``PageHandoffChannel``, injectable for drills).

    The parameters stay as the caller laid them here: each slice places
    its own copy of the caller's tree on its own devices below, after
    the base class is built, so the colocated engine's placement
    (``InferenceEngine._param_orders``: the order of dimensions the
    compiler chooses for the decode program) is not asked for; no
    benchmark cell runs this engine."""

    # ``_timed_decode`` below waits for every step it dispatches: a
    # readback finds the decode slice idle, so a step's tokens wait for
    # the next dispatch, whose wait gives their consumers the
    # interpreter, and this thread never stands aside for them
    _DISPATCH_BLOCKS = True

    def _param_orders(self, params, steps):
        return None

    def __init__(self, params, cfg, *,
                 devices: Optional[List[Any]] = None,
                 disagg_split: Any = None,
                 budget_path: Optional[str] = None,
                 prefill_pool_pages: Optional[int] = None,
                 channel: Optional[PageHandoffChannel] = None,
                 **kw) -> None:
        if kw.get("mesh") is not None:
            raise ValueError(
                "DisaggregatedEngine owns its slice meshes; pass "
                "devices/disagg_split instead of mesh")
        if carries_state(cfg):
            raise NotImplementedError(
                f"DisaggregatedEngine: {type(cfg).__name__} has "
                "state-carrying layers, and what is missing is the "
                "hand-off of a request's recurrent state from the "
                "prefill slice to the decode slice (the channel moves "
                "pages; a state has none, and no snapshots at page "
                "boundaries exist to move instead)")
        if window_of(cfg) is not None:
            raise NotImplementedError(
                f"DisaggregatedEngine: {type(cfg).__name__} has "
                "window-attention layers, whose K/V is kept by slot in a "
                "ring; what is missing is the hand-off of a request's "
                "rings from the prefill slice to the decode slice (the "
                "channel moves the full-attention layers' pages only)")
        if latent_of(cfg):
            raise NotImplementedError(
                f"DisaggregatedEngine: {type(cfg).__name__} has latent "
                "attention; what is missing is the hand-off of latent "
                "pages (one pool of [c | k_r] rows, where the channel "
                "moves a K page and a V page) from the prefill slice to "
                "the decode slice")
        devs = list(devices) if devices is not None else list(jax.devices())
        if isinstance(disagg_split, str):
            disagg_split = parse_disagg_spec(disagg_split)
        if disagg_split is None:
            disagg_split = plan_slice_split(
                len(devs), budget_path=budget_path)
        n_p, n_d = disagg_split
        if n_p < 1 or n_d < 1:
            raise ValueError(
                f"each slice needs >= 1 device, got {n_p}:{n_d}")
        if n_p + n_d > len(devs):
            raise ValueError(
                f"slice spec {n_p}:{n_d} needs {n_p + n_d} devices but "
                f"only {len(devs)} are visible")
        prefill_devs = devs[:n_p]
        decode_devs = devs[n_p:n_p + n_d]

        super().__init__(params, cfg, **kw)
        # the prefill slice's own call (``_prefill_admit``) runs the one
        # full shape, its rows in admission order
        self.prefill_shapes = self.prefill_shapes[-1:]

        # two disjoint 1-D meshes; replicated placement per slice (the
        # CPU simulation shape — TP within a slice layers on via the
        # kv_cache sharding helpers once slices grow past one program
        # copy)
        self.prefill_mesh = Mesh(np.array(prefill_devs), ("slice",))
        self.decode_mesh = Mesh(np.array(decode_devs), ("slice",))
        self._prefill_place = NamedSharding(self.prefill_mesh, P())
        self._decode_place = NamedSharding(self.decode_mesh, P())
        # MPMD placement: decode program state on the decode slice, a
        # second param copy + scratch pool on the prefill slice. jit
        # follows committed operands — each program compiles once for
        # its slice and never again.
        self.params = jax.device_put(self.params, self._decode_place)
        self.cache = jax.device_put(self.cache, self._decode_place)
        self._token_home = self._decode_place if n_d > 1 else None
        self._params_prefill = jax.device_put(params, self._prefill_place)

        # prefill-side scratch pool: PROMPT pages only — a request's
        # generation pages exist solely on the decode side
        prompt_pages_max = ceil_div(self.prefill_len, self.page_size)
        if prefill_pool_pages is None:
            prefill_pool_pages = self.max_slots * prompt_pages_max + 1
        if prefill_pool_pages < prompt_pages_max + 1:
            raise ValueError(
                f"prefill_pool_pages {prefill_pool_pages} cannot hold "
                f"one max-length prompt ({prompt_pages_max} pages + "
                f"trash page)")
        self.prefill_num_pages = prefill_pool_pages
        self.prefill_cache = init_paged_kv_cache(
            cfg, prefill_pool_pages, self.page_size,
            dtype=self.cache.k.dtype, sharding=self._prefill_place)
        self.prefill_allocator = PageAllocator(prefill_pool_pages)
        self._prefill_keys = np.zeros((self.max_slots, 2), np.uint32)
        self._handoff: deque[_PendingHandoff] = deque()
        self.channel = channel if channel is not None \
            else PageHandoffChannel(self._decode_place)
        if self.channel.dst_sharding is None:
            self.channel.dst_sharding = self._decode_place

        # decode busy attribution: wrap the jitted step, keep the
        # compiled callable reachable for the compile-count attestation
        self._decode_jit = self._decode

        def _timed_decode(*args):
            t0 = time.monotonic()
            out = self._decode_jit(*args)
            # the tick loop syncs on these outputs immediately after
            # (np.asarray on the sampled tokens), so blocking here just
            # moves that sync inside the busy window
            jax.block_until_ready(out[0])
            self.metrics.decode_busy_s += time.monotonic() - t0
            return out

        self._decode = _timed_decode

        metrics = DisaggMetrics(num_slots=self.max_slots)
        metrics.prefill_slice_devices = n_p
        metrics.decode_slice_devices = n_d
        self.metrics = metrics
        self._update_page_gauges()
        self._exported_key = self._export_key()
        logger.info(
            "disaggregated engine: prefill slice %d device(s) "
            "(%d-page pool), decode slice %d device(s) (%d-page pool)",
            n_p, prefill_pool_pages, n_d, self.num_pages)

    # ---- compile accounting (wrapper-aware) --------------------------
    @property
    def decode_compile_count(self) -> int:
        return self._decode_jit._cache_size()

    # ---- conservation (both pools) -----------------------------------
    def check_conservation(self) -> None:
        """Green iff NEITHER pool leaked: free + allocated == capacity
        and positive refcounts on both allocators."""
        self.allocator.check_conservation()
        self.prefill_allocator.check_conservation()

    @property
    def pending(self) -> int:
        return (len(self._queue) + len(self._handoff)
                + sum(s.active for s in self._slots))

    def _update_page_gauges(self) -> None:
        super()._update_page_gauges()
        alloc = getattr(self, "prefill_allocator", None)
        if alloc is not None and isinstance(self.metrics, DisaggMetrics):
            self.metrics.prefill_pages_in_use = alloc.used_count
            self.metrics.prefill_pool_free = alloc.free_count

    # ---- phase scheduler ---------------------------------------------
    def _admission_due(self) -> bool:
        # either phase may have work; the sweeps decide what fits
        return bool(self._queue or self._handoff)

    def _tick_device(self, flight) -> None:
        """This engine keeps its own order around an admission: a tick
        with one due reads the step in flight FIRST, runs the phase
        scheduler (``_admit``, which reads its prefill slice's result
        back and binds decode slots with their first token on the
        host), and leaves the next step, fed from the host, in flight.
        The colocated engine's order (the prefill call behind the step
        in flight, the next step behind the call, nothing read before
        both are dispatched) has nothing to win here: the two phases
        run on different devices, the first token crosses slices
        through the host, and the decode wrapper above blocks on every
        step it dispatches."""
        read = False
        if flight is not None and self._admission_due():
            self._read(flight)
            flight, read = None, True
        self._admit()
        if flight is None:
            flight = self._dispatch(None)
        if flight is not None and not read:
            self._in_flight = self._dispatch(flight)
            self._read(flight)
            self._drop_dead_flight()  # its streams may have ended here
        else:
            self._in_flight = flight

    def _admit(self) -> None:
        """The phase scheduler: blocking, nothing left unread (see
        ``_tick_device``)."""
        with span("handoff", self.tracer, pending=len(self._handoff)):
            self._handoff_sweep(time.monotonic())
        self._prefill_admit()
        if self._handoff:
            # same-tick pipeline: a request prefilled above reaches a
            # decode slot before this tick's decode step, exactly the
            # colocated admit-then-decode cadence
            with span("handoff", self.tracer, pending=len(self._handoff)):
                self._handoff_sweep(time.monotonic())

    def _expire(self, now: float) -> None:
        super()._expire(now)
        if self._handoff:
            kept: deque[_PendingHandoff] = deque()
            for h in self._handoff:
                if (h.req.deadline is not None
                        and now >= h.req.deadline):
                    self._drop_handoff(
                        h, "timeout",
                        detail="deadline exceeded awaiting handoff",
                        now=now)
                else:
                    kept.append(h)
            self._handoff = kept

    def cancel(self, request_id: int, *,
               detail: str = "cancelled by client") -> bool:
        now = time.monotonic()
        for h in self._handoff:
            if h.req.request_id == request_id:
                self._handoff.remove(h)
                self._drop_handoff(h, "aborted", detail=detail, now=now)
                return True
        return super().cancel(request_id, detail=detail)

    def _abort_pending(self, detail: str) -> None:
        now = time.monotonic()
        while self._handoff:
            self._drop_handoff(
                self._handoff.popleft(), "aborted", detail=detail,
                now=now)
        super()._abort_pending(detail)

    def _drop_handoff(self, h: _PendingHandoff, outcome: str, *,
                      detail: str, now: float) -> None:
        """Mid-handoff death: release the prefill-side pages and record
        the request's single terminal result (its already-streamed first
        token attached). The decode side holds nothing yet — exactly one
        outcome, zero leaks on either pool."""
        for p in h.pages:
            self.prefill_allocator.release(p)
        self._req_event("e", h.req, "req.handoff", outcome=outcome)
        self._finalize(
            h.req, outcome, tokens=[h.first_token], detail=detail,
            ttft_t=h.first_token_t, prefill_s=h.prefill_s, now=now)
        self._update_page_gauges()

    def warm_prefill_shapes(self) -> None:
        raise NotImplementedError(
            "the prefill slice compiles its one shape at its first "
            "admission")

    # ---- phase 1: prefill slice --------------------------------------
    def _prefill_admit(self) -> None:
        """Admit queued requests into the prefill slice by PREFILL-pool
        budget — one batched prefill call for everything admitted this
        tick, first tokens emitted (streamed) straight from the slice,
        poison prompts quarantined with their pool lines cleared."""
        if not self._queue:
            return
        self._release_tokens()  # no held token waits for a prefill call
        b = self.max_slots
        admitted: List[Tuple[int, Request, List[int]]] = []
        tokens = np.zeros((b, self.prefill_len), np.int32)
        tail_lens = np.ones(b, np.int32)
        starts = np.zeros(b, np.int32)
        write_mask = np.zeros(b, bool)
        tables = np.full((b, self._pages_per_slot), TRASH_PAGE, np.int32)
        row = 0
        while row < b and self._queue:
            req = self._queue[0]
            n_pages = ceil_div(len(req.prompt), self.page_size)
            pages = self.prefill_allocator.alloc(n_pages)
            if pages is None:
                break  # prefill-pool budget: head of the line waits
            self._queue.popleft()
            req.admit_time = time.monotonic()
            self.metrics.hist["queue_wait"].observe(
                req.admit_time - req.submit_time)
            self._req_event("e", req, "req.queued")
            self._req_event("n", req, "req.admitted", slot=row,
                            slice="prefill")
            self.metrics.requests_admitted += 1
            tokens[row, :len(req.prompt)] = req.prompt
            tail_lens[row] = len(req.prompt)
            write_mask[row] = True
            tables[row, :n_pages] = pages
            self._prefill_keys[row] = np.asarray(
                jax.random.PRNGKey(req.seed), np.uint32)
            admitted.append((row, req, pages))
            row += 1
        if not admitted:
            return
        t0 = time.monotonic()
        for _, req, _ in admitted:
            self._req_event("b", req, "req.prefill", slice="prefill")
        with self._phase("engine.tick.prefill"):
            first, _logits, finite, self.prefill_cache = self._prefill(
                self._params_prefill, jnp.asarray(tokens),
                jnp.asarray(tail_lens), jnp.asarray(starts),
                jnp.asarray(write_mask), jnp.asarray(tables),
                self.prefill_cache, jnp.asarray(self._prefill_keys))
        self.metrics.prefill_calls += 1
        # the slice holds no prefix: every row starts at position 0
        self.metrics.prefill_calls_self_attended += 1
        self.metrics.prefill_positions_run += tokens.size
        self.metrics.prefill_positions_admitted += sum(
            len(req.prompt) for _, req, _ in admitted)
        with self._phase("engine.tick.prefill_wait"):
            first = np.asarray(first)
            finite = np.asarray(finite)
        now = time.monotonic()
        prefill_s = now - t0
        self.metrics.prefill_busy_s += prefill_s
        poison_mask = np.zeros(self.prefill_num_pages, bool)
        poisoned: List[Tuple[Request, List[int]]] = []
        for row, req, pages in admitted:
            self.metrics.hist["prefill"].observe(prefill_s)
            self._req_event("e", req, "req.prefill")
            if not finite[row]:
                poison_mask[pages] = True
                poisoned.append((req, pages))
                continue
            self._finish_prefill(req, pages, int(first[row]),
                                 prefill_s, now)
        if poisoned:
            # the NaN K/V must not outlive the request on THIS pool
            # either — same masked clear quarantine uses on the decode
            # pool, compiled once per pool shape
            self.prefill_cache = self._fill_slots(
                self.prefill_cache, jnp.asarray(poison_mask),
                jnp.asarray(0.0, jnp.float32))
            for req, pages in poisoned:
                for p in pages:
                    self.prefill_allocator.release(p)
                self._finalize(
                    req, "quarantined", tokens=[],
                    detail="non-finite logits at prefill",
                    prefill_s=prefill_s, now=now)
        self._update_page_gauges()
        self.metrics.queue_depth = len(self._queue)

    def _finish_prefill(self, req: Request, pages: List[int],
                        token: int, prefill_s: float,
                        now: float) -> None:
        """Healthy prefill: stream the first token, then either finish
        the request outright (stop condition at token one — no decode
        phase needed) or queue it for handoff."""
        self.metrics.tokens_generated += 1
        self.metrics.record_ttft(now - req.submit_time)
        if self.on_tokens is not None:
            self.metrics.tokens_handed_at_readback += 1
            try:
                self.on_tokens(-1, req.request_id, [token], now)
            except Exception:
                logger.exception(
                    "on_tokens hook raised; disarming the hook")
                self.on_tokens = None
        reason = None
        if req.eos_id is not None and token == req.eos_id:
            reason = "eos"
        elif req.max_new_tokens <= 1:
            reason = "length"
        elif len(req.prompt) + 1 >= self.max_seq:
            reason = "max_seq"
        if reason is not None:
            for p in pages:
                self.prefill_allocator.release(p)
            self._finalize(req, "ok", tokens=[token], reason=reason,
                           ttft_t=now, prefill_s=prefill_s, now=now)
            return
        self._handoff.append(_PendingHandoff(
            req, pages, token, prefill_s, now, now))
        self._req_event("b", req, "req.handoff")

    # ---- phase 2: the wire -------------------------------------------
    def _handoff_sweep(self, now: float) -> None:
        """Bind prefilled requests into free decode slots, FIFO. The
        head blocks on decode-pool budget (pages free as slots retire);
        a transport fault finalizes the head and the sweep continues."""
        while self._handoff:
            free = [i for i, s in enumerate(self._slots) if not s.active]
            if not free:
                return
            status = self._try_handoff(free[0], self._handoff[0], now)
            if status == "wait":
                return
            self._handoff.popleft()

    def _try_handoff(self, i: int, h: _PendingHandoff,
                     now: float) -> str:
        """All-or-nothing ownership flip for one request: reserve on the
        decode pool (radix prefix shared, rest allocated — identical
        math to colocated admission), move only the NON-SHARED prompt
        pages over the wire, register the prompt prefix frozen in the
        decode radix, bind the slot, release the prefill pages. Any
        failure rolls the decode-side reservation back whole. Returns
        'done' | 'wait' | 'failed'."""
        req = h.req
        plen = len(req.prompt)
        ps = self.page_size
        reserved = self._reserve_pages(req)
        if reserved is None:
            return "wait"
        shared, pages = reserved
        n_shared = shared // ps
        prompt_pages = ceil_div(plen, ps)
        src = h.pages[n_shared:prompt_pages]
        dst = pages[n_shared:prompt_pages]
        try:
            k_pages, v_pages, nbytes = self.channel.transfer(
                self.prefill_cache, src)
        except HandoffError as exc:
            for p in pages:
                self.allocator.release(p)
            for p in h.pages:
                self.prefill_allocator.release(p)
            self.metrics.handoff_failures += 1
            self._req_event("e", req, "req.handoff", error=str(exc))
            self._finalize(
                req, "aborted", tokens=[h.first_token],
                detail=f"page handoff failed: {exc}",
                ttft_t=h.first_token_t, prefill_s=h.prefill_s, now=now)
            self._update_page_gauges()
            return "failed"
        # scatter the transferred pages into the decode pool (eager
        # update on the committed pool — on hardware this becomes the
        # donated in-place write the ICI transfer lands into)
        dst_idx = jnp.asarray(np.asarray(dst, np.int32))
        self.cache = type(self.cache)(
            self.cache.k.at[:, dst_idx].set(k_pages),
            self.cache.v.at[:, dst_idx].set(v_pages))
        # destination registered before the source releases: the pages
        # are never owned by zero allocators
        slot = self._slots[i]
        slot.request = req
        slot.tokens = list(req.prompt) + [h.first_token]
        slot.position = plen
        slot.generated = 1
        slot.first_token_t = h.first_token_t
        slot.last_token_t = h.first_token_t
        slot.prefill_s = h.prefill_s
        slot.prefix_hit = shared > 0
        self._slot_pages[i] = pages
        self._slot_frozen[i] = n_shared
        self._tables[i, :] = TRASH_PAGE
        self._tables[i, :len(pages)] = pages
        self._tables_dev = None
        self._base_keys[i] = np.asarray(
            jax.random.PRNGKey(req.seed), np.uint32)
        self._base_keys_dev = None
        if shared:
            self.metrics.prefix_hits += 1
        if self.radix is not None:
            # the page-aligned prompt prefix was written once by a
            # healthy prefill and is immutable from here on: register
            # it frozen (shareable, exempt from quarantine clears,
            # evictable at refcount zero like any chain)
            frozen = (plen // ps) * ps
            if frozen:
                n = frozen // ps
                self.radix.insert(req.prompt[:frozen],
                                  [int(p) for p in pages[:n]])
                self._slot_frozen[i] = n
        for p in h.pages:
            self.prefill_allocator.release(p)
        done = time.monotonic()
        self.metrics.handoffs += 1
        self.metrics.pages_handed_off += len(src)
        self.metrics.handoff_bytes += nbytes
        self.metrics.hist["handoff"].observe(done - h.ready_t)
        self._req_event("e", req, "req.handoff", pages=len(src),
                        shared_tokens=shared)
        self._req_event("b", req, "req.decode", slot=i, slice="decode")
        self._update_page_gauges()
        return "done"

    # ---- export ------------------------------------------------------
    def _export_snapshot(self) -> None:
        made_progress = self._export_key() != self._exported_key
        super()._export_snapshot()
        if made_progress and self.exporter is not None:
            m = self.metrics
            busy_p, busy_d = m.busy_fractions()
            self.exporter.emit("disagg", {
                "prefill_slice_devices": m.prefill_slice_devices,
                "decode_slice_devices": m.decode_slice_devices,
                "handoffs": m.handoffs,
                "handoff_failures": m.handoff_failures,
                "pages_handed_off": m.pages_handed_off,
                "handoff_bytes": m.handoff_bytes,
                "prefill_pages_in_use": m.prefill_pages_in_use,
                "prefill_pool_free": m.prefill_pool_free,
                "prefill_slice_busy_fraction": busy_p,
                "decode_slice_busy_fraction": busy_d,
            })


# ---- jaxlint deep/memory-tier audit targets --------------------------


def audit_entry_prefill_slice():
    """Deep-tier audit target: the PREFILL slice's single program — the
    same jitted paged prefill step the colocated engine runs, as the
    disaggregated engine calls it (full-prompt prefill into a
    prompt-pages pool), attested under the disagg name. Contract: pool
    donation survives lowering (ST702/ST1002), the single-device
    program compiles to ZERO collectives (the comm budget pins an empty
    row; slice-internal TP would add axes here, cross-slice traffic
    rides the handoff channel, never a collective), and the pinned
    ``kv_cache`` geometry matches the compiled pool buffer (ST1005) —
    the per-phase ``peak_mb`` row this writes into
    ``tools/hbm_budget.json`` is what ``plan_slice_split`` sizes the
    prefill slice by."""
    from scaletorch_tpu.inference.decode import audit_entry_paged_prefill

    entry = audit_entry_paged_prefill()
    entry["name"] = "disagg_prefill_slice"
    entry["file"] = "scaletorch_tpu/inference/disagg.py"
    return entry


def audit_entry_decode_slice():
    """Deep-tier audit target: the DECODE slice's single program — the
    same jitted paged decode step the colocated engine runs (the slice
    changes placement, never the program), attested under the disagg
    name so its ``peak_mb`` row sizes the decode slice in
    ``plan_slice_split`` and a drift in EITHER phase's footprint moves
    the CI-pinned split, not a hand-edited constant."""
    from scaletorch_tpu.inference.decode import audit_entry_paged_decode

    entry = audit_entry_paged_decode()
    entry["name"] = "disagg_decode_slice"
    entry["file"] = "scaletorch_tpu/inference/disagg.py"
    return entry
