"""TPU-native inference: KV-cache decode, continuous batching, sharded serving.

The serving half of the framework (ROADMAP north star: "serves heavy
traffic from millions of users"), reusing the training stack's mesh, TP
sharding specs, and attention math:

  * ``kv_cache``  — KV caches in the models' scan layout: the page
    pool the engine serves from — a global pool of fixed-size pages
    ``[L, n_pages, Hkv, page_size, D]`` with per-slot page tables, a
    host-side ``PageAllocator`` (free list + refcounts) and a
    ``RadixPrefixCache`` sharing page-aligned prompt prefixes across
    requests, head-sharded with the existing TP NamedSharding specs —
    plus the contiguous ``[L, B, Hkv, S_max, D]`` reference cache and
    the MLA latent-only cache.
  * ``decode``    — the jitted steps (prompt-tail prefill, single-
    token decode) over the models' cache-aware forwards; static
    shapes, donated pool, two compiles total.
  * ``sampling``  — greedy / temperature / top-k / top-p with per-slot
    PRNG keys.
  * ``engine``    — continuous batching over a fixed-slot batch: admit
    queued requests into freed slots between decode steps (the jitted
    step never retraces), engine metrics riding the monitor plumbing.
  * ``disagg``    — disaggregated prefill/decode serving: MPMD phase
    slices (two meshes over disjoint device subsets, one jitted
    program each) with page-ownership handoff between two allocators
    through a ``PageHandoffChannel``; slice sizing from the CI-pinned
    per-phase HBM rows.
  * ``resilience`` — serving fault tolerance: the terminal-outcome
    taxonomy (ok / timeout / shed / rejected / quarantined / aborted),
    bounded admission + load shedding, non-finite quarantine, graceful
    drain, the serving stall watchdog (exit code 44), and the
    ``ServingFaultInjector`` driving hermetic end-to-end drills.
"""

from scaletorch_tpu.inference.kv_cache import (  # noqa: F401
    KVCache,
    MLACache,
    PageAllocator,
    PagedKVCache,
    PagedKVIO,
    RadixPrefixCache,
    cache_nbytes,
    init_kv_cache,
    init_mla_cache,
    init_paged_kv_cache,
    kv_cache_bytes,
    kv_cache_shape,
    paged_kv_cache_shape,
    paged_kv_cache_shardings,
    paged_kv_cache_specs,
)
from scaletorch_tpu.inference.sampling import (  # noqa: F401
    SamplingParams,
    sample,
    sample_one,
)
from scaletorch_tpu.inference.decode import (  # noqa: F401
    make_fill_slots_step,
    make_paged_decode_step,
    make_paged_prefill_step,
    resolve_forward_cached,
)
from scaletorch_tpu.inference.resilience import (  # noqa: F401
    SERVING_STALL_EXIT_CODE,
    TERMINAL_OUTCOMES,
    EngineDraining,
    ServingFaultInjector,
    make_serving_watchdog,
)
from scaletorch_tpu.inference.engine import (  # noqa: F401
    EngineMetrics,
    InferenceEngine,
    Request,
    RequestResult,
)
from scaletorch_tpu.inference.disagg import (  # noqa: F401
    DisaggMetrics,
    DisaggregatedEngine,
    HandoffError,
    PageHandoffChannel,
    parse_disagg_spec,
    plan_slice_split,
)
