"""KV-cache containers in the models' stacked-scan layout.

The serving engine keeps ONE cache layout, the page pool:
``PagedKVCache`` is a global pool of fixed-size pages
``[L, n_pages, Hkv, page_size, D]`` with the layer axis leading — the
same stacked layout the training params use, so the cached forward
carries the pool through its layer loop (models/llama.py
forward_cached) and compile time stays O(1) in depth — plus per-slot
page tables (``[B, max_pages]`` int32, TRASH_PAGE-padded). Slots
reserve only the pages their request can actually touch — HBM scales
with tokens cached, not ``B × S_max`` — and requests sharing a token
prefix share pages: ``PageAllocator`` (host-side free list + refcounts)
and ``RadixPrefixCache`` (page-granular radix tree over token chunks)
keep the bookkeeping; ``PagedKVIO`` adapts the models' cache-aware
forwards to the pool: they carry it whole through their layer loop and
the adapter writes and reads it at a layer index
(ops/pallas/paged_attention.py holds the two pairs: the Mosaic page
write + decode kernel, in place, and the lax scatter + gather).

Sharding reuses the training stack's TP placement: K/V projections are
column-parallel over ``tp`` (tensor_parallel.llama_param_specs), so the
pool shards its KV-head axis over the same ``tp`` mesh axis
(``paged_kv_cache_specs``). Placement is declarative (NamedSharding +
device_put); the jitted steps run GSPMD — no shard_map needed.

The contiguous ``KVCache`` (``[L, B, Hkv, S_max, D]``, one row of
positions per sequence) is the REFERENCE: what the cached forwards read
and write by default (``layers.DenseKVIO``), what
``decode.teacher_forced_decode`` and ``gpt_moe.generate`` run on, and
what the parity tests hold the paged path to. The engine never builds
one.

A model with state-carrying layers (Olmo-Hybrid: gated delta-rule layers
between its full-attention layers) keeps two kinds of memory in ONE
cache pytree, ``HybridCache``: the page pool over its full-attention
layers only, and beside it, indexed by SLOT, the linear layers'
recurrent state ``f32[linear layers, slots, H, d_k, d_v]`` and their
convolution tail ``[linear layers, slots, kernel - 1, channels]``. A
slot's state has no pages, no table and no snapshots: it is whatever the
slot's request has read so far, started from zero by its prefill. A
state-space model (Jamba: Mamba-1 layers between two attention layers)
keeps the same pytree with a state of another shape, ``f32[mamba
layers, slots, N, R, 128]``, and a Mamba-2 model (granitemoehybrid:
nine Mamba-2 layers to one attention layer) a third, ``f32[mamba
layers, slots, N, H P]``: a head's ``[P, N]`` state matrix transposed
and the heads side by side, so that a decode step advances every slot
of a layer in one in-place pass over the donated buffer
(``ops/pallas/ssd_update.py``). The family's ``recurrent_state_shapes``
says which, and only ``[layers, slots]`` is common to them.

A model whose layers mix window and full attention (afmoe: three
``sliding_attention`` layers to one ``full_attention``) keeps its two
kinds of K/V in ONE cache pytree too, ``WindowCache``: the page pool
over its full-attention layers (pages by request, through the tables)
and beside it, by SLOT, a ring of ``window_ring_pages`` pages a slot
and window layer: position p lives in ring page ``(p // page_size) %
ring_pages`` of its slot. No table reaches the device for it:
``RingKVIO`` computes one inside the jitted step from the positions,
whose logical page ``t // page_size`` repeats the ring, so the same
``paged_write`` / ``paged_attention`` pair serves it, told the window
(the mask is by position, the kernel's walk starts at the window's
first page). What a slot's earlier request left in its ring is never
read: a position inside a request's window was written by that request.
The pool and the rings each have their own ``(K/V heads, key width as
stored, value width)`` (``kv_head_shapes``): afmoe's coincide; in
mimo_v2_flash a full layer has 4 K/V heads and a window layer 8, and a
key is 192 wide on a value of 128, stored at 256 (whole 128-lane tiles,
the trailing 64 zeros: ``stored_key_width``) beside the value's 128.
Where the family's prefill row names its slot (``rows_name_slots``)
``RingKVIO`` is handed the rows' slot ids and a one-row call writes the
rings of its slot.

A model with latent attention (pangu_ultra_moe: every layer) keeps
ONE row a token and layer, ``[c | k_r]``: the normed latent
(``kv_lora_rank`` wide: the key's first part AND the value) and the one
rotary key every head shares, rotated when written. That is the SERVED
latent cache, ``LatentCache``: one page pool ``[L, n_pages, 1,
page_size, row]`` addressed through the engine's tables, the row
``kv_lora_rank + qk_rope_head_dim`` padded with zeros to whole 128-lane
tiles, which a Mosaic copy of a page needs (512 + 64 -> 640 numbers a
token and layer as stored, 576 as written down: 1,280 B in bfloat16
where 128 expanded heads would be 81,920). ``paged_write`` writes the
row as it writes a K row; a decode step reads it in the absorbed form
(``PagedKVIO.attend_latent`` -> ``paged_attention.latent_attention``:
every query head against the one cached head, whose value is the row's
first ``kv_lora_rank`` columns). It is addressed by page (no state, no
ring), and refuses prefix sharing all the same (``no_prefix_reason``).

A model with latent attention AND state-carrying layers (kimi_linear:
Kimi Delta Attention layers between its latent layers) keeps both in
ONE ``HybridCache`` whose ``k`` is the latent pool over the latent
layers ``[latent layers, n_pages, 1, page_size, row]``, whose ``v`` is
ABSENT (None: a latent row is key and value at once) and whose
``state`` / ``conv`` are by slot as any state-carrying family's. No
fifth cache kind: every reader of ``HybridCache`` goes by field name,
and the two that walk all fields (the masked fill, the prefill step's
scatter by slot id) skip an absent one.

``MLACache`` (``[B, S_max, kv_rank]``, dense, no decoupled rotary key,
no norm on the latent) is the TEACHING variant's cache
(models/attention/variants.py MultiHeadLatentAttention): it re-expands
K/V per step, serves no model's forward, and the engine never builds
one.
"""

from __future__ import annotations

import math
from collections import deque
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from scaletorch_tpu.ops.pallas.paged_attention import (
    _LANES,
    TRASH_PAGE,
    latent_attention,
    paged_attention,
    paged_write,
)


class KVCache(NamedTuple):
    """Stacked per-layer contiguous cache buffers, each
    [L, B, Hkv, S_max, D]: the reference layout (module docstring).

    A NamedTuple so it is a pytree (jit/donate/scan-friendly) and
    unpacks as the plain ``(k, v)`` pair the models' cache-aware
    forwards consume.
    """

    k: jax.Array
    v: jax.Array


class MLACache(NamedTuple):
    """Latent-only dense cache [B, S_max, kv_rank] of the teaching
    variant (module docstring); the served one is ``LatentCache``."""

    latent: jax.Array


def kv_cache_shape(cfg, batch: int, max_seq: int) -> Tuple[int, ...]:
    """[L, B, Hkv, S_max, D] for a Llama-family config, or
    [L, B, H, S_max, D] for GPT-MoE (full per-head K/V)."""
    if latent_of(cfg):
        raise TypeError(
            f"{type(cfg).__name__} caches one latent row a token, no "
            "head's K/V: its cache is kv_cache.LatentCache "
            "(latent_cache_shape), served through the page pool only")
    if hasattr(cfg, "num_key_value_heads"):  # Llama / Qwen3 / Qwen3-MoE
        # a hybrid model keeps K/V for its full-attention layers only
        layers = getattr(cfg, "num_kv_cache_layers", cfg.num_hidden_layers)
        (heads, d_k, d_v), _ = kv_head_shapes(cfg)
        if d_k != d_v:
            raise TypeError(
                f"{type(cfg).__name__} stores keys {d_k} wide beside "
                f"values {d_v} wide: its K and V buffers differ in shape "
                "(paged_kv_cache_shapes); a contiguous cache for it is not "
                "written")
        return (layers, batch, heads, max_seq, d_k)
    if hasattr(cfg, "n_layer"):  # GPTMoEConfig
        return (cfg.n_layer, batch, cfg.n_head, max_seq, cfg.head_dim)
    raise TypeError(f"no KV-cache layout known for config {type(cfg).__name__}")


def kv_cache_bytes(
    cfg, num_pages: int, page_size: int, dtype: Any = None
) -> int:
    """Total footprint of the page pool (both buffers,
    ``[L, num_pages, Hkv, page_size, D]`` each) — the capacity-planning
    number the engine logs at startup and the memory audit holds the
    compiled programs to (ST1005)."""
    if page_size < 1:
        raise ValueError(f"page_size must be >= 1, got {page_size}")
    dt = jnp.dtype(dtype or getattr(cfg, "dtype", jnp.bfloat16))
    if latent_of(cfg):   # one pool, a row a token
        return math.prod(
            latent_cache_shape(cfg, num_pages, page_size)) * dt.itemsize
    return sum(math.prod(shape) for shape in paged_kv_cache_shapes(
        cfg, num_pages, page_size)) * dt.itemsize


def cache_nbytes(cache: Any) -> int:
    """Actual bytes of a cache pytree (arrays OR ShapeDtypeStructs) —
    the measured twin of :func:`kv_cache_bytes`. The jaxlint memory
    tier's ST1005 check (analysis/memory.py) and the quick-tier
    cross-check tests compare the two so the engine's page-budget
    admission math can never drift from what XLA actually allocates."""
    from scaletorch_tpu.utils.misc import tree_bytes

    return tree_bytes(cache)


def init_kv_cache(
    cfg,
    batch: int,
    max_seq: int,
    *,
    dtype: Any = None,
) -> Any:
    """Zeroed contiguous cache in the model's compute dtype (bf16 on
    TPU): the reference layout, for parity harnesses and
    single-sequence sampling (``gpt_moe.generate``), never the engine.
    A model with state-carrying layers gets a ``HybridCache`` whose K/V
    are contiguous and whose slots are the ``batch`` sequences."""
    shape = kv_cache_shape(cfg, batch, max_seq)
    dt = dtype or getattr(cfg, "dtype", jnp.bfloat16)
    k, v = jnp.zeros(shape, dt), jnp.zeros(shape, dt)
    if carries_state(cfg):
        return HybridCache(k, v, *_zero_recurrent_state(cfg, batch, dt))
    return KVCache(k=k, v=v)


def init_mla_cache(attn_cfg, batch: int, max_seq: int,
                   *, dtype: Any = None) -> MLACache:
    """Zeroed latent cache for an AttentionConfig with MLA ranks."""
    return MLACache(latent=jnp.zeros(
        (batch, max_seq, attn_cfg.kv_lora_rank), dtype or attn_cfg.dtype
    ))


# ---------------------------------------------------------------------------
# the page pool
# ---------------------------------------------------------------------------
def ceil_div(a: int, b: int) -> int:
    """Page-count rounding, shared by every pages-for-N-tokens site
    (engine admission, decode step shapes, bench sizing)."""
    return -(-a // b)


class PagedKVCache(NamedTuple):
    """Stacked page pools, each [L, n_pages, Hkv, page_size, D].

    The device half of the paged cache: a global pool of fixed-size
    pages shared by every slot. Which slot owns which page lives
    host-side (``PageAllocator`` + the engine's page tables) and reaches
    the device as DATA — page-table contents are ints, never shapes, so
    the jitted steps compile once regardless of admissions, prefix hits,
    quarantine clears, and frees.
    """

    k: jax.Array
    v: jax.Array


class HybridCache(NamedTuple):
    """The cache of a model with full-attention AND state-carrying
    layers, one pytree that the step programs donate and return: the
    page pools ``k`` / ``v`` ``[full layers, n_pages, Hkv, page_size,
    D]`` (where the attention is latent attention, kimi_linear: ``k``
    the latent pool ``[latent layers, n_pages, 1, page_size, row]`` and
    ``v`` None) and, indexed by slot and not by page, the recurrent ``state``
    (float32) and the convolution tail ``conv`` (the serving dtype), in
    whatever shapes the family's ``recurrent_state_shapes(slots)``
    returns: each begins ``[state-carrying layers, slots]`` and may
    have any rank after that. The gated delta rule's (Olmo-Hybrid,
    Qwen3-Next): a matrix per head, ``[linear layers, slots, H, d_k,
    d_v]``, and ``[linear layers, slots, kernel - 1, channels]``;
    Mamba-1's (Jamba): elementwise per channel with the channels on the
    lanes, ``[mamba layers, slots, N, R, 128]`` (``channels = R *
    128``), and ``[mamba layers, slots, d_conv - 1, channels]``;
    Mamba-2's (granitemoehybrid): a matrix per head, transposed and the
    heads side by side, ``[mamba layers, slots, N, H P]``, and ``[mamba
    layers, slots, d_conv - 1, H P + 2 N]`` (the convolution runs over
    ``x``, ``B`` and ``C``). Nothing
    but the family's own forward reads past the first two axes: the
    engine fills by slot (``decode.make_fill_slots_step`` masks axis 1)
    and counts bytes."""

    k: jax.Array
    v: Optional[jax.Array]
    state: jax.Array
    conv: jax.Array


class WindowCache(NamedTuple):
    """The cache of a model with window AND full-attention layers, one
    pytree that the step programs donate and return: the page pools
    ``k`` / ``v`` ``[full layers, n_pages, Hkv, page_size, D]`` (pages
    by request) and the rings ``wk`` / ``wv`` ``[window layers, 1 +
    slots * ring_pages, Hkv, page_size, D]`` (page 0 TRASH, then each
    slot's ring; module docstring). ``Hkv`` and ``D`` are the pool's in
    ``k`` / ``v`` and the rings' in ``wk`` / ``wv``, and a K buffer's
    ``D`` (the key as stored) need not be its V buffer's
    (``kv_head_shapes``)."""

    k: jax.Array
    v: jax.Array
    wk: jax.Array
    wv: jax.Array


class LatentCache(NamedTuple):
    """The cache of a model with latent attention, one pytree that the
    step programs donate and return: ``k`` ``[L, n_pages, 1, page_size,
    row]`` holds a cached token's ``[c | k_r | 0...]`` (module
    docstring): its key as the absorbed form reads it, and in the
    first ``kv_lora_rank`` columns its value. The field keeps the page
    pool's name because the engine's own reads (shape, dtype, placement)
    go by it; nothing expanded is ever stored."""

    k: jax.Array


def latent_of(cfg) -> bool:
    """Whether the model's attention is latent attention: its cache
    holds one row ``[c | k_r]`` a token and layer and nothing per
    head."""
    return hasattr(cfg, "kv_lora_rank")


def latent_row_width(cfg) -> int:
    """A cached row as stored: ``kv_lora_rank + qk_rope_head_dim``
    rounded up to whole 128-lane tiles (576 -> 640), which a Mosaic
    copy of a page wants (``paged_attention.kernel_serves``)."""
    return ceil_div(cfg.kv_lora_rank + cfg.qk_rope_head_dim, _LANES) * _LANES


def latent_cache_shape(cfg, num_pages: int, page_size: int
                       ) -> Tuple[int, ...]:
    """The one pool of a ``LatentCache``."""
    return (cfg.num_kv_cache_layers, num_pages, 1, page_size,
            latent_row_width(cfg))


def latent_cache_bytes(cache: Any) -> int:
    """Bytes of a cache's latent pool (a ``LatentCache``'s, or the
    ``k`` of a ``HybridCache`` without a ``v``); 0 for any other
    cache."""
    latent = isinstance(cache, LatentCache) or (
        isinstance(cache, HybridCache) and cache.v is None)
    return cache.k.nbytes if latent else 0


# the fields of a cache whose axis 1 counts SLOTS (every other field's
# counts pages): what a masked fill over slots touches
SLOT_FIELDS = ("state", "conv")
# the fields whose axis 1 counts a TRASH page and then every slot's
# ring of pages: a masked fill over slots touches a slot's whole ring
RING_FIELDS = ("wk", "wv")


def window_of(cfg) -> Optional[int]:
    """The window of a model's window-attention layers; None for a model
    without any (its cache is the page pool alone)."""
    return cfg.sliding_window if hasattr(cfg, "num_window_layers") else None


def stored_key_width(width: int) -> int:
    """A key ``width`` wide as a pool stores it: whole 128-lane tiles
    (192 -> 256, the trailing numbers zeros), which a Mosaic copy of a
    page needs (``paged_attention.kernel_serves``). One layout on every
    platform: the CPU's scatter and gather read the same rows."""
    return ceil_div(width, _LANES) * _LANES


def kv_head_shapes(cfg) -> Tuple[Tuple[int, int, int], Tuple[int, int, int]]:
    """``(K/V heads, key width as stored, value width)`` of the page
    pool's layers and of the window layers' rings: the family's own
    where its config says so (mimo_v2_flash: ``(4, 256, 128)`` and
    ``(8, 256, 128)``), else one head count and one width for all."""
    own = getattr(cfg, "kv_head_shapes", None)
    if own is not None:
        return own
    one = (cfg.num_key_value_heads, cfg.actual_head_dim,
           cfg.actual_head_dim)
    return one, one


def no_prefix_reason(cfg) -> Optional[str]:
    """Why a model's pages are no prefix another request could share or
    a peer import (None: they are one). The one place that says so: the
    engine drops its radix tree and refuses the prefix exchange on it,
    the disaggregated engine refuses the model."""
    if carries_state(cfg):
        return ("has state-carrying layers, and what is missing is "
                "snapshots of the recurrent state at page boundaries; "
                "without them a shared or transferred prefix page has no "
                "state to continue from"
                + (" (and its attention is latent attention, whose pages "
                   "hold [c | k_r]: a shared prefix would have to be "
                   "expanded through W_ukv in the prefill as well)"
                   if latent_of(cfg) else ""))
    if window_of(cfg) is not None:
        return ("has window-attention layers, whose K/V is kept by slot "
                "in a ring that holds a suffix of the slot's tokens; a "
                "shared or transferred prefix page of the full-attention "
                "layers has no window-layer K/V to go with it")
    if latent_of(cfg):
        return ("has latent attention, whose pages hold [c | k_r] and no "
                "head's key or value; a shared prefix would have to be "
                "expanded through W_ukv in the prefill; not written (a "
                "prompt starts at 0 and attends to itself in key blocks)")
    return None


def window_ring_pages(window: int, page_size: int) -> int:
    """Pages of one slot's ring in one window layer: the window's keys
    lie on at most ``ceil(window / page_size) + 1`` pages (129 at 2048 /
    16), whatever the position."""
    return ceil_div(window, page_size) + 1


def window_cache_bytes(cache: Any) -> int:
    """Bytes of the rings of a cache; 0 without any."""
    return sum(getattr(cache, name).nbytes for name in RING_FIELDS
               if hasattr(cache, name))


def carries_state(cfg) -> bool:
    """Whether the model has state-carrying layers: its cache holds a
    per-slot recurrent state beside the page pool."""
    return hasattr(cfg, "recurrent_state_shapes")


def _zero_recurrent_state(cfg, slots: int, dtype: Any,
                          sharding: Optional[Any] = None):
    """The zeroed ``(state, conv)`` of ``slots`` slots: float32 and
    ``dtype``, in the two shapes ``cfg.recurrent_state_shapes(slots)``
    gives (``[layers, slots, ...]``, any rank after that: no rank is
    assumed here or by the masked fill over slots)."""
    state, conv = cfg.recurrent_state_shapes(slots)
    if isinstance(sharding, NamedSharding):   # the pool's mesh, replicated
        sharding = NamedSharding(sharding.mesh, P())
    return (jnp.zeros(state, jnp.float32, device=sharding),
            jnp.zeros(conv, dtype, device=sharding))


def recurrent_state_bytes(cache: Any) -> int:
    """Bytes of the slot-indexed buffers of a cache; 0 without any."""
    return sum(getattr(cache, name).nbytes for name in SLOT_FIELDS
               if hasattr(cache, name))


def paged_kv_cache_shape(cfg, num_pages: int, page_size: int
                         ) -> Tuple[int, ...]:
    """[L, n_pages, Hkv, page_size, D] for any config ``kv_cache_shape``
    knows (page 0 is the reserved TRASH page — size the pool with it)."""
    l, _, h, _, d = kv_cache_shape(cfg, 1, 1)
    return (l, num_pages, h, page_size, d)


def paged_kv_cache_shapes(cfg, num_pages: int, page_size: int
                          ) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
    """The K pool's and the V pool's shape: ``paged_kv_cache_shape``
    twice, except where a key is stored wider than a value
    (``kv_head_shapes``)."""
    if not hasattr(cfg, "kv_head_shapes"):
        shape = paged_kv_cache_shape(cfg, num_pages, page_size)
        return shape, shape
    (heads, d_k, d_v), _ = kv_head_shapes(cfg)
    lead = (cfg.num_kv_cache_layers, num_pages, heads, page_size)
    return lead + (d_k,), lead + (d_v,)


def init_paged_kv_cache(
    cfg,
    num_pages: int,
    page_size: int,
    *,
    dtype: Any = None,
    sharding: Optional[Any] = None,
    slots: Optional[int] = None,
) -> Any:
    """Zeroed page pool in the model's compute dtype; with ``sharding``
    (a NamedSharding applied to both pools, or a PagedKVCache of them)
    the pools are created directly on their shards. A model with
    state-carrying layers gets a ``HybridCache``: the pool over its
    full-attention layers plus the zeroed state and convolution tail of
    ``slots`` slots (on one device, or replicated: sharding a recurrent
    state over heads is not written); where its attention is latent
    attention the pool is the latent one and there is no ``v``."""
    dt = dtype or getattr(cfg, "dtype", jnp.bfloat16)
    sk, sv = (sharding.k, sharding.v) \
        if isinstance(sharding, PagedKVCache) else (sharding, sharding)
    window = window_of(cfg)
    by_slot = window is not None or carries_state(cfg)
    if by_slot and slots is None:
        raise ValueError(
            f"{type(cfg).__name__} keeps memory by slot beside its pages "
            "(a recurrent state, or window layers' rings): its cache "
            "needs the number of slots beside the number of pages")
    if latent_of(cfg):
        if sk is not None and len(sk.device_set) > 1:
            raise NotImplementedError(
                "a latent cache over several devices is not written (its "
                "one cached head has no axis to shard; a deployment runs "
                "such attention data-parallel): serve this model on one "
                "device")
        pool = jnp.zeros(
            latent_cache_shape(cfg, num_pages, page_size), dt, device=sk)
        if not by_slot:
            return LatentCache(k=pool)
        return HybridCache(pool, None,
                           *_zero_recurrent_state(cfg, slots, dt, sk))
    k_shape, v_shape = paged_kv_cache_shapes(cfg, num_pages, page_size)
    if by_slot and sk is not None and len(sk.device_set) > 1:
        # asked before the pools are made: a family with ONE K/V head
        # (Jamba) has no head axis a second device could take
        raise NotImplementedError(
            "a recurrent state or a window layer's ring over several "
            "devices (tensor parallelism over such layers) is not "
            "written: serve this model on one device")
    # device=: allocated on the shards, never whole on the default device
    k = jnp.zeros(k_shape, dt, device=sk)
    v = jnp.zeros(v_shape, dt, device=sv)
    if not by_slot:
        return PagedKVCache(k=k, v=v)
    if window is not None:
        _, (heads, d_k, d_v) = kv_head_shapes(cfg)
        ring = (cfg.num_window_layers,
                1 + slots * window_ring_pages(window, page_size),
                heads, page_size)
        return WindowCache(k, v, jnp.zeros(ring + (d_k,), dt, device=sk),
                           jnp.zeros(ring + (d_v,), dt, device=sv))
    return HybridCache(k, v, *_zero_recurrent_state(cfg, slots, dt, sk))


def paged_kv_cache_specs(
    *, tp_axis: Optional[str] = "tp"
) -> PagedKVCache:
    """PartitionSpec pair for the page pools — the pool-side
    counterpart of ``llama_param_specs``: KV heads over ``tp`` (matching
    the column-parallel k/v projections, so the decode matmuls never
    re-shard). The page axis stays unsharded —
    pages are the unit of host-side ownership and any page must be
    reachable from any slot's table."""
    spec = P(None, None, tp_axis, None, None)
    return PagedKVCache(k=spec, v=spec)


def paged_kv_cache_shardings(
    mesh, *, tp_axis: Optional[str] = "tp"
) -> PagedKVCache:
    """NamedShardings over ``mesh`` for the page pools. An axis of one
    device shards nothing, and what a jitted step returns says so
    (``PartitionSpec()``): the pool is placed as the steps return it,
    or a step's first call after the constructor is a call signature
    of its own (one more entry of ``prefill_compile_count`` for the
    first shape called, and that shape traced again at its next call)."""
    if tp_axis is not None and mesh.shape[tp_axis] == 1:
        whole = NamedSharding(mesh, P())
        return PagedKVCache(k=whole, v=whole)
    specs = paged_kv_cache_specs(tp_axis=tp_axis)
    return PagedKVCache(
        k=NamedSharding(mesh, specs.k), v=NamedSharding(mesh, specs.v)
    )


class PageAllocator:
    """Host-side page bookkeeping: free list + per-page refcounts.

    Page ids are indices into the device pool; page ``TRASH_PAGE`` (0)
    is reserved at construction and never handed out. A page is either
    FREE (on the free list, refcount 0) or ALLOCATED (refcount >= 1):
    ``alloc`` hands out pages at refcount 1, ``retain`` adds a
    reference (a prefix-sharing slot, the radix tree), ``release``
    drops one and returns the page to the free list at zero. Double
    release and foreign retain raise — the conservation invariant
    (free + allocated == capacity, every allocated page's refcount >= 1)
    is property-tested across randomized admit/retire/quarantine
    schedules.
    """

    def __init__(self, num_pages: int,
                 reserved: Tuple[int, ...] = (TRASH_PAGE,)) -> None:
        if num_pages < len(reserved) + 1:
            raise ValueError(
                f"page pool needs at least {len(reserved) + 1} pages "
                f"({len(reserved)} reserved), got {num_pages}"
            )
        self.num_pages = num_pages
        self.reserved = tuple(reserved)
        self._free: deque[int] = deque(
            p for p in range(num_pages) if p not in reserved)
        self._ref: Dict[int, int] = {}

    @property
    def capacity(self) -> int:
        """Allocatable pages (pool minus reserved)."""
        return self.num_pages - len(self.reserved)

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def used_count(self) -> int:
        return len(self._ref)

    def refcount(self, page: int) -> int:
        """0 for free pages."""
        return self._ref.get(page, 0)

    def alloc(self, n: int) -> Optional[List[int]]:
        """``n`` fresh pages at refcount 1, or None (allocation is
        all-or-nothing — a partially admitted request would leak)."""
        if n < 0:
            raise ValueError(f"alloc needs n >= 0, got {n}")
        if n > len(self._free):
            return None
        pages = [self._free.popleft() for _ in range(n)]
        for p in pages:
            self._ref[p] = 1
        return pages

    def retain(self, page: int) -> None:
        if page not in self._ref:
            raise ValueError(f"retain of unallocated page {page}")
        self._ref[page] += 1

    def release(self, page: int) -> None:
        count = self._ref.get(page)
        if count is None:
            raise ValueError(f"double free of page {page}")
        if count == 1:
            del self._ref[page]
            self._free.append(page)
        else:
            self._ref[page] = count - 1

    def check_conservation(self) -> None:
        """Raise unless free + allocated == capacity and every allocated
        page holds a positive refcount (the property tests' oracle)."""
        if len(self._free) + len(self._ref) != self.capacity:
            raise AssertionError(
                f"page leak: {len(self._free)} free + {len(self._ref)} "
                f"allocated != capacity {self.capacity}"
            )
        bad = [p for p, c in self._ref.items() if c < 1]
        if bad:
            raise AssertionError(f"non-positive refcounts: {bad}")
        overlap = set(self._free) & set(self._ref)
        if overlap:
            raise AssertionError(f"pages both free and allocated: {overlap}")


class _RadixNode:
    __slots__ = ("children", "page", "last_used")

    def __init__(self, page: int = TRASH_PAGE) -> None:
        self.children: Dict[Tuple[int, ...], "_RadixNode"] = {}
        self.page = page
        self.last_used = 0


class RadixPrefixCache:
    """Page-granular radix tree over token prefixes.

    Each edge is one full ``page_size`` token chunk; a node owns the pool
    page holding that chunk's K/V. Prefix sharing is copy-on-write *at
    the page boundary*: only FULLY-FROZEN prompt pages (every position
    written at prefill, never written again) are ever registered, so a
    shared page is immutable by construction — a partially-filled
    boundary page is re-prefilled into the new request's own page
    instead of being split.

    The tree holds ONE allocator reference per registered page
    (``retain`` at insert); slots sharing the page add their own. A node
    is evictable only when no slot references its page (allocator
    refcount back down to the tree's single reference) — eviction is
    LRU over leaves, releasing the tree's reference so the page returns
    to the free list at refcount 0.
    """

    def __init__(self, page_size: int,
                 retain: Callable[[int], None],
                 release: Callable[[int], None],
                 refcount: Callable[[int], int]) -> None:
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self.page_size = page_size
        self._retain = retain
        self._release = release
        self._refcount = refcount
        self.root = _RadixNode()
        self._clock = 0

    def _chunks(self, tokens) -> List[Tuple[int, ...]]:
        p = self.page_size
        return [tuple(tokens[i:i + p])
                for i in range(0, (len(tokens) // p) * p, p)]

    def __len__(self) -> int:
        count = 0
        stack = [self.root]
        while stack:
            node = stack.pop()
            count += len(node.children)
            stack.extend(node.children.values())
        return count

    def match(self, tokens) -> Tuple[int, List[int]]:
        """Longest page-aligned cached prefix of ``tokens``:
        (matched token count — a multiple of page_size — and the page
        ids, root-first). Touches the matched path's LRU clocks. The
        caller must ``retain`` every returned page before anything else
        can evict."""
        self._clock += 1
        node = self.root
        pages: List[int] = []
        for chunk in self._chunks(tokens):
            child = node.children.get(chunk)
            if child is None:
                break
            child.last_used = self._clock
            pages.append(child.page)
            node = child
        return len(pages) * self.page_size, pages

    def insert(self, tokens, pages: List[int]) -> int:
        """Register ``tokens`` (length a multiple of page_size) held in
        ``pages`` (one per chunk, root-first). Chunks already present
        keep their existing page (first writer wins — concurrent
        admissions of the same prompt each computed identical K/V, the
        duplicate copy stays private to its slot); new nodes take one
        allocator reference on their page. Returns the number of new
        nodes."""
        chunks = self._chunks(tokens)
        if len(chunks) != len(pages) or len(tokens) % self.page_size:
            raise ValueError(
                f"insert needs page-aligned tokens and one page per "
                f"chunk: {len(tokens)} tokens, {len(pages)} pages"
            )
        self._clock += 1
        node = self.root
        created = 0
        for chunk, page in zip(chunks, pages):
            child = node.children.get(chunk)
            if child is None:
                child = _RadixNode(page=page)
                node.children[chunk] = child
                self._retain(page)
                created += 1
            child.last_used = self._clock
            node = child
        return created

    def chains(self) -> List[Tuple[List[int], List[int]]]:
        """Snapshot every root-to-leaf path as ``(tokens, pages)`` —
        the donor half of warm rejoin. Leaf chains subsume their
        ancestors (the recipient re-inserts prefixes for free), so the
        list is the minimal set that reconstructs the tree. Pure read:
        no LRU touch, no refcount change — the caller decides which
        pages to retain for how long."""
        out: List[Tuple[List[int], List[int]]] = []
        stack: List[Tuple[_RadixNode, List[int], List[int]]] = [
            (self.root, [], [])]
        while stack:
            node, tokens, pages = stack.pop()
            if not node.children and pages:
                out.append((tokens, pages))
                continue
            for chunk, child in node.children.items():
                stack.append((child, tokens + list(chunk),
                              pages + [child.page]))
        return out

    def registered_pages(self) -> List[int]:
        """Every page the tree currently holds a reference on (the
        frozen set a donor may stream; anything else is mutable slot
        state and must never leave the process)."""
        pages: List[int] = []
        stack = [self.root]
        while stack:
            node = stack.pop()
            for child in node.children.values():
                pages.append(child.page)
                stack.append(child)
        return pages

    def evict(self, n_pages: int) -> int:
        """Free up to ``n_pages`` pages by pruning LRU leaves whose page
        no live slot references (allocator refcount == 1, the tree's
        own). Returns how many were released. Inner nodes become
        evictable once their children go — the loop re-scans until the
        target is met or nothing more can move."""
        freed = 0
        while freed < n_pages:
            leaves: List[Tuple[int, _RadixNode, Tuple[int, ...],
                               _RadixNode]] = []
            stack = [self.root]
            while stack:
                node = stack.pop()
                for chunk, child in node.children.items():
                    if child.children:
                        stack.append(child)
                    elif self._refcount(child.page) == 1:
                        leaves.append((child.last_used, id(child), chunk,
                                       node))
                    # leaves with live slot references are pinned
            if not leaves:
                break
            leaves.sort()
            for _, _, chunk, parent in leaves:
                child = parent.children.pop(chunk)
                self._release(child.page)
                freed += 1
                if freed >= n_pages:
                    break
        return freed


def _latent_row(latent: jax.Array, rotary: jax.Array,
                width: int) -> jax.Array:
    """``[latent | rotary | 0...]`` along the last axis, ``width`` wide:
    a cached row, or the query that reads it."""
    row = jnp.concatenate([latent, rotary], axis=-1)
    return jnp.pad(row, ((0, 0),) * (row.ndim - 1)
                   + ((0, width - row.shape[-1]),))


class PagedKVIO:
    """Paged-cache adapter for the models' cache-aware forwards.

    The forwards carry the whole pool pair [L, n_pages, Hkv, page_size,
    D] through their layer loop (``llama.scan_layers_cached``) and touch
    it only through ``write`` and ``attend`` at a layer index, as they
    touch the reference cache through ``layers.DenseKVIO`` — this object is
    constructed INSIDE the jitted step from the traced page tables, so
    tables are data and the step compiles once. Neither method slices a
    layer out: the write is ``paged_write`` (the Mosaic page write, in
    place, on a TPU whose head_dim the kernels serve; a scatter at
    ``pool.at[layer, ...]`` elsewhere) and the read ``paged_attention``
    (the decode kernel at ``pool.at[layer, page]``; a gather of whole
    pages ``pool[layer, page_tables]`` for prefill and the fallback).
    ``seq_limit`` crops the fallback's gathered view to the engine's
    ``max_seq`` (the operand shapes of the contiguous reference);
    ``kernel`` forces the pair for both (None = auto, one predicate:
    ``paged_attention.in_place_pair``).

    ``prefix_hit`` is what the caller knows of a multi-row call's rows:
    whether any of them continues a prefix that lies in the pool
    (``paged_attention``: a traced bool, the prefill step's ``starts``,
    chooses on the device between the attention over the gathered view
    and the prompts' attention to themselves in key blocks; False, a
    family that refuses prefix sharing, leaves the gather out of the
    program; None, not said: every call reads the pool).
    """

    def __init__(self, page_tables: jax.Array, page_size: int, *,
                 seq_limit: Optional[int] = None,
                 kernel: Optional[bool] = None,
                 interpret: bool = False,
                 prefix_hit: Any = None) -> None:
        self.page_tables = page_tables
        self.page_size = page_size
        self.seq_limit = seq_limit
        self.kernel = kernel
        self.interpret = interpret
        self.prefix_hit = prefix_hit

    def write(self, pool: jax.Array, layer: jax.Array, new: jax.Array,
              positions: jax.Array,
              write_mask: Optional[jax.Array]) -> jax.Array:
        return paged_write(
            pool, new, positions, self.page_tables, write_mask,
            layer=layer, kernel=self.kernel, interpret=self.interpret)

    def attend(self, q: jax.Array, pool_k: jax.Array, pool_v: jax.Array,
               layer: jax.Array, q_positions: jax.Array,
               own: Optional[Tuple[jax.Array, jax.Array]] = None,
               *, scale: Optional[float] = None,
               sink: Optional[jax.Array] = None) -> jax.Array:
        """``q`` [B, Hq, S, D] against ``layer`` of the pool, which
        already holds the call's own K/V; ``own`` is that K/V as the
        call made it ([B, Hkv, S, D] each), for a multi-row call none
        of whose rows has a prefix in the pool (``prefix_hit``).
        ``scale``: the scores' factor where it is not ``D ** -0.5`` (a
        query padded to the stored key's width); ``sink`` [Hq]: a logit
        a query head in every softmax (``paged_attention``)."""
        return paged_attention(
            q, pool_k, pool_v, self.page_tables, q_positions,
            page_size=self.page_size, layer=layer, seq_limit=self.seq_limit,
            scale=scale, kernel=None if self.kernel is None
            else self.kernel and q.shape[2] == 1,
            interpret=self.interpret, own=own, prefix_hit=self.prefix_hit,
            sink=sink,
        )

    def write_latent(self, pool: jax.Array, layer: jax.Array, c: jax.Array,
                     k_r: jax.Array, positions: jax.Array,
                     write_mask: Optional[jax.Array]) -> jax.Array:
        """A ``LatentCache``'s write: the normed latent ``c`` [B, 1, S,
        kv_lora_rank] and the rotated ``k_r`` [B, 1, S, rope] as one row
        ``[c | k_r | 0...]`` of the pool's width."""
        return self.write(pool, layer, _latent_row(c, k_r, pool.shape[-1]),
                          positions, write_mask)

    def attend_latent(self, q_c: jax.Array, q_r: jax.Array,
                      pool: jax.Array, layer: jax.Array,
                      q_positions: jax.Array, *, scale: float) -> jax.Array:
        """A ``LatentCache``'s one-token read in the absorbed form:
        ``q_c`` [B, H, kv_lora_rank] and the rotated ``q_r`` [B, H,
        rope] of every head, as one query row ``[q_c | q_r | 0...]``
        against the one cached head; [B, H, kv_lora_rank] back
        (``paged_attention.latent_attention``)."""
        return latent_attention(
            _latent_row(q_c, q_r, pool.shape[-1]), pool, self.page_tables,
            q_positions, layer=layer,
            value_width=q_c.shape[-1], scale=scale,
            seq_limit=self.seq_limit, kernel=self.kernel,
            interpret=self.interpret)


class RingKVIO:
    """``PagedKVIO``'s twin for the window layers of a ``WindowCache``:
    the same pair of a write and a read, on the rings ``[window layers,
    1 + slots * ring_pages, Hkv, page_size, D]``, through tables that
    are computed here, inside the jitted step, from the call's
    positions (nothing about a ring reaches the device as an operand).

    ``tables`` ``[B, max_pages]``: logical page ``t // page_size`` of
    slot b is ring page ``1 + b * ring_pages + (t // page_size) %
    ring_pages``, so position t lands where ``paged_write`` and the
    decode kernel look for it, and the newest ``window`` positions of a
    slot are always resident (the ring holds ``window + page_size`` at
    the least). A call's rows run from ``first`` [B] for ``live`` [B]
    rows a slot (a prefill's prompt, a decode step's one token): of
    their pages only the newest ``ring_pages`` are written, the others
    go to TRASH, since an older page of a prompt longer than the ring
    would land on a newer one's place, and so would the rows a fixed-
    shape prefill buffer holds past the prompt's end.
    ``slots`` [B]: the slot each row of the call is (None: row b is
    slot b, a decode step's rows and afmoe's prefill call over every
    slot; a family whose prefill row names its slot hands its ids).
    ``attend`` serves the one-token read (``scale`` and ``sink`` as
    ``paged_attention`` has them); a multi-row call of such a family
    attends to itself and does not come here."""

    def __init__(self, paged: "PagedKVIO", window: int, first: jax.Array,
                 live: jax.Array, slots: Optional[jax.Array] = None) -> None:
        self.paged, self.window = paged, window
        rows, width = paged.page_tables.shape
        ring = window_ring_pages(window, paged.page_size)
        logical = jnp.arange(width, dtype=jnp.int32)[None, :]
        if slots is None:
            slots = jnp.arange(rows, dtype=jnp.int32)
        own = 1 + ring * slots.astype(jnp.int32)[:, None]
        self.tables = own + logical % ring
        last = ((first + live - 1) // paged.page_size)[:, None]
        self.write_tables = jnp.where(
            (logical <= last) & (logical > last - ring), self.tables,
            TRASH_PAGE)

    def write(self, pool: jax.Array, layer: jax.Array, new: jax.Array,
              positions: jax.Array,
              write_mask: Optional[jax.Array]) -> jax.Array:
        return paged_write(
            pool, new, positions, self.write_tables, write_mask,
            layer=layer, kernel=self.paged.kernel,
            interpret=self.paged.interpret)

    def attend(self, q: jax.Array, pool_k: jax.Array, pool_v: jax.Array,
               layer: jax.Array, q_positions: jax.Array, *,
               scale: Optional[float] = None,
               sink: Optional[jax.Array] = None) -> jax.Array:
        if q.shape[2] != 1:
            raise ValueError(
                "a window layer's ring serves one-token reads; a "
                f"{q.shape[2]}-row call attends to itself")
        return paged_attention(
            q, pool_k, pool_v, self.tables, q_positions,
            page_size=self.paged.page_size, layer=layer,
            seq_limit=self.paged.seq_limit, scale=scale,
            kernel=self.paged.kernel, interpret=self.paged.interpret,
            window=self.window, sink=sink)
