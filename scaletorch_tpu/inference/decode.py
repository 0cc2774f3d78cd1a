"""The two jitted engine steps: prompt-tail prefill and one-token decode,
both over the page pool (kv_cache.PagedKVCache).

Static shapes everywhere — the engine compiles the decode step exactly
once per run and the prefill step once a shape of a short list fixed at
construction (``prefill_shapes``), however many requests flow through:

  * ``prefill``: a causal forward over a ``[rows, length]`` prompt
    buffer (each admitted slot's non-shared prompt tail) that writes
    the rows named by ``write_mask`` into their own pages (live slots'
    pages are untouched) and returns the first sampled token per row.
    Admitting a request is "set a row of the buffer and of the page
    table, flip its mask bit" — no new trace; the engine takes the
    smallest listed shape that holds what it admitted.
  * ``decode``: one token per slot at per-slot absolute positions,
    RoPE at the absolute position, one row appended to the slot's
    current page, attention over its page table, sample. The pool is
    DONATED and carried whole through the forwards' layer loop
    (``llama.scan_layers_cached``) — the append happens in place
    instead of copying the whole pool every token.

The weights lie where these programs read them
(``compile_decode_for_layouts`` + ``chosen_orders`` + ``place_params``,
called once when an engine is built): the decode step is compiled with
the layout of every parameter left to the compiler, each leaf it reads
in another order of dimensions is stored once, transposed into that
order, as a new array (the caller's arrays stay as they were), and both
steps are built to read the placed tree through a transposition back
that costs nothing (``param_orders``). Such a leaf that the decode
program reads ONLY one static layer at a time (``a[index]`` in an
unrolled forward) is stored as its layers, each an array and a program
parameter of its own (``ByLayer``): sliced out of one stored stack, a
layer was copied from HBM to HBM every step. No family has code for it:
the rule asks about each model's own decode program.

Both lower onto the models' cache-aware forwards
(models/llama.py forward_cached & family), resolved per config by
``resolve_forward_cached``, with ``kv_cache.PagedKVIO`` as their cache
adapter. ``teacher_forced_decode`` (contiguous cache) and
``teacher_forced_decode_paged`` are the parity harnesses.
"""

from __future__ import annotations

import hashlib
import json
import os
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Format, Layout

from scaletorch_tpu.inference.kv_cache import (
    RING_FIELDS,
    SLOT_FIELDS,
    carries_state,
    no_prefix_reason,
    window_of,
)
from scaletorch_tpu.inference.routing_counters import step_counts
from scaletorch_tpu.inference.sampling import (
    SamplingParams,
    finite_mask,
    sample,
    slot_keys,
)
from scaletorch_tpu.models.families import family_of


def _resolve_donate(donate_cache: Optional[bool]) -> bool:
    """None = donate wherever the backend honours it (TPU/GPU); the CPU
    runtime ignores donation and warns per call, so skip it there."""
    if donate_cache is not None:
        return donate_cache
    return jax.default_backend() != "cpu"


def resolve_forward_cached(cfg) -> Callable:
    """The cache-aware forward of a config's family
    (``models/families.py``, by the config's exact class)."""
    return family_of(cfg).module.forward_cached


def counts_routing(cfg) -> bool:
    """Whether the config's cached forward counts what it routes
    (``return_routing``); such a step takes the row mask of a
    state-carrying model and the routing accumulator side by side."""
    return family_of(cfg).counts_routing


def rows_name_slots(cfg) -> bool:
    """Whether a prefill row of the config's model names its slot: its
    cache keeps memory by slot (a recurrent state, or window layers'
    rings) and its family's column says the write at a slot id is
    tested for it (``models/families.py``)."""
    return ((carries_state(cfg) or window_of(cfg) is not None)
            and family_of(cfg).rows_name_slots)


def prefill_shapes(max_slots: int,
                   prefill_len: int) -> Tuple[Tuple[int, int], ...]:
    """The static ``(rows, length)`` shapes the prefill step of an engine
    whose cache is addressed by page is called at, fewest positions
    first; the last is ``(max_slots, prefill_len)``, which holds any
    admission (a burst; the benchmark's reference check).

    One shape goes before it: ONE row of half the length. Under steady
    traffic an admission is one request (a tick admits whoever waits,
    and ticks are milliseconds apart), so the row count that pays is 1,
    and half the buffer holds most prompts of any population the buffer
    was sized for. Every further shape is a trace and a lowering of the
    whole model when a server starts, whatever the compile cache holds:
    0.5 s for a dense model and 0.8-1.5 s for a sparse one on the v5e's
    host, against start-ups of 24-33 s (PERF.md section 6, PR 44); a
    quarter-length row bought 5 ms of a 31 ms admission there, a
    four-row shape nothing (1-3 admissions of 155 held two requests).
    The engine takes any list (tests/inference/test_prefill_shapes.py
    runs it on rows 1 / 4 / all and lengths a quarter / half / whole)."""
    shapes = {(1, -(-prefill_len // 2)), (max_slots, prefill_len)}
    return tuple(sorted(shapes, key=lambda s: (s[0] * s[1], s[0])))


def make_fill_slots_step(*, donate_cache: Optional[bool] = None) -> Callable:
    """Build the jitted masked fill over axis 1 of the stacked cache:
    the PAGE axis of the [L, n_pages, Hkv, page_size, D] pools.

    fill_slots(cache, mask bool, value scalar) -> cache with every
    masked page set to ``value``; unmasked bytes pass through
    bit-identical. A cache with slot-indexed buffers
    (``kv_cache.HybridCache``: recurrent state, convolution tail) takes
    a second mask, ``slot_mask`` [slots] bool, for those: one call
    clears (or poisons) a slot's pages and its state together. The
    rings of a ``kv_cache.WindowCache`` take the same ``slot_mask``,
    spread over each named slot's ring pages (TRASH, page 0 of a ring
    buffer, is never filled).

    One compile serves the scalar consumers — quarantine hygiene
    (value 0: a retired poison slot's NaN K/V must not outlive the
    request) and fault injection (value NaN: poison a slot's mutable
    pages so its next decode step goes non-finite) — because the mask
    and the fill value are data, never shapes. ``value`` may also be a
    cache-shaped tuple (one buffer per cache field): the warm-rejoin
    import writes transferred page CONTENTS through this same step —
    masked pages take the tuple's bytes, unmasked pages pass through
    bit-identical. That is a second argument STRUCTURE, hence a second
    specialization of this function only; the decode/prefill entries
    the deep-tier audit pins never retrace. The cache is donated like
    the engine steps, so XLA rewrites the masked lanes in place.
    """

    def fill_slots(cache, mask, value, slot_mask=None):
        vals = tuple(value) if isinstance(value, tuple) \
            else (value,) * len(cache)
        names = getattr(cache, "_fields", ("",) * len(cache))

        def over_rings(pages):
            """``slot_mask`` spread over each slot's ring pages, TRASH
            (page 0) left out."""
            ring = (pages - 1) // slot_mask.shape[0]
            return jnp.concatenate([
                jnp.zeros((1,), bool), jnp.repeat(slot_mask, ring)])

        def fill(buf, val, name):
            if buf is None:     # a latent pool's absent ``v``
                return None
            m = (slot_mask if name in SLOT_FIELDS
                 else over_rings(buf.shape[1]) if name in RING_FIELDS
                 else mask)
            m = m.reshape((1, m.shape[0]) + (1,) * (buf.ndim - 2))
            return jnp.where(m, jnp.asarray(val, buf.dtype), buf)

        return type(cache)(*(fill(buf, val, name) for buf, val, name
                             in zip(cache, vals, names)))

    return jax.jit(
        fill_slots,
        donate_argnums=(0,) if _resolve_donate(donate_cache) else (),
    )


def make_paged_prefill_step(
    cfg,
    sampling: SamplingParams,
    *,
    page_size: int,
    seq_limit: Optional[int] = None,
    forward_fn: Optional[Callable] = None,
    donate_cache: Optional[bool] = None,
    routing_counts: bool = False,
    param_orders: Any = None,
) -> Callable:
    """Build the jitted prefill step.

    prefill(params, tokens [B, P], tail_lens [B], starts [B],
            write_mask [B] bool, page_tables [B, max_pages] i32,
            pool (PagedKVCache), base_keys [B, 2])
      -> (first_token [B] i32, last_logits [B, V] f32, finite [B] bool,
          new_pool)

    ``[B, P]`` is whatever the caller hands it, one compiled program a
    shape (``prefill_shapes``): a row is a slot only through its row of
    the page tables and of the base keys, so where the cache is
    addressed by page B may be fewer than the engine's slots. Each
    admitted slot prefills only its NON-SHARED prompt tail, for
    the rows named by ``write_mask``. ``starts`` is the
    page-aligned count of tokens already cached via a radix prefix hit
    (0 without one); the tail tokens sit at buffer rows [0, tail_len)
    and run at absolute positions ``starts + row`` — their attention
    reads the shared prefix pages straight out of the pool through the
    page table, so the shared positions cost ZERO forward compute.
    Writes land in the slot's own pages only (prefix sharing is
    page-aligned and shared pages are frozen); rows past ``tail_len``
    write garbage into the slot's own later pages or the TRASH page —
    invisible, because the j <= p attention mask never reaches past the
    current position and decode overwrites position p before attending
    to it. Whether any admitted row has a prefix there is one predicate
    a call (``PagedKVIO``'s ``prefix_hit``): without one every prompt
    attends to itself in key blocks and no layer reads the pool; a
    family that refuses prefix sharing (``kv_cache.no_prefix_reason``)
    never has one, and its program holds no read of the pool. The
    first token samples from the logits at row
    ``tail_len - 1`` with the slot's (seed, prompt_len - 1) key: the
    forward is told that row (``logit_rows``), so its final norm and
    head run on [B, 1, hidden] as the decode step's do and no
    [B, P, V] logits exist in the program (``last_logits`` is that one
    row a slot; a slot outside ``write_mask`` yields an ignored row).
    ``finite`` flags the slots whose sampled-from logits are all finite
    (``sampling.finite_mask``) — the engine quarantines a False slot
    instead of emitting its garbage sample.

    ``routing_counts`` (a model whose cached forward takes
    ``return_routing``: the MoE families) adds a last argument and a
    fifth result, the uint32 accumulator of
    ``inference/routing_counters.py``, and tells the forward which rows
    exist: those of admitted slots below their ``tail_len``. A model
    with state-carrying layers (``kv_cache.carries_state``) is told the same: for
    K/V a row past ``tail_len`` is harmless garbage, for a recurrence it
    is a wrong answer, so such rows and every slot outside
    ``write_mask`` leave state and convolution tail untouched, and the
    pool the step donates and returns is that model's whole cache
    (``kv_cache.HybridCache``).

    Where a row names its slot (``rows_name_slots``: the delta-rule
    families) the step takes ``slot_ids [B] i32`` after ``base_keys``
    (before the routing accumulator) and a call's rows are its admitted
    prompts. No such family shares a prefix, so every row is at position
    0 and begins from ``S = 0`` and an empty tail: nothing of the
    ``[layers, slots, ...]`` buffers is read. The forward runs on
    ``[layers, B, ...]`` of zeros, where a row is its own slot as ever,
    and each written row's final state and tail land at ``[layer,
    slot_ids[row]]`` of the donated cache in one scatter a buffer; a row
    outside ``write_mask``, or one whose id is past the last slot,
    writes nothing, and every other slot's state and tail pass through
    bit for bit. What comes back is ``SlotRows`` around the jitted
    program: handed ``slot_ids`` it is the program, handed today's
    eight operands it serves any ``[B, P]`` with a row its own slot.

    ``param_orders`` (``chosen_orders``): the step takes the parameters
    as ``place_params`` stored them and reads them ``in_model_order``.
    """
    fwd = forward_fn or resolve_forward_cached(cfg)
    # a row that is no token would otherwise enter a recurrent state:
    # told to the model's own forward and to a ``forward_fn`` in its
    # place alike (one that cannot take ``row_mask``, or the
    # ``logit_rows`` every prefill names, fails at the trace)
    row_masked = carries_state(cfg)
    # whether a row of a call can continue a prefix that lies in the pool
    shares_prefixes = no_prefix_reason(cfg) is None
    by_id = rows_name_slots(cfg)
    # what a named slot addresses: the by-slot state buffers, scattered
    # here, or the window layers' rings, whose tables the family's
    # forward builds from the ids (``kv_cache.RingKVIO``)
    state_by_id = by_id and carries_state(cfg)

    def prefill(params, tokens, tail_lens, starts, write_mask,
                page_tables, pool, base_keys, *routing):
        from scaletorch_tpu.inference.kv_cache import PagedKVIO

        params = in_model_order(params, param_orders)
        b, p = tokens.shape
        if by_id:
            slot_ids, *routing = routing
        if state_by_id:
            held = {name: getattr(pool, name) for name in SLOT_FIELDS}
            pool = pool._replace(**{
                name: jnp.zeros((buf.shape[0], b) + buf.shape[2:], buf.dtype)
                for name, buf in held.items()})
        rows = jnp.broadcast_to(jnp.arange(p, dtype=jnp.int32), (b, p))
        positions = starts[:, None] + rows
        kv_io = PagedKVIO(
            page_tables, page_size, seq_limit=seq_limit,
            prefix_hit=shares_prefixes
            and jnp.any(write_mask & (starts > 0)))
        counted = {}
        if routing_counts or row_masked:
            counted = dict(
                row_mask=write_mask[:, None] & (rows < tail_lens[:, None]))
        if routing_counts:
            counted["return_routing"] = True
        if by_id and not state_by_id:
            counted["slot_ids"] = slot_ids
        logits, new_pool, *counts = fwd(
            params, tokens, cfg, tuple(pool),
            positions=positions, write_mask=write_mask, kv_io=kv_io,
            logit_rows=tail_lens - 1, **counted,
        )
        new_pool = type(pool)(*new_pool)
        if state_by_id:
            # past the last slot: dropped
            at = jnp.where(write_mask, slot_ids, held["state"].shape[1])
            new_pool = new_pool._replace(**{
                name: buf.at[:, at].set(getattr(new_pool, name), mode="drop")
                for name, buf in held.items()})
        last = logits[:, 0, :]
        keys = slot_keys(base_keys, starts + tail_lens - 1)
        first = sample(last, keys, sampling)
        out = (first, last.astype(jnp.float32), finite_mask(last), new_pool)
        if routing_counts:
            out += (routing[0] + step_counts(counts[0], prefill=True),)
        return out

    step = jax.jit(
        prefill, donate_argnums=(6,) if _resolve_donate(donate_cache) else ()
    )
    return SlotRows(step, int(routing_counts)) if by_id else step


class SlotRows:
    """The prefill step of a family whose rows name their slots
    (``make_paged_prefill_step``), as an engine holds it: ONE jitted
    program, whatever it is handed. Called with ``slot_ids`` after
    ``base_keys`` it is that program (an admission: one row a call).
    Called with the eight operands every family's step takes (the
    benchmark's check, which hands ``[max_slots, prefill_len]`` with row
    b = slot b) it serves any ``[B, P]`` by running the program once a
    written row at ``[1, P]`` with the row as its slot id, the cache
    (and an MoE model's accumulator) handed from call to call, and
    joins the results ``[B]``; a row that is not written comes back as
    zeros (not finite), as nothing reads it. Such a caller is idle:
    reading its operands back to cut them by row waits for nothing.
    Attributes (``_cache_size``, ``lower``) are the jitted
    function's, so the compile count is the one program's."""

    def __init__(self, step: Callable, trailing: int) -> None:
        # ``trailing``: operands after ``slot_ids`` (the accumulator)
        self._step, self._trailing = step, trailing

    def __call__(self, params, tokens, tail_lens, starts, write_mask,
                 page_tables, pool, base_keys, *rest):
        if len(rest) > self._trailing:
            return self._step(params, tokens, tail_lens, starts, write_mask,
                              page_tables, pool, base_keys, *rest)
        write_mask, base_keys = np.asarray(write_mask), np.asarray(base_keys)
        lead = [np.asarray(a) for a in (
            tokens, tail_lens, starts, write_mask, page_tables)]
        done = {}
        # (no row written: one masked call, for the results' shapes)
        for row in np.flatnonzero(write_mask) if write_mask.any() else (0,):
            at = slice(row, row + 1)
            first, logits, finite, pool, *rest = self._step(
                params, *(a[at] for a in lead), pool, base_keys[at],
                np.array([row], np.int32), *rest)
            done[row] = (first, logits, finite)
        blank = [jnp.zeros_like(a) for a in next(iter(done.values()))]
        joined = [jnp.concatenate(parts) for parts in zip(*(
            done.get(row, blank) for row in range(len(write_mask))))]
        return (*joined, pool, *rest)

    def __getattr__(self, item):
        return getattr(self._step, item)


def make_paged_decode_step(
    cfg,
    sampling: SamplingParams,
    *,
    page_size: int,
    seq_limit: Optional[int] = None,
    forward_fn: Optional[Callable] = None,
    donate_cache: Optional[bool] = None,
    routing_counts: bool = False,
    param_orders: Any = None,
) -> Callable:
    """Build the jitted single-token decode step.

    decode(params, tokens [B] i32, positions [B] i32, active [B] bool,
           page_tables [B, max_pages] i32, pool (PagedKVCache),
           base_keys [B, 2])
      -> (next_token [B] i32, logits [B, V] f32, finite [B] bool,
          new_pool)

    Feeds each slot's current token at its absolute position (RoPE at
    that position) and samples the next token with the slot's (seed,
    position) key. For ACTIVE slots only, the K/V append writes one row
    of the slot's current page and attention walks its table, the
    donated pool carried whole through the layer loop (the Mosaic pair
    on TPU, in place: ``paged_write`` + the paged-decode kernel at a
    layer index; the lax scatter + gather on other platforms —
    ops/pallas/paged_attention.py). ``finite`` is the in-step
    non-finite guard (``sampling.finite_mask`` over the step logits): a
    False slot carries NaN/Inf numerics — the engine retires it as
    ``quarantined`` and never emits its sample. Inactive slots compute
    garbage that goes nowhere — their mask bit keeps their pages intact
    and the engine ignores their sample. Page-table contents are DATA:
    admissions, prefix hits, quarantine clears, and frees all mutate
    tables host-side and this one compile serves them all.
    ``routing_counts`` and ``param_orders`` as in
    ``make_paged_prefill_step``; the rows that exist are the active
    slots'.
    """
    fwd = forward_fn or resolve_forward_cached(cfg)
    # a row that is no token would otherwise enter a recurrent state:
    # told to the model's own forward and to a ``forward_fn`` in its
    # place alike (one that cannot take ``row_mask`` fails at the trace)
    row_masked = carries_state(cfg)

    def decode(params, tokens, positions, active, page_tables, pool,
               base_keys, *routing):
        from scaletorch_tpu.inference.kv_cache import PagedKVIO

        params = in_model_order(params, param_orders)
        kv_io = PagedKVIO(page_tables, page_size, seq_limit=seq_limit)
        counted = {}
        if routing_counts or row_masked:
            counted["row_mask"] = active[:, None]
        if routing_counts:
            counted["return_routing"] = True
        logits, new_pool, *counts = fwd(
            params, tokens[:, None], cfg, tuple(pool),
            positions=positions[:, None], write_mask=active, kv_io=kv_io,
            **counted,
        )
        step_logits = logits[:, 0, :]
        keys = slot_keys(base_keys, positions)
        nxt = sample(step_logits, keys, sampling)
        out = (nxt, step_logits.astype(jnp.float32),
               finite_mask(step_logits), type(pool)(*new_pool))
        if routing_counts:
            out += (routing[0] + step_counts(counts[0], prefill=False),)
        return out

    return jax.jit(
        decode, donate_argnums=(5,) if _resolve_donate(donate_cache) else ()
    )


def abstract(tree):
    """``tree`` as shapes, each leaf where (and how sharded) it lies."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        tree)


def compile_decode_for_layouts(decode_step: Callable, params, operands, *,
                               donate_cache: Optional[bool] = None):
    """The decode program compiled with the layout of every leaf of
    ``params`` left to the compiler (``Format(Layout.AUTO, <the leaf's
    sharding>)``; the other ``operands`` as a call hands them over, the
    pool donated as the step donates it): the executable, whose
    ``input_formats`` say how the program wants each weight to lie, and
    the jaxpr it was compiled from, which says how the step reads each
    weight (``chosen_orders`` takes both).
    Nothing runs and nothing is placed: ``params`` and ``operands`` may
    be arrays or shapes with shardings (``abstract``). The jitted step
    itself is what is compiled, inside a jit that names the layouts, so
    where nothing is then moved its trace is the one its first call
    finds again."""
    free = jax.tree.map(lambda x: Format(Layout.AUTO, x.sharding), params)
    traced = jax.jit(
        decode_step,
        in_shardings=(free,) + (None,) * len(operands),
        donate_argnums=(5,) if _resolve_donate(donate_cache) else (),
    ).trace(abstract(params), *operands)
    return traced.lower().compile(), traced.jaxpr


class ByLayer(tuple):
    """An order of dimensions (``chosen_orders``) for a stack that is
    stored as its layers: ``shape[0]`` arrays, each in this order
    without the leading axis."""

    @property
    def of_a_layer(self) -> Tuple[int, ...]:
        return tuple(d - 1 for d in self if d)


class Layers:
    """In a step, in the place of a stack stored as its layers
    (``ByLayer``): ``[index]`` with a static index is that layer, a
    program parameter of its own, which is how the decode program was
    seen to read the stack. Indexed in any other way, or handed to a
    ``jnp`` function (another program of the same forward might), it is
    the stack put together again: the same values, and a copy."""

    def __init__(self, layers):
        self.layers = tuple(layers)

    @property
    def shape(self):
        return (len(self.layers),) + self.layers[0].shape

    @property
    def dtype(self):
        return self.layers[0].dtype

    def __jax_array__(self):
        return jnp.stack(self.layers)

    def __getitem__(self, index):
        if isinstance(index, (int, np.integer)):
            return self.layers[index]
        return self.__jax_array__()[index]


def _read_by_layer(jaxpr, var) -> bool:
    """Whether ``jaxpr`` reads ``var`` ONLY as slices of extent 1 along
    axis 0 at static indices (what ``a[index]`` with a Python int traces
    to), looking into a plain ``jit`` equation's body the same way. Any
    other reader (a ``scan`` over the stack, a ``dynamic_slice`` under a
    loop's counter, a kernel handed the whole stack) says no, and so
    does a stack nothing reads."""
    if any(out is var for out in jaxpr.outvars):
        return False
    read = False
    for eqn in jaxpr.eqns:
        for at, operand in enumerate(eqn.invars):
            if operand is not var:
                continue
            read = True
            if eqn.primitive.name == "slice":
                if (eqn.params["limit_indices"][0]
                        - eqn.params["start_indices"][0]) != 1:
                    return False
            elif eqn.primitive.name == "jit":
                body = eqn.params["jaxpr"].jaxpr
                if not _read_by_layer(body, body.invars[at]):
                    return False
            else:
                return False
    return read


def _is_order(x) -> bool:
    return isinstance(x, tuple)


def chosen_orders(params, executable, jaxpr=None):
    """What ``executable`` (``compile_decode_for_layouts``) asks to be
    moved, as a tree like ``params``: for a leaf the program reads in
    another order of dimensions than the leaf lies in, that order
    (``major_to_minor``, most major first); ``()`` for every other
    leaf. None where no leaf is asked for (every CPU: the compiler
    answers with the layouts the arrays have).

    Orders are compared with dimensions of 1 left out (they lie
    anywhere) and tilings apart (a few small vectors). A leaf asked for
    in the row-major order stays too: nothing a transposition stores
    differs from what the device already keeps.

    With the program's ``jaxpr`` (its first operands the leaves of
    ``params``), a leaf that is asked for AND that the program reads
    only one static layer at a time (``_read_by_layer``) gets its order
    as ``ByLayer``. A leaf that is not moved anyway stays whole (no byte
    is added, and the compiler slices a stack it reads as it lies
    inside the matmul's own fusion), and so does one that lies across
    several devices."""
    def asked(leaf, chosen, var):
        if chosen.layout is None:       # a leaf the program does not read
            return ()
        own = leaf.format.layout
        own = (tuple(range(leaf.ndim)) if own is None
               else own.major_to_minor)
        order = tuple(chosen.layout.major_to_minor)

        def lies(o):
            return [d for d in o if leaf.shape[d] != 1]

        if lies(order) == lies(own) or lies(order) == sorted(lies(order)):
            return ()
        if (var is not None and len(leaf.sharding.device_set) == 1
                and _read_by_layer(jaxpr.jaxpr, var)):
            return ByLayer(order)
        return order

    leaves, tree = jax.tree.flatten(params)
    reads = ([None] * len(leaves) if jaxpr is None
             else jaxpr.jaxpr.invars[:len(leaves)])
    orders = tree.unflatten([
        asked(leaf, chosen, var) for leaf, chosen, var in zip(
            leaves, tree.flatten_up_to(executable.input_formats[0][0]),
            reads)])
    return orders if any(jax.tree.leaves(orders, is_leaf=_is_order)) else None


def orders_key(*built_from) -> str:
    """The name ``chosen_orders``' answer is kept under between
    processes: a digest of what the decode program is built from, short
    of tracing it. ``built_from`` is what the caller built the step
    with (configuration, sampling, shapes and shardings of the
    parameters and operands, as ``repr`` prints them); added here are
    the installation (jax, jaxlib, the runtime's version, the device's
    kind) and this package's own source under ``inference/``,
    ``models/`` and ``ops/``. An answer found under a name that should
    have changed and did not costs time, never a token: any orders are
    correct, the steps read the placed tree back through them."""
    import jaxlib

    device = jax.devices()[0]
    digest = hashlib.sha256(repr((
        jax.__version__, jaxlib.__version__, device.device_kind,
        device.client.platform_version, built_from)).encode())
    package = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for part in ("inference", "models", "ops"):
        for folder, _, files in sorted(os.walk(os.path.join(package, part))):
            for name in sorted(files):
                if name.endswith(".py"):
                    with open(os.path.join(folder, name), "rb") as f:
                        digest.update(f.read())
    return digest.hexdigest()


def _orders_file(key: Optional[str]) -> Optional[str]:
    """Beside jax's persistent compile cache, which holds the programs
    the answer is about; nowhere where no such cache is kept, or for a
    program that has no name (``key`` None)."""
    from scaletorch_tpu.env import compile_cache_dir

    folder = compile_cache_dir()
    return (os.path.join(folder, f"param_orders-{key}.json")
            if key and folder else None)


def load_orders(key: Optional[str], params) -> Tuple[bool, Any]:
    """(found, orders) of an earlier process' ``store_orders`` under
    ``key``; an unreadable file counts as none."""
    path = _orders_file(key)
    if path is None:
        return False, None
    try:
        with open(path) as f:
            kept = json.load(f)
        by_layer = set(kept["by_layer"])
        moved = {leaf: (ByLayer if leaf in by_layer else tuple)(order)
                 for leaf, order in kept["moved"].items()}
    except (OSError, ValueError, KeyError, TypeError, AttributeError):
        return False, None
    if not moved:
        return True, None
    return True, jax.tree_util.tree_map_with_path(
        lambda at, _: moved.get(jax.tree_util.keystr(at), ()), params)


def store_orders(key: Optional[str], orders) -> None:
    """Keep ``orders`` under ``key`` for the processes that come after
    (written beside and renamed: a reader sees a whole file or none):
    ``moved`` the order of each leaf that has one, ``by_layer`` those of
    them that are stored as their layers."""
    path = _orders_file(key)
    if path is None:
        return
    moved = {} if orders is None else {
        jax.tree_util.keystr(at): order for at, order in
        jax.tree_util.tree_flatten_with_path(orders, is_leaf=_is_order)[0]
        if order}
    os.makedirs(os.path.dirname(path), exist_ok=True)
    scratch = f"{path}.{os.getpid()}"
    with open(scratch, "w") as f:
        json.dump({"moved": moved, "by_layer": sorted(
            leaf for leaf, order in moved.items()
            if isinstance(order, ByLayer))}, f)
    os.replace(scratch, path)


def place_params(params, orders) -> Tuple[Any, dict]:
    """``params`` as the decode program reads them (``chosen_orders``):
    a leaf that is asked for in another order of dimensions is stored
    ONCE, transposed into that order, as a new array in the device's
    default layout, which is the chosen layout of the leaf's own shape
    (``ByLayer``: as a tuple of such arrays, one a layer, the same
    bytes); every other leaf is handed on as it is. The caller's arrays
    are not donated and stay as they were. Returns the placed tree and
    what moved, under the engine's counters' names:
    ``params_relaid_leaves`` / ``_bytes`` (every leaf stored anew) and
    ``params_layered_leaves`` / ``_bytes`` (those of them stored as
    their layers).

    The steps built with the same ``orders`` (``param_orders``) read the
    placed tree through ``in_model_order``, a transposition back that
    the compiler folds into the layout it wanted: the weight copies a
    step made (``q_proj``'s stack re-laid contraction-minor every
    token) are paid here, once. Stored so, and not as the same shape
    under a custom device layout (``jax.device_put`` to a ``Format``),
    because a program compiled against custom parameter layouts came
    back from the persistent compile cache as the default-layout
    program on the v5e and read the re-laid weights as garbage
    (PERF.md, PR 48): every program that runs has default layouts."""
    relaid, layered = [], []

    def place(order, leaf):
        if not order:
            return leaf
        relaid.append(leaf.nbytes)
        if not isinstance(order, ByLayer):
            return jnp.transpose(leaf, order)
        layered.append(leaf.nbytes)
        return tuple(jnp.transpose(leaf[index], order.of_a_layer)
                     for index in range(leaf.shape[0]))

    placed = params if orders is None else jax.tree.map(
        place, orders, params, is_leaf=_is_order)
    return placed, {
        "params_relaid_leaves": len(relaid),
        "params_relaid_bytes": sum(relaid),
        "params_layered_leaves": len(layered),
        "params_layered_bytes": sum(layered)}


def in_model_order(params, orders):
    """Inside a step: the placed tree (``place_params``) as the model's
    forward indexes it. A transposition of a program's parameter, which
    the compiler turns into the layout of what reads it: no copy. A
    stack stored as its layers comes as ``Layers``, whose ``[index]`` is
    one of them transposed back: no slice of a stack is in the
    program."""
    if orders is None:
        return params

    def back(order, leaf):
        if not order:
            return leaf
        if not isinstance(order, ByLayer):
            return jnp.transpose(leaf, np.argsort(order))
        return Layers(jnp.transpose(layer, np.argsort(order.of_a_layer))
                      for layer in leaf)

    return jax.tree.map(back, orders, params, is_leaf=_is_order)


def teacher_forced_decode_paged(
    params,
    cfg,
    tokens: jax.Array,
    *,
    page_size: int,
    max_seq: Optional[int] = None,
    prefill_len: int = 1,
    forward_fn: Optional[Callable] = None,
    dtype=None,
) -> jax.Array:
    """Paged twin of ``teacher_forced_decode``: the same prefill-then-
    teacher-forced-decode schedule run against a page pool through an
    identity page table (slot ``b`` owns pages ``b*max_pages+1 ..``,
    page 0 reserved as TRASH). Returns [B, S, V] logits — the parity
    oracle proving the paged read/write path is positionally identical
    to the contiguous reference cache, layer by layer, token by token."""
    from scaletorch_tpu.inference.kv_cache import (
        PagedKVIO,
        ceil_div,
        init_paged_kv_cache,
    )

    fwd = forward_fn or resolve_forward_cached(cfg)
    b, s = tokens.shape
    s_max = max_seq or s
    max_pages = ceil_div(s_max, page_size)
    pool = init_paged_kv_cache(
        cfg, b * max_pages + 1, page_size,
        dtype=dtype or getattr(cfg, "dtype", None), slots=b)
    tables = (np.arange(b * max_pages, dtype=np.int32) + 1).reshape(
        b, max_pages)
    kv_io = PagedKVIO(jnp.asarray(tables), page_size, seq_limit=s_max)
    return _teacher_forced(fwd, params, cfg, tokens, pool, prefill_len,
                           kv_io=kv_io)


def _teacher_forced(fwd, params, cfg, tokens, cache, p, **io) -> jax.Array:
    """The schedule of both harnesses: the first ``p`` tokens in one
    call, then the rest one at a time at their positions."""
    b, s = tokens.shape
    logits, cache = fwd(
        params, tokens[:, :p], cfg, tuple(cache), **io,
        positions=jnp.broadcast_to(jnp.arange(p, dtype=jnp.int32), (b, p)))
    chunks = [logits]
    for t in range(p, s):
        logits, cache = fwd(
            params, tokens[:, t:t + 1], cfg, tuple(cache), **io,
            positions=jnp.full((b, 1), t, jnp.int32))
        chunks.append(logits)
    return jnp.concatenate(chunks, axis=1)


# the audit targets' geometry: slots, positions a slot, tokens a page
_AUDIT_SLOTS, _AUDIT_SEQ, _AUDIT_PAGE = 2, 32, 8


def _audit_entry(name, make_step, lead, *, pool_pages=None,
                 compute_dtype="fp32", forward_fn=None):
    """One inference audit target (analysis/jaxpr_audit.py): the step
    ``make_step`` builds, greedy and donating, on one device at the tiny
    geometry above, called as ``step(params, *lead, page_tables, pool,
    base_keys)``.

    Contract: donation of the PAGE POOL survives lowering
    (``donate_cache=True`` — the CPU default skips donation, which is
    exactly what the audit must not silently accept; the pool is the
    whole serving cache, so losing the alias doubles serving HBM per
    step), and the single-device step compiles to ZERO collectives — any
    collective that appears is unbudgeted by definition
    (tools/comm_budget.json records an empty set for these entries).

    Memory-tier contract (analysis/memory.py): the donated pool's bytes
    show up as input/output alias savings (``donated_min_mb`` — ST1002),
    and the engine's ``kv_cache_bytes`` matches the compiled pool
    buffers (``kv_cache`` — ST1005). Both are pinned to the DEFAULT pool
    (every slot full, plus the trash page), NOT derived from the built
    objects, so a sizing drift fails the gate instead of relaxing it:
    ``pool_pages`` exists so the ST1005 tests can build a shrunken pool
    and prove the gate catches the engine/compiled-bytes drift.
    ``compute_dtype`` selects the activation/pool dtype so the ST1003
    injection tests can build a bf16-contracted entry; the manifest
    default stays fp32 (the CPU-mesh numerics the parity oracles
    attest)."""
    from scaletorch_tpu.inference.kv_cache import (
        init_paged_kv_cache,
        kv_cache_bytes,
    )
    from scaletorch_tpu.models.llama import LlamaConfig, init_params

    dt = jnp.bfloat16 if compute_dtype in ("bf16", "bfloat16") \
        else jnp.float32
    cfg = LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=256,
        dtype=dt, param_dtype=jnp.float32,
    )
    b, page_size = _AUDIT_SLOTS, _AUDIT_PAGE
    max_pages = _AUDIT_SEQ // page_size
    num_pages = b * max_pages + 1
    params = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg))
    pool = jax.eval_shape(
        lambda: init_paged_kv_cache(
            cfg, pool_pages if pool_pages is not None else num_pages,
            page_size, dtype=dt))
    fn = make_step(
        cfg, SamplingParams(temperature=0.0), page_size=page_size,
        seq_limit=_AUDIT_SEQ, forward_fn=forward_fn, donate_cache=True)
    args = (
        params,
        *lead,
        jax.ShapeDtypeStruct((b, max_pages), jnp.int32),   # page tables
        pool,
        jax.ShapeDtypeStruct((b, 2), jnp.uint32),          # base_keys
    )
    pool_mb = kv_cache_bytes(cfg, num_pages, page_size, dt) / 1e6
    return {
        "name": name,
        "file": "scaletorch_tpu/inference/decode.py",
        "fn": fn,
        "args": args,
        "min_devices": 1,
        "quantized_axis": None,
        "expect_donation": True,
        "hoisted_axes": (),
        "max_collective_result_mb": 1.0,
        "compute_dtype": compute_dtype,
        "donated_min_mb": round(0.9 * pool_mb, 4),
        "kv_cache": {
            "cfg": cfg, "dtype": dt, "page_size": page_size,
            "num_pages": num_pages, "arg_index": len(lead) + 2,
        },
    }


def audit_entry_paged_prefill(pool_pages: Optional[int] = None):
    """Deep-tier audit target: the jitted prefill step, the program that
    holds the memory peak of a serving engine (``_audit_entry`` for the
    contract)."""
    b = _AUDIT_SLOTS
    return _audit_entry(
        "paged_prefill_step", make_paged_prefill_step,
        (
            jax.ShapeDtypeStruct((b, _AUDIT_SEQ), jnp.int32),  # tokens
            jax.ShapeDtypeStruct((b,), jnp.int32),             # tail_lens
            jax.ShapeDtypeStruct((b,), jnp.int32),             # starts
            jax.ShapeDtypeStruct((b,), jnp.bool_),             # write_mask
        ),
        pool_pages=pool_pages)


def audit_entry_paged_decode(
    pool_pages: Optional[int] = None,
    compute_dtype: str = "fp32",
    fp32_residual: bool = False,
):
    """Deep-tier audit target: the jitted one-token decode step
    (``_audit_entry`` for the contract).

    ``fp32_residual=True`` routes the pool through a large fp32
    round-trip in the forward — the accidental upcast the memory tier's
    precision-leak check (ST1003) must attribute to its source line in
    a ``compute_dtype="bf16"`` entry. The manifest build stays fp32
    (check inert, like the train steps).
    """
    forward_fn = None
    if fp32_residual:
        from scaletorch_tpu.models.llama import forward_cached as base_fwd

        def forward_fn(p, tokens, c, kv, **kw):
            logits, new_kv = base_fwd(p, tokens, c, kv, **kw)
            # the injected leak: a full-pool fp32 round trip
            new_kv = jax.tree.map(
                lambda x: (x.astype(jnp.float32) + 0.0).astype(x.dtype),
                new_kv,
            )
            return logits, new_kv

    b = _AUDIT_SLOTS
    entry = _audit_entry(
        "paged_decode_step", make_paged_decode_step,
        (
            jax.ShapeDtypeStruct((b,), jnp.int32),             # tokens
            jax.ShapeDtypeStruct((b,), jnp.int32),             # positions
            jax.ShapeDtypeStruct((b,), jnp.bool_),             # active
        ),
        pool_pages=pool_pages, compute_dtype=compute_dtype,
        forward_fn=forward_fn)
    # one pool buffer (k or v) counts as "large" — the smallest fp32
    # intermediate the leak injection materialises
    entry["fp32_large_elems"] = 2048
    return entry


def teacher_forced_decode(
    params,
    cfg,
    tokens: jax.Array,
    *,
    max_seq: Optional[int] = None,
    prefill_len: int = 1,
    forward_fn: Optional[Callable] = None,
    dtype=None,
) -> jax.Array:
    """Reference harness on the contiguous cache (``init_kv_cache``, the
    cached forwards' ``kv_io=None`` default): prefill the first
    ``prefill_len`` tokens, then decode the rest one at a time with the
    GROUND-TRUTH token at each step (no sampling). Returns [B, S, V]
    logits position-aligned with the full-sequence training forward —
    the parity oracle of the cached forwards (ISSUE 4 acceptance:
    prefill+decode logit parity under teacher forcing).
    """
    from scaletorch_tpu.inference.kv_cache import init_kv_cache

    fwd = forward_fn or resolve_forward_cached(cfg)
    b, s = tokens.shape
    cache = init_kv_cache(cfg, b, max_seq or s,
                          dtype=dtype or getattr(cfg, "dtype", None))
    return _teacher_forced(fwd, params, cfg, tokens, cache, prefill_len)
