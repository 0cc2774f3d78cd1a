"""Expert parallelism: top-k routing + capacity-based all-to-all dispatch.

Capability parity with reference scaletorch/parallel/expert_parallel/
ep_comms.py:14-171 (sort-based variable-split all-to-all dispatch) and
scaletorch/models/moe.py:350-640 (capacity-factor dispatch), re-designed
TPU-first:

  * XLA collectives are static-shape, so the jitted path uses
    **capacity-factor dispatch** (the GShard/Switch recipe the reference
    implements single-device in moe.py:510-600): each expert accepts at
    most C tokens per rank; routing builds a [N, E, C] one-hot dispatch
    tensor and token movement is einsum + ``lax.all_to_all`` over the ep
    axis — dense MXU work instead of gather/scatter.
  * The reference's sort-based exchange (argsort by destination rank,
    count exchange, 3 variable all-to-alls — ep_comms.py:41-133) relies on
    ragged NCCL/HCCL splits; its *invariants* (every kept token routed to
    the rank owning its expert, weights preserved, order restored) are the
    compatibility surface and are tested identically (reference
    tests/parallel/test_ep_comms.py:69-96).
  * Aux losses: Switch load-balance loss (f·P·E) and router z-loss,
    matching MoERouter (model_qwen3_moe.py:30-92) and the GPT-MoE router
    (moe.py:350-600).

Token flow (inside shard_map, ep axis size = ep, E experts total,
E_local = E / ep per rank, N local tokens, capacity C):

    route     [N, H] -> dispatch [N, E, C] one-hot, combine [N, E, C]
    dispatch  einsum('nh,nec->ech') -> [E, C, H]
              all_to_all over ep    -> [E_local, ep·C, H]
    compute   batched expert SwiGLU (grouped-matmul role of
              npu_grouped_matmul, models/npu_patch.py:94-131)
    return    reverse all_to_all    -> [E, C, H]
    combine   einsum('ech,nec->nh') -> [N, H]
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from scaletorch_tpu.parallel.tensor_parallel import pvary_missing


def expert_capacity(
    num_tokens: int, num_experts: int, top_k: int, capacity_factor: float
) -> int:
    """Per-expert token capacity (reference moe.py capacity computation):
    C = ceil(capacity_factor * N * k / E), at least 1, at most N."""
    c = int(-(-capacity_factor * num_tokens * top_k // num_experts))
    return max(1, min(c, num_tokens))


def _route_core(
    router_logits: jax.Array,
    top_k: int,
    capacity: int,
    normalize_weights: bool,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, Dict[str, jax.Array]]:
    """Shared routing math for BOTH dispatch forms — gate choice, capacity
    queue position, drop mask, and aux losses. 'Identical math across
    modes' is this module's load-bearing invariant; it lives in exactly
    one place. Returns (gate_idx, gate_w, pos, kept, aux)."""
    n, e = router_logits.shape
    logits32 = router_logits.astype(jnp.float32)
    probs = jax.nn.softmax(logits32, axis=-1)  # [N, E]
    gate_w, gate_idx = jax.lax.top_k(probs, top_k)  # [N, k]
    if normalize_weights:
        gate_w = gate_w / jnp.sum(gate_w, axis=-1, keepdims=True)

    # Position of each (token, choice) in its expert's queue: tokens are
    # served in index order, choice-major (k-th choices queue after all
    # (k-1)-th choices of earlier tokens — the Switch convention).
    # Explicit iota==index one-hot instead of jax.nn.one_hot: the latter
    # lowers through a closed_call whose MLIR lowering-cache entry goes
    # missing when an interpret-mode pallas_call is lowered in the same
    # program (the grouped-MLP kernel tests on CPU).
    onehot = (gate_idx[..., None] == jnp.arange(e)).astype(jnp.int32)
    # flatten choices to [k*N, E] in choice-major order so cumsum ranks
    # first choices of all tokens before any second choice.
    flat = onehot.transpose(1, 0, 2).reshape(top_k * n, e)
    position_in_expert = jnp.cumsum(flat, axis=0) - flat  # [k*N, E]
    pos = jnp.sum(position_in_expert * flat, axis=-1)  # [k*N]
    pos = pos.reshape(top_k, n).transpose(1, 0)  # [N, k]
    kept = pos < capacity

    # Switch aux loss: E * sum_e f_e * P_e (pre-capacity assignment counts)
    f = jnp.mean(jnp.sum(onehot.astype(jnp.float32), axis=1), axis=0)  # [E]
    p = jnp.mean(probs, axis=0)  # [E]
    aux = {
        "aux_loss": e * jnp.sum(f * p) / top_k,
        "z_loss": jnp.mean(jnp.square(jax.nn.logsumexp(logits32, axis=-1))),
        "expert_load": f,
        "dropped_fraction": 1.0 - jnp.sum(kept) / (n * top_k),
    }
    return gate_idx, gate_w, pos, kept, aux


def top_k_routing(
    router_logits: jax.Array,
    top_k: int,
    capacity: int,
    *,
    normalize_weights: bool = True,
) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """Softmax top-k routing with capacity truncation.

    router_logits: [N, E] (fp32 recommended). Returns
      dispatch [N, E, C] one-hot {0,1} — token n occupies slot c of expert e
      combine  [N, E, C] — dispatch · gating weight
      aux      {'aux_loss', 'z_loss', 'expert_load', 'dropped_fraction'}

    Gate math: softmax over ALL experts, take top-k, optionally
    renormalise the top-k weights to sum to 1. With
    ``normalize_weights=True`` (default) this equals the reference
    MoERouter exactly — softmax_all(topk)/Σ ≡ softmax over the top-k
    logits (model_qwen3_moe.py:48-89; the reference's own norm_topk_prob
    renorm is a no-op since its softmax already sums to 1). With
    ``normalize_weights=False`` the weights follow HF transformers'
    norm_topk_prob=False semantics (full-softmax weights, sum < 1) and
    diverge from the reference, which always sums to 1.

    The aux loss is the Switch load-balance loss E · Σ_e f_e · P_e / k,
    with f the fraction of (token, choice) pairs landing on e (so f sums
    to k) and P the mean router probability. The 1/k matches HF
    transformers' load_balancing_loss_func — calibrate
    ``router_aux_loss_coef`` against HF; the reference omits the 1/k
    (model_qwen3_moe.py:74-88), so its coefficient is k× weaker for the
    same value. Tokens beyond an expert's capacity are dropped
    (contribute zero output — residual passes them through), matching
    capacity-based MoE semantics (moe.py:510-600).
    """
    n, e = router_logits.shape
    gate_idx, gate_w, pos, kept, aux = _route_core(
        router_logits, top_k, capacity, normalize_weights)

    def onehot_f(idx, depth):
        return (idx[..., None] == jnp.arange(depth)).astype(jnp.float32)

    # dispatch/combine tensors (dropped choices map to a one-hot column
    # at index `capacity`, which onehot_f truncates away)
    dispatch = (
        onehot_f(gate_idx, e)[..., None]
        * onehot_f(jnp.where(kept, pos, capacity), capacity)[:, :, None, :]
    )  # [N, k, E, C]
    dispatch = jnp.sum(dispatch, axis=1)  # [N, E, C]
    combine = (
        onehot_f(gate_idx, e)
        * jnp.where(kept, gate_w, 0.0)[..., None]
    )  # [N, k, E]
    combine = jnp.einsum("nke,nkc->nec", combine,
                         onehot_f(jnp.where(kept, pos, capacity), capacity))
    return dispatch, combine, aux


def _exchange_to_experts(slots: jax.Array, axis: Optional[str]) -> jax.Array:
    """[E, G·C, H] full-expert slabs -> [E_local, ep·G·C, H] on the rank
    owning each expert (identity at axis=None — the world_size==1 no-op
    contract of the reference collectives, collective_ops.py:137)."""
    e, gc, h = slots.shape
    if axis is None:
        return slots
    slots = pvary_missing(slots, axis)
    ep = jax.lax.axis_size(axis)
    e_local = e // ep
    # [E, G·C, H] -> [ep, E_local, G·C, H]; exchange leading dim so each
    # rank collects its own experts' slabs from every peer.
    slots = slots.reshape(ep, e_local, gc, h)
    slots = jax.lax.all_to_all(slots, axis, split_axis=0, concat_axis=0,
                               tiled=False)  # [ep, E_local, G·C, H]
    # merge (source_rank, slot) into one token dim per local expert
    return slots.transpose(1, 0, 2, 3).reshape(e_local, ep * gc, h)


def _exchange_from_experts(expert_out: jax.Array,
                           axis: Optional[str]) -> jax.Array:
    """Reverse of ``_exchange_to_experts``: [E_local, ep·G·C, H] back to
    the source ranks' [E, G·C, H] slab layout."""
    if axis is None:
        return expert_out
    expert_out = pvary_missing(expert_out, axis)
    ep = jax.lax.axis_size(axis)
    e_local = expert_out.shape[0]
    gc = expert_out.shape[1] // ep
    h = expert_out.shape[-1]
    slots = expert_out.reshape(e_local, ep, gc, h).transpose(1, 0, 2, 3)
    slots = jax.lax.all_to_all(slots, axis, split_axis=0, concat_axis=0,
                               tiled=False)  # [ep, E_local, G·C, H]
    return slots.reshape(ep * e_local, gc, h)


def dispatch_tokens(
    x: jax.Array,
    dispatch: jax.Array,
    *,
    axis: Optional[str] = None,
) -> jax.Array:
    """Route tokens to their experts' owning ranks.

    x: [N, H] or grouped [G, N, H]; dispatch: [N, E, C] or [G, N, E, C]
    (groups routed independently — the GShard trick that keeps the
    dispatch tensors O(G·N²/G²) = O(N²/G) instead of O(N²)). Returns
    [E_local, ep·G·C, H] (with ``axis``) or [E, G·C, H] (axis=None,
    single-rank semantics — the world_size==1 no-op contract of the
    reference collectives, collective_ops.py:137).

    TPU-native equivalent of the reference's argsort + variable-split
    all-to-all (ep_comms.py:41-133): the einsum IS the sort (dense,
    MXU-friendly) and the all_to_all moves equal-size [E_local, G·C] slabs.

    COST NOTE: the one-hot einsum does O(N·E·C·H) MAC work — dominant
    over the expert matmuls themselves once E·C >> k·3·I (measured: ~4.5x
    the expert FLOPs at Qwen3-30B-A3B's E=128/top-8). Large-E configs
    should route through ``dispatch_tokens_indexed`` (O(N·k·H) scatter),
    which ``moe_block`` auto-selects.
    """
    if x.ndim == 2:
        x, dispatch = x[None], dispatch[None]
    slots = jnp.einsum("gnh,gnec->egch", x, dispatch.astype(x.dtype))
    e, g, c, h = slots.shape
    return _exchange_to_experts(slots.reshape(e, g * c, h), axis)


def gather_tokens(
    expert_out: jax.Array,
    combine: jax.Array,
    *,
    axis: Optional[str] = None,
) -> jax.Array:
    """Return expert outputs to their source ranks and combine top-k.

    expert_out: [E_local, ep·G·C, H] (or [E, G·C, H] with axis=None);
    combine: [N, E, C] or grouped [G, N, E, C]. Returns [N, H] / [G, N, H]
    — the weighted sum over each token's kept expert slots (reference
    gather_tokens + caller top-k sum, ep_comms.py:136-171).
    """
    grouped = combine.ndim == 4
    if not grouped:
        combine = combine[None]
    g, n, e, c = combine.shape
    combine = combine.astype(expert_out.dtype)
    if axis is not None:
        combine = pvary_missing(combine, axis)
    expert_out = _exchange_from_experts(expert_out, axis)
    h = expert_out.shape[-1]
    slots = expert_out.reshape(e, g, c, h)  # [E, G, C, H]
    y = jnp.einsum("egch,gnec->gnh", slots, combine)
    return y if grouped else y[0]


def top_k_routing_indexed(
    router_logits: jax.Array,
    top_k: int,
    capacity: int,
    *,
    normalize_weights: bool = True,
) -> Tuple[Dict[str, jax.Array], Dict[str, jax.Array]]:
    """Index-form of ``top_k_routing`` — identical routing decisions and
    aux losses, WITHOUT materialising the [N, E, C] one-hot tensors.

    Returns (routing, aux) with routing =
      expert_idx [N, k] int32 — chosen expert per (token, choice)
      slot       [N, k] int32 — capacity-queue position; >= capacity means
                                the choice was dropped
      weight     [N, k] f32   — gating weight, already zeroed for drops

    Why this exists: the one-hot dispatch/combine einsums cost
    O(N·E·C·H) MACs and O(N·E·C) memory — at large expert counts
    (Qwen3-30B-A3B: E=128, top-8, cf 1.25) that is ~4.5x the FLOPs of the
    expert matmuls themselves. The index form scatters/gathers exactly
    the O(N·k·H) rows that move. Same math, same drops, same aux.
    """
    gate_idx, gate_w, pos, kept, aux = _route_core(
        router_logits, top_k, capacity, normalize_weights)
    routing = {
        "expert_idx": gate_idx.astype(jnp.int32),
        "slot": pos.astype(jnp.int32),
        "weight": jnp.where(kept, gate_w, 0.0),
    }
    return routing, aux


def dispatch_tokens_indexed(
    x: jax.Array,
    routing: Dict[str, jax.Array],
    *,
    num_experts: int,
    capacity: int,
    axis: Optional[str] = None,
) -> jax.Array:
    """Index-based counterpart of ``dispatch_tokens``: scatter each kept
    (token, choice) row into its [E, G, C, H] capacity slot — O(N·k·H)
    moved rows instead of the one-hot's O(N·E·C·H) einsum — then ride the
    same equal-slab ``all_to_all``. Output layout is identical to
    ``dispatch_tokens`` ([E_local, ep·G·C, H] / [E, G·C, H]), so
    ``moe_mlp`` and the grouped Pallas kernel are path-agnostic.

    x: [N, H] or [G, N, H]; routing leaves [N, k] or [G, N, k].
    """
    if x.ndim == 2:
        x = x[None]
        routing = {k: v[None] for k, v in routing.items()}
    if axis is not None:
        # Mirror gather_tokens_indexed: routing normally derives from
        # ep-varying activations, but a caller feeding REPLICATED routing
        # (precomputed indices) would otherwise hit a vma mismatch only on
        # the dispatch side (ADVICE r4) — pvary is a no-op when already
        # varying.
        routing = {k: pvary_missing(v, axis) for k, v in routing.items()}
    g, n, h = x.shape
    k = routing["expert_idx"].shape[-1]
    gi = jnp.broadcast_to(jnp.arange(g)[:, None, None], (g, n, k))
    ni = jnp.broadcast_to(jnp.arange(n)[None, :, None], (g, n, k))
    # rows past capacity carry slot >= C: mode='drop' discards them, which
    # IS the capacity-drop semantics (residual passes those tokens through)
    slots = jnp.zeros((num_experts, g, capacity, h), x.dtype).at[
        routing["expert_idx"].reshape(-1),
        gi.reshape(-1),
        routing["slot"].reshape(-1),
    ].set(x[gi.reshape(-1), ni.reshape(-1)], mode="drop")
    slots = slots.reshape(num_experts, g * capacity, h)
    return _exchange_to_experts(slots, axis)


def gather_tokens_indexed(
    expert_out: jax.Array,
    routing: Dict[str, jax.Array],
    *,
    num_experts: int,
    capacity: int,
    axis: Optional[str] = None,
) -> jax.Array:
    """Index-based counterpart of ``gather_tokens``: bring expert outputs
    home over the reverse ``all_to_all``, then gather each (token, choice)
    slot and take the weight-combined top-k sum — O(N·k·H) gathered rows.
    Dropped choices contribute zero (their weight is zeroed in routing).
    """
    grouped = routing["expert_idx"].ndim == 3
    if not grouped:
        routing = {k: v[None] for k, v in routing.items()}
    if axis is not None:
        routing = {k: pvary_missing(v, axis) for k, v in routing.items()}
    expert_out = _exchange_from_experts(expert_out, axis)
    h = expert_out.shape[-1]
    g, n, k = routing["expert_idx"].shape
    slots = expert_out.reshape(num_experts, g, capacity, h)
    gi = jnp.broadcast_to(jnp.arange(g)[:, None, None], (g, n, k))
    safe_slot = jnp.minimum(routing["slot"], capacity - 1)
    vals = slots[routing["expert_idx"], gi, safe_slot]  # [G, N, k, H]
    w = routing["weight"].astype(expert_out.dtype)[..., None]
    y = jnp.sum(w * vals, axis=2)  # [G, N, H]
    return y if grouped else y[0]


# ---------------------------------------------------------------------------
# Mode-aware wrappers: ONE dispatch API over the einsum/index forms, so
# every MoE model (qwen3_moe.moe_block, gpt_moe, custom families) is
# movement-implementation-agnostic. ``state`` is a dict of arrays either
# way (vmap/pytree friendly); ``mode`` stays a static kwarg.
# ---------------------------------------------------------------------------


def resolve_moe_dispatch(mode: str, num_experts: int) -> str:
    """'auto' -> the form the evidence favors at this expert count.

    AOT_DISPATCH_CROSSOVER.json (XLA cost analysis of the full train
    step, E swept 4..64): the one-hot einsums' O(N*E*C*H) cost is
    E-INDEPENDENT at fixed capacity factor (E*C = N*k*cf), a flat ~25%
    FLOP overhead that the index form avoids at EVERY expert count —
    there is no compiled-FLOP crossover; index wins from E=4 up. CPU
    wall-clock mechanics agree at E=8 (1.19x). 'auto' therefore always
    picks index; 'einsum' stays selectable for A/B runs
    (tools/bench_moe_dispatch.py, bench.py phase 3.5) and as a fallback
    should silicon ever disagree (scatter/gather can be memory-bound
    where einsum is MXU-bound — the wall-clock A/B is the final word)."""
    _check_mode(mode, allow_auto=True)
    if mode != "auto":
        return mode
    return "index"


def _check_mode(mode: str, allow_auto: bool = False) -> None:
    ok = ("auto", "einsum", "index") if allow_auto else ("einsum", "index")
    if mode not in ok:
        raise ValueError(
            f"moe dispatch mode must be one of {ok}, got {mode!r}"
        )


def route_tokens(
    router_logits: jax.Array,
    top_k: int,
    capacity: int,
    *,
    mode: str,
    normalize_weights: bool = True,
) -> Tuple[Dict[str, jax.Array], Dict[str, jax.Array]]:
    """(state, aux) for ``mode`` in {'einsum', 'index'} — identical routing
    decisions, drops, and aux losses in both forms."""
    _check_mode(mode)
    if mode == "index":
        return top_k_routing_indexed(
            router_logits, top_k, capacity,
            normalize_weights=normalize_weights)
    dispatch, combine, aux = top_k_routing(
        router_logits, top_k, capacity, normalize_weights=normalize_weights)
    return {"dispatch": dispatch, "combine": combine}, aux


def dispatch_routed(
    x: jax.Array,
    state: Dict[str, jax.Array],
    *,
    mode: str,
    num_experts: int,
    capacity: int,
    axis: Optional[str] = None,
) -> jax.Array:
    """Move tokens to their experts under ``state`` from ``route_tokens``.
    Output layout is identical for both modes ([E_local, ep·G·C, H])."""
    _check_mode(mode)
    if mode == "index":
        return dispatch_tokens_indexed(
            x, state, num_experts=num_experts, capacity=capacity, axis=axis)
    return dispatch_tokens(x, state["dispatch"], axis=axis)


def combine_routed(
    expert_out: jax.Array,
    state: Dict[str, jax.Array],
    *,
    mode: str,
    num_experts: int,
    capacity: int,
    axis: Optional[str] = None,
) -> jax.Array:
    """Bring expert outputs home and take the weighted top-k sum."""
    _check_mode(mode)
    if mode == "index":
        return gather_tokens_indexed(
            expert_out, state, num_experts=num_experts, capacity=capacity,
            axis=axis)
    return gather_tokens(expert_out, state["combine"], axis=axis)


def routed_fill_counts(
    state: Dict[str, jax.Array],
    *,
    mode: str,
    num_experts: int,
    capacity: int,
) -> jax.Array:
    """[E, G] per-(expert, group) fill counts for the slot-skipping
    grouped kernel, from either state form."""
    _check_mode(mode)
    if mode == "index":
        return slot_fill_counts_indexed(state, num_experts, capacity)
    from scaletorch_tpu.ops.pallas.grouped_mlp import slot_fill_counts

    return slot_fill_counts(state["dispatch"])


def slot_fill_counts_indexed(
    routing: Dict[str, jax.Array], num_experts: int, capacity: int
) -> jax.Array:
    """[E, G] int32 fill counts from index-form routing (the counterpart
    of ops.pallas.grouped_mlp.slot_fill_counts for the one-hot form):
    capacity dispatch fills each expert's slots as a prefix, so the count
    is the number of kept (token, choice) rows per (expert, group)."""
    ei = routing["expert_idx"]
    if ei.ndim == 2:
        ei, slot = ei[None], routing["slot"][None]
    else:
        slot = routing["slot"]
    kept = slot < capacity
    onehot = (ei[..., None] == jnp.arange(num_experts)) & kept[..., None]
    return jnp.sum(onehot, axis=(1, 2)).astype(jnp.int32).T  # [E, G]


def sorted_dispatch_reference(
    x: jax.Array, expert_ids: jax.Array, num_experts: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Sort-based dispatch semantics (host/test path; NOT jit-static).

    Mirrors the reference's stable argsort-by-destination
    (ep_comms.py:41-133) so its invariants can be asserted directly:
    returns (sorted_tokens, sort_idx, counts_per_expert) with
    ``sorted_tokens = x[sort_idx]`` grouped by expert id, stable within
    groups, and ``counts`` summing to N. Used by tests and as the
    fallback for ragged (non-capacity) flows outside jit.
    """
    sort_idx = jnp.argsort(expert_ids, stable=True)
    counts = jnp.bincount(expert_ids, length=num_experts)
    return x[sort_idx], sort_idx, counts


# ---------------------------------------------------------------------------
# Sort-based dispatch — the reference's ragged exchange, TPU-native
# ---------------------------------------------------------------------------
#
# The reference's production dispatch is argsort-by-destination + count
# exchange + 3 variable-split all-to-alls (ep_comms.py:41-133) — ZERO
# token drops, ragged splits. XLA collectives want static shapes (and
# XLA:CPU, the test backend, lacks ragged-all-to-all entirely), so the
# exchange pads each destination chunk to a static per-peer capacity and
# moves equal [ep, P] slabs with the dense ``all_to_all``; the ragged
# truth lives in the exchanged size vector, exactly the reference's count
# all-to-all. This path trades the capacity path's token drops for masked
# compute: every local expert runs over the whole receive buffer with a
# membership mask (E_local× the matmul work), so it suits
# correctness-critical flows and low expert counts; the capacity path
# stays the throughput default (dense MXU slots, bounded memory).

def _excl_cumsum(x):
    return jnp.concatenate([jnp.zeros((1,), x.dtype), jnp.cumsum(x)[:-1]])


def sort_dispatch_tokens(
    x: jax.Array,
    expert_ids: jax.Array,
    *,
    axis: str,
    num_experts: int,
    chunk_capacity: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, Dict[str, jax.Array]]:
    """Reference-parity sort-based dispatch (ep_comms.py:41-133) in jit.

    x: [N, H] local (token·choice) rows; expert_ids: [N] global expert of
    each row. Stable-argsorts rows by destination rank, scatters them
    into per-destination slabs of ``chunk_capacity`` rows (default N —
    the zero-drop worst case; smaller values bound memory but can drop
    under extreme skew), exchanges the slabs, and returns

      recv_x     [ep·P, H]  received rows, grouped by source rank
      recv_local [ep·P]     each row's LOCAL expert index; E_local (an
                            invalid id) marks empty slots
      recv_valid [ep·P]     bool mask of filled slots
      meta                  bookkeeping consumed by ``sort_gather_tokens``

    Invariant parity with reference test_ep_comms.py:69-96: chunk sizes
    sum to N, the send permutation is stable within destination groups,
    and every received id falls in this rank's local range.
    """
    ep = jax.lax.axis_size(axis)
    n, h = x.shape
    e_local = num_experts // ep
    p = chunk_capacity or n
    me = jax.lax.axis_index(axis)

    x = pvary_missing(x, axis)
    expert_ids = pvary_missing(expert_ids, axis)
    dest = expert_ids // e_local
    order = jnp.argsort(dest, stable=True)
    x_s = x[order]
    ids_s = expert_ids[order]
    dest_s = dest[order]
    send_sizes = jnp.bincount(dest, length=ep)          # [ep]
    slot = jnp.arange(n) - _excl_cumsum(send_sizes)[dest_s]

    # pad each destination's chunk into a static [ep, P] slab; rows past
    # the slab (only possible when chunk_capacity < its send size) drop
    send_x = jnp.zeros((ep, p, h), x.dtype).at[dest_s, slot].set(
        x_s, mode="drop")
    send_ids = jnp.full((ep, p), num_experts, ids_s.dtype).at[
        dest_s, slot].set(ids_s, mode="drop")

    # the reference's count all-to-all + 2 payload all-to-alls
    recv_sizes = jax.lax.all_to_all(
        send_sizes[:, None], axis, split_axis=0, concat_axis=0)[:, 0]
    recv_x = jax.lax.all_to_all(send_x, axis, split_axis=0, concat_axis=0)
    recv_ids = jax.lax.all_to_all(send_ids, axis, split_axis=0, concat_axis=0)

    recv_valid = (
        jnp.arange(p)[None, :] < jnp.minimum(recv_sizes, p)[:, None]
    ).reshape(-1)
    recv_local = jnp.where(
        recv_valid, recv_ids.reshape(-1) - me * e_local, e_local)
    meta = {
        "order": order, "dest_s": dest_s, "slot": slot, "n": n, "p": p,
        # send-side rows past a destination slab (only when chunk_capacity
        # undercuts a skewed send size) — 0 on the default zero-drop
        # capacity; surfaces skew-induced drops instead of burying them
        # in the docstring
        "dropped_rows": jnp.sum(jnp.maximum(send_sizes - p, 0)),
    }
    return recv_x.reshape(ep * p, h), recv_local, recv_valid, meta


def sort_gather_tokens(
    expert_out: jax.Array, meta: Dict[str, jax.Array], *, axis: str
) -> jax.Array:
    """Return expert outputs to their source ranks and restore the
    original row order (reference gather_tokens, ep_comms.py:136-171).
    expert_out: [ep·P, H] in the receive-slab layout. Returns [N, H]."""
    ep = jax.lax.axis_size(axis)
    p, n = meta["p"], meta["n"]
    h = expert_out.shape[-1]
    back = jax.lax.all_to_all(
        expert_out.reshape(ep, p, h), axis, split_axis=0, concat_axis=0)
    # slab [d, slot] holds the result of sorted row with that (dest, slot);
    # rows that overflowed the slab were never exchanged — they must come
    # back as zeros, not as the clamped gather's copy of the last slot
    kept = meta["slot"] < p
    sorted_back = jnp.where(
        kept[:, None],
        back[meta["dest_s"], jnp.minimum(meta["slot"], p - 1)],
        0,
    )
    # un-sort: row i of the send order was x[order[i]]
    return jnp.zeros((n, h), back.dtype).at[meta["order"]].set(sorted_back)


def sorted_moe_forward(
    x: jax.Array,
    gate_idx: jax.Array,
    gate_w: jax.Array,
    gate_proj: jax.Array,
    up_proj: jax.Array,
    down_proj: jax.Array,
    *,
    axis: Optional[str] = None,
    num_experts: int,
    chunk_capacity: Optional[int] = None,
    compute_dtype: Any = None,
) -> jax.Array:
    """Zero-drop MoE forward over the sort-based exchange.

    x: [N, H]; gate_idx/gate_w: [N, k] top-k expert ids and weights;
    gate/up/down_proj: local expert weights [E_local, H, I]/[E_local, I, H].
    Returns [N, H]. With ``axis=None`` runs single-rank (E_local = E),
    the world_size==1 no-op contract.
    """
    n, h = x.shape
    k = gate_idx.shape[-1]
    cdt = compute_dtype or x.dtype
    flat_x = jnp.repeat(x, k, axis=0)                 # row n·k+j = choice j
    flat_ids = gate_idx.reshape(-1)

    if axis is None:
        recv, local_ids, valid = flat_x, flat_ids, jnp.ones(n * k, bool)
    else:
        recv, local_ids, valid, meta = sort_dispatch_tokens(
            flat_x, flat_ids, axis=axis, num_experts=num_experts,
            chunk_capacity=chunk_capacity)

    from scaletorch_tpu.models.layers import swiglu

    e_local = gate_proj.shape[0]
    if e_local > 4:
        import warnings

        warnings.warn(
            f"sorted_moe_forward with E_local={e_local}: every local expert "
            "matmuls the WHOLE receive buffer under a membership mask, so "
            f"compute scales {e_local}x vs the capacity path's dense slots. "
            "This path is correctness-tier — for E_local > 4 use the "
            "capacity dispatch (dispatch_tokens/moe_mlp, the moe_block "
            "default) or raise expert_parallel_size so each rank holds "
            "<= 4 experts.",
            RuntimeWarning,
            stacklevel=2,
        )
    recv_c = jnp.where(valid[:, None], recv, 0).astype(cdt)
    out = jnp.zeros(recv.shape, cdt)
    for e in range(e_local):  # static loop; each expert masks its rows
        mask = (local_ids == e)[:, None]
        g = recv_c @ gate_proj[e].astype(cdt)
        u = recv_c @ up_proj[e].astype(cdt)
        out = out + jnp.where(mask, swiglu(g, u) @ down_proj[e].astype(cdt), 0)

    if axis is not None:
        out = sort_gather_tokens(out, meta, axis=axis)
    y = out.reshape(n, k, h) * gate_w[..., None].astype(cdt)
    return jnp.sum(y, axis=1)


def validate_ep_divisibility(cfg, ep: int) -> None:
    """Experts shard evenly over the ep axis (reference
    model_qwen3_moe.py:192-207 requires num_experts % ep_size == 0)."""
    if cfg.num_experts % ep != 0:
        raise ValueError(
            f"num_experts={cfg.num_experts} not divisible by ep={ep}"
        )


def moe_mlp(
    x_grouped: jax.Array,
    gate_w: jax.Array,
    up_w: jax.Array,
    down_w: jax.Array,
    *,
    tp_axis: Optional[str] = None,
    compute_dtype: Any = None,
    reduce: str = "sum",
    slot_counts: Optional[jax.Array] = None,
    capacity: Optional[int] = None,
) -> jax.Array:
    """Batched per-expert SwiGLU: the grouped-matmul role of
    npu_grouped_matmul (reference models/npu_patch.py:94-131) as a single
    batched einsum — XLA tiles it onto the MXU directly.

    x_grouped: [E_local, T, H]; gate/up: [E_local, H, I(/tp)];
    down: [E_local, I(/tp), H]. With ``tp_axis``, gate/up are
    column-parallel and down row-parallel within each expert (the
    reference's EP×TP composition, model_qwen3_moe.py:192-207);
    ``reduce='none'`` skips the completing psum so the caller can fuse it
    into a sequence reduce-scatter (the SP exit path).

    Passing ``slot_counts`` [E_local, T/capacity] + ``capacity`` opts in
    to the slot-skipping Pallas kernel (ops/pallas/grouped_mlp.py) —
    empty capacity slots past each block's fill count cost nothing. The
    ``SCALETORCH_TPU_GROUPED_MLP_KERNEL`` env toggle gates only the
    production call site (qwen3_moe.moe_block).
    """
    cdt = compute_dtype or x_grouped.dtype
    gate_w, up_w, down_w = (w.astype(cdt) for w in (gate_w, up_w, down_w))
    if tp_axis is not None:
        gate_w = pvary_missing(gate_w, tp_axis)
        up_w = pvary_missing(up_w, tp_axis)
        down_w = pvary_missing(down_w, tp_axis)
        x_grouped = pvary_missing(x_grouped, tp_axis)
    # Passing slot_counts+capacity IS the opt-in (the env toggle gates
    # the single production call site, qwen3_moe.moe_block); re-checking
    # the env here would silently no-op explicit callers.
    if slot_counts is not None and capacity:
        from scaletorch_tpu.ops.flash_attention import _pallas_available
        from scaletorch_tpu.ops.pallas.grouped_mlp import (
            grouped_swiglu_mlp,
            masked_grouped_mlp,
        )

        e_l, t, hd = x_grouped.shape
        x4 = x_grouped.reshape(e_l, t // capacity, capacity, hd).astype(cdt)
        if _pallas_available():
            # custom_vjp: trailing config args are positional (nondiff)
            out = grouped_swiglu_mlp(x4, slot_counts, gate_w, up_w, down_w)
        else:
            # off-TPU: identical masked semantics, no pallas lowering
            out = masked_grouped_mlp(x4, slot_counts, gate_w, up_w, down_w)
        out = out.reshape(e_l, t, hd)
    else:
        from scaletorch_tpu.models.layers import swiglu

        g = jnp.einsum("eth,ehi->eti", x_grouped, gate_w)
        u = jnp.einsum("eth,ehi->eti", x_grouped, up_w)
        out = jnp.einsum("eti,eih->eth", swiglu(g, u), down_w)
    if tp_axis is not None and reduce == "sum":
        out = jax.lax.psum(out, tp_axis)
    return out


def exchange_slot_counts(counts: jax.Array, axis: Optional[str]) -> jax.Array:
    """[E, G] per-(expert, group) fill counts -> this rank's receive-slab
    order [E_local, ep·G], matching dispatch_tokens' token layout (blocks
    of ``capacity`` ordered (source_rank, group))."""
    if axis is None:
        return counts
    counts = pvary_missing(counts, axis)
    ep = jax.lax.axis_size(axis)
    e, g = counts.shape
    c = counts.reshape(ep, e // ep, g)
    c = jax.lax.all_to_all(c, axis, split_axis=0, concat_axis=0)
    return c.transpose(1, 0, 2).reshape(e // ep, ep * g)
