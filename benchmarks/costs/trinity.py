"""What a Trinity-Mini (afmoe) decode step, its paged-attention and
expert kernels and its prefill attention must do, from shapes alone (the
``cost_module`` of ``serve_trinity_decode_step_hbm_roofline``,
``serve_trinity_paged_attn_roofline``, ``serve_trinity_expert_mlp_roofline``
and ``serve_trinity_prefill_attn_roofline``; the arithmetic is written
out in ``trinity.md`` beside this file).

A decode step of ``slots`` live tokens reads every weight that
multiplies every token (attention with its gate projection, the leading
dense MLPs, every sparse layer's router at its full width with its bias
and its shared expert, the norm gains, the slice of the output head held
here), the three matrices of each HELD expert that at least one token
chose, and the K and V the tokens in the slots hold: a full-attention
layer every cached token's, a window layer at most ``sliding_window`` a
slot. The embedding is a gather of ``slots`` rows and is not charged.

A reader can hand a cost function the configuration and ``live_tokens``
only (the mean over the traced window of the tokens the slots hold in
total), neither the step's routing nor a slot's own length. So the
experts touched are an expectation under uniform routing (a held expert
is one of a token's ``k`` distinct choices with probability ``k /
routed``: 128 routed, top 8, 8 slots: 12.9 of the 32 held a layer; the
engine counts what was touched, ``engine.moe_expert_visits``; where the
count falls more than 10 % short, ``MEASURED_FLOOR`` is charged), and a
window layer is charged ``min(live_tokens, slots x sliding_window)``
keys: exact where every slot holds at least a window, which the cell's
prompts (2,048 and more) see to; where some slot held less it would
charge too much.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmarks.lib.costs import dims

SLIDING, FULL = "sliding_attention", "full_attention"

# held experts touched per sparse layer and decode step as the engine
# counted them on the chip (``engine.moe_expert_visits /
# (engine.decode_steps x 14)``), rounded down, where that is more than
# 10 % under the uniform expectation (``costs/olmoe.py``'s rule: neither
# roofline share may be flattered by experts that were never read);
# None: the expectation is charged. Counted on the v5e (PERF.md, PR 42):
# 11.55-11.88 over three runs of the cell where even routing gives
# 12.90 (the 8 slots' router inputs are not independent draws: every
# sub-block adds a unit-rms vector that the tokens of a stream share in
# part), and 9.1-9.5 with the embedding at the program's own 0.02
MEASURED_FLOOR: Optional[float] = 11.0


def layer_counts(config: Dict[str, Any]) -> Dict[str, int]:
    layers = int(config["num_hidden_layers"])
    every = int(config.get("global_attn_every_n_layers", 4))
    kinds = list(config.get("layer_types") or (
        FULL if (i + 1) % every == 0 else SLIDING for i in range(layers)))
    dense = int(config["num_dense_layers"])
    return {"window": kinds.count(SLIDING), "full": kinds.count(FULL),
            "dense": dense, "sparse": layers - dense}


def expert_dims(config: Dict[str, Any]) -> Dict[str, int]:
    held = int(config["num_experts"])
    return {"held": held,
            "routed": int(config.get("num_routed_experts") or held),
            "top_k": int(config["num_experts_per_tok"]),
            "width": int(config["moe_intermediate_size"]),
            "shared": int(config.get("num_shared_experts", 1))}


def attention_params(config: Dict[str, Any]) -> int:
    """One layer outside its MLP: q, gate, o (the query's width), k, v,
    the two per-head norm gains, the four layer norms."""
    d = dims(config)
    q, kv = d["heads"] * d["head_dim"], d["kv_heads"] * d["head_dim"]
    return (d["hidden"] * (3 * q + 2 * kv) + 2 * d["head_dim"]
            + 4 * d["hidden"])


def sparse_mlp_dense_params(config: Dict[str, Any]) -> int:
    """What every token multiplies in one sparse MLP: the router at its
    full width, its bias, the shared experts."""
    d, e = dims(config), expert_dims(config)
    return (d["hidden"] * e["routed"] + e["routed"]
            + e["shared"] * 3 * d["hidden"] * e["width"])


def expert_matrix_bytes(config: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """One of an expert's three matrices ([hidden, width] or back)."""
    return dims(config)["hidden"] * expert_dims(config)["width"] \
        * dtype_bytes


def dense_weight_bytes(config: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """Weights every token multiplies: every layer's attention and
    norms, the dense MLPs, the sparse layers' router and shared expert,
    the final norm, the head's slice."""
    d, n = dims(config), layer_counts(config)
    return (d["layers"] * attention_params(config)
            + n["dense"] * 3 * d["hidden"] * d["ffn"]
            + n["sparse"] * sparse_mlp_dense_params(config)
            + d["hidden"] + d["hidden"] * d["vocab"]) * dtype_bytes


def experts_touched(config: Dict[str, Any]) -> float:
    """Held experts with at least one of a decode step's choices, per
    sparse layer (module docstring), or ``MEASURED_FLOOR``."""
    if MEASURED_FLOOR is not None:
        return MEASURED_FLOOR
    e = expert_dims(config)
    slots = int(config["serve"]["max_slots"])
    return e["held"] * (1.0 - (1.0 - e["top_k"] / e["routed"]) ** slots)


def kv_bytes_per_token_and_layer(config: Dict[str, Any],
                                 dtype_bytes: int = 2) -> int:
    d = dims(config)
    return 2 * d["kv_heads"] * d["head_dim"] * dtype_bytes


def window_keys(config: Dict[str, Any], live_tokens: float) -> float:
    """Keys one window layer holds for the slots' tokens (module
    docstring: exact where every slot holds a window or more)."""
    slots = int(config["serve"]["max_slots"])
    return min(float(live_tokens), slots * int(config["sliding_window"]))


def kv_step_bytes(config: Dict[str, Any], live_tokens: float) -> float:
    """K and V one decode step reads over all layers."""
    n = layer_counts(config)
    return kv_bytes_per_token_and_layer(config) * (
        n["full"] * live_tokens
        + n["window"] * window_keys(config, live_tokens))


def paged_attn_call_bytes(config: Dict[str, Any],
                          live_tokens: float) -> float:
    """K and V bytes one paged-decode kernel call has to read, the mean
    over a step's calls (one a layer; a window layer's and a full
    layer's return the same shape and are told apart by nothing the
    trace holds)."""
    n = layer_counts(config)
    return kv_step_bytes(config, live_tokens) / (n["full"] + n["window"])


def expert_decode_call_bytes(config: Dict[str, Any]) -> float:
    """Bytes one grouped-matmul call of a decode step has to read: one
    matrix of each held expert touched (the rows it multiplies are under
    1 MB and are not charged)."""
    return experts_touched(config) * expert_matrix_bytes(config)


def decode_step_bytes(config: Dict[str, Any], live_tokens: float) -> float:
    """Bytes one decode step has to move through HBM when the slots hold
    ``live_tokens`` cached tokens in total."""
    n = layer_counts(config)
    return (dense_weight_bytes(config)
            + n["sparse"] * 3 * expert_decode_call_bytes(config)
            + kv_step_bytes(config, live_tokens))


def visible_pairs(rows: int, window: int | None = None) -> int:
    """(query, key) pairs of a causal ``rows x rows`` score matrix:
    row i sees i + 1 keys, or ``window`` of them once it is past it."""
    if window is None or window >= rows:
        return rows * (rows + 1) // 2
    return window * (window + 1) // 2 + (rows - window) * window


def prefill_attn_call_flops(config: Dict[str, Any]) -> float:
    """FLOPs one prefill attention call has to do over the fixed-shape
    ``[slots, prefill_len]`` buffer, the mean over a call's 16 layers:
    two matmuls (q k^T and p v) over the visible pairs, 4 x head_dim
    FLOPs a pair and query head. The count is of the work, whatever
    computes it; every row of the buffer is charged, live or not, since
    every row is computed."""
    d, n = dims(config), layer_counts(config)
    serve = config["serve"]
    rows = int(serve["prefill_len"])
    pairs = (n["full"] * visible_pairs(rows)
             + n["window"] * visible_pairs(rows, int(config["sliding_window"]))
             ) / (n["full"] + n["window"])
    return (int(serve["max_slots"]) * d["heads"] * pairs
            * 4 * d["head_dim"])
