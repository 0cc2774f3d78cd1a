"""What a MiMo-V2-Flash (mimo_v2_flash) decode step, its two paged
attention calls, its expert kernel and its prefill attention must do,
from shapes alone (the ``cost_module`` of
``serve_mimo_decode_step_hbm_roofline``,
``serve_mimo_full_attn_hbm_roofline``,
``serve_mimo_window_attn_hbm_roofline``,
``serve_mimo_expert_mlp_roofline`` and
``serve_mimo_prefill_attn_roofline``; the arithmetic is written out in
``mimo_v2_flash.md`` beside this file).

A decode step of ``slots`` live tokens reads every weight that
multiplies every token (the seven mixers with their sinks, the leading
dense MLP, every sparse layer's router at its full width with its
selection bias, the norm gains, the slice of the output head held
here), the three matrices of each HELD expert that at least one token
chose, and the K and V the tokens in the slots hold AS STORED: a key of
192 numbers lies in a row of 256 (whole 128-lane tiles; the 64 zeros
are read, so they are charged) beside a value of 128. A full layer
reads every cached token's K/V on its 4 heads, a window layer at most
``sliding_window`` tokens a slot on its 8. The embedding is a gather of
``slots`` rows and is not charged.

A reader can hand a cost function the configuration and ``live_tokens``
only (the mean over the traced window of the tokens the slots hold in
total), neither the step's routing nor a call's own rows. So the
experts touched are an expectation under uniform routing (``held x (1 -
(1 - k / routed) ** slots)``: 16 held of 256, top 8, 32 slots: 10.2 a
layer; the engine counts what was touched, ``engine.moe_expert_visits``;
where the count falls more than 10 % short ``MEASURED_FLOOR`` is
charged), a window layer is charged ``min(live_tokens, slots x
sliding_window)`` keys (exact where every slot holds a window or more,
which prompts of 3,072 and more see to), and a prefill call's rows are
the one program's there is: ONE row of ``serve.prefill_len`` (the
family's rows name their slots), never ``max_slots`` of them.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmarks.lib.costs import dims

_LANES = 128
FULL, WINDOW = 0, 1

# held experts touched per sparse layer and decode step as the engine
# counted them on the chip (``engine.moe_expert_visits /
# (engine.decode_steps x 6)``), rounded down, where that is more than
# 10 % under the uniform expectation (``costs/olmoe.py``'s rule: neither
# roofline share may be flattered by experts that were never read);
# None: the expectation is charged (counted on the v5e: PERF.md, PR 59)
MEASURED_FLOOR: Optional[float] = None


def mimo_dims(config: Dict[str, Any]) -> Dict[str, Any]:
    d = dims(config)
    kinds = [int(x) for x in config["hybrid_layer_pattern"]]
    sparse = sum(int(x) for x in config["moe_layer_freq"])
    held = int(config["n_routed_experts"])
    return {
        "full_layers": kinds.count(FULL), "window_layers": kinds.count(WINDOW),
        "kv_heads": (d["kv_heads"], int(config["swa_num_key_value_heads"])),
        "k_dim": d["head_dim"], "v_dim": int(config["v_head_dim"]),
        "stored_k": -(-d["head_dim"] // _LANES) * _LANES,
        "window": int(config["sliding_window"]),
        "sparse_layers": sparse, "dense_layers": d["layers"] - sparse,
        "held": held,
        "routed": int(config.get("num_routed_experts") or held),
        "top_k": int(config["num_experts_per_tok"]),
        "width": int(config["moe_intermediate_size"]),
        "slots": int(config["serve"]["max_slots"]),
        "prefill_len": int(config["serve"]["prefill_len"]),
    }


def mixer_params(config: Dict[str, Any], kind: int) -> int:
    """One mixer: q, k, v and o, and a window layer's sink a head."""
    d, m = dims(config), mimo_dims(config)
    return (d["hidden"] * d["heads"] * m["k_dim"]
            + d["hidden"] * m["kv_heads"][kind] * (m["k_dim"] + m["v_dim"])
            + d["heads"] * m["v_dim"] * d["hidden"]
            + (d["heads"] if kind == WINDOW else 0))


def expert_matrix_bytes(config: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """One of an expert's three matrices ([hidden, width] or back)."""
    return dims(config)["hidden"] * mimo_dims(config)["width"] * dtype_bytes


def dense_weight_bytes(config: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """Weights every token multiplies: the mixers, two norm gains a
    layer, the dense MLPs, every sparse layer's router with its bias,
    the final norm, the head's slice."""
    d, m = dims(config), mimo_dims(config)
    return (m["full_layers"] * mixer_params(config, FULL)
            + m["window_layers"] * mixer_params(config, WINDOW)
            + d["layers"] * 2 * d["hidden"]
            + m["dense_layers"] * 3 * d["hidden"] * d["ffn"]
            + m["sparse_layers"] * (d["hidden"] * m["routed"] + m["routed"])
            + d["hidden"] + d["hidden"] * d["vocab"]) * dtype_bytes


def experts_touched(config: Dict[str, Any]) -> float:
    """Held experts with at least one of a decode step's choices, per
    sparse layer (module docstring), or ``MEASURED_FLOOR``."""
    if MEASURED_FLOOR is not None:
        return MEASURED_FLOOR
    m = mimo_dims(config)
    return m["held"] * (1.0 - (1.0 - m["top_k"] / m["routed"]) ** m["slots"])


def kv_bytes_per_token(config: Dict[str, Any], kind: int,
                       dtype_bytes: int = 2) -> int:
    """K and V of one cached token in one layer of ``kind``, as stored."""
    m = mimo_dims(config)
    return m["kv_heads"][kind] * (m["stored_k"] + m["v_dim"]) * dtype_bytes


def full_attn_call_bytes(config: Dict[str, Any],
                         live_tokens: float) -> float:
    """K and V bytes one paged-decode call of a FULL layer has to read:
    every cached token's, on 4 heads, as stored."""
    return kv_bytes_per_token(config, FULL) * float(live_tokens)


def window_attn_call_bytes(config: Dict[str, Any]) -> float:
    """K and V bytes one paged-decode call of a WINDOW layer has to
    read: the window's keys of every slot, on 8 heads, as stored (the
    kernel walks the 9 pages that hold them; the 16 rows past the
    window are the implementation's)."""
    m = mimo_dims(config)
    return kv_bytes_per_token(config, WINDOW) * m["slots"] * m["window"]


def expert_decode_call_bytes(config: Dict[str, Any]) -> float:
    """Bytes one grouped-matmul call of a decode step has to read: one
    matrix of each held expert touched (the rows it multiplies are under
    1 MB and are not charged)."""
    return experts_touched(config) * expert_matrix_bytes(config)


def decode_step_bytes(config: Dict[str, Any], live_tokens: float) -> float:
    """Bytes one decode step has to move through HBM when the slots hold
    ``live_tokens`` cached tokens in total."""
    m = mimo_dims(config)
    window_tokens = min(float(live_tokens), m["slots"] * m["window"])
    return (dense_weight_bytes(config)
            + m["sparse_layers"] * 3 * expert_decode_call_bytes(config)
            + m["full_layers"] * full_attn_call_bytes(config, live_tokens)
            + m["window_layers"] * kv_bytes_per_token(config, WINDOW)
            * window_tokens)


def visible_pairs(rows: int, window: Optional[int] = None) -> int:
    """(query, key) pairs of a causal ``rows x rows`` score matrix:
    row i sees i + 1 keys, or ``window`` of them once it is past it."""
    if window is None or window >= rows:
        return rows * (rows + 1) // 2
    return window * (window + 1) // 2 + (rows - window) * window


def prefill_attn_call_flops(config: Dict[str, Any]) -> float:
    """FLOPs one prefill attention call has to do over the call's own
    rows, ONE row of ``prefill_len``, the mean over a call's 2 full and
    5 window layers (their flash calls return the same shape): q k^T at
    ``head_dim`` and p v at ``v_head_dim`` over the visible pairs, 2 x
    (192 + 128) FLOPs a pair and query head. The count is of the work,
    whatever computes it; every row of the buffer is charged, live or
    not, since every row is computed."""
    d, m = dims(config), mimo_dims(config)
    rows = m["prefill_len"]
    pairs = (m["full_layers"] * visible_pairs(rows)
             + m["window_layers"] * visible_pairs(rows, m["window"])
             ) / (m["full_layers"] + m["window_layers"])
    return d["heads"] * pairs * 2 * (m["k_dim"] + m["v_dim"])
