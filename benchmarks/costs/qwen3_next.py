"""What a Qwen3-Next decode step, its expert kernel and its state update
must move, from shapes alone (the ``cost_module`` of
``serve_qwen3_next_decode_step_hbm_roofline``,
``serve_qwen3_next_expert_mlp_roofline`` and
``serve_qwen3_next_gdn_state_update_roofline``; the arithmetic is
written out in ``qwen3_next.md`` beside this file).

A decode step of ``slots`` live tokens reads every weight that
multiplies every token (both mixers' projections, the convolution, the
gates' vectors, the router at its full width, the shared expert, the
norm gains, the slice of the output head held here), the three matrices
of each HELD expert that at least one token chose, the K and V that the
full-attention layers hold for the tokens in the slots, and reads and
writes each linear layer's recurrent state and convolution tail once.
The embedding is a gather of ``slots`` rows and is not charged.

A reader can hand a cost function the configuration and ``live_tokens``
only, not the step's routing, so the experts touched are an expectation
under uniform routing: a token's ``k`` choices are distinct, so an
expert is one of them with probability ``k / routed``, and over
``slots`` independent tokens a held expert is touched with probability
``1 - (1 - k / routed) ** slots`` (512 routed, top 10, 16 slots: 0.2707,
34.65 of the 128 held a layer). The engine counts what was touched
(``engine.moe_expert_visits / (engine.decode_steps x layers)``: 34.6
on the v5e, PERF.md, PR 35).
"""

from __future__ import annotations

from typing import Any, Dict

from benchmarks.costs.olmo_hybrid import (  # noqa: F401  (readers name them)
    conv_tail_call_bytes,
    state_dims,
    state_update_call_bytes,
)
from benchmarks.lib.costs import dims


def layer_counts(config: Dict[str, Any]) -> Dict[str, int]:
    layers = int(config["num_hidden_layers"])
    full = layers // int(config["full_attention_interval"])
    return {"linear": layers - full, "full": full}


def expert_dims(config: Dict[str, Any]) -> Dict[str, int]:
    held = int(config["num_experts"])
    return {"held": held,
            "routed": int(config.get("num_routed_experts") or held),
            "top_k": int(config["num_experts_per_tok"]),
            "width": int(config["moe_intermediate_size"]),
            "shared_width": int(config["shared_expert_intermediate_size"])}


def linear_mixer_params(config: Dict[str, Any]) -> int:
    """One gated delta-rule mixer: q, k (key width), v, gate, out (value
    width), the two gate projections with ``A_log`` and ``dt_bias``, the
    convolution, the gate norm's gain, the input norm."""
    d, s = dims(config), state_dims(config)
    channels = 2 * s["key_size"] + s["value_size"]
    return (d["hidden"] * (2 * s["key_size"] + 3 * s["value_size"])
            + 2 * d["hidden"] * s["heads"] + 2 * s["heads"]
            + channels * s["conv_kernel"] + s["d_v"] + d["hidden"])


def full_mixer_params(config: Dict[str, Any]) -> int:
    """One gated attention mixer: q with its gate (twice the query
    width), k, v, o, the two per-head norm gains, the input norm."""
    d = dims(config)
    q, kv = d["heads"] * d["head_dim"], d["kv_heads"] * d["head_dim"]
    return (d["hidden"] * (3 * q + 2 * kv) + 2 * d["head_dim"]
            + d["hidden"])


def sparse_mlp_dense_params(config: Dict[str, Any]) -> int:
    """What every token multiplies in one sparse MLP: the router at its
    full width, the shared expert with its gate, the block's norm."""
    d, e = dims(config), expert_dims(config)
    return (d["hidden"] * e["routed"]
            + (3 * e["shared_width"] + 1) * d["hidden"] + d["hidden"])


def expert_matrix_bytes(config: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """One of an expert's three matrices ([hidden, width] or back)."""
    return dims(config)["hidden"] * expert_dims(config)["width"] \
        * dtype_bytes


def dense_weight_bytes(config: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """Weights every token multiplies: the mixers, every layer's router,
    shared expert and norms, the final norm, the head's slice."""
    d, n = dims(config), layer_counts(config)
    return (n["linear"] * linear_mixer_params(config)
            + n["full"] * full_mixer_params(config)
            + d["layers"] * sparse_mlp_dense_params(config)
            + d["hidden"] + d["hidden"] * d["vocab"]) * dtype_bytes


def experts_touched(config: Dict[str, Any]) -> float:
    """Held experts with at least one of a decode step's choices, per
    layer (module docstring)."""
    e = expert_dims(config)
    slots = int(config["serve"]["max_slots"])
    return e["held"] * (1.0 - (1.0 - e["top_k"] / e["routed"]) ** slots)


def kv_bytes_per_token(config: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """K and V one cached token holds: in the full-attention layers."""
    d = dims(config)
    return (2 * layer_counts(config)["full"] * d["kv_heads"]
            * d["head_dim"] * dtype_bytes)


def expert_decode_call_bytes(config: Dict[str, Any]) -> float:
    """Bytes one grouped-matmul call of a decode step has to read: one
    matrix of each held expert touched (the rows it multiplies are under
    1 MB and are not charged)."""
    return experts_touched(config) * expert_matrix_bytes(config)


def decode_step_bytes(config: Dict[str, Any], live_tokens: float) -> float:
    """Bytes one decode step has to move through HBM when the slots hold
    ``live_tokens`` cached tokens in total."""
    d, n = dims(config), layer_counts(config)
    experts = d["layers"] * 3 * expert_decode_call_bytes(config)
    return (dense_weight_bytes(config) + experts
            + kv_bytes_per_token(config) * live_tokens
            + n["linear"] * (state_update_call_bytes(config)
                             + conv_tail_call_bytes(config)))
