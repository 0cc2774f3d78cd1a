"""What an Olmo-Hybrid decode step and its state update must move, from
shapes alone (the ``cost_module`` of ``serve_hybrid_decode_step_hbm_
roofline`` and ``serve_gdn_state_update_roofline``).

A decode step of ``slots`` live tokens reads every weight that
multiplies every token (both layer kinds' projections and MLPs, the
convolution, the gates' vectors, the norm gains, the output head), the
K and V that the FULL-attention layers hold for the tokens in the slots
(the linear layers keep none), and reads and writes each linear layer's
recurrent state and convolution tail once: a state is not appended to,
it is replaced. The embedding is a gather of ``slots`` rows and is not
charged. Bytes as the arrays are declared: the tiled layout a chip pads
a ``[96, 192]`` float32 matrix to is the implementation's and is not
charged.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmarks.lib.costs import dims

LINEAR, FULL = "linear_attention", "full_attention"


def layer_counts(config: Dict[str, Any]) -> Dict[str, int]:
    kinds = list(config["layer_types"])
    return {"linear": kinds.count(LINEAR), "full": kinds.count(FULL)}


def state_dims(config: Dict[str, Any]) -> Dict[str, int]:
    heads = int(config["linear_num_value_heads"])
    d_k = int(config["linear_key_head_dim"])
    d_v = int(config["linear_value_head_dim"])
    return {"heads": heads, "d_k": d_k, "d_v": d_v,
            "key_size": int(config["linear_num_key_heads"]) * d_k,
            "value_size": heads * d_v,
            "conv_kernel": int(config["linear_conv_kernel_dim"]),
            "slots": int(config["serve"]["max_slots"])}


def mlp_params(config: Dict[str, Any]) -> int:
    """The SwiGLU MLP of one layer and the two norm gains of its block."""
    d = dims(config)
    return 3 * d["hidden"] * d["ffn"] + 2 * d["hidden"]


def linear_layer_params(config: Dict[str, Any]) -> int:
    """One gated delta-rule layer: q, k (key width), v, gate, out (value
    width), the two gate projections with ``A_log`` and ``dt_bias``, the
    convolution, the gate norm's gain, the MLP."""
    d, s = dims(config), state_dims(config)
    channels = 2 * s["key_size"] + s["value_size"]
    return (d["hidden"] * (2 * s["key_size"] + 3 * s["value_size"])
            + 2 * d["hidden"] * s["heads"] + 2 * s["heads"]
            + channels * s["conv_kernel"] + s["d_v"] + mlp_params(config))


def full_layer_params(config: Dict[str, Any]) -> int:
    """One full-attention layer: four projections, the q/k norm gains
    over the whole projection width, the MLP."""
    d = dims(config)
    q, kv = d["heads"] * d["head_dim"], d["kv_heads"] * d["head_dim"]
    return d["hidden"] * (2 * q + 2 * kv) + q + kv + mlp_params(config)


def weight_bytes(config: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """Weights every token multiplies: all layers, the final norm, the
    output head (untied here; a tied head still multiplies)."""
    d, n = dims(config), layer_counts(config)
    return (n["linear"] * linear_layer_params(config)
            + n["full"] * full_layer_params(config)
            + d["hidden"] + d["hidden"] * d["vocab"]) * dtype_bytes


def kv_bytes_per_token(config: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """K and V one cached token holds: in the full-attention layers."""
    d = dims(config)
    return (2 * layer_counts(config)["full"] * d["kv_heads"]
            * d["head_dim"] * dtype_bytes)


def state_update_call_bytes(config: Dict[str, Any]) -> float:
    """Bytes one linear layer's decode-shaped state update must move:
    the float32 state of every slot read once and written once."""
    s = state_dims(config)
    return float(s["slots"] * s["heads"] * s["d_k"] * s["d_v"] * 4 * 2)


def conv_tail_call_bytes(config: Dict[str, Any],
                         dtype_bytes: int = 2) -> float:
    """One linear layer's convolution tail, read and written."""
    s = state_dims(config)
    channels = 2 * s["key_size"] + s["value_size"]
    return float(s["slots"] * (s["conv_kernel"] - 1) * channels
                 * dtype_bytes * 2)


def decode_step_bytes(config: Dict[str, Any], live_tokens: float) -> float:
    """Bytes one decode step has to move through HBM when the slots hold
    ``live_tokens`` cached tokens in total."""
    linear = layer_counts(config)["linear"]
    return (weight_bytes(config)
            + kv_bytes_per_token(config) * live_tokens
            + linear * (state_update_call_bytes(config)
                        + conv_tail_call_bytes(config)))
