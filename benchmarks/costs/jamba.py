"""What a Jamba decode step and a prompt's selective scan must move,
from shapes alone (the ``cost_module`` of
``serve_jamba_decode_step_hbm_roofline`` and
``serve_jamba_ssm_scan_roofline``; ``jamba.md`` beside this file).

A decode step of ``slots`` live tokens reads every weight that
multiplies every token (both layer kinds' projections and MLPs, the
convolution, the scan's ``A_log`` / ``D`` / step bias, the norm gains,
the embedding as the tied output head), the K and V that the two
attention layers hold for the tokens in the slots (the Mamba layers
keep none), and reads and writes each Mamba layer's state and
convolution tail once: a state is not appended to, it is replaced. The
embedding's gather of ``slots`` rows is not charged.

A prompt's scan call (one Mamba layer, the fixed ``(slots,
prefill_len)`` buffer) reads ``u`` (the serving dtype) and ``dt``
(float32) and writes ``y`` (float32) once a row and channel, reads B
and C (float32) once a row, and reads and writes the state once. It
multiplies nothing: its arithmetic is ``scan_call_vector_ops``, one
``exp`` and six float32 operations a state element and row, on the VPU
/ EUP, for which ``peaks.json`` has no peak.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmarks.lib.costs import dims


def scan_dims(config: Dict[str, Any]) -> Dict[str, int]:
    d = dims(config)
    period = int(config["attn_layer_period"])
    attention = d["layers"] // period
    serve = config.get("serve", {})
    return {
        "channels": int(config["mamba_expand"]) * d["hidden"],
        "state": int(config["mamba_d_state"]),
        "dt_rank": int(config["mamba_dt_rank"]),
        "conv_kernel": int(config["mamba_d_conv"]),
        "conv_bias": bool(config.get("mamba_conv_bias", True)),
        "attention_layers": attention,
        "mamba_layers": d["layers"] - attention,
        "slots": int(serve.get("max_slots", 1)),
        "prefill_len": int(serve.get("prefill_len", 1)),
    }


def mlp_params(config: Dict[str, Any]) -> int:
    """The SwiGLU MLP of one layer and the two norm gains of its block."""
    d = dims(config)
    return 3 * d["hidden"] * d["ffn"] + 2 * d["hidden"]


def mamba_mixer_params(config: Dict[str, Any]) -> int:
    """One Mamba mixer: ``W_in``, the convolution (and its bias),
    ``W_x`` and the three inner norms' gains, ``W_dt`` and its bias,
    ``A_log``, ``D``, ``W_out``."""
    d, s = dims(config), scan_dims(config)
    c, n, r = s["channels"], s["state"], s["dt_rank"]
    return (d["hidden"] * 2 * c + c * s["conv_kernel"]
            + (c if s["conv_bias"] else 0)
            + c * (r + 2 * n) + (r + 2 * n)
            + r * c + c + n * c + c + c * d["hidden"])


def attention_mixer_params(config: Dict[str, Any]) -> int:
    d = dims(config)
    q, kv = d["heads"] * d["head_dim"], d["kv_heads"] * d["head_dim"]
    return d["hidden"] * (2 * q + 2 * kv)


def num_params(config: Dict[str, Any]) -> int:
    d, s = dims(config), scan_dims(config)
    head = 0 if d["tied"] else d["hidden"] * d["vocab"]
    return (s["mamba_layers"] * (mamba_mixer_params(config)
                                 + mlp_params(config))
            + s["attention_layers"] * (attention_mixer_params(config)
                                       + mlp_params(config))
            + d["hidden"] * d["vocab"] + d["hidden"] + head)


def weight_bytes(config: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """Every parameter once: a tied embedding is read as the head."""
    return num_params(config) * dtype_bytes


def kv_bytes_per_token(config: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """K and V one cached token holds: in the attention layers."""
    d = dims(config)
    return (2 * scan_dims(config)["attention_layers"] * d["kv_heads"]
            * d["head_dim"] * dtype_bytes)


def state_bytes(config: Dict[str, Any]) -> int:
    """One Mamba layer's float32 state over every slot."""
    s = scan_dims(config)
    return s["slots"] * s["state"] * s["channels"] * 4


def conv_tail_bytes(config: Dict[str, Any], dtype_bytes: int = 2) -> int:
    s = scan_dims(config)
    return (s["slots"] * (s["conv_kernel"] - 1) * s["channels"]
            * dtype_bytes)


def decode_step_bytes(config: Dict[str, Any], live_tokens: float) -> float:
    """Bytes one decode step has to move through HBM when the slots hold
    ``live_tokens`` cached tokens in total."""
    s = scan_dims(config)
    return float(
        weight_bytes(config) + kv_bytes_per_token(config) * live_tokens
        + s["mamba_layers"] * 2 * (state_bytes(config)
                                   + conv_tail_bytes(config)))


def scan_call_bytes(config: Dict[str, Any], dtype_bytes: int = 2) -> float:
    """Bytes one prefill-shaped scan call (one layer) has to move."""
    s = scan_dims(config)
    rows = s["slots"] * s["prefill_len"]
    per_row = s["channels"] * (dtype_bytes + 4 + 4) + 2 * s["state"] * 4
    return float(rows * per_row + 2 * state_bytes(config))


def scan_call_vector_ops(config: Dict[str, Any]) -> Dict[str, float]:
    """The arithmetic of the same call: per row and state element one
    ``exp`` and six float32 operations (``dt A``, ``decay S``, ``drive
    B``, their sum, ``S C``, its sum into y), per row and channel one
    more (``dt u``)."""
    s = scan_dims(config)
    elements = float(s["slots"] * s["prefill_len"] * s["channels"]
                     * s["state"])
    return {"exp": elements,
            "f32_ops": 6.0 * elements + elements / s["state"]}
