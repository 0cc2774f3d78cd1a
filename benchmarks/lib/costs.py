"""What a kernel call or a trained token must do, from shapes alone.

The operations and bytes the *algorithm* needs, not what a particular
implementation executes: causal attention is charged the lower triangle
(half the square), recomputation under gradient checkpointing is not
charged at all. Each roofline metric names one of these functions and
the peak it is measured against (``benchmarks/peaks.json``).
"""

from __future__ import annotations

from typing import Any, Dict


def dims(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes of a configuration file, from the published
    ``config.json`` names (shared with the plain reference)."""
    heads = int(config["num_attention_heads"])
    return {
        "hidden": int(config["hidden_size"]),
        "layers": int(config["num_hidden_layers"]),
        "heads": heads,
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config.get("head_dim")
                        or config["hidden_size"] // heads),
        "ffn": int(config["intermediate_size"]),
        "vocab": int(config["vocab_size"]),
        "tied": bool(config["tie_word_embeddings"]),
        "eps": float(config.get("rms_norm_eps", 1e-6)),
        "theta": float(config.get("rope_theta", 10000.0)),
    }


def matmul_params(config: Dict[str, Any]) -> int:
    """Weights that multiply every token: the projections and MLP of
    every layer plus the output head (the embedding lookup is a gather
    and costs no matmul; a tied head still multiplies)."""
    d = dims(config)
    q = d["heads"] * d["head_dim"]
    kv = d["kv_heads"] * d["head_dim"]
    per_layer = d["hidden"] * (2 * q + 2 * kv) + 3 * d["hidden"] * d["ffn"]
    return d["layers"] * per_layer + d["hidden"] * d["vocab"]


def num_params(config: Dict[str, Any]) -> int:
    d = dims(config)
    norms = 2 * d["hidden"] + 2 * d["head_dim"]
    total = matmul_params(config) + d["layers"] * norms + d["hidden"]
    return total if d["tied"] else total + d["hidden"] * d["vocab"]


def causal_attention_flops(seq: int, heads: int, head_dim: int) -> float:
    """Forward QK^T and PV over the lower triangle of one sequence, all
    heads of one layer: 2 matmuls x 2 FLOP x S(S+1)/2 x D per head. The
    backward needs dV, dP, dQ and dK: four such matmuls, twice the
    forward (a flash backward also recomputes the scores; that fifth
    matmul is the implementation's and is not charged)."""
    return 2 * 2 * (seq * (seq + 1) / 2) * head_dim * heads


def train_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """Required forward + backward FLOPs per trained token: 6 per matmul
    weight, plus causal attention forward (1x) and backward (2x of the
    forward's two matmuls), no recomputation."""
    d = dims(config)
    attn_fwd = causal_attention_flops(seq, d["heads"], d["head_dim"])
    return 6.0 * matmul_params(config) + 3.0 * d["layers"] * attn_fwd / seq


def train_flops_per_token_full_square(config: Dict[str, Any],
                                      seq: int) -> float:
    """The repo's ``utils/misc.get_flops_per_token`` convention
    (6N + 12 L H D S: every parameter, attention at the full square),
    kept so that PERF.md can state MFU both ways."""
    d = dims(config)
    return (6.0 * num_params(config)
            + 12.0 * d["layers"] * d["heads"] * d["head_dim"] * seq)


def flash_train_call_flops(config: Dict[str, Any], seq_local: int,
                           seq_total: int) -> Dict[str, float]:
    """Causal FLOPs one device's attention kernels must do per layer and
    step when it holds ``seq_local`` of ``seq_total`` query rows and the
    causal work is spread evenly (one chip: all of it; zigzag ring: an
    equal share per rank)."""
    d = dims(config)
    share = seq_local / seq_total
    fwd = causal_attention_flops(seq_total, d["heads"],
                                 d["head_dim"]) * share
    return {"forward": fwd, "backward": 2.0 * fwd}


def kv_bytes_per_token(config: Dict[str, Any], dtype_bytes: int = 2) -> int:
    d = dims(config)
    return 2 * d["layers"] * d["kv_heads"] * d["head_dim"] * dtype_bytes


def paged_decode_kv_bytes(config: Dict[str, Any], live_tokens: float,
                          dtype_bytes: int = 2) -> float:
    """K and V bytes one layer's paged-decode kernel call must read when
    the slots hold ``live_tokens`` cached tokens in total."""
    d = dims(config)
    return 2.0 * d["kv_heads"] * d["head_dim"] * dtype_bytes * live_tokens


def weight_bytes(config: Dict[str, Any], dtype_bytes: int = 2) -> int:
    return num_params(config) * dtype_bytes
