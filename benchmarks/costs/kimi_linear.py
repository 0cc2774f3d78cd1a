"""What a Kimi-Linear (kimi_linear) decode step, its KDA state update, its
latent decode kernel, its expert kernel and a prompt's per-channel scan
must do, from shapes alone (the ``cost_module`` of
``serve_kimi_decode_step_hbm_roofline``,
``serve_kimi_kda_state_update_roofline``,
``serve_kimi_latent_attn_hbm_roofline`` and
``serve_kimi_expert_mlp_roofline``; the arithmetic is written out in
``kimi_linear.md`` beside this file).

A decode step of ``slots`` live tokens reads every weight that
multiplies every token (the KDA and latent mixers, the leading dense
MLP, every sparse layer's router at its full width with its selection
bias and shared expert, the norm gains, the slice of the output head
held here), the three matrices of each HELD expert that at least one
token chose, one latent row ``[c | k_r]`` a cached token and latent
layer AS STORED (640 numbers: the 576 written down padded to whole
128-lane tiles; the padding is read, so it is charged), and reads and
writes each KDA layer's float32 state and convolution tail once. The
embedding is a gather of ``slots`` rows and is not charged.

A reader can hand a cost function the configuration and ``live_tokens``
only, neither the step's routing nor a call's own rows. So the experts
touched are an expectation under uniform routing (``held x (1 - (1 - k
/ routed) ** slots)``: 64 held of 256, top 8, 32 slots: 40.8 a layer;
the engine counts what was touched, ``engine.moe_expert_visits``), and
a prefill call's rows are the one program's there is: ONE row of
``serve.prefill_len`` (the family's rows name their slots), never
``max_slots`` of them.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmarks.lib.costs import dims

_LANES = 128
# rows of a chunk and of a sub-block of the per-channel scan
# (``models/olmo_hybrid.py`` CHUNK / SUB_BLOCK)
CHUNK, SUB_BLOCK = 64, 16


def kimi_dims(config: Dict[str, Any]) -> Dict[str, int]:
    lists = config["linear_attn_config"]
    held = int(config["num_experts"])
    rank, rot = int(config["kv_lora_rank"]), int(config["qk_rope_head_dim"])
    dense = int(config["first_k_dense_replace"])
    layers = int(config["num_hidden_layers"])
    return {
        "kda_layers": len(lists["kda_layers"]),
        "mla_layers": len(lists["full_attn_layers"]),
        "kda_heads": int(lists["num_heads"]),
        "kda_dim": int(lists["head_dim"]),
        "conv_kernel": int(lists["short_conv_kernel_size"]),
        "kv_rank": rank, "rot": rot,
        "nope": int(config["qk_nope_head_dim"]),
        "v_dim": int(config["v_head_dim"]),
        "row": rank + rot,
        "stored_row": -(-(rank + rot) // _LANES) * _LANES,
        "dense_layers": dense, "sparse_layers": layers - dense,
        "held": held,
        "routed": int(config.get("num_routed_experts") or held),
        "top_k": int(config["num_experts_per_token"]),
        "width": int(config["moe_intermediate_size"]),
        "shared_width": int(config.get("num_shared_experts", 1))
        * int(config["moe_intermediate_size"]),
        "slots": int(config["serve"]["max_slots"]),
        "prefill_len": int(config["serve"]["prefill_len"]),
    }


def kda_mixer_params(config: Dict[str, Any]) -> int:
    """One KDA mixer: q, k, v and o, the two low-rank gates, ``b_proj``,
    the convolution, ``A_log``, ``dt_bias``, the output norm's gain."""
    d, k = dims(config), kimi_dims(config)
    w = k["kda_heads"] * k["kda_dim"]
    return (4 * d["hidden"] * w
            + 2 * (d["hidden"] * k["kda_dim"] + k["kda_dim"] * w)
            + d["hidden"] * k["kda_heads"] + 3 * w * k["conv_kernel"]
            + k["kda_heads"] + w + k["kda_dim"])


def latent_mixer_params(config: Dict[str, Any]) -> int:
    """One latent-attention mixer: ``q_proj``, ``kv_a_proj_with_mqa``
    with its norm, ``kv_b_proj``, ``o_proj``."""
    d, k = dims(config), kimi_dims(config)
    return (d["hidden"] * d["heads"] * (k["nope"] + k["rot"])
            + d["hidden"] * k["row"] + k["kv_rank"]
            + k["kv_rank"] * d["heads"] * (k["nope"] + k["v_dim"])
            + d["heads"] * k["v_dim"] * d["hidden"])


def sparse_mlp_dense_params(config: Dict[str, Any]) -> int:
    """What every token multiplies in one sparse MLP: the router at its
    full width with its bias, the ungated shared expert."""
    d, k = dims(config), kimi_dims(config)
    return (d["hidden"] * k["routed"] + k["routed"]
            + 3 * k["shared_width"] * d["hidden"])


def expert_matrix_bytes(config: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """One of an expert's three matrices ([hidden, width] or back)."""
    return dims(config)["hidden"] * kimi_dims(config)["width"] * dtype_bytes


def dense_weight_bytes(config: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """Weights every token multiplies: the mixers, two norm gains a
    layer, the leading dense MLP, every sparse layer's router and shared
    expert, the final norm, the head's slice."""
    d, k = dims(config), kimi_dims(config)
    return (k["kda_layers"] * kda_mixer_params(config)
            + k["mla_layers"] * latent_mixer_params(config)
            + d["layers"] * 2 * d["hidden"]
            + k["dense_layers"] * 3 * d["hidden"] * d["ffn"]
            + k["sparse_layers"] * sparse_mlp_dense_params(config)
            + d["hidden"] + d["hidden"] * d["vocab"]) * dtype_bytes


def experts_touched(config: Dict[str, Any]) -> float:
    """Held experts with at least one of a decode step's choices, per
    sparse layer, under uniform routing (module docstring)."""
    k = kimi_dims(config)
    return k["held"] * (1.0 - (1.0 - k["top_k"] / k["routed"]) ** k["slots"])


def expert_decode_call_bytes(config: Dict[str, Any]) -> float:
    """Bytes one grouped-matmul call of a decode step has to read: one
    matrix of each held expert touched (the rows it multiplies are under
    2 MB and are not charged)."""
    return experts_touched(config) * expert_matrix_bytes(config)


def kda_state_update_bytes(config: Dict[str, Any]) -> float:
    """Bytes one KDA layer's decode-shaped state update must move: the
    float32 ``[d, d]`` state of every head and slot read once and
    written once."""
    k = kimi_dims(config)
    return float(k["slots"] * k["kda_heads"] * k["kda_dim"] ** 2 * 4 * 2)


def conv_tail_call_bytes(config: Dict[str, Any],
                         dtype_bytes: int = 2) -> float:
    """One KDA layer's convolution tail, read and written."""
    k = kimi_dims(config)
    return float(k["slots"] * (k["conv_kernel"] - 1)
                 * 3 * k["kda_heads"] * k["kda_dim"] * dtype_bytes * 2)


def latent_bytes_per_token(config: Dict[str, Any],
                           dtype_bytes: int = 2) -> int:
    """What one cached token holds over the latent layers, as stored."""
    k = kimi_dims(config)
    return k["mla_layers"] * k["stored_row"] * dtype_bytes


def latent_attn_call_bytes(config: Dict[str, Any], live_tokens: float,
                           dtype_bytes: int = 2) -> float:
    """Bytes one call of the latent decode kernel (one layer) has to
    read: one stored row a cached token, once for all heads."""
    return kimi_dims(config)["stored_row"] * dtype_bytes * live_tokens


def decode_step_bytes(config: Dict[str, Any], live_tokens: float) -> float:
    """Bytes one decode step has to move through HBM when the slots hold
    ``live_tokens`` cached tokens in total."""
    k = kimi_dims(config)
    experts = k["sparse_layers"] * 3 * expert_decode_call_bytes(config)
    return (dense_weight_bytes(config) + experts
            + latent_bytes_per_token(config) * live_tokens
            + k["kda_layers"] * (kda_state_update_bytes(config)
                                 + conv_tail_call_bytes(config)))


def kda_scan_call_flops(config: Dict[str, Any],
                        rows: Optional[int] = None) -> float:
    """Floating-point operations of ONE KDA layer's chunked scan over a
    prefill call of ``rows`` positions (None: the one program's, one row
    of ``serve.prefill_len``): per chunk of C rows and head of width d,
    the pairwise decays of the diagonal sub-blocks (``C c d`` exps and
    4 multiply-adds of them for ``K K^T`` and ``Q K^T``), their
    off-diagonal blocks (``2 x 2 C (C - c) / 2 x d``), the triangular
    inverse (``C^3 / 3`` in blocks) and its product with the right-hand
    side (``2 C^2 2 d``), and the four products that meet the state (``W
    S``, ``Q S``: ``2 C d^2`` each; ``(Q K^T) U``: ``2 C^2 d``; ``K^T
    U``: ``2 C d^2``). Float32 at ``highest``: each product is six
    bfloat16 passes on the MXU, which a share of the bf16 peak would
    have to count; no metric does (the scan is XLA's, not a kernel)."""
    k = kimi_dims(config)
    rows = k["prefill_len"] if rows is None else rows
    c, b, d = CHUNK, SUB_BLOCK, k["kda_dim"]
    chunks = -(-rows // c)
    per_chunk_head = (
        5 * c * b * d                       # pairwise decays, two sums
        + 2 * 2 * c * (c - b) // 2 * d      # off-diagonal K K^T and Q K^T
        + c ** 3 // 3 + 2 * c * c * 2 * d   # inverse, its product
        + 3 * 2 * c * d * d + 2 * c * c * d)
    return float(chunks * k["kda_heads"] * per_chunk_head)
