"""What a Granite 4.0-H (granitemoehybrid) decode step, its Mamba-2 state
update, its expert kernel and a prompt's chunked scan must do, from
shapes alone (the ``cost_module`` of
``serve_granite_decode_step_hbm_roofline``,
``serve_granite_ssd_state_update_hbm_roofline``,
``serve_granite_expert_mlp_roofline`` and
``serve_granite_ssd_prefill_roofline``; the arithmetic is written out in
``granite_moe_hybrid.md`` beside this file).

A decode step of ``slots`` live tokens reads every weight that
multiplies every token (the Mamba-2 and attention mixers, every layer's
router at its full width with its shared expert, the norm gains, the
slice of the tied embedding that is the head), the three matrices of
each HELD expert that at least one token chose, one K and one V row a
cached token and attention layer, and reads and writes each Mamba-2
layer's float32 state and convolution tail once. The embedding lookup is
a gather of ``slots`` rows and is not charged.

A reader can hand a cost function the configuration and ``live_tokens``
only, neither the step's routing nor a call's own rows. So the experts
touched are an expectation under uniform routing (``held x (1 - (1 - k
/ routed) ** slots)``: 36 held of 72, top 10, 64 slots: 35.997 a layer,
every one; the engine counts what was touched,
``engine.moe_expert_visits``), and a prefill call's rows are the one
program's there is: ONE row of ``serve.prefill_len`` (the family's rows
name their slots), never ``max_slots`` of them.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmarks.lib.costs import dims

MAMBA, ATTENTION = "mamba", "attention"


def granite_dims(config: Dict[str, Any]) -> Dict[str, int]:
    kinds = list(config["layer_types"])
    held = int(config["num_local_experts"])
    heads, p = int(config["mamba_n_heads"]), int(config["mamba_d_head"])
    n = int(config["mamba_d_state"])
    return {
        "mamba_layers": kinds.count(MAMBA),
        "attn_layers": kinds.count(ATTENTION),
        "ssd_heads": heads, "ssd_dim": p, "state": n,
        "channels": heads * p,
        "conv_dim": heads * p + 2 * int(config.get("mamba_n_groups", 1)) * n,
        "conv_kernel": int(config["mamba_d_conv"]),
        "chunk": int(config["mamba_chunk_size"]),
        "held": held,
        "routed": int(config.get("num_routed_experts") or held),
        "top_k": int(config["num_experts_per_tok"]),
        "width": int(config["intermediate_size"]),
        "shared_width": int(config["shared_intermediate_size"]),
        "slots": int(config["serve"]["max_slots"]),
        "prefill_len": int(config["serve"]["prefill_len"]),
    }


def mamba_mixer_params(config: Dict[str, Any]) -> int:
    """One Mamba-2 mixer: ``in_proj`` (z | x | B | C | dt), the
    convolution with its bias, ``dt_bias``, ``A_log``, ``D``, the gated
    norm's gain, ``out_proj``."""
    d, g = dims(config), granite_dims(config)
    return (d["hidden"] * (g["channels"] + g["conv_dim"] + g["ssd_heads"])
            + g["conv_dim"] * (g["conv_kernel"] + 1) + 3 * g["ssd_heads"]
            + g["channels"] + g["channels"] * d["hidden"])


def attention_mixer_params(config: Dict[str, Any]) -> int:
    """One attention mixer: q and o at ``heads x head_dim``, k and v at
    ``kv_heads x head_dim``."""
    d = dims(config)
    return 2 * d["hidden"] * d["head_dim"] * (d["heads"] + d["kv_heads"])


def sparse_mlp_dense_params(config: Dict[str, Any]) -> int:
    """What every token multiplies in one layer's MLP: the router at its
    full width, the ungated shared expert."""
    d, g = dims(config), granite_dims(config)
    return d["hidden"] * g["routed"] + 3 * g["shared_width"] * d["hidden"]


def expert_matrix_bytes(config: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """One of an expert's three matrices ([hidden, width] or back)."""
    return dims(config)["hidden"] * granite_dims(config)["width"] * dtype_bytes


def dense_weight_bytes(config: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """Weights every token multiplies: the mixers, two norm gains a
    layer, every layer's router and shared expert, the final norm, the
    head's slice."""
    d, g = dims(config), granite_dims(config)
    return (g["mamba_layers"] * mamba_mixer_params(config)
            + g["attn_layers"] * attention_mixer_params(config)
            + d["layers"] * (2 * d["hidden"]
                             + sparse_mlp_dense_params(config))
            + d["hidden"] + d["hidden"] * d["vocab"]) * dtype_bytes


def experts_touched(config: Dict[str, Any]) -> float:
    """Held experts with at least one of a decode step's choices, per
    layer, under uniform routing (module docstring)."""
    g = granite_dims(config)
    return g["held"] * (1.0 - (1.0 - g["top_k"] / g["routed"]) ** g["slots"])


def expert_decode_call_bytes(config: Dict[str, Any]) -> float:
    """Bytes one grouped-matmul call of a decode step has to read: one
    matrix of each held expert touched (the rows it multiplies are under
    6 MB and are not charged)."""
    return experts_touched(config) * expert_matrix_bytes(config)


def ssd_state_bytes_per_slot(config: Dict[str, Any]) -> int:
    """One slot's float32 state of one Mamba-2 layer: ``[N, H P]``."""
    g = granite_dims(config)
    return g["state"] * g["channels"] * 4


def ssd_state_update_bytes(config: Dict[str, Any]) -> float:
    """Bytes one Mamba-2 layer's decode-shaped state update must move:
    every slot's float32 state read once and written once."""
    return float(granite_dims(config)["slots"]
                 * ssd_state_bytes_per_slot(config) * 2)


def conv_tail_call_bytes(config: Dict[str, Any],
                         dtype_bytes: int = 2) -> float:
    """One Mamba-2 layer's convolution tail, read and written."""
    g = granite_dims(config)
    return float(g["slots"] * (g["conv_kernel"] - 1) * g["conv_dim"]
                 * dtype_bytes * 2)


def kv_bytes_per_token(config: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """What one cached token holds over the attention layers: a K and a
    V row of ``kv_heads x head_dim``."""
    d = dims(config)
    return (granite_dims(config)["attn_layers"] * 2 * d["kv_heads"]
            * d["head_dim"] * dtype_bytes)


def decode_step_bytes(config: Dict[str, Any], live_tokens: float) -> float:
    """Bytes one decode step has to move through HBM when the slots hold
    ``live_tokens`` cached tokens in total."""
    d, g = dims(config), granite_dims(config)
    experts = d["layers"] * 3 * expert_decode_call_bytes(config)
    return (dense_weight_bytes(config) + experts
            + kv_bytes_per_token(config) * live_tokens
            + g["mamba_layers"] * (ssd_state_update_bytes(config)
                                   + conv_tail_call_bytes(config)))


def ssd_prefill_call_flops(config: Dict[str, Any],
                           rows: Optional[int] = None) -> float:
    """Floating-point operations of ONE Mamba-2 layer's chunked scan
    over a prefill call of ``rows`` positions (None: the one program's,
    one row of ``serve.prefill_len``): per chunk of Q rows, ``C B^T``
    once for all heads (``2 Q^2 N``), its masked product with ``dt x``
    (``2 H Q^2 P``), the carried state's term and the state's own update
    (``2 H P N Q`` each). Counted once whatever the precision: run in
    float32 at ``highest`` each product is six bfloat16 passes."""
    g = granite_dims(config)
    rows = g["prefill_len"] if rows is None else rows
    q, h, p, n = g["chunk"], g["ssd_heads"], g["ssd_dim"], g["state"]
    chunks = -(-rows // q)
    return float(chunks * (2 * q * q * n + 2 * h * q * q * p
                           + 4 * h * p * n * q))


def ssd_prefill_call_bytes(config: Dict[str, Any],
                           rows: Optional[int] = None) -> float:
    """Bytes ONE Mamba-2 layer's scan over a prefill call must move
    whatever implements it: ``x``, ``dt``, ``B`` and ``C`` float32 in
    and ``y`` float32 out, once a row, and the state out (a prompt
    starts from none). At the v5e's peaks this binds the scan (0.17 ms
    a call against 0.09 ms of its operations at the bf16 peak): the
    reader holds it to the HBM peak."""
    g = granite_dims(config)
    rows = g["prefill_len"] if rows is None else rows
    per_row = 2 * g["channels"] + g["ssd_heads"] + 2 * g["state"]
    return float(rows * per_row * 4 + ssd_state_bytes_per_slot(config))
