"""What an openPangu-Ultra-MoE (pangu_ultra_moe) decode step, its latent
decode kernel, its expert kernel and its prefill attention must do, from
shapes alone (the ``cost_module`` of
``serve_pangu_decode_step_hbm_roofline``,
``serve_pangu_latent_attn_mxu_roofline`` /
``serve_pangu_latent_attn_hbm_roofline``,
``serve_pangu_expert_mlp_roofline`` and
``serve_pangu_prefill_attn_roofline``; the arithmetic is written out in
``pangu_ultra_moe.md`` beside this file).

A decode step of ``slots`` live tokens reads every weight that
multiplies every token (every layer's latent projections and output
projection, the leading dense MLPs, every sparse layer's router at its
full width with its shared expert, the norm gains, the slice of the
output head held here), the three matrices of each HELD expert that at
least one token chose, and one latent row ``[c | k_r]`` a cached token
and layer, AS STORED (640 numbers: the 576 written down padded to whole
128-lane tiles; the padding is read, so it is charged). The embedding is
a gather of ``slots`` rows and is not charged.

The latent decode kernel is charged twice, against both peaks, because
it sits on the v5e's ridge: a cached token costs 1,280 B of HBM traffic
(once for all 128 heads) and ``heads x 2 x (576 + 512)`` FLOPs (every
head's score against the row's 576 numbers that are no padding, every
head's sum over its 512 value columns): 217 FLOP a byte against
197e12 / 819e9 = 240.

A reader can hand a cost function the configuration and ``live_tokens``
only, neither the step's routing nor a slot's own length. So the experts
touched are an expectation under uniform routing (``held x (1 - (1 - k /
routed) ** slots)``: 8 held of 256, top 8, 8 slots: 1.79 a layer; the
engine counts what was touched, ``engine.moe_expert_visits``; where the
count falls more than 10 % short, ``MEASURED_FLOOR`` is charged).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmarks.lib.costs import dims

_LANES = 128

# held experts touched per sparse layer and decode step as the engine
# counted them on the chip (``engine.moe_expert_visits /
# (engine.decode_steps x sparse layers)``), rounded down to a tenth,
# where that is more than 10 % under the uniform expectation
# (``costs/olmoe.py``'s rule); None: the expectation is charged
MEASURED_FLOOR: Optional[float] = None


def latent_dims(config: Dict[str, Any]) -> Dict[str, int]:
    held = int(config["n_routed_experts"])
    rank, rot = int(config["kv_lora_rank"]), int(config["qk_rope_head_dim"])
    dense = int(config["first_k_dense_replace"])
    return {
        "q_rank": int(config["q_lora_rank"]), "kv_rank": rank, "rot": rot,
        "nope": int(config["qk_nope_head_dim"]),
        "v_dim": int(config["v_head_dim"]),
        "row": rank + rot,
        "row_stored": -(-(rank + rot) // _LANES) * _LANES,
        "dense": dense,
        "sparse": int(config["num_hidden_layers"]) - dense,
        "held": held,
        "routed": int(config.get("num_routed_experts") or held),
        "top_k": int(config["num_experts_per_tok"]),
        "width": int(config["moe_intermediate_size"]),
        "shared": int(config.get("n_shared_experts", 1)),
    }


def attention_params(config: Dict[str, Any]) -> int:
    """One layer outside its MLP: W_dq and its norm, W_uq, W_dkv and the
    latent's norm, W_ukv, W_o, the four layer norms."""
    d, m = dims(config), latent_dims(config)
    h, heads = d["hidden"], d["heads"]
    return (h * m["q_rank"] + m["q_rank"]
            + m["q_rank"] * heads * (m["nope"] + m["rot"])
            + h * m["row"] + m["kv_rank"]
            + m["kv_rank"] * heads * (m["nope"] + m["v_dim"])
            + heads * m["v_dim"] * h + 4 * h)


def sparse_mlp_dense_params(config: Dict[str, Any]) -> int:
    """What every token multiplies in one sparse MLP: the router at its
    full width, the shared experts."""
    d, m = dims(config), latent_dims(config)
    return (d["hidden"] * m["routed"]
            + m["shared"] * 3 * d["hidden"] * m["width"])


def expert_matrix_bytes(config: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """One of an expert's three matrices ([hidden, width] or back)."""
    return dims(config)["hidden"] * latent_dims(config)["width"] \
        * dtype_bytes


def dense_weight_bytes(config: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """Weights every token multiplies: every layer's attention and
    norms, the dense MLPs, the sparse layers' router and shared expert,
    the final norm, the head's slice."""
    d, m = dims(config), latent_dims(config)
    return (d["layers"] * attention_params(config)
            + m["dense"] * 3 * d["hidden"] * d["ffn"]
            + m["sparse"] * sparse_mlp_dense_params(config)
            + d["hidden"] + d["hidden"] * d["vocab"]) * dtype_bytes


def experts_touched(config: Dict[str, Any]) -> float:
    """Held experts with at least one of a decode step's choices, per
    sparse layer (module docstring), or ``MEASURED_FLOOR``."""
    if MEASURED_FLOOR is not None:
        return MEASURED_FLOOR
    m = latent_dims(config)
    slots = int(config["serve"]["max_slots"])
    return m["held"] * (1.0 - (1.0 - m["top_k"] / m["routed"]) ** slots)


def latent_row_bytes(config: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """A cached token's row in one layer, as stored."""
    return latent_dims(config)["row_stored"] * dtype_bytes


def latent_attn_call_bytes(config: Dict[str, Any],
                           live_tokens: float) -> float:
    """Bytes one latent decode kernel call (one layer) has to read: every
    cached token's row once, for all heads."""
    return latent_row_bytes(config) * float(live_tokens)


def latent_attn_call_flops(config: Dict[str, Any],
                           live_tokens: float) -> float:
    """FLOPs one latent decode kernel call has to do: every head's score
    against each cached row (its ``kv_rank + rope`` numbers that are no
    padding) and its weighted sum of the row's ``kv_rank`` value
    columns: the work, whatever padding computes it."""
    m = latent_dims(config)
    return (dims(config)["heads"] * 2.0 * (m["row"] + m["kv_rank"])
            * float(live_tokens))


def expert_decode_call_bytes(config: Dict[str, Any]) -> float:
    """Bytes one grouped-matmul call of a decode step has to read: one
    matrix of each held expert touched (the rows it multiplies are under
    2 MB and are not charged)."""
    return experts_touched(config) * expert_matrix_bytes(config)


def decode_step_bytes(config: Dict[str, Any], live_tokens: float) -> float:
    """Bytes one decode step has to move through HBM when the slots hold
    ``live_tokens`` cached tokens in total."""
    d, m = dims(config), latent_dims(config)
    return (dense_weight_bytes(config)
            + m["sparse"] * 3 * expert_decode_call_bytes(config)
            + d["layers"] * latent_attn_call_bytes(config, live_tokens))


def prefill_head_groups(config: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """In how many equal groups of heads the full-shape prefill call
    runs its attention, one flash forward a group and layer: the
    program's rule restated (``models/pangu_ultra_moe.head_groups``: the
    fewest groups that divide the heads and keep a group's expanded
    queries within 512 MiB; ``tests/test_paged_kernel_aot.py`` holds the
    compiled call's head count to it): 4 of 32 heads at 8 x 3,072."""
    d, m = dims(config), latent_dims(config)
    serve = config["serve"]
    total = (int(serve["max_slots"]) * int(serve["prefill_len"])
             * d["heads"] * (m["nope"] + m["rot"]) * dtype_bytes)
    return next(g for g in range(1, d["heads"] + 1)
                if d["heads"] % g == 0 and total // g <= 1 << 29)


def prefill_attn_call_flops(config: Dict[str, Any]) -> float:
    """FLOPs one prefill attention call (one layer's group of heads) has
    to do over the fixed-shape ``[slots, prefill_len]`` buffer in the
    expanded form: q k^T at ``nope + rope`` and p v at ``v_head_dim``
    over the causal pairs, every head of the group. The count is of the
    work, whatever computes it; every row of the buffer is charged, live or not, since
    every row is computed."""
    d, m = dims(config), latent_dims(config)
    serve = config["serve"]
    rows = int(serve["prefill_len"])
    pairs = rows * (rows + 1) // 2
    heads = d["heads"] // prefill_head_groups(config)
    return (int(serve["max_slots"]) * heads * pairs
            * 2.0 * (m["nope"] + m["rot"] + m["v_dim"]))
