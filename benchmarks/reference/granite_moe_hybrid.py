"""Plain float32 reference of the Granite 4.0-H decoder (forward, loss).

The yardstick that decides ``correct`` for a ``granitemoehybrid``
configuration: straightforward ``jax.numpy``, float32 throughout, every
matmul under ``jax.default_matmul_precision("highest")``, the recurrence
written as the recurrence (a ``lax.scan`` over tokens: no chunks), no
cache, no sort, no kernels. After transformers'
``modeling_granitemoehybrid.py`` (whose mixer follows ``modeling_bamba.py``
and state-spaces/mamba ``mamba2.py``), as ISSUE 61 writes the equations
out. With ``N(x; g) = g * x / sqrt(mean(x^2) + eps)``:

    h0 = embedding_multiplier * E[ids]
    h <- h + residual_multiplier * Mix(N(h; g_1))
    u = N(h; g_2);   h <- h + residual_multiplier * (MoE(u) + Shared(u))
    logits = (N(h; g_f) E^T) / logits_scaling            (tied embedding)

Layer ``i`` is what ``layer_types[i]`` names; no bias but the
convolution's; no positional embedding anywhere.

*Attention layer*: ``q = x W_q`` (heads of ``hidden / heads``), ``k = x
W_k``, ``v = x W_v`` (``num_key_value_heads`` heads, each shared by
``heads / kv heads`` query heads), causal softmax of ``q k^T *
attention_multiplier``, ``W_o``.

*Mamba-2 layer*, ``H = mamba_n_heads`` heads of ``P = mamba_d_head``, ``N
= mamba_d_state``, one group:

    [z, xBC, dt] = x W_in                 (H P | H P + 2 N | H)
    xBC <- silu(conv4(xBC) + b_conv)      depthwise, causal, x, B and C
    dt_h = softplus(dt_h + dt_bias_h);    a_h = exp(-dt_h exp(A_log_h))
    S_t[h] = a_h S_{t-1}[h] + dt_h x_t[h] B_t^T;   from S = 0
    y_t[h] = S_t[h] C_t + D_h x_t[h]
    Mix = N(y * silu(z); g_n) W_out       one norm over all H P channels

*Experts*: ``l = u W_r`` over all ``num_routed_experts``; the
``num_experts_per_tok`` largest kept; gates = softmax over the kept
logits; expert ``e``: ``down_e(silu(gate_e u) * up_e u)``; plus the
ungated shared SwiGLU. **A share**: the file's ``num_local_experts``
counts the experts held (ids ``[first_expert_id, first_expert_id +
num_local_experts)`` of ``num_routed_experts``); the routed sum is then
over the held experts only, each under the gate the uncut layer gives
it, and that partial result goes on to the next layer. The expert sum is
in its plainest form: every held expert on every token under a 0 / gate
matrix, ``expert_chunk`` experts at a time, each widened to float32 as
it is used.

It imports nothing from ``scaletorch_tpu``. What it shares with the
system is the layout of the parameter tree it is handed
(``models/granite_moe_hybrid.py``): ``layers.block.*`` ``[layers, ...]``,
``layers.mamba.*`` / ``layers.attention.*`` ``[layers of the kind,
...]``, ``layers.moe.*`` ``[layers, ...]``, ``x @ W`` orientation,
``in_proj`` columns ``z | x | B | C | dt``, ``conv [4, H P + 2 N]`` with
``conv[3]`` the weight of the current row.

Departures from the published description, none of them mathematics:
attention in query blocks; weights widened to float32 a layer (an expert
chunk) at a time; the cross entropy only in ``make_loss_fn``.

``wrong`` selects a deliberately wrong variant, there only to show that
the tolerance rejects it, each a step a later PR would be tempted by:
``"bf16_state"`` rounds the state to bfloat16 after every token (a state
kept in the serving dtype: half the bytes a decode step moves for it);
``"norm_before_gate"`` computes ``N(y; g_n) * silu(z)`` (Mamba-2's other
published order); ``"conv_on_x_only"`` leaves ``B`` and ``C`` as the
projection gives them (Mamba-1's convolution); ``"no_residual_multiplier"``
adds both branches at 1; ``"sqrt_d_attention_scale"`` scales the scores
by ``head_dim ** -0.5`` (every other family's); ``"rope_on_attention"``
turns q and k by a rotary embedding at the file's unused ``rope_theta``;
``"gates_not_renormalised"`` weights the kept experts by the softmax
over ALL routed experts; ``"no_d_skip"`` drops ``D x``;
``"fp8_activations"`` rounds the activation operand of every matmul (the
normed input of every sub-block and of the head, what ``W_out``, ``W_o``
and the down projections read) to 3 bits of mantissa, float8 e4m3's: the
nearest precision below the bfloat16 such a configuration is served in
(the exponent keeps bfloat16's range: the precision alone is lowered;
weights, accumulation and the recurrence stay float32).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from benchmarks.lib.costs import dims
from benchmarks.reference.olmo_hybrid import short_conv
from benchmarks.reference.pangu_ultra_moe import causal_attention, swiglu
from benchmarks.reference.qwen3 import (
    _chunked_nll,
    _sum_squares,
    head_weight,
    rms_norm,
    rope,
)
from benchmarks.reference.trinity import operand

F32 = jnp.float32
MAMBA, ATTENTION = "mamba", "attention"

GAIN_KEYS = ("input_layernorm", "post_attention_layernorm")
_EXPERT_KEYS = ("expert_gate_proj", "expert_up_proj", "expert_down_proj")
WRONG = ("bf16_state", "norm_before_gate", "conv_on_x_only",
         "no_residual_multiplier", "sqrt_d_attention_scale",
         "rope_on_attention", "gates_not_renormalised", "no_d_skip",
         "fp8_activations")


def granite_dims(config):
    d = dims(config)
    kinds = tuple(config["layer_types"])
    if len(kinds) != d["layers"] or set(kinds) - {MAMBA, ATTENTION}:
        raise ValueError("layer_types does not name each layer")
    if int(config.get("mamba_n_groups", 1)) != 1:
        raise ValueError("mamba_n_groups is not 1")
    if config.get("mamba_proj_bias", False):
        raise ValueError("mamba_proj_bias is not built")
    if config.get("position_embedding_type", "nope") != "nope":
        raise ValueError("position_embedding_type is not nope")
    held = int(config["num_local_experts"])
    d.update(
        kinds=kinds,
        ssd_heads=int(config["mamba_n_heads"]),
        ssd_dim=int(config["mamba_d_head"]),
        state=int(config["mamba_d_state"]),
        conv_bias=bool(config.get("mamba_conv_bias", True)),
        held=held,
        routed=int(config.get("num_routed_experts") or held),
        first=int(config.get("first_expert_id", 0)),
        top_k=int(config["num_experts_per_tok"]),
        embedding_multiplier=float(config["embedding_multiplier"]),
        attention_multiplier=float(config["attention_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        logits_scaling=float(config["logits_scaling"]))
    return d


def recurrence(x, dt, a, bm, cm, wrong=None):
    """x [S, H, P], dt, a [S, H], bm, cm [S, N] -> y [S, H, P] without
    the ``D x`` skip: the recurrence of the head comment from ``S = 0``,
    one token after another."""
    def token(state, row):
        x_t, dt_t, a_t, b_t, c_t = row
        state = (a_t[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        if wrong == "bf16_state":
            state = jax.lax.reduce_precision(
                state, exponent_bits=8, mantissa_bits=7)
        return state, jnp.einsum("hpn,n->hp", state, c_t)

    state0 = jnp.zeros(x.shape[1:] + bm.shape[1:], F32)
    _, y = jax.lax.scan(token, state0, (x, dt, a, bm, cm))
    return y


def mamba_part(u, lp, d, wrong=None):
    """The Mamba-2 mixer of the normed ``u`` [S, hidden]."""
    s = u.shape[0]
    heads, p, n = d["ssd_heads"], d["ssd_dim"], d["state"]
    c = heads * p
    zxbcdt = u @ lp["in_proj"]
    z, xbc, dt = zxbcdt[:, :c], zxbcdt[:, c:2 * c + 2 * n], zxbcdt[:, 2 * c + 2 * n:]
    mixed = short_conv(xbc, lp["conv"])
    if d["conv_bias"]:
        mixed = mixed + lp["conv_bias"]
    mixed = jax.nn.silu(mixed)
    if wrong == "conv_on_x_only":
        mixed = jnp.concatenate([mixed[:, :c], xbc[:, c:]], axis=-1)
    x = mixed[:, :c].reshape(s, heads, p)
    bm, cm = mixed[:, c:c + n], mixed[:, c + n:]
    dt = jax.nn.softplus(dt + lp["dt_bias"])
    a = jnp.exp(-dt * jnp.exp(lp["A_log"]))
    y = recurrence(x, dt, a, bm, cm, wrong)
    if wrong != "no_d_skip":
        y = y + lp["D"][:, None] * x
    y, gate = y.reshape(s, c), jax.nn.silu(z)
    if wrong == "norm_before_gate":
        y = rms_norm(y, lp["norm"], d["eps"]) * gate
    else:
        y = rms_norm(y * gate, lp["norm"], d["eps"])
    return operand(y, wrong) @ lp["out_proj"]


def attention_part(u, lp, positions, d, q_block, wrong=None):
    """The attention mixer of the normed ``u`` [S, hidden]."""
    s = u.shape[0]
    heads, hkv, hd = d["heads"], d["kv_heads"], d["head_dim"]
    q = (u @ lp["q_proj"]).reshape(s, heads, hd)
    k = (u @ lp["k_proj"]).reshape(s, hkv, hd)
    v = (u @ lp["v_proj"]).reshape(s, hkv, hd)
    if wrong == "rope_on_attention":
        q, k = rope(q, positions, d["theta"]), rope(k, positions, d["theta"])
    scale = (hd ** -0.5 if wrong == "sqrt_d_attention_scale"
             else d["attention_multiplier"])
    # query head j reads K/V head j // (heads / kv heads)
    k, v = (jnp.repeat(a, heads // hkv, axis=1) for a in (k, v))
    attn = causal_attention(q, k, v, scale, q_block)
    return operand(attn.reshape(s, heads * hd), wrong) @ lp["o_proj"]


def expert_weights(m, router, d, wrong=None):
    """[S, held] float32: the gate each HELD expert's output is summed
    under for each token: the uncut layer's gate where the token chose
    the expert, 0 where it did not."""
    logits = (m @ router).astype(F32)
    kept, choice = jax.lax.top_k(logits, d["top_k"])
    if wrong == "gates_not_renormalised":
        kept = jnp.take_along_axis(
            jax.nn.softmax(logits, axis=-1), choice, axis=-1)
    else:
        kept = jax.nn.softmax(kept, axis=-1)
    member = (choice[:, :, None] == jnp.arange(d["routed"])[None, None, :])
    every = jnp.sum(member * kept[:, :, None], axis=1)       # [S, routed]
    return every[:, d["first"]:d["first"] + d["held"]]


def moe_part(m, small, experts, place, d, expert_chunk, wrong=None):
    """The sparse MLP of the normed ``m`` [S, hidden]. ``small``: this
    layer's router and shared expert, float32; ``experts``: the expert
    stacks of ALL layers as served, ``[layers, held, ...]``, of which
    layer ``place``'s are read ``expert_chunk`` at a time."""
    weights = expert_weights(m, small["router"], d, wrong)
    chunk = min(expert_chunk, d["held"])
    if d["held"] % chunk:
        raise ValueError(f"{d['held']} experts in chunks of {chunk}")

    def some_experts(c):
        def of(name):
            a = experts[name]
            return jax.lax.dynamic_slice(
                a, (place, c * chunk, 0, 0), (1, chunk) + a.shape[2:]
            )[0].astype(F32)

        mid = jax.nn.silu(jnp.einsum("sh,ehi->esi", m, of(_EXPERT_KEYS[0]))) \
            * jnp.einsum("sh,ehi->esi", m, of(_EXPERT_KEYS[1]))
        out = jnp.einsum("esi,eih->esh", operand(mid, wrong),
                         of(_EXPERT_KEYS[2]))
        w = jax.lax.dynamic_slice_in_dim(weights, c * chunk, chunk, axis=1)
        return jnp.einsum("esh,se->sh", out, w)

    routed = jnp.sum(jax.lax.map(
        some_experts, jnp.arange(d["held"] // chunk)), axis=0)
    return routed + swiglu(m, small["shared_gate_proj"],
                           small["shared_up_proj"],
                           small["shared_down_proj"], wrong)


def final_hidden(params, tokens, positions, d, q_block=512,
                 expert_chunk=6, wrong=None):
    """tokens [S] -> final-normed hidden states [S, hidden], float32."""
    if wrong is not None and wrong not in WRONG:
        raise ValueError(f"unknown wrong variant {wrong!r}")
    x = d["embedding_multiplier"] * params["embed_tokens"][tokens].astype(F32)
    layers = params["layers"]
    experts = {name: layers["moe"][name] for name in _EXPERT_KEYS}
    eps, kinds = d["eps"], d["kinds"]
    res = 1.0 if wrong == "no_residual_multiplier" \
        else d["residual_multiplier"]

    def of(stack, index, skip=()):
        # widened one layer at a time
        return {name: a[index].astype(F32)
                for name, a in stack.items() if name not in skip}

    for layer, kind in enumerate(kinds):
        norms = of(layers["block"], layer)
        place = kinds[:layer].count(kind)
        u = operand(rms_norm(x, norms["input_layernorm"], eps), wrong)
        if kind == MAMBA:
            x = x + res * mamba_part(u, of(layers["mamba"], place), d, wrong)
        else:
            x = x + res * attention_part(
                u, of(layers["attention"], place), positions, d, q_block,
                wrong)
        m = operand(rms_norm(x, norms["post_attention_layernorm"], eps),
                    wrong)
        x = x + res * moe_part(m, of(layers["moe"], layer, _EXPERT_KEYS),
                               experts, layer, d, expert_chunk, wrong)
    return operand(rms_norm(x, params["norm"].astype(F32), eps), wrong)


def loss(params, tokens, targets, positions, d, *, q_block=512,
         loss_chunk=1024, expert_chunk=6, wrong=None):
    """Mean next-token cross entropy of one sequence."""
    hidden = final_hidden(params, tokens, positions, d, q_block,
                          expert_chunk, wrong)
    return _chunked_nll(hidden, head_weight(params, d) / d["logits_scaling"],
                        targets, loss_chunk)


def logits_at(params, tokens, rows, d, *, q_block=512, expert_chunk=6,
              wrong=None):
    """tokens [S], rows [R] -> logits [R, vocab] of a full forward pass
    at those rows, float32."""
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    hidden = final_hidden(params, tokens, positions, d, q_block,
                          expert_chunk, wrong)
    return hidden[rows] @ head_weight(params, d) / d["logits_scaling"]


def make_loss_fn(config, *, q_block=512, loss_chunk=1024, expert_chunk=6,
                 wrong=None, with_gradients=False):
    """A jitted ``(params, tokens [S], targets [S], positions [S]) ->
    loss`` or ``-> (loss, global gradient norm, gradients of the norm
    gains)``, at ``highest`` matmul precision. The gradient is
    ``jax.grad`` of the whole tree at once: right for the sizes a test
    has (the family is served, not trained)."""
    d = granite_dims(config)
    fn = functools.partial(loss, d=d, q_block=q_block, loss_chunk=loss_chunk,
                           expert_chunk=expert_chunk, wrong=wrong)

    def loss_only(params, tokens, targets, positions):
        with jax.default_matmul_precision("highest"):
            return fn(params, tokens, targets, positions)

    def both(params, tokens, targets, positions):
        with jax.default_matmul_precision("highest"):
            value, grads = jax.value_and_grad(fn)(
                params, tokens, targets, positions)
        gains = {k: g.astype(F32)
                 for k, g in grads["layers"]["block"].items()
                 if k in GAIN_KEYS}
        return (value, jnp.sqrt(_sum_squares(grads)),
                {"layers": {"block": gains},
                 "norm": grads["norm"].astype(F32)})

    return jax.jit(both if with_gradients else loss_only)


def make_logits_fn(config, *, q_block=512, expert_chunk=6,
                   wrong: Optional[str] = None):
    """A jitted ``(params, tokens [B, S], rows [B, R]) -> logits
    [B, R, vocab]``: the full forward pass of each sequence alone."""
    d = granite_dims(config)

    def batch_logits(params, tokens, rows):
        with jax.default_matmul_precision("highest"):
            one = functools.partial(
                logits_at, params, d=d, q_block=q_block,
                expert_chunk=expert_chunk, wrong=wrong)
            return jax.lax.map(lambda tr: one(tr[0], tr[1]), (tokens, rows))

    return jax.jit(batch_logits)
