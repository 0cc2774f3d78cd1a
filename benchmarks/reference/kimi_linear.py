"""Plain float32 reference of the Kimi-Linear decoder (forward, loss).

The yardstick that decides ``correct`` for a ``kimi_linear``
configuration: straightforward ``jax.numpy``, float32 throughout, every
matmul under ``jax.default_matmul_precision("highest")``, no cache, no
chunked scan, no absorbed form, no sort, no kernels. After
``modeling_kimi.py`` of moonshotai/Kimi-Linear-48B-A3B-Instruct and
arXiv:2510.26692, as ISSUE 54 writes the equations out. With ``N(x; g)
= x / sqrt(mean(x^2) + eps) * g`` layer ``i`` (1-based) is a KDA layer
where ``linear_attn_config.kda_layers`` lists it and a latent layer
where ``full_attn_layers`` does, and every layer is

    h <- h + Mix(N(h; g_in))        h <- h + MLP(N(h; g_post))

with ``N(h; g_f)`` and an untied head after the last; no bias anywhere.

*KDA layer*, ``x`` the normed input, ``H`` heads of ``d``: ``q^, k^, v =
silu(conv4(x W_q | x W_k | x W_v))`` (depthwise, causal, no bias); a
head's ``q = l2norm(q^) d^-0.5``, ``k = l2norm(k^)`` (1e-6 under the
root); ``g_t = -exp(A_log_h) softplus((x W_fa) W_fb + dt_bias)`` per KEY
CHANNEL; ``beta_t = sigmoid(x W_b)`` per head; per head and token, from
``S = 0``:

    S <- diag(exp(g_t)) S;   u = beta_t (v_t - S^T k_t);   S <- S + k_t u^T
    o_t = S^T q_t

``Mix = concat_h(N(o_h; g_o) * sigmoid(z_h)) W_o``, ``z = (x W_ga)
W_gb``, ``g_o`` [d] shared by the heads.

*Latent layer (NoPE)*: ``q = x W_q`` (a head ``[q_n | q_r]``); ``[c_raw
| k_r] = x W_dkv``; ``c = N(c_raw; g_kv)``; ``[k_n,h | v_h] = c
W_ukv,h``; ``s_h(i, j) = (q_n,h(i) . k_n,h(j) + q_r,h(i) . k_r(j)) /
sqrt(nope + rope)``, causal softmax; ``Mix = concat(o_h) W_o``. Nothing
is rotated.

*MLP*: the first ``first_k_dense_replace`` layers a SwiGLU; the others
``s = sigmoid(m W_r)`` over all ``num_routed_experts``, the top k of
``s + b`` (``expert_bias``), weights ``s_e / (sum of the k + 1e-20)``
times ``routed_scaling_factor``, expert ``e``: ``down_e(silu(gate_e m)
* up_e m)``, plus the ungated shared SwiGLU. **A share**: the file's
``num_experts`` counts the experts held (ids ``[first_expert_id,
first_expert_id + num_experts)`` of ``num_routed_experts``); the routed
sum is then over the held experts only, each under the weight the uncut
layer gives it, and that partial result goes on to the next layer. The
expert sum is in its plainest form: every held expert on every token
under a 0 / weight matrix, ``expert_chunk`` experts at a time, each
widened to float32 as it is used.

It imports nothing from ``scaletorch_tpu``. What it shares with the
system is the layout of the parameter tree it is handed
(``models/kimi_linear.py``): ``layers.block.*`` ``[layers, ...]``,
``layers.kda.*`` / ``layers.mla.*`` ``[layers of the kind, ...]``,
``layers.dense.*``, ``layers.moe.*`` ``[sparse layers, ...]``, ``x @ W``
orientation, ``conv [4, 3 H d]`` with channels ``q | k | v`` side by
side and ``conv[3]`` the weight of the current row, ``kv_b_proj [rank,
heads, nope + v]``.

Departures from the published description, none of them mathematics:
attention in query blocks; weights widened to float32 a layer (an expert
chunk) at a time; the cross entropy only in ``make_loss_fn``.

``wrong`` selects a deliberately wrong variant, there only to show that
the tolerance rejects it: ``"scalar_gate"`` replaces a head's decay by
the mean of its channels' (what the scalar-gated delta rule of the
benchmark's other two families computes); ``"decay_after_update"``
writes ``k u^T`` into the state before the decay and not after;
``"no_qk_l2norm"`` leaves ``q^`` and ``k^`` un-normed;
``"swish_output_gate"`` gates by ``silu(z)`` (the scalar-gated rule's
output gate); ``"rope_on_latent_key"`` rotates ``q_r`` and ``k_r`` at
``rope_theta`` (what every other latent attention does);
``"no_latent_norm"`` expands keys and values from ``c_raw``;
``"softmax_router"`` scores by a softmax; ``"no_route_scale"`` drops
``routed_scaling_factor``; ``"bf16_state"`` keeps the recurrent state in
bfloat16 between tokens; ``"fp8_activations"`` rounds the activation
operand of every matmul (the normed input of every sub-block and of the
head, the latent, what ``o_proj`` and the down projections read) to 3
bits of mantissa, float8 e4m3's: the nearest precision below the
bfloat16 such a configuration is served in (the exponent keeps
bfloat16's range: the precision alone is lowered; weights and
accumulation stay float32); ``"fp8_layers"`` is the same inside the
layers and leaves the head's input alone.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from benchmarks.lib.costs import dims
from benchmarks.reference.olmo_hybrid import l2norm, short_conv
from benchmarks.reference.pangu_ultra_moe import causal_attention, swiglu
from benchmarks.reference.qwen3 import (
    _chunked_nll,
    _sum_squares,
    head_weight,
    rms_norm,
    rope,
)

F32 = jnp.float32
KDA, FULL = "kda", "full"

GAIN_KEYS = ("input_layernorm", "post_attention_layernorm")
_EXPERT_KEYS = ("expert_gate_proj", "expert_up_proj", "expert_down_proj")
WRONG = ("scalar_gate", "decay_after_update", "no_qk_l2norm",
         "swish_output_gate", "rope_on_latent_key", "no_latent_norm",
         "softmax_router", "no_route_scale", "bf16_state",
         "fp8_activations", "fp8_layers")


def kimi_dims(config):
    d = dims(config)
    lists = config["linear_attn_config"]
    full = set(lists["full_attn_layers"])
    if sorted(list(lists["kda_layers"]) + list(full)) != list(
            range(1, d["layers"] + 1)):
        raise ValueError("linear_attn_config does not name each layer once")
    if config.get("q_lora_rank") is not None:
        raise ValueError("q_lora_rank is not null")
    held = int(config["num_experts"])
    d.update(
        kinds=tuple(FULL if i + 1 in full else KDA
                    for i in range(d["layers"])),
        kda_heads=int(lists["num_heads"]), kda_dim=int(lists["head_dim"]),
        kv_rank=int(config["kv_lora_rank"]),
        nope=int(config["qk_nope_head_dim"]),
        rot=int(config["qk_rope_head_dim"]),
        dense=int(config["first_k_dense_replace"]),
        held=held,
        routed=int(config.get("num_routed_experts") or held),
        first=int(config.get("first_expert_id", 0)),
        top_k=int(config["num_experts_per_token"]),
        shared=int(config.get("num_shared_experts", 1)),
        renormalise=bool(config.get("moe_renormalize", True)),
        route_scale=float(config.get("routed_scaling_factor", 1.0)))
    return d


def operand(x, wrong=None):
    """The activation operand of a matmul: as it is, or at 3 bits of
    mantissa (``reduce_precision``, not a pair of converts: XLA may drop
    such a pair, excess precision being allowed by default)."""
    if wrong in ("fp8_activations", "fp8_layers"):
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=3)
    return x


def delta_rule(q, k, v, g, beta, wrong=None):
    """q, k, g [S, H, d], v [S, H, d], beta [S, H] -> o [S, H, d]: the
    recurrence of the head comment, one row after another, from S = 0;
    ``g`` the log of the decay of each key channel."""
    keep = jnp.bfloat16 if wrong == "bf16_state" else F32

    def step(state, row):
        q_t, k_t, v_t, g_t, b_t = row
        state = state.astype(F32)
        if wrong != "decay_after_update":
            state = jnp.exp(g_t)[:, :, None] * state
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", state, k_t))
        state = state + jnp.einsum("hk,hv->hkv", k_t, u)
        if wrong == "decay_after_update":
            state = jnp.exp(g_t)[:, :, None] * state
        state = state.astype(keep)
        return state, jnp.einsum("hkv,hk->hv", state.astype(F32), q_t)

    state0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), keep)
    _, o = jax.lax.scan(step, state0, (q, k, v, g, beta))
    return o


def kda_part(x, lp, d, wrong=None):
    """The KDA mixer of the normed ``x`` [S, hidden]."""
    s = x.shape[0]
    h, w = d["kda_heads"], d["kda_heads"] * d["kda_dim"]
    qkv = jnp.concatenate(
        [x @ lp["q_proj"], x @ lp["k_proj"], x @ lp["v_proj"]], axis=-1)
    qkv = jax.nn.silu(short_conv(qkv, lp["conv"]))
    q, k, v = (qkv[:, i * w:(i + 1) * w].reshape(s, h, -1)
               for i in range(3))
    if wrong != "no_qk_l2norm":
        q, k = l2norm(q), l2norm(k)
    q = q * d["kda_dim"] ** -0.5
    g = -jnp.exp(lp["A_log"])[:, None] * jax.nn.softplus(
        operand(x @ lp["f_a_proj"], wrong) @ lp["f_b_proj"]
        + lp["dt_bias"]).reshape(s, h, -1)
    if wrong == "scalar_gate":
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid(x @ lp["b_proj"])
    o = delta_rule(q, k, v, g, beta, wrong)
    z = (operand(x @ lp["g_a_proj"], wrong) @ lp["g_b_proj"]).reshape(
        s, h, -1)
    gate = jax.nn.silu(z) if wrong == "swish_output_gate" \
        else jax.nn.sigmoid(z)
    y = rms_norm(o, lp["o_norm"], d["eps"]) * gate
    return operand(y.reshape(s, w), wrong) @ lp["o_proj"]


def latent_part(x, lp, positions, d, q_block, wrong=None):
    """The latent-attention mixer of the normed ``x`` [S, hidden], in
    the expanded form; nothing rotated."""
    s = x.shape[0]
    heads, nope, rot = d["heads"], d["nope"], d["rot"]
    q = (x @ lp["q_proj"]).reshape(s, heads, nope + rot)
    kv_a = x @ lp["kv_a_proj_with_mqa"]
    c_raw, k_r = kv_a[:, :d["kv_rank"]], kv_a[:, None, d["kv_rank"]:]
    c = (c_raw if wrong == "no_latent_norm"
         else rms_norm(c_raw, lp["kv_a_layernorm"], d["eps"]))
    kv = jnp.einsum("sc,chd->shd", operand(c, wrong), lp["kv_b_proj"])
    if wrong == "rope_on_latent_key":
        q = jnp.concatenate(
            [q[..., :nope], rope(q[..., nope:], positions, d["theta"])],
            axis=-1)
        k_r = rope(k_r, positions, d["theta"])
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r, (s, heads, rot))], axis=-1)
    attn = causal_attention(q, k, kv[..., nope:], (nope + rot) ** -0.5,
                            q_block)
    return operand(attn.reshape(s, -1), wrong) @ lp["o_proj"]


def expert_weights(m, small, d, wrong=None):
    """[S, held] float32: the weight each HELD expert's output is summed
    under for each token: the uncut layer's weight where the token chose
    the expert, 0 where it did not. The selection bias steers the
    choice and never the weight."""
    logits = (m @ small["router"]).astype(F32)
    scores = (jax.nn.softmax(logits, axis=-1) if wrong == "softmax_router"
              else jax.nn.sigmoid(logits))
    _, choice = jax.lax.top_k(scores + small["expert_bias"], d["top_k"])
    kept = jnp.take_along_axis(scores, choice, axis=-1)
    if d["renormalise"]:
        kept = kept / (jnp.sum(kept, axis=-1, keepdims=True) + 1e-20)
    if wrong != "no_route_scale":
        kept = kept * d["route_scale"]
    member = (choice[:, :, None] == jnp.arange(d["routed"])[None, None, :])
    every = jnp.sum(member * kept[:, :, None], axis=1)       # [S, routed]
    return every[:, d["first"]:d["first"] + d["held"]]


def moe_part(m, small, experts, place, d, expert_chunk, wrong=None):
    """The sparse MLP of the normed ``m`` [S, hidden]. ``small``: this
    layer's router, bias and shared expert, float32; ``experts``: the
    expert stacks of ALL sparse layers as served, ``[sparse layers,
    held, ...]``, of which layer ``place``'s are read ``expert_chunk``
    at a time."""
    weights = expert_weights(m, small, d, wrong)
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
    if not d["shared"]:
        return routed
    return routed + swiglu(m, small["shared_gate_proj"],
                           small["shared_up_proj"],
                           small["shared_down_proj"], wrong)


def final_hidden(params, tokens, positions, d, q_block=512,
                 expert_chunk=8, wrong=None):
    """tokens [S] -> final-normed hidden states [S, hidden], float32."""
    if wrong is not None and wrong not in WRONG:
        raise ValueError(f"unknown wrong variant {wrong!r}")
    x = params["embed_tokens"][tokens].astype(F32)
    layers = params["layers"]
    experts = {name: layers["moe"][name] for name in _EXPERT_KEYS}
    eps, kinds = d["eps"], d["kinds"]

    def of(stack, index, skip=()):
        # widened one layer at a time
        return {name: a[index].astype(F32)
                for name, a in stack.items() if name not in skip}

    for layer, kind in enumerate(kinds):
        norms = of(layers["block"], layer)
        place = kinds[:layer].count(kind)
        u = operand(rms_norm(x, norms["input_layernorm"], eps), wrong)
        if kind == KDA:
            x = x + kda_part(u, of(layers["kda"], place), d, wrong)
        else:
            x = x + latent_part(u, of(layers["mla"], place), positions, d,
                                q_block, wrong)
        m = operand(rms_norm(x, norms["post_attention_layernorm"], eps),
                    wrong)
        if layer < d["dense"]:
            mlp = of(layers["dense"], layer)
            x = x + swiglu(m, mlp["gate_proj"], mlp["up_proj"],
                           mlp["down_proj"], wrong)
        else:
            at = layer - d["dense"]
            x = x + moe_part(m, of(layers["moe"], at, _EXPERT_KEYS),
                             experts, at, d, expert_chunk, wrong)
    return operand(rms_norm(x, params["norm"].astype(F32), eps),
                   None if wrong == "fp8_layers" else wrong)


def loss(params, tokens, targets, positions, d, *, q_block=512,
         loss_chunk=1024, expert_chunk=8, wrong=None):
    """Mean next-token cross entropy of one sequence."""
    hidden = final_hidden(params, tokens, positions, d, q_block,
                          expert_chunk, wrong)
    return _chunked_nll(hidden, head_weight(params, d), targets, loss_chunk)


def logits_at(params, tokens, rows, d, *, q_block=512, expert_chunk=8,
              wrong=None):
    """tokens [S], rows [R] -> logits [R, vocab] of a full forward pass
    at those rows, float32."""
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    hidden = final_hidden(params, tokens, positions, d, q_block,
                          expert_chunk, wrong)
    return hidden[rows] @ head_weight(params, d)


def make_loss_fn(config, *, q_block=512, loss_chunk=1024, expert_chunk=8,
                 wrong=None, with_gradients=False):
    """A jitted ``(params, tokens [S], targets [S], positions [S]) ->
    loss`` or ``-> (loss, global gradient norm, gradients of the norm
    gains)``, at ``highest`` matmul precision. The gradient is
    ``jax.grad`` of the whole tree at once: right for the sizes a test
    has (the family is served, not trained)."""
    d = kimi_dims(config)
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


def make_logits_fn(config, *, q_block=512, expert_chunk=8,
                   wrong: Optional[str] = None):
    """A jitted ``(params, tokens [B, S], rows [B, R]) -> logits
    [B, R, vocab]``: the full forward pass of each sequence alone."""
    d = kimi_dims(config)

    def batch_logits(params, tokens, rows):
        with jax.default_matmul_precision("highest"):
            one = functools.partial(
                logits_at, params, d=d, q_block=q_block,
                expert_chunk=expert_chunk, wrong=wrong)
            return jax.lax.map(lambda tr: one(tr[0], tr[1]), (tokens, rows))

    return jax.jit(batch_logits)
