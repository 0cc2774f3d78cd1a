"""Plain float32 reference of the Qwen3-Next decoder (forward, loss).

The yardstick that decides ``correct`` for a ``qwen3_next``
configuration: straightforward ``jax.numpy``, float32 throughout, every
matmul under ``jax.default_matmul_precision("highest")``, no cache, no
chunked scan, no sort, no kernels. With ``N(x; w) = x / sqrt(mean(x^2)
+ eps) * (1 + w)`` (the zero-centred gain) layer ``i`` is
``full_attention`` when ``(i + 1) % full_attention_interval == 0`` and
``linear_attention`` otherwise, and every layer is

    x <- x + Mix(N(x; w1))        x <- x + MoE(N(x; w2))

with ``N(x; wf)`` and an untied head after the last.

*Full attention:* ``q_proj`` gives each of the heads 2 D numbers, a
query ``q`` and a gate ``g``; ``k_proj``, ``v_proj`` give the K/V
heads. ``q <- N(q; wq)``, ``k <- N(k; wk)`` over each head's D; rotary
embedding (rotate-half) on the first ``partial_rotary_factor D`` dims
of each head, the others pass through; causal softmax attention at
scale ``D^-0.5``, a K/V head shared by ``heads / kv_heads`` query heads;
``out = o_proj(concat_heads(attn) * sigmoid(concat_heads(g)))``.

*Gated delta rule* (``Hk`` key heads, ``Hv`` value heads): project
``q, k`` (Hk x d_k), ``v, z`` (Hv x d_v), ``b, a`` (Hv); a depthwise
causal convolution of width 4 without bias, then SiLU, over the channels
of ``(q, k, v)``; ``q, k <- l2norm`` per head (1e-6 under the root),
each key head repeated over ``Hv / Hk`` consecutive value heads,
``q <- q d_k^-0.5``; ``beta = sigmoid(b)``, ``log alpha = -exp(A_log)
softplus(a + dt_bias)``; per value head and token, from ``S = 0``:

    S <- exp(log alpha) S;   u = beta (v - S^T k);   S <- S + k u^T;   o = S^T q

``out = out_proj(concat_heads(rmsnorm(o; w_g) * silu(z)))`` with a
plain gain ``w_g`` over each head's ``d_v``.

*Sparse MLP:* router ``x W_r`` over all ``num_routed_experts`` in
float32, softmax, top k, the k weights divided by their sum; expert
``e``: ``down_e(silu(gate_e x) * up_e x)``; the shared expert the same
SwiGLU times ``sigmoid(x w_s)``; result = routed sum + gated shared
expert. **A share**: the file's ``num_experts`` counts the experts held
(ids ``[first_expert_id, first_expert_id + num_experts)`` of the
``num_routed_experts`` the router chooses from); the routed sum is then
over the held experts only, each under the weight the uncut layer gives
it, and that partial result goes on to the next layer. The expert sum
is in its plainest form: every held expert on every token under a 0 /
weight matrix, ``expert_chunk`` experts at a time, each widened to
float32 as it is used.

It imports nothing from ``scaletorch_tpu``; the attention core, the
plain norm, RoPE and the chunked loss are the ones ``reference/
qwen3.py`` has, ``l2norm`` and the short convolution the ones
``reference/olmo_hybrid.py`` has. What it shares with the system is
the layout of the parameter tree it is handed: ``layers.linear.*`` /
``layers.full.*``
stacked ``[periods, layers of the kind in a period, ...]``,
``layers.moe.*`` stacked ``[layers, ...]``, ``x @ W`` orientation,
``q_proj`` columns ``[head][query | gate]``, ``conv [4, channels]`` with
channels ``q~ | k~ | v~`` side by side and ``conv[3]`` the weight of the
current row.

Departures from the published description, none of them mathematics:
attention in query blocks; the layer stack a ``lax.scan`` over periods,
weights widened to float32 a layer (an expert chunk) at a time; the
cross-entropy only in ``make_loss_fn`` (no auxiliary loss). Not built:
the multi-token-prediction module.

``wrong`` selects a deliberately wrong variant, there only to show that
the tolerance rejects it: ``"no_output_gate"`` skips the attention
gate; ``"rope_on_whole_head"`` rotates all D dims; ``"plain_norm_gain"``
uses ``w`` for ``1 + w``; ``"no_shared_expert_gate"`` adds the shared
expert ungated; ``"topk_not_renormalised"`` keeps the k weights as the
softmax gave them; ``"key_heads_not_repeated"`` lets value head ``j``
read key head ``j mod Hk``; ``"bf16_router"`` computes the router's
logits from bfloat16 inputs with bfloat16 accumulation;
``"fp8_activations"`` rounds the activation operand of every matmul
(the normed input of every mixer, sparse MLP and the head, what
``o_proj`` and the experts' and the shared expert's down projections
read) to 3 bits of
mantissa, float8 e4m3's: the nearest precision below the bfloat16 such
a configuration is served in. The exponent keeps bfloat16's range, so
nothing underflows (a deployment in float8 scales its tensors into
range): the precision alone is lowered. Weights and accumulation stay
float32.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from benchmarks.lib.costs import dims
from benchmarks.reference.olmo_hybrid import l2norm, short_conv
from benchmarks.reference.qwen3 import (
    _chunked_nll,
    _sum_squares,
    causal_attention,
    head_weight,
    rms_norm,
    rope,
)

F32 = jnp.float32
LINEAR, FULL = "linear_attention", "full_attention"

GAIN_KEYS = ("input_layernorm", "post_attention_layernorm", "q_norm",
             "k_norm", "o_norm")
_EXPERT_KEYS = ("expert_gate_proj", "expert_up_proj", "expert_down_proj")


def next_dims(config):
    d = dims(config)
    interval = int(config["full_attention_interval"])
    kinds = tuple(config.get("layer_types") or (
        FULL if (i + 1) % interval == 0 else LINEAR
        for i in range(d["layers"])))
    period = next(p for p in range(1, len(kinds) + 1)
                  if len(kinds) % p == 0
                  and kinds == kinds[:p] * (len(kinds) // p))
    held = int(config["num_experts"])
    d.update(
        pattern=kinds[:period],
        rotary=int(d["head_dim"] * float(config["partial_rotary_factor"])),
        key_heads=int(config["linear_num_key_heads"]),
        lin_heads=int(config["linear_num_value_heads"]),
        d_k=int(config["linear_key_head_dim"]),
        d_v=int(config["linear_value_head_dim"]),
        held=held,
        routed=int(config.get("num_routed_experts") or held),
        first=int(config.get("first_expert_id", 0)),
        top_k=int(config["num_experts_per_tok"]),
        renormalise=bool(config.get("norm_topk_prob", True)))
    return d


def norm(x, w, eps, wrong=None):
    """``N(x; w)``: the zero-centred gain."""
    return rms_norm(x, w if wrong == "plain_norm_gain" else 1.0 + w, eps)


def operand(x, wrong=None):
    """The activation operand of a matmul: as it is, or at 3 bits of
    mantissa (``reduce_precision``, not a pair of converts: XLA may drop
    such a pair, excess precision being allowed by default)."""
    if wrong == "fp8_activations":
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=3)
    return x


def delta_rule(q, k, v, log_alpha, beta):
    """q, k [S, H, d_k], v [S, H, d_v], log_alpha, beta [S, H] -> o
    [S, H, d_v]: the recurrence of the head comment, one row after
    another, from S = 0."""
    def step(state, row):
        q_t, k_t, v_t, a_t, b_t = row
        state = jnp.exp(a_t)[:, None, None] * state
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", state, k_t))
        state = state + jnp.einsum("hk,hv->hkv", k_t, u)
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    state0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), F32)
    _, o = jax.lax.scan(step, state0, (q, k, v, log_alpha, beta))
    return o


def linear_part(x, lp, d, wrong=None):
    """The gated delta-rule mixer of the normed ``x`` [S, hidden]."""
    s = x.shape[0]
    hk, hv, dk, dv = d["key_heads"], d["lin_heads"], d["d_k"], d["d_v"]
    qkv = jnp.concatenate(
        [x @ lp["q_proj"], x @ lp["k_proj"], x @ lp["v_proj"]], axis=-1)
    qkv = jax.nn.silu(short_conv(qkv, lp["conv"]))
    q = l2norm(qkv[:, :hk * dk].reshape(s, hk, dk)) / dk ** 0.5
    k = l2norm(qkv[:, hk * dk:2 * hk * dk].reshape(s, hk, dk))
    if wrong == "key_heads_not_repeated":
        q, k = (jnp.tile(a, (1, hv // hk, 1)) for a in (q, k))
    else:
        q, k = (jnp.repeat(a, hv // hk, axis=1) for a in (q, k))
    v = qkv[:, 2 * hk * dk:].reshape(s, hv, dv)
    beta = jax.nn.sigmoid(x @ lp["b_proj"])
    log_alpha = -jnp.exp(lp["A_log"]) * jax.nn.softplus(
        x @ lp["a_proj"] + lp["dt_bias"])
    o = delta_rule(q, k, v, log_alpha, beta)
    z = (x @ lp["g_proj"]).reshape(s, hv, dv)
    y = rms_norm(o, lp["o_norm"], d["eps"]) * jax.nn.silu(z)
    return operand(y.reshape(s, hv * dv), wrong) @ lp["o_proj"]


def partial_rope(x, positions, d, wrong=None):
    """x [S, H, D]: the first ``rotary`` dims of each head rotated."""
    if wrong == "rope_on_whole_head":
        return rope(x, positions, d["theta"])
    r = d["rotary"]
    return jnp.concatenate(
        [rope(x[..., :r], positions, d["theta"]), x[..., r:]], axis=-1)


def full_part(x, lp, positions, d, q_block, wrong=None):
    """Gated softmax attention of the normed ``x`` [S, hidden]."""
    s = x.shape[0]
    hkv, g, hd = d["kv_heads"], d["heads"] // d["kv_heads"], d["head_dim"]
    qg = (x @ lp["q_proj"]).reshape(s, hkv * g, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = (x @ lp["k_proj"]).reshape(s, hkv, hd)
    v = (x @ lp["v_proj"]).reshape(s, hkv, hd)
    q = partial_rope(norm(q, lp["q_norm"], d["eps"], wrong), positions, d,
                     wrong)
    k = partial_rope(norm(k, lp["k_norm"], d["eps"], wrong), positions, d,
                     wrong)
    attn = causal_attention(q.reshape(s, hkv, g, hd), k, v, positions,
                            q_block).reshape(s, hkv * g * hd)
    if wrong != "no_output_gate":
        attn = attn * jax.nn.sigmoid(gate.reshape(s, hkv * g * hd))
    return operand(attn, wrong) @ lp["o_proj"]


def expert_weights(h, router, d, wrong=None):
    """[S, held] float32: the weight each HELD expert's output is summed
    under for each token: the uncut layer's weight where the token chose
    the expert, 0 where it did not."""
    if wrong == "bf16_router":
        # bfloat16 inputs, the product rounded to bfloat16
        # (reduce_precision: XLA may drop a pair of converts)
        def bf(a):
            return jax.lax.reduce_precision(a, exponent_bits=8,
                                            mantissa_bits=7)

        logits = bf(bf(h) @ bf(router))
    else:
        logits = h @ router
    probs = jax.nn.softmax(logits.astype(F32), axis=-1)
    top_p, choice = jax.lax.top_k(probs, d["top_k"])
    if d["renormalise"] and wrong != "topk_not_renormalised":
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    member = (choice[:, :, None] == jnp.arange(d["routed"])[None, None, :])
    every = jnp.sum(member * top_p[:, :, None], axis=1)      # [S, routed]
    return every[:, d["first"]:d["first"] + d["held"]]


def moe_part(x, small, experts, layer, d, expert_chunk, wrong=None):
    """The sparse MLP of the normed ``x`` [S, hidden]. ``small``: this
    layer's router, shared expert and gate, float32; ``experts``: the
    expert stacks of ALL layers as served, ``[layers, held, ...]``, of
    which ``layer``'s are read ``expert_chunk`` at a time."""
    weights = expert_weights(x, small["router"], d, wrong)
    chunk = min(expert_chunk, d["held"])
    if d["held"] % chunk:
        raise ValueError(f"{d['held']} experts in chunks of {chunk}")

    def some_experts(c):
        def of(name):
            a = experts[name]
            return jax.lax.dynamic_slice(
                a, (layer, c * chunk, 0, 0), (1, chunk) + a.shape[2:]
            )[0].astype(F32)

        mid = jax.nn.silu(jnp.einsum("sh,ehi->esi", x, of(_EXPERT_KEYS[0]))) \
            * jnp.einsum("sh,ehi->esi", x, of(_EXPERT_KEYS[1]))
        out = jnp.einsum("esi,eih->esh", operand(mid, wrong),
                         of(_EXPERT_KEYS[2]))
        w = jax.lax.dynamic_slice_in_dim(weights, c * chunk, chunk, axis=1)
        return jnp.einsum("esh,se->sh", out, w)

    routed = jnp.sum(jax.lax.map(
        some_experts, jnp.arange(d["held"] // chunk)), axis=0)
    shared = operand(
        jax.nn.silu(x @ small["shared_gate_proj"])
        * (x @ small["shared_up_proj"]), wrong) @ small["shared_down_proj"]
    if wrong != "no_shared_expert_gate":
        shared = shared * jax.nn.sigmoid(x @ small["shared_expert_gate"])
    return routed + shared


def final_hidden(params, tokens, positions, d, q_block=512,
                 expert_chunk=16, wrong=None):
    """tokens [S] -> final-normed hidden states [S, hidden], float32."""
    x = params["embed_tokens"][tokens].astype(F32)
    layers = params["layers"]
    experts = {name: layers["moe"][name] for name in _EXPERT_KEYS}
    pattern = d["pattern"]

    @jax.checkpoint
    def period(h, p):
        taken = {LINEAR: 0, FULL: 0}
        for place, kind in enumerate(pattern):
            stack = layers["linear" if kind == LINEAR else "full"]
            # widened one layer at a time
            lp = {name: a[p, taken[kind]].astype(F32)
                  for name, a in stack.items()}
            taken[kind] += 1
            layer = p * len(pattern) + place
            small = {name: a[layer].astype(F32)
                     for name, a in layers["moe"].items()
                     if name not in _EXPERT_KEYS}
            u = operand(norm(h, lp["input_layernorm"], d["eps"], wrong),
                        wrong)
            h = h + (linear_part(u, lp, d, wrong) if kind == LINEAR
                     else full_part(u, lp, positions, d, q_block, wrong))
            u = operand(norm(h, small["post_attention_layernorm"], d["eps"],
                             wrong), wrong)
            h = h + moe_part(u, small, experts, layer, d, expert_chunk,
                             wrong)
        return h, None

    x, _ = jax.lax.scan(
        period, x, jnp.arange(d["layers"] // len(pattern), dtype=jnp.int32))
    return operand(norm(x, params["norm"].astype(F32), d["eps"], wrong),
                   wrong)


def loss(params, tokens, targets, positions, d, *, q_block=512,
         loss_chunk=1024, expert_chunk=16, wrong=None):
    """Mean next-token cross entropy of one sequence."""
    hidden = final_hidden(params, tokens, positions, d, q_block,
                          expert_chunk, wrong)
    return _chunked_nll(hidden, head_weight(params, d), targets, loss_chunk)


def logits_at(params, tokens, rows, d, *, q_block=512, expert_chunk=16,
              wrong=None):
    """tokens [S], rows [R] -> logits [R, vocab] of a full forward pass
    at those rows, float32."""
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    hidden = final_hidden(params, tokens, positions, d, q_block,
                          expert_chunk, wrong)
    return hidden[rows] @ head_weight(params, d)


def make_loss_fn(config, *, q_block=512, loss_chunk=1024, expert_chunk=16,
                 wrong=None, with_gradients=False):
    """A jitted ``(params, tokens [S], targets [S], positions [S]) ->
    loss`` or ``-> (loss, global gradient norm, gradients of the norm
    gains)``, at ``highest`` matmul precision. The gradient is
    ``jax.grad`` of the whole tree at once: right for the sizes a test
    has (the family is served, not trained)."""
    d = next_dims(config)
    fn = functools.partial(loss, d=d, q_block=q_block, loss_chunk=loss_chunk,
                           expert_chunk=expert_chunk, wrong=wrong)

    def loss_only(params, tokens, targets, positions):
        with jax.default_matmul_precision("highest"):
            return fn(params, tokens, targets, positions)

    def both(params, tokens, targets, positions):
        with jax.default_matmul_precision("highest"):
            value, grads = jax.value_and_grad(fn)(
                params, tokens, targets, positions)
        gains = {kind: {k: g.astype(F32) for k, g in stack.items()
                        if k in GAIN_KEYS}
                 for kind, stack in grads["layers"].items()}
        return (value, jnp.sqrt(_sum_squares(grads)),
                {"layers": gains, "norm": grads["norm"].astype(F32)})

    return jax.jit(both if with_gradients else loss_only)


def make_logits_fn(config, *, q_block=512, expert_chunk=16,
                   wrong: Optional[str] = None):
    """A jitted ``(params, tokens [B, S], rows [B, R]) -> logits
    [B, R, vocab]``: the full forward pass of each sequence alone."""
    d = next_dims(config)

    def batch_logits(params, tokens, rows):
        with jax.default_matmul_precision("highest"):
            one = functools.partial(
                logits_at, params, d=d, q_block=q_block,
                expert_chunk=expert_chunk, wrong=wrong)
            return jax.lax.map(lambda tr: one(tr[0], tr[1]), (tokens, rows))

    return jax.jit(batch_logits)
