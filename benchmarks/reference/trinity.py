"""Plain float32 reference of the afmoe (Arcee Trinity) decoder.

The yardstick that decides ``correct`` for an ``afmoe`` configuration:
straightforward ``jax.numpy``, float32 throughout, every matmul under
``jax.default_matmul_precision("highest")``, no cache, no ring, no
sort, no kernels. With the plain gain ``N(x; w) = x / sqrt(mean(x^2) +
eps) * w`` and ``d`` the hidden size:

    h0 = E[token] * sqrt(d)                               (mup_enabled)
    h <- h + N(Attn_l(N(h; w_in)); w_post_attn)
    h <- h + N(MLP_l(N(h; w_pre_mlp)); w_post_mlp)
    logits = N(h_L; w_f) W_head                           (untied)

*Attention*, ``a`` the normed input: ``q = a Wq`` (heads x D), ``k = a
Wk``, ``v = a Wv`` (kv heads x D), ``g = a Wg`` (heads x D, a
``gate_proj`` of its own); ``q <- N(q; wq)``, ``k <- N(k; wk)`` over
each head's D. ``layer_types[l] == sliding_attention``: rotary embedding
(rotate-half, ``rope_theta``, the whole head) at absolute positions and
key j visible to query i iff ``0 <= i - j < sliding_window``;
``full_attention``: NO rotary embedding, j <= i. Scale ``D^-0.5``, a K/V
head shared by ``heads / kv_heads`` query heads; ``y =
(concat_heads(softmax(.) v) * sigmoid(g)) Wo``.

*MLP*, ``m`` the normed input: ``l < num_dense_layers``: SwiGLU at
``intermediate_size``. Else ``s = sigmoid(m Wr)`` in float32 over all
``num_routed_experts``; the ``num_experts_per_tok`` chosen are the top
of ``s + b`` (``expert_bias``); their weights are ``s_e`` WITHOUT b,
divided by ``(sum + 1e-20)`` (``route_norm``), times ``route_scale``;
``f = sum_e w_e Expert_e(m) + Shared(m)``, each a SwiGLU at
``moe_intermediate_size``, the shared one ungated; dropless, no group
limit. **A share**: the file's ``num_experts`` counts the experts held
(ids ``[first_expert_id, first_expert_id + num_experts)`` of the
``num_routed_experts`` the router chooses from); the routed sum is then
over the held experts only, each under the weight the uncut layer gives
it, and that partial result goes on to the next layer. The expert sum
is in its plainest form: every held expert on every token under a 0 /
weight matrix, ``expert_chunk`` experts at a time, each widened to
float32 as it is used.

It imports nothing from ``scaletorch_tpu``; the plain norm, RoPE and
the chunked loss are the ones ``reference/qwen3.py`` has. What it
shares with the system is the layout of the parameter tree it is
handed: ``layers.block.*`` (attention and the four norms) stacked
``[layers, ...]``, ``layers.dense.*`` ``[num_dense_layers, ...]``,
``layers.moe.*`` ``[layers - num_dense_layers, ...]``, ``x @ W``
orientation.

Departures from the published description, none of them mathematics:
attention in query blocks of ``q_block``; the layer stack ONE
``lax.scan`` over all layers whose body picks the layer's kind by data
(the window is ``sliding_window`` or longer than the sequence, the
rotated q/k or the plain ones are selected, a ``lax.cond`` runs the
dense or the sparse MLP), weights widened to float32 a layer (an expert
chunk) at a time; the cross entropy only in ``make_loss_fn``
(``load_balance_coeff`` is training's and is not built).

``wrong`` selects a deliberately wrong variant, there only to show that
the tolerance rejects it: ``"window_ignored"`` lets a window layer see
every j <= i; ``"rope_on_full_layers"`` rotates q and k in the full
layers too; ``"no_output_gate"`` skips ``sigmoid(g)``;
``"bias_in_weights"`` weights a chosen expert by ``s + b``;
``"topk_not_renormalised"`` skips the division; ``"no_route_scale"`` the
factor; ``"softmax_router"`` scores by a softmax over the routed experts
in place of the sigmoid; ``"shared_expert_gated"`` multiplies the shared
expert by ``sigmoid(m w)`` with ``w`` the router's first column (the
family has no such weight: any fixed one shows what a gate would do);
``"no_post_norms"`` adds both sub-blocks' outputs un-normed;
``"no_embed_scale"`` drops ``sqrt(d)``; ``"fp8_activations"`` rounds
the activation operand of every matmul (the normed input of every
sub-block and of the head, what ``o_proj`` and the down projections
read) to 3 bits of mantissa, float8 e4m3's: the nearest precision below
the bfloat16 such a configuration is served in. The exponent keeps
bfloat16's range, so nothing underflows: the precision alone is
lowered. Weights and accumulation stay float32.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from benchmarks.lib.costs import dims
from benchmarks.reference.qwen3 import (
    _chunked_nll,
    _sum_squares,
    head_weight,
    rms_norm,
    rope,
)

F32 = jnp.float32
SLIDING, FULL = "sliding_attention", "full_attention"

GAIN_KEYS = ("input_layernorm", "post_attention_layernorm",
             "pre_mlp_layernorm", "post_mlp_layernorm", "q_norm", "k_norm")
_EXPERT_KEYS = ("expert_gate_proj", "expert_up_proj", "expert_down_proj")
WRONG = ("window_ignored", "rope_on_full_layers", "no_output_gate",
         "bias_in_weights", "topk_not_renormalised", "no_route_scale",
         "softmax_router", "shared_expert_gated", "no_post_norms",
         "no_embed_scale", "fp8_activations")


def trinity_dims(config):
    d = dims(config)
    every = int(config.get("global_attn_every_n_layers", 4))
    kinds = tuple(config.get("layer_types") or (
        FULL if (i + 1) % every == 0 else SLIDING
        for i in range(d["layers"])))
    if len(kinds) != d["layers"] or set(kinds) - {SLIDING, FULL}:
        raise ValueError(f"layer_types {kinds} for {d['layers']} layers")
    if config.get("rope_scaling") is not None:
        raise ValueError("rope_scaling is not null")
    if int(config.get("n_group", 1)) != 1 or \
            int(config.get("topk_group", 1)) != 1:
        raise ValueError("a group-limited choice of experts is not built")
    if config.get("score_func", "sigmoid") != "sigmoid":
        raise ValueError(f"score_func {config['score_func']!r}")
    held = int(config["num_experts"])
    d.update(
        sliding=tuple(kind == SLIDING for kind in kinds),
        window=int(config["sliding_window"]),
        dense=int(config["num_dense_layers"]),
        embed_scale=(d["hidden"] ** 0.5
                     if config.get("mup_enabled", True) else 1.0),
        held=held,
        routed=int(config.get("num_routed_experts") or held),
        first=int(config.get("first_expert_id", 0)),
        top_k=int(config["num_experts_per_tok"]),
        shared=int(config.get("num_shared_experts", 1)),
        renormalise=bool(config.get("route_norm", True)),
        route_scale=float(config.get("route_scale", 1.0)))
    return d


def operand(x, wrong=None):
    """The activation operand of a matmul: as it is, or at 3 bits of
    mantissa (``reduce_precision``, not a pair of converts: XLA may drop
    such a pair, excess precision being allowed by default)."""
    if wrong == "fp8_activations":
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=3)
    return x


def banded_attention(q, k, v, positions, window, q_block):
    """q [S, Hkv, G, D], k/v [S, Hkv, D], positions [S]; key j is
    visible to query i iff ``0 <= pos_i - pos_j < window`` (``window``
    an int32 scalar: longer than the sequence for a full layer).
    Softmax attention in query blocks of ``q_block``."""
    s = q.shape[0]
    scale = q.shape[-1] ** -0.5
    block = min(q_block, s)
    if s % block:
        raise ValueError(f"sequence {s} is not a multiple of block {block}")

    def one_block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * block, block, 0)
        pb = jax.lax.dynamic_slice_in_dim(positions, i * block, block, 0)
        scores = jnp.einsum("qhgd,khd->hgqk", qb, k) * scale
        gap = pb[:, None] - positions[None, :]               # [q, k]
        visible = (gap >= 0) & (gap < window)
        scores = jnp.where(visible[None, None], scores.astype(F32), -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", probs, v).astype(F32)

    out = jax.lax.map(one_block, jnp.arange(s // block))
    return out.reshape((s,) + q.shape[1:])


def attention_part(a, lp, sliding, positions, d, q_block, wrong=None):
    """The mixer of the normed ``a`` [S, hidden] before its output
    norm; ``sliding`` a traced bool: the layer's kind."""
    s = a.shape[0]
    hkv, g, hd = d["kv_heads"], d["heads"] // d["kv_heads"], d["head_dim"]
    q = rms_norm((a @ lp["q_proj"]).reshape(s, hkv * g, hd), lp["q_norm"],
                 d["eps"])
    k = rms_norm((a @ lp["k_proj"]).reshape(s, hkv, hd), lp["k_norm"],
                 d["eps"])
    v = (a @ lp["v_proj"]).reshape(s, hkv, hd)
    gate = a @ lp["gate_proj"]
    turned = sliding | (wrong == "rope_on_full_layers")
    q = jnp.where(turned, rope(q, positions, d["theta"]), q)
    k = jnp.where(turned, rope(k, positions, d["theta"]), k)
    banded = sliding & (wrong != "window_ignored")
    window = jnp.where(banded, d["window"], s + 1).astype(jnp.int32)
    attn = banded_attention(q.reshape(s, hkv, g, hd), k, v, positions,
                            window, q_block).reshape(s, hkv * g * hd)
    if wrong != "no_output_gate":
        attn = attn * jax.nn.sigmoid(gate)
    return operand(attn, wrong) @ lp["o_proj"]


def expert_weights(m, small, d, wrong=None):
    """[S, held] float32: the weight each HELD expert's output is summed
    under for each token: the uncut layer's weight where the token chose
    the expert, 0 where it did not."""
    logits = (m @ small["router"]).astype(F32)
    scores = (jax.nn.softmax(logits, axis=-1) if wrong == "softmax_router"
              else jax.nn.sigmoid(logits))
    biased = scores + small["expert_bias"]
    _, choice = jax.lax.top_k(biased, d["top_k"])
    kept = jnp.take_along_axis(
        biased if wrong == "bias_in_weights" else scores, choice, axis=-1)
    if d["renormalise"] and wrong != "topk_not_renormalised":
        kept = kept / (jnp.sum(kept, axis=-1, keepdims=True) + 1e-20)
    if wrong != "no_route_scale":
        kept = kept * d["route_scale"]
    member = (choice[:, :, None] == jnp.arange(d["routed"])[None, None, :])
    every = jnp.sum(member * kept[:, :, None], axis=1)       # [S, routed]
    return every[:, d["first"]:d["first"] + d["held"]]


def swiglu(x, gate, up, down, wrong=None):
    return operand(jax.nn.silu(x @ gate) * (x @ up), wrong) @ down


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
    shared = swiglu(m, small["shared_gate_proj"], small["shared_up_proj"],
                    small["shared_down_proj"], wrong)
    if wrong == "shared_expert_gated":
        shared = shared * jax.nn.sigmoid(m @ small["router"][:, :1])
    return routed + shared


def final_hidden(params, tokens, positions, d, q_block=512,
                 expert_chunk=16, wrong=None):
    """tokens [S] -> final-normed hidden states [S, hidden], float32."""
    if wrong is not None and wrong not in WRONG:
        raise ValueError(f"unknown wrong variant {wrong!r}")
    x = params["embed_tokens"][tokens].astype(F32)
    if wrong != "no_embed_scale":
        x = x * d["embed_scale"]
    layers = params["layers"]
    experts = {name: layers["moe"][name] for name in _EXPERT_KEYS}
    n_dense, n_sparse = d["dense"], d["layers"] - d["dense"]
    eps = d["eps"]

    def post(y, w):
        return y if wrong == "no_post_norms" else rms_norm(y, w, eps)

    def of(stack, index, skip=()):
        # widened one layer at a time
        return {name: jax.lax.dynamic_index_in_dim(
            a, index, 0, keepdims=False).astype(F32)
            for name, a in stack.items() if name not in skip}

    def dense_mlp(m, layer):
        lp = of(layers["dense"], jnp.clip(layer, 0, max(n_dense - 1, 0)))
        return swiglu(m, lp["gate_proj"], lp["up_proj"], lp["down_proj"],
                      wrong)

    def sparse_mlp(m, layer):
        place = jnp.clip(layer - n_dense, 0, n_sparse - 1)
        return moe_part(m, of(layers["moe"], place, _EXPERT_KEYS), experts,
                        place, d, expert_chunk, wrong)

    def one_layer(h, xs):
        layer, sliding = xs
        lp = of(layers["block"], layer)
        a = operand(rms_norm(h, lp["input_layernorm"], eps), wrong)
        h = h + post(
            attention_part(a, lp, sliding, positions, d, q_block, wrong),
            lp["post_attention_layernorm"])
        m = operand(rms_norm(h, lp["pre_mlp_layernorm"], eps), wrong)
        f = (jax.lax.cond(layer < n_dense, dense_mlp, sparse_mlp, m, layer)
             if n_dense else sparse_mlp(m, layer))
        return h + post(f, lp["post_mlp_layernorm"]), None

    x, _ = jax.lax.scan(
        jax.checkpoint(one_layer), x,
        (jnp.arange(d["layers"], dtype=jnp.int32),
         jnp.asarray(d["sliding"], bool)))
    return operand(rms_norm(x, params["norm"].astype(F32), eps), wrong)


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
    d = trinity_dims(config)
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


def make_logits_fn(config, *, q_block=512, expert_chunk=16,
                   wrong: Optional[str] = None):
    """A jitted ``(params, tokens [B, S], rows [B, R]) -> logits
    [B, R, vocab]``: the full forward pass of each sequence alone."""
    d = trinity_dims(config)

    def batch_logits(params, tokens, rows):
        with jax.default_matmul_precision("highest"):
            one = functools.partial(
                logits_at, params, d=d, q_block=q_block,
                expert_chunk=expert_chunk, wrong=wrong)
            return jax.lax.map(lambda tr: one(tr[0], tr[1]), (tokens, rows))

    return jax.jit(batch_logits)
