"""Plain float32 reference of the mimo_v2_flash (MiMo-V2-Flash) decoder.

The yardstick that decides ``correct`` for a ``mimo_v2_flash``
configuration: straightforward ``jax.numpy``, float32 throughout, every
matmul under ``jax.default_matmul_precision("highest")``, a full softmax
with the sink as one more column, the band as a mask, no cache, no
ring, no padding of a key, no sort, no kernels. With the plain gain
``N(x; g) = x / sqrt(mean(x^2) + layernorm_epsilon) * g``:

    h0 = E[token]                                          (not scaled)
    h <- h + Mix_l(N(h; g_in));  h <- h + MLP_l(N(h; g_post))
    logits = N(h_L; g_f) W_head                            (untied)

*Mixer* of a layer of kind c (``hybrid_layer_pattern[l]``: 0 full, 1
window), ``x`` the normed input: ``q = x Wq`` (heads x ``head_dim``),
``k = x Wk`` (Hkv_c x ``head_dim``), ``v = attention_value_scale * (x
Wv)`` (Hkv_c x ``v_head_dim``); Hkv = ``num_key_value_heads`` on a full
layer and ``swa_num_key_value_heads`` on a window layer; no q/k norm.
Rotary embedding (rotate-half, absolute positions) at base
``rope_theta`` (full) or ``swa_rope_theta`` (window) on the first
``int(head_dim * partial_rotary_factor)`` dims of each q and k head,
the others pass. A K/V head is shared by ``heads / Hkv`` query heads.
``s_h(i, j) = q_h(i) . k_g(j) * head_dim^-0.5`` over the visible j:
every j <= i on a full layer, ``0 <= i - j < sliding_window`` on a
window layer. A window layer's softmax has one more column, the sink
``b_h`` (``attention_sink_bias``, a float32 logit a query head), which
takes its share of the mass and carries no value; a full layer has
none. ``Mix = concat_h(sum_j p_h(i, j) v_g(j)) Wo``.

*MLP*, ``m`` the normed input: ``moe_layer_freq[l] == 0``: SwiGLU at
``intermediate_size``. Else ``s = sigmoid(m Wr)`` in float32 over all
``num_routed_experts``; the ``num_experts_per_tok`` chosen are the top
of ``s + b`` (``expert_bias``: the ``e_score_correction_bias`` of
``noaux_tc``); their weights are ``s_e`` WITHOUT b, divided by ``(sum +
1e-20)`` (``norm_topk_prob``), times ``routed_scaling_factor`` (null:
1); ``f = sum_e w_e Expert_e(m)``, each a SwiGLU at
``moe_intermediate_size``; dropless, no group limit, no shared expert.
**A share**: the file's ``n_routed_experts`` counts the experts held
(ids ``[first_expert_id, first_expert_id + n_routed_experts)`` of the
``num_routed_experts`` the router chooses from); the routed sum is then
over the held experts only, each under the weight the uncut layer gives
it, and that partial result goes on to the next layer. The expert sum
is in its plainest form: every held expert on every token under a 0 /
weight matrix, ``expert_chunk`` experts at a time, each widened to
float32 as it is used.

It imports nothing from ``scaletorch_tpu``; the plain norm and the
chunked loss are the ones ``reference/qwen3.py`` has. What it shares
with the system is the layout of the parameter tree it is handed:
``layers.block.*`` (the two norms, ``q_proj``, ``o_proj``) stacked
``[layers, ...]``, ``layers.full.*`` / ``layers.window.*`` (``k_proj``,
``v_proj``; the window layers' ``attention_sink_bias``) stacked over
the layers of their kind, ``layers.dense.*`` / ``layers.moe.*`` over
the dense / sparse layers, ``x @ W`` orientation.

Departures from the published description, none of them mathematics:
attention in query blocks of ``q_block``; the layers unrolled, weights
widened to float32 a layer (an expert chunk) at a time; the cross
entropy only in ``make_loss_fn`` (training is not built).

``wrong`` selects a deliberately wrong variant, there only to show that
the tolerance rejects it: ``"no_sink"`` leaves the sink's column out;
``"sink_on_full_layers"`` gives the full layers a sink too (the first
window layer's: the family has no such parameter, any fixed one shows
what a sink would do); ``"window_off_by_one"`` lets a window row see
``sliding_window - 1`` keys; ``"no_value_scale"`` drops
``attention_value_scale``; ``"one_rope_theta"`` turns every layer at
``rope_theta``; ``"rope_whole_head"`` turns all ``head_dim`` dims;
``"kv_heads_swapped"`` groups a full layer's query heads as a window
layer's are grouped and the reverse (head h reads K/V head ``(h //
(heads / Hkv of the other kind)) % Hkv``; the heads are gathered, a
test's size); ``"bias_in_weights"`` weights a chosen expert by ``s +
b``; ``"softmax_router"`` scores by a softmax over the routed experts;
``"fp8_activations"`` rounds the activation operand of every matmul
(the normed input of every sub-block and of the head, what ``o_proj``
and the down projections read) to 3 bits of mantissa, float8 e4m3's:
the nearest precision below the bfloat16 such a configuration is served
in (the exponent keeps bfloat16's range: the precision alone is
lowered; weights and accumulation stay float32); ``"fp8_layers"`` is
the same inside the layers with the head's input left alone.
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
)

F32 = jnp.float32
FULL, WINDOW = 0, 1

GAIN_KEYS = ("input_layernorm", "post_attention_layernorm")
_EXPERT_KEYS = ("expert_gate_proj", "expert_up_proj", "expert_down_proj")
WRONG = ("no_sink", "sink_on_full_layers", "window_off_by_one",
         "no_value_scale", "one_rope_theta", "rope_whole_head",
         "kv_heads_swapped", "bias_in_weights", "softmax_router",
         "fp8_activations", "fp8_layers")


def mimo_dims(config):
    d = dims(config)
    kinds = tuple(int(x) for x in config["hybrid_layer_pattern"])
    sparse = tuple(int(x) for x in config["moe_layer_freq"])
    if len(kinds) != d["layers"] or len(sparse) != d["layers"] or \
            set(kinds) | set(sparse) > {0, 1}:
        raise ValueError(f"hybrid_layer_pattern {kinds} / moe_layer_freq "
                         f"{sparse} for {d['layers']} layers")
    if int(config.get("n_group", 1)) != 1 or \
            int(config.get("topk_group", 1)) != 1:
        raise ValueError("a group-limited choice of experts is not built")
    if config.get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError(f"scoring_func {config['scoring_func']!r}")
    if config.get("n_shared_experts"):
        raise ValueError("a shared expert is not built")
    if config.get("add_full_attention_sink_bias"):
        raise ValueError("a sink on the full layers is not built")
    held = int(config["n_routed_experts"])
    scale = config.get("routed_scaling_factor")
    d.update(
        kinds=kinds, sparse=sparse,
        eps=float(config["layernorm_epsilon"]),
        v_dim=int(config["v_head_dim"]),
        kv_heads=(d["kv_heads"], int(config["swa_num_key_value_heads"])),
        thetas=(d["theta"], float(config["swa_rope_theta"])),
        rotary=int(d["head_dim"] * float(config["partial_rotary_factor"])),
        window=int(config["sliding_window"]),
        value_scale=float(config["attention_value_scale"]),
        sink=bool(config.get("add_swa_attention_sink_bias", True)),
        held=held,
        routed=int(config.get("num_routed_experts") or held),
        first=int(config.get("first_expert_id", 0)),
        top_k=int(config["num_experts_per_tok"]),
        renormalise=bool(config.get("norm_topk_prob", True)),
        route_scale=1.0 if scale is None else float(scale))
    return d


def operand(x, wrong=None):
    """The activation operand of a matmul: as it is, or at 3 bits of
    mantissa (``reduce_precision``, not a pair of converts: XLA may drop
    such a pair, excess precision being allowed by default)."""
    if wrong in ("fp8_activations", "fp8_layers"):
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=3)
    return x


def partial_rope(x, positions, theta, rotary):
    """x [S, H, D], positions [S]: the first ``rotary`` dims of each head
    turned (rotate-half among themselves, inverse frequencies over
    ``rotary``), the others as they are."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, rotary, 2, dtype=F32) / rotary))
    angles = positions.astype(F32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles), jnp.cos(angles)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angles), jnp.sin(angles)], -1)[:, None, :]
    turned, rest = x[..., :rotary], x[..., rotary:]
    x1, x2 = turned[..., : rotary // 2], turned[..., rotary // 2:]
    turned = turned * cos + jnp.concatenate([-x2, x1], -1) * sin
    return jnp.concatenate([turned, rest], -1)


def sink_attention(q, k, v, positions, window, sink, q_block):
    """q [S, H, D], k [S, H, D], v [S, H, Dv] (K/V already one a query
    head), positions [S]; key j is visible to query i iff ``0 <= pos_i -
    pos_j < window`` (None: every j <= i). ``sink`` [H] or None: one more
    column of each row's softmax, with no value. Softmax attention in
    query blocks of ``q_block``."""
    s = q.shape[0]
    scale = q.shape[-1] ** -0.5
    block = min(q_block, s)
    if s % block:
        raise ValueError(f"sequence {s} is not a multiple of block {block}")

    def one_block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * block, block, 0)
        pb = jax.lax.dynamic_slice_in_dim(positions, i * block, block, 0)
        scores = (jnp.einsum("qhd,khd->hqk", qb, k) * scale).astype(F32)
        gap = pb[:, None] - positions[None, :]               # [q, k]
        visible = gap >= 0
        if window is not None:
            visible &= gap < window
        scores = jnp.where(visible[None], scores, -jnp.inf)
        if sink is not None:
            column = jnp.broadcast_to(sink[:, None, None],
                                      scores.shape[:2] + (1,))
            scores = jnp.concatenate([scores, column], axis=-1)
        probs = jax.nn.softmax(scores, axis=-1)[..., :s]
        return jnp.einsum("hqk,khd->qhd", probs, v).astype(F32)

    out = jax.lax.map(one_block, jnp.arange(s // block))
    return out.reshape((s,) + out.shape[2:])


def attention_part(x, block, own, kind, other_sink, positions, d, q_block,
                   wrong=None):
    """The mixer of the normed ``x`` [S, hidden] of a layer of ``kind``;
    ``block``: its q_proj / o_proj, ``own``: its kind's k_proj / v_proj
    (and sink), float32. ``other_sink``: what ``sink_on_full_layers``
    gives a full layer."""
    s = x.shape[0]
    heads, dk, dv = d["heads"], d["head_dim"], d["v_dim"]
    hkv = d["kv_heads"][kind]
    q = (x @ block["q_proj"]).reshape(s, heads, dk)
    k = (x @ own["k_proj"]).reshape(s, hkv, dk)
    v = (x @ own["v_proj"]).reshape(s, hkv, dv)
    if wrong != "no_value_scale":
        v = v * d["value_scale"]
    theta = d["thetas"][FULL if wrong == "one_rope_theta" else kind]
    rotary = dk if wrong == "rope_whole_head" else d["rotary"]
    q = partial_rope(q, positions, theta, rotary)
    k = partial_rope(k, positions, theta, rotary)
    group = heads // hkv
    if wrong == "kv_heads_swapped":
        group = heads // d["kv_heads"][1 - kind]
    head_of = (jnp.arange(heads) // group) % hkv
    k, v = k[:, head_of], v[:, head_of]                      # [S, H, .]
    window = None
    if kind == WINDOW:
        window = d["window"] - (wrong == "window_off_by_one")
    sink = own["attention_sink_bias"] if kind == WINDOW and d["sink"] \
        else None
    if wrong == "no_sink":
        sink = None
    if wrong == "sink_on_full_layers" and kind == FULL:
        sink = other_sink
    attn = sink_attention(q, k, v, positions, window, sink, q_block)
    return operand(attn.reshape(s, heads * dv), wrong) @ block["o_proj"]


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
    if d["renormalise"]:
        kept = kept / (jnp.sum(kept, axis=-1, keepdims=True) + 1e-20)
    kept = kept * d["route_scale"]
    member = (choice[:, :, None] == jnp.arange(d["routed"])[None, None, :])
    every = jnp.sum(member * kept[:, :, None], axis=1)       # [S, routed]
    return every[:, d["first"]:d["first"] + d["held"]]


def swiglu(x, gate, up, down, wrong=None):
    return operand(jax.nn.silu(x @ gate) * (x @ up), wrong) @ down


def moe_part(m, small, experts, place, d, expert_chunk, wrong=None):
    """The sparse MLP of the normed ``m`` [S, hidden]. ``small``: this
    layer's router and bias, float32; ``experts``: the expert stacks of
    ALL sparse layers as served, ``[sparse layers, held, ...]``, of
    which layer ``place``'s are read ``expert_chunk`` at a time."""
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

    return jnp.sum(jax.lax.map(
        some_experts, jnp.arange(d["held"] // chunk)), axis=0)


def final_hidden(params, tokens, positions, d, q_block=512,
                 expert_chunk=4, wrong=None):
    """tokens [S] -> final-normed hidden states [S, hidden], float32."""
    if wrong is not None and wrong not in WRONG:
        raise ValueError(f"unknown wrong variant {wrong!r}")
    x = params["embed_tokens"][tokens].astype(F32)
    layers, eps = params["layers"], d["eps"]
    experts = {name: layers["moe"][name] for name in _EXPERT_KEYS}
    kind_stacks = (layers["full"], layers["window"])
    other_sink = None
    if wrong == "sink_on_full_layers":
        other_sink = layers["window"]["attention_sink_bias"][0].astype(F32)

    def of(stack, index, skip=()):
        # widened one layer at a time
        return {name: a[index].astype(F32)
                for name, a in stack.items() if name not in skip}

    for layer, kind in enumerate(d["kinds"]):
        block = of(layers["block"], layer)
        place = d["kinds"][:layer].count(kind)
        u = operand(rms_norm(x, block["input_layernorm"], eps), wrong)
        x = x + attention_part(u, block, of(kind_stacks[kind], place), kind,
                               other_sink, positions, d, q_block, wrong)
        m = operand(rms_norm(x, block["post_attention_layernorm"], eps),
                    wrong)
        at = sum(d["sparse"][:layer])
        if d["sparse"][layer]:
            x = x + moe_part(m, of(layers["moe"], at, _EXPERT_KEYS),
                             experts, at, d, expert_chunk, wrong)
        else:
            mlp = of(layers["dense"], layer - at)
            x = x + swiglu(m, mlp["gate_proj"], mlp["up_proj"],
                           mlp["down_proj"], wrong)
    return operand(rms_norm(x, params["norm"].astype(F32), eps),
                   None if wrong == "fp8_layers" else wrong)


def loss(params, tokens, targets, positions, d, *, q_block=512,
         loss_chunk=1024, expert_chunk=4, wrong=None):
    """Mean next-token cross entropy of one sequence."""
    hidden = final_hidden(params, tokens, positions, d, q_block,
                          expert_chunk, wrong)
    return _chunked_nll(hidden, head_weight(params, d), targets, loss_chunk)


def logits_at(params, tokens, rows, d, *, q_block=512, expert_chunk=4,
              wrong=None):
    """tokens [S], rows [R] -> logits [R, vocab] of a full forward pass
    at those rows, float32."""
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    hidden = final_hidden(params, tokens, positions, d, q_block,
                          expert_chunk, wrong)
    return hidden[rows] @ head_weight(params, d)


def make_loss_fn(config, *, q_block=512, loss_chunk=1024, expert_chunk=4,
                 wrong=None, with_gradients=False):
    """A jitted ``(params, tokens [S], targets [S], positions [S]) ->
    loss`` or ``-> (loss, global gradient norm, gradients of the norm
    gains)``, at ``highest`` matmul precision. The gradient is
    ``jax.grad`` of the whole tree at once: right for the sizes a test
    has (the family is served, not trained)."""
    d = mimo_dims(config)
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


def make_logits_fn(config, *, q_block=512, expert_chunk=4,
                   wrong: Optional[str] = None):
    """A jitted ``(params, tokens [B, S], rows [B, R]) -> logits
    [B, R, vocab]``: the full forward pass of each sequence alone."""
    d = mimo_dims(config)

    def batch_logits(params, tokens, rows):
        with jax.default_matmul_precision("highest"):
            one = functools.partial(
                logits_at, params, d=d, q_block=q_block,
                expert_chunk=expert_chunk, wrong=wrong)
            return jax.lax.map(lambda tr: one(tr[0], tr[1]), (tokens, rows))

    return jax.jit(batch_logits)
