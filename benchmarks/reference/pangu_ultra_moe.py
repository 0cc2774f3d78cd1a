"""Plain float32 reference of the pangu_ultra_moe (openPangu-Ultra-MoE)
decoder.

The yardstick that decides ``correct`` for a ``pangu_ultra_moe``
configuration: straightforward ``jax.numpy``, float32 throughout, every
matmul under ``jax.default_matmul_precision("highest")``, no cache, no
absorbed form, no sort, no kernels. With the plain gain ``N(x; g) = x /
sqrt(mean(x^2) + eps) * g`` (``sandwich_norm`` true: four norms a layer,
dense and sparse alike):

    h0 = E[token]                                   (not scaled)
    h <- h + N(Attn_l(N(h; g_in)); g_post_attn)
    h <- h + N(MLP_l(N(h; g_pre_mlp)); g_post_mlp)
    logits = N(h_L; g_f) W_head                     (untied)

*Latent attention*, the EXPANDED form only, ``x`` the normed input:
``c_q = N(x W_dq; g_q)``; ``q = c_q W_uq`` (heads x (nope + rope)), a
head's ``q = [q_n, q_r]``; ``[c_raw, k_raw] = x W_dkv``; ``c = N(c_raw;
g_kv)``; ``k_r = rope(k_raw)``: ONE rotary key shared by every head;
``q_r <- rope(q_r)`` per head (rotate-half over the rope dims,
``rope_theta``, absolute positions, no scaling of the frequencies);
``[k_n,h, v_h] = c W_ukv,h``; ``s_h(i, j) = (q_n,h(i) . k_n,h(j) +
q_r,h(i) . k_r(j)) / sqrt(nope + rope)``, causal softmax; ``out =
concat_h(sum_j p_h(i, j) v_h(j)) W_o``. No bias anywhere.

*MLP*, ``m`` the normed input: ``l < first_k_dense_replace``: SwiGLU at
``intermediate_size``. Else ``s = sigmoid(m W_r)`` in float32 over all
``num_routed_experts``; the ``num_experts_per_tok`` chosen are the top
of ``s`` (no group limit, no selection bias); their weights ``s_e /
(sum + 1e-20)`` (``norm_topk_prob``) times ``routed_scaling_factor``;
``f = sum_e w_e Expert_e(m) + Shared(m)``, each a SwiGLU at
``moe_intermediate_size``, the shared one ungated; dropless. **A
share**: the file's ``n_routed_experts`` counts the experts held (ids
``[first_expert_id, first_expert_id + n_routed_experts)`` of the
``num_routed_experts`` the router chooses from); the routed sum is then
over the held experts only, each under the weight the uncut layer gives
it, and that partial result goes on to the next layer. The expert sum
is in its plainest form: every held expert on every token under a 0 /
weight matrix, ``expert_chunk`` experts at a time, each widened to
float32 as it is used.

``num_nextn_predict_layers`` names a multi-token-prediction module; the
main model is exact without it and it is not built.

It imports nothing from ``scaletorch_tpu``; the plain norm, RoPE and the
chunked loss are the ones ``reference/qwen3.py`` has. What it shares
with the system is the layout of the parameter tree it is handed:
``layers.block.*`` (attention and the four norms) stacked ``[layers,
...]`` with ``kv_b_proj`` ``[layers, kv_lora_rank, heads, nope + v]``,
``layers.dense.*`` ``[first_k_dense_replace, ...]``, ``layers.moe.*``
``[layers - first_k_dense_replace, ...]``, ``x @ W`` orientation.

Departures from the published description, none of them mathematics:
attention in query blocks of ``q_block``; the leading dense layers
unrolled and the sparse ones one ``lax.scan``, weights widened to
float32 a layer (an expert chunk) at a time; the cross entropy only in
``make_loss_fn``.

``wrong`` selects a deliberately wrong variant, there only to show that
the tolerance rejects it, each a temptation of the absorbed path:
``"no_latent_norm"`` expands keys and values from ``c_raw`` (``g_kv``'s
norm skipped: what a cache written before the norm would hold);
``"rope_key_dropped"`` scores by the nope part alone (a cached row read
512 wide); ``"scale_by_128"`` takes the softmax scale ``nope^-0.5``
(the width of the part a head owns); ``"rope_on_whole_head"`` rotates
all of a head's query and of its key ``[k_n,h | k_raw]``;
``"pre_norm_only"`` adds both sub-blocks' outputs un-normed;
``"softmax_router"`` scores by a softmax over the routed experts;
``"no_route_scale"`` drops ``routed_scaling_factor``;
``"fp8_activations"`` rounds the activation operand of every matmul
(the normed input of every sub-block and of the head, the two latents,
what ``o_proj`` and the down projections read) to 3 bits of mantissa,
float8 e4m3's: the nearest precision below the bfloat16 such a
configuration is served in. The exponent keeps bfloat16's range, so
nothing underflows: the precision alone is lowered. Weights and
accumulation stay float32. ``"fp8_layers"`` is the same inside the six
layers and leaves the head's input alone: what it reads is the layers'
own share of the control, none of it the head matmul's.
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

GAIN_KEYS = ("input_layernorm", "post_attention_layernorm",
             "pre_mlp_layernorm", "post_mlp_layernorm", "q_a_layernorm",
             "kv_a_layernorm")
_EXPERT_KEYS = ("expert_gate_proj", "expert_up_proj", "expert_down_proj")
WRONG = ("no_latent_norm", "rope_key_dropped", "scale_by_128",
         "rope_on_whole_head", "pre_norm_only", "softmax_router",
         "no_route_scale", "fp8_activations", "fp8_layers")


def pangu_dims(config):
    d = dims(config)
    if not config.get("sandwich_norm", True):
        raise ValueError("sandwich_norm false is not built")
    if config.get("rope_scaling") is not None:
        raise ValueError("rope_scaling is not null")
    held = int(config["n_routed_experts"])
    d.update(
        kv_rank=int(config["kv_lora_rank"]),
        nope=int(config["qk_nope_head_dim"]),
        rot=int(config["qk_rope_head_dim"]),
        dense=int(config["first_k_dense_replace"]),
        held=held,
        routed=int(config.get("num_routed_experts") or held),
        first=int(config.get("first_expert_id", 0)),
        top_k=int(config["num_experts_per_tok"]),
        shared=int(config.get("n_shared_experts", 1)),
        renormalise=bool(config.get("norm_topk_prob", True)),
        route_scale=float(config.get("routed_scaling_factor", 1.0)))
    return d


def operand(x, wrong=None):
    """The activation operand of a matmul: as it is, or at 3 bits of
    mantissa (``reduce_precision``, not a pair of converts: XLA may drop
    such a pair, excess precision being allowed by default)."""
    if wrong in ("fp8_activations", "fp8_layers"):
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=3)
    return x


def causal_attention(q, k, v, scale, q_block):
    """q [S, H, Dk], k [S, H, Dk], v [S, H, Dv]; key j is visible to
    query i iff j <= i. Softmax attention in query blocks of
    ``q_block``."""
    s = q.shape[0]
    block = min(q_block, s)
    if s % block:
        raise ValueError(f"sequence {s} is not a multiple of block {block}")
    keys = jnp.arange(s)

    def one_block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * block, block, 0)
        rows = i * block + jnp.arange(block)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        visible = keys[None, :] <= rows[:, None]
        scores = jnp.where(visible[None], scores.astype(F32), -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v).astype(F32)

    out = jax.lax.map(one_block, jnp.arange(s // block))
    return out.reshape((s,) + v.shape[1:])


def attention_part(x, lp, positions, d, q_block, wrong=None):
    """The latent-attention mixer of the normed ``x`` [S, hidden] before
    its output norm, in the expanded form."""
    s = x.shape[0]
    heads, nope, rot = d["heads"], d["nope"], d["rot"]
    c_q = rms_norm(x @ lp["q_a_proj"], lp["q_a_layernorm"], d["eps"])
    q = (operand(c_q, wrong) @ lp["q_b_proj"]).reshape(s, heads, nope + rot)
    kv_a = x @ lp["kv_a_proj_with_mqa"]
    c_raw, k_raw = kv_a[:, :d["kv_rank"]], kv_a[:, d["kv_rank"]:]
    c = (c_raw if wrong == "no_latent_norm"
         else rms_norm(c_raw, lp["kv_a_layernorm"], d["eps"]))
    kv = jnp.einsum("sc,chd->shd", operand(c, wrong), lp["kv_b_proj"])
    k_n, v = kv[..., :nope], kv[..., nope:]
    if wrong == "rope_on_whole_head":
        q = rope(q, positions, d["theta"])
        k = rope(jnp.concatenate(
            [k_n, jnp.broadcast_to(k_raw[:, None, :], (s, heads, rot))],
            axis=-1), positions, d["theta"])
    else:
        q_r = rope(q[..., nope:], positions, d["theta"])
        k_r = rope(k_raw[:, None, :], positions, d["theta"])
        if wrong == "rope_key_dropped":
            k_r = jnp.zeros_like(k_r)
        q = jnp.concatenate([q[..., :nope], q_r], axis=-1)
        k = jnp.concatenate(
            [k_n, jnp.broadcast_to(k_r, (s, heads, rot))], axis=-1)
    width = nope if wrong == "scale_by_128" else nope + rot
    attn = causal_attention(q, k, v, width ** -0.5, q_block)
    return operand(attn.reshape(s, -1), wrong) @ lp["o_proj"]


def expert_weights(m, small, d, wrong=None):
    """[S, held] float32: the weight each HELD expert's output is summed
    under for each token: the uncut layer's weight where the token chose
    the expert, 0 where it did not."""
    logits = (m @ small["router"]).astype(F32)
    scores = (jax.nn.softmax(logits, axis=-1) if wrong == "softmax_router"
              else jax.nn.sigmoid(logits))
    kept, choice = jax.lax.top_k(scores, d["top_k"])
    if d["renormalise"]:
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
    layer's router and shared expert, float32; ``experts``: the expert
    stacks of ALL sparse layers as served, ``[sparse layers, held,
    ...]``, of which layer ``place``'s are read ``expert_chunk`` at a
    time."""
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
                 expert_chunk=4, wrong=None):
    """tokens [S] -> final-normed hidden states [S, hidden], float32."""
    if wrong is not None and wrong not in WRONG:
        raise ValueError(f"unknown wrong variant {wrong!r}")
    x = params["embed_tokens"][tokens].astype(F32)
    layers = params["layers"]
    experts = {name: layers["moe"][name] for name in _EXPERT_KEYS}
    n_dense = d["dense"]
    eps = d["eps"]

    def post(y, w):
        return y if wrong == "pre_norm_only" else rms_norm(y, w, eps)

    def of(stack, index, skip=()):
        # widened one layer at a time
        return {name: jax.lax.dynamic_index_in_dim(
            a, index, 0, keepdims=False).astype(F32)
            for name, a in stack.items() if name not in skip}

    def one_layer(h, layer):
        """``layer`` a Python int (dense) or traced (sparse)."""
        lp = of(layers["block"], layer)
        a = operand(rms_norm(h, lp["input_layernorm"], eps), wrong)
        h = h + post(attention_part(a, lp, positions, d, q_block, wrong),
                     lp["post_attention_layernorm"])
        m = operand(rms_norm(h, lp["pre_mlp_layernorm"], eps), wrong)
        if isinstance(layer, int):
            mlp = of(layers["dense"], layer)
            f = swiglu(m, mlp["gate_proj"], mlp["up_proj"],
                       mlp["down_proj"], wrong)
        else:
            place = layer - n_dense
            f = moe_part(m, of(layers["moe"], place, _EXPERT_KEYS), experts,
                         place, d, expert_chunk, wrong)
        return h + post(f, lp["post_mlp_layernorm"]), None

    for layer in range(n_dense):
        x, _ = jax.checkpoint(one_layer, static_argnums=1)(x, layer)
    x, _ = jax.lax.scan(
        jax.checkpoint(one_layer), x,
        jnp.arange(n_dense, d["layers"], dtype=jnp.int32))
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
    d = pangu_dims(config)
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
    d = pangu_dims(config)

    def batch_logits(params, tokens, rows):
        with jax.default_matmul_precision("highest"):
            one = functools.partial(
                logits_at, params, d=d, q_block=q_block,
                expert_chunk=expert_chunk, wrong=wrong)
            return jax.lax.map(lambda tr: one(tr[0], tr[1]), (tokens, rows))

    return jax.jit(batch_logits)
