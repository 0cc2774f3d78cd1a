"""Plain float32 reference of the Olmo-Hybrid decoder (forward, loss).

The yardstick that decides ``correct`` for an ``olmo_hybrid``
configuration: straightforward ``jax.numpy``, float32 throughout, every
matmul under ``jax.default_matmul_precision("highest")``, no cache, no
chunks, no kernels. The layer stack follows the file's ``layer_types``
(published: three ``linear_attention`` layers, one ``full_attention``,
repeated), every layer the Olmo family's block, which norms what a
sub-block returns:

    x <- x + RMSNorm(Mix(x))        x <- x + RMSNorm(SwiGLU-MLP(x))

*Full-attention layer:* ``q = RMSNorm_[heads*D](x Wq)``, ``k`` likewise
(over the WHOLE projection width, OLMoE's), ``v = x Wv``, no rotary
embedding (``rope_parameters.rope_theta`` is null), causal softmax
attention in query blocks, ``Wo``.

*Linear-attention layer* (gated delta rule), ``u`` its input, ``H``
heads, key width ``d_k``, value width ``d_v``:

    q~ = u Wq   k~ = u Wk   v~ = u Wv   z = u Wg
    (q', k', v') = silu(conv(q~, k~, v~))
    q = l2norm(q') / sqrt(d_k)   k = l2norm(k')   v = v'
    beta  = 2 sigmoid(u Wb)          alpha = exp(-exp(A_log) softplus(u Wa + dt_bias))
    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T,   S_0 = 0
    o_t = S_t^T q_t
    y_t = RMSNorm_[d_v](o_t) * silu(z_t)      out = y Wo

the recurrence as a ``lax.scan`` over time, one row after another, with
``S`` a float32 ``[d_k, d_v]`` matrix per head; the convolution
(depthwise, causal, width 4, no bias) as a sum of four shifted rows.

It imports nothing from ``scaletorch_tpu``; the attention core, the
norm, RoPE (for a wrong variant only) and the chunked loss are the ones
``reference/qwen3.py`` has (``jax.numpy`` only); sizes come from
``benchmarks/lib/costs.dims`` and the file's ``layer_types`` and
``linear_*`` keys. What it shares with the system is the layout of the
parameter tree it is handed: ``layers.linear.*`` stacked ``[periods,
linear layers of a period, ...]`` and ``layers.full.*`` ``[periods,
full layers of a period, ...]``, ``x @ W`` orientation, ``conv [4,
channels]`` with channels ``q~ | k~ | v~`` side by side and ``conv[3]``
the weight of the current row.

Departures from the published description (HF ``config.json`` of
allenai/Olmo-Hybrid-7B, the gated delta rule of its
``linear_attention`` layers), none of them mathematics: attention in
query blocks; the layer stack a ``lax.scan`` over periods, weights
widened to float32 one layer at a time; the cross-entropy only in
``make_loss_fn``. Assumed where the file has no key (the
configuration's ``assumed`` lists each): the reordered norm and the
whole-width q/k norm (the Olmo family's), no convolution bias, ``l2norm``
with 1e-6 under the root, the gate norm's epsilon = ``rms_norm_eps``.

``wrong`` selects a deliberately wrong variant, there only to show that
the tolerance rejects it: ``"beta_unscaled"`` leaves the factor 2 off
``beta`` (``linear_allow_neg_eigval`` false); ``"no_decay"`` sets
``alpha = 1``; ``"no_short_conv"`` feeds ``silu(q~, k~, v~)`` without
the convolution; ``"rope_on_full_layers"`` rotates q and k of the full
layers at theta 500000; ``"bf16_state"`` keeps the state in bfloat16
(rounded after every token).
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
    causal_attention,
    head_weight,
    rms_norm,
    rope,
)

F32 = jnp.float32
LINEAR, FULL = "linear_attention", "full_attention"

GAIN_KEYS = ("post_attention_layernorm", "post_feedforward_layernorm",
             "q_norm", "k_norm", "o_norm")


def hybrid_dims(config):
    d = dims(config)
    kinds = tuple(config["layer_types"])
    if len(kinds) != d["layers"]:
        raise ValueError(f"layer_types names {len(kinds)} layers, "
                         f"num_hidden_layers is {d['layers']}")
    period = next(p for p in range(1, len(kinds) + 1)
                  if len(kinds) % p == 0
                  and kinds == kinds[:p] * (len(kinds) // p))
    theta = (config.get("rope_parameters") or {}).get("rope_theta")
    d.update(
        pattern=kinds[:period],
        lin_heads=int(config["linear_num_value_heads"]),
        d_k=int(config["linear_key_head_dim"]),
        d_v=int(config["linear_value_head_dim"]),
        conv_k=int(config["linear_conv_kernel_dim"]),
        neg_eigval=bool(config["linear_allow_neg_eigval"]),
        theta=None if theta is None else float(theta))
    if int(config["linear_num_key_heads"]) != d["lin_heads"]:
        raise ValueError("key heads != value heads is not written")
    return d


def l2norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def short_conv(x, weight):
    """x [S, C], weight [K, C]: y_t = sum_j w_j x_{t-K+1+j}, rows before
    the first taken as zero."""
    k, s = weight.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    return sum(padded[j:j + s] * weight[j] for j in range(k))


def delta_rule(q, k, v, alpha, beta, wrong=None):
    """q, k [S, H, d_k], v [S, H, d_v], alpha, beta [S, H] -> o [S, H,
    d_v]: the recurrence of the head comment, one row after another,
    from S_0 = 0."""
    keep = jnp.bfloat16 if wrong == "bf16_state" else F32

    def step(state, row):
        q_t, k_t, v_t, a_t, b_t = row
        decayed = a_t[:, None, None] * state.astype(F32)
        seen = jnp.einsum("hkv,hk->hv", decayed, k_t)
        state = decayed + b_t[:, None, None] * jnp.einsum(
            "hk,hv->hkv", k_t, v_t - seen)
        state = state.astype(keep)
        return state, jnp.einsum("hkv,hk->hv", state.astype(F32), q_t)

    state0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), keep)
    _, o = jax.lax.scan(step, state0, (q, k, v, alpha, beta))
    return o


def linear_part(x, lp, d, wrong=None):
    """The gated delta-rule mixer of one block: ``Mix(x)``, before the
    block's output norm."""
    s = x.shape[0]
    h, dk, dv = d["lin_heads"], d["d_k"], d["d_v"]
    qkv = jnp.concatenate(
        [x @ lp["q_proj"], x @ lp["k_proj"], x @ lp["v_proj"]], axis=-1)
    if wrong != "no_short_conv":
        qkv = short_conv(qkv, lp["conv"])
    qkv = jax.nn.silu(qkv)
    q = l2norm(qkv[:, :h * dk].reshape(s, h, dk)) / dk ** 0.5
    k = l2norm(qkv[:, h * dk:2 * h * dk].reshape(s, h, dk))
    v = qkv[:, 2 * h * dk:].reshape(s, h, dv)
    beta = jax.nn.sigmoid(x @ lp["b_proj"])
    if d["neg_eigval"] and wrong != "beta_unscaled":
        beta = 2.0 * beta
    alpha = jnp.exp(-jnp.exp(lp["A_log"])
                    * jax.nn.softplus(x @ lp["a_proj"] + lp["dt_bias"]))
    if wrong == "no_decay":
        alpha = jnp.ones_like(alpha)
    o = delta_rule(q, k, v, alpha, beta, wrong)
    z = (x @ lp["g_proj"]).reshape(s, h, dv)
    y = rms_norm(o, lp["o_norm"], d["eps"]) * jax.nn.silu(z)
    return y.reshape(s, h * dv) @ lp["o_proj"]


def full_part(x, lp, positions, d, q_block, wrong=None):
    """Softmax attention of one block: ``Mix(x)``, before the block's
    output norm."""
    s = x.shape[0]
    hkv, g, hd = d["kv_heads"], d["heads"] // d["kv_heads"], d["head_dim"]
    q = rms_norm(x @ lp["q_proj"], lp["q_norm"], d["eps"])
    k = rms_norm(x @ lp["k_proj"], lp["k_norm"], d["eps"])
    q = q.reshape(s, hkv * g, hd)
    k = k.reshape(s, hkv, hd)
    theta = 500000.0 if wrong == "rope_on_full_layers" else d["theta"]
    if theta is not None:
        q, k = rope(q, positions, theta), rope(k, positions, theta)
    v = (x @ lp["v_proj"]).reshape(s, hkv, hd)
    attn = causal_attention(q.reshape(s, hkv, g, hd), k, v, positions,
                            q_block)
    return attn.reshape(s, hkv * g * hd) @ lp["o_proj"]


def mlp_part(x, lp):
    return (jax.nn.silu(x @ lp["gate_proj"]) * (x @ lp["up_proj"])) \
        @ lp["down_proj"]


def block(x, lp, kind, positions, d, q_block, wrong=None):
    mix = (linear_part(x, lp, d, wrong) if kind == LINEAR
           else full_part(x, lp, positions, d, q_block, wrong))
    x = x + rms_norm(mix, lp["post_attention_layernorm"], d["eps"])
    return x + rms_norm(mlp_part(x, lp), lp["post_feedforward_layernorm"],
                        d["eps"])


def final_hidden(params, tokens, positions, d, q_block=512, wrong=None):
    """tokens [S] -> final-normed hidden states [S, hidden], float32."""
    x = params["embed_tokens"][tokens].astype(F32)

    @jax.checkpoint
    def period(h, stacks):
        taken = {LINEAR: 0, FULL: 0}
        for kind in d["pattern"]:
            stack = stacks["linear" if kind == LINEAR else "full"]
            # widened one layer at a time: a float32 copy of a period is
            # 3.3 GB at the published widths
            lp = {name: a[taken[kind]].astype(F32)
                  for name, a in stack.items()}
            taken[kind] += 1
            h = block(h, lp, kind, positions, d, q_block, wrong)
        return h, None

    x, _ = jax.lax.scan(period, x, params["layers"])
    return rms_norm(x, params["norm"].astype(F32), d["eps"])


def loss(params, tokens, targets, positions, d, *, q_block=512,
         loss_chunk=1024, wrong=None):
    """Mean next-token cross entropy of one sequence."""
    hidden = final_hidden(params, tokens, positions, d, q_block, wrong)
    return _chunked_nll(hidden, head_weight(params, d), targets, loss_chunk)


def logits_at(params, tokens, rows, d, *, q_block=512, wrong=None):
    """tokens [S], rows [R] -> logits [R, vocab] of a full forward pass
    at those rows, float32."""
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    hidden = final_hidden(params, tokens, positions, d, q_block, wrong)
    return hidden[rows] @ head_weight(params, d)


def make_loss_fn(config, *, q_block=512, loss_chunk=1024, wrong=None,
                 with_gradients=False):
    """A jitted ``(params, tokens [S], targets [S], positions [S]) ->
    loss`` or ``-> (loss, global gradient norm, gradients of the norm
    gains)``, at ``highest`` matmul precision. The gradient is
    ``jax.grad`` of the whole tree at once: right for the sizes a test
    has."""
    d = hybrid_dims(config)
    fn = functools.partial(loss, d=d, q_block=q_block,
                           loss_chunk=loss_chunk, wrong=wrong)

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


def make_logits_fn(config, *, q_block=512, wrong: Optional[str] = None):
    """A jitted ``(params, tokens [B, S], rows [B, R]) -> logits
    [B, R, vocab]``: the full forward pass of each sequence alone."""
    d = hybrid_dims(config)

    def batch_logits(params, tokens, rows):
        with jax.default_matmul_precision("highest"):
            one = functools.partial(logits_at, params, d=d,
                                    q_block=q_block, wrong=wrong)
            return jax.lax.map(lambda tr: one(tr[0], tr[1]), (tokens, rows))

    return jax.jit(batch_logits)
