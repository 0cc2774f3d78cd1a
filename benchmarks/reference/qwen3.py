"""Plain float32 reference of the Qwen3 dense decoder (forward, loss).

The yardstick that decides ``correct``: straightforward ``jax.numpy``,
float32 throughout, every matmul under
``jax.default_matmul_precision("highest")`` (on a TPU a float32 matmul
otherwise runs in bf16 passes), no kernels, no cache, no sharding, no
batching tricks. It follows the published architecture (HF
``Qwen3ForCausalLM``): pre-norm blocks, grouped-query attention with a
per-head RMSNorm on q and k before RoPE (half-rotation convention),
SwiGLU MLP, a final RMSNorm and a head tied to the embedding where the
configuration says so. It imports nothing from ``scaletorch_tpu`` (its
sizes come from ``benchmarks/lib/costs.dims``); the
only thing it shares with the system is the layout of the parameter
tree it is handed (``embed_tokens [V, H]``, ``layers.*`` stacked on a
leading layer axis with ``x @ W`` orientation, ``norm``).

Departures from a textbook forward, all for memory and none for maths:
attention runs in query blocks (a 32k x 32k score matrix per head does
not fit), the loss runs in position chunks (a 32k x 151,936 logit
matrix does not fit), and the layer stack is a ``lax.scan`` with
``jax.checkpoint`` around each layer so that ``jax.grad`` holds one
layer's activations at a time.

``wrong`` selects a deliberately wrong variant, used only to show that
the tolerance in ``check.py`` rejects it: ``"bf16_attention"`` rounds
q, k, v and the probabilities to bf16 and accumulates in bf16,
``"drop_block"`` hides the first quarter of the keys from the last
quarter of the queries (one ring hop of cp = 4 lost), ``"no_qk_norm"``
skips the q/k norms.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from benchmarks.lib.costs import dims  # sizes by config.json names

F32 = jnp.float32


def rms_norm(x, weight, eps):
    variance = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(variance + eps) * weight


def rope(x, positions, theta):
    """x [S, H, D], positions [S] -> rotated x (half-rotation: the two
    halves of D pair up, angles repeated across the halves)."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    angles = positions.astype(F32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles), jnp.cos(angles)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angles), jnp.sin(angles)], -1)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def causal_attention(q, k, v, positions, q_block, wrong=None):
    """q [S, Hkv, G, D], k/v [S, Hkv, D], positions [S] (the absolute
    position of each row; a key is visible to a query when its position
    is not later). Softmax attention in query blocks of ``q_block``."""
    s = q.shape[0]
    scale = q.shape[-1] ** -0.5
    block = min(q_block, s)
    if s % block:
        raise ValueError(f"sequence {s} is not a multiple of block {block}")
    n_blocks = s // block
    if wrong == "bf16_attention":
        q, k, v = (a.astype(jnp.bfloat16) for a in (q, k, v))

    @jax.checkpoint  # a backward pass keeps no block's probabilities
    def one_block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * block, block, 0)
        pb = jax.lax.dynamic_slice_in_dim(positions, i * block, block, 0)
        scores = jnp.einsum("qhgd,khd->hgqk", qb, k) * scale
        visible = positions[None, :] <= pb[:, None]          # [q, k]
        if wrong == "drop_block":
            lost = (pb[:, None] >= 3 * s // 4) & (positions[None, :] < s // 4)
            visible = visible & ~lost
        scores = jnp.where(visible[None, None], scores.astype(F32), -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        if wrong == "bf16_attention":
            probs = probs.astype(jnp.bfloat16)
        return jnp.einsum("hgqk,khd->qhgd", probs, v).astype(F32)

    out = jax.lax.map(one_block, jnp.arange(n_blocks))
    return out.reshape((s,) + q.shape[1:])


def attention_part(x, lp, positions, d, q_block, wrong=None):
    """Pre-norm grouped-query attention of one block: the residual's
    increment, [S, hidden]."""
    s = x.shape[0]
    hkv, g, hd = d["kv_heads"], d["heads"] // d["kv_heads"], d["head_dim"]
    h = rms_norm(x, lp["input_layernorm"], d["eps"])
    q = (h @ lp["q_proj"]).reshape(s, hkv * g, hd)
    k = (h @ lp["k_proj"]).reshape(s, hkv, hd)
    v = (h @ lp["v_proj"]).reshape(s, hkv, hd)
    if wrong != "no_qk_norm":
        q = rms_norm(q, lp["q_norm"], d["eps"])
        k = rms_norm(k, lp["k_norm"], d["eps"])
    q = rope(q, positions, d["theta"]).reshape(s, hkv, g, hd)
    k = rope(k, positions, d["theta"])
    attn = causal_attention(q, k, v, positions, q_block, wrong)
    return attn.reshape(s, hkv * g * hd) @ lp["o_proj"]


def mlp_part(x, lp, d):
    """Pre-norm SwiGLU MLP of one block: the residual's increment."""
    h = rms_norm(x, lp["post_attention_layernorm"], d["eps"])
    return (jax.nn.silu(h @ lp["gate_proj"]) * (h @ lp["up_proj"])) \
        @ lp["down_proj"]


def decoder_layer(x, lp, positions, d, q_block, wrong=None):
    """One pre-norm block on x [S, hidden]. Each half is its own
    ``jax.checkpoint``: a backward pass through one layer then keeps the
    two residual-stream values and recomputes the rest, half by half
    (at 32k the MLP's intermediates alone are 1.2 GB)."""
    attn = jax.checkpoint(
        lambda x_, lp_: attention_part(x_, lp_, positions, d, q_block,
                                       wrong))
    mlp = jax.checkpoint(lambda x_, lp_: mlp_part(x_, lp_, d))
    x = x + attn(x, lp)
    return x + mlp(x, lp)


def final_hidden(params, tokens, positions, d, q_block=512, wrong=None):
    """tokens [S] -> final-normed hidden states [S, hidden], float32."""
    x = params["embed_tokens"][tokens].astype(F32)

    @jax.checkpoint
    def body(h, lp):
        # weights served in bf16 are widened one layer at a time: a
        # float32 copy of the whole model would not fit beside it
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        return decoder_layer(h, lp, positions, d, q_block, wrong), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    return rms_norm(x, params["norm"].astype(F32), d["eps"])


def head_weight(params, d):
    if d["tied"]:
        return params["embed_tokens"].T.astype(F32)
    return params["lm_head"].astype(F32)


def _chunked_nll(hidden, head, targets, loss_chunk):
    """Mean over positions of log-sum-exp(h @ head) - gold logit, in
    chunks of ``loss_chunk`` positions."""
    s = hidden.shape[0]
    chunk = min(loss_chunk, s)
    if s % chunk:
        raise ValueError(f"sequence {s} is not a multiple of chunk {chunk}")

    @jax.checkpoint
    def chunk_nll(args):
        h, t = args
        logits = h @ head
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, t[:, None], axis=-1)[:, 0]
        return jnp.sum(logz - gold)

    sums = jax.lax.map(chunk_nll, (hidden.reshape(s // chunk, chunk, -1),
                                   targets.reshape(s // chunk, chunk)))
    return jnp.sum(sums) / s


def loss(params, tokens, targets, positions, d, *, q_block=512,
         loss_chunk=1024, wrong=None):
    """Mean next-token cross entropy of one sequence: tokens, targets,
    positions all [S]."""
    hidden = final_hidden(params, tokens, positions, d, q_block, wrong)
    return _chunked_nll(hidden, head_weight(params, d), targets, loss_chunk)


def _sum_squares(tree):
    return sum(jnp.sum(jnp.square(g.astype(F32)))
               for g in jax.tree.leaves(tree))


# the parameters whose whole gradient the reference hands back: every
# norm gain of the model, 0.07 M numbers. Each sums over all positions
# (and, for q_norm / k_norm, over all heads) what attention sent back,
# so a block of keys wrongly hidden from some queries moves them by
# tens of per cent where it moves the gradient's norm by a thousandth.
GAIN_KEYS = ("input_layernorm", "post_attention_layernorm", "q_norm",
             "k_norm")


def loss_and_gradients(params, tokens, targets, positions, d, *,
                       q_block=512, loss_chunk=1024, wrong=None):
    """The loss, the global L2 norm of its gradient with respect to
    every parameter, and the gradient itself with respect to the norm
    gains (``GAIN_KEYS`` stacked over layers, and the final ``norm``).
    Backpropagation written out layer by layer (``jax.vjp`` of one layer
    at a time, walking the stack backwards) so that no more than one
    layer's gradient exists at once: the stacked gradient of ``jax.grad``
    would not fit beside the trainer's state. The tests hold it to
    ``jax.grad`` of ``loss``."""
    if not d["tied"]:
        raise NotImplementedError("untied head: add its gradient here")
    layers = params["layers"]
    embed = params["embed_tokens"].astype(F32)

    def layer(x, lp):
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        return decoder_layer(x, lp, positions, d, q_block, wrong)

    def forward(h, lp):
        return layer(h, lp), h            # keep each layer's input

    x_last, inputs = jax.lax.scan(forward, embed[tokens], layers)

    def tail(x, norm_weight, table):
        hidden = rms_norm(x, norm_weight, d["eps"])
        return _chunked_nll(hidden, table.T, targets, loss_chunk)

    value, tail_vjp = jax.vjp(tail, x_last, params["norm"].astype(F32),
                              embed)
    dx, dnorm, dtable = tail_vjp(jnp.ones((), F32))

    def backward(carry, xs):
        dx, squares = carry
        lp, x_in = xs
        _, layer_vjp = jax.vjp(layer, x_in, lp)
        dx_in, dlp = layer_vjp(dx)
        return ((dx_in, squares + _sum_squares(dlp)),
                {k: dlp[k].astype(F32) for k in GAIN_KEYS if k in dlp})

    (dx0, squares), gains = jax.lax.scan(
        backward, (dx, jnp.zeros((), F32)), (layers, inputs), reverse=True)
    dtable = dtable.at[tokens].add(dx0)   # the tied table is also looked up
    total = squares + _sum_squares(dnorm) + _sum_squares(dtable)
    return value, jnp.sqrt(total), {"layers": gains, "norm": dnorm}


def logits_at(params, tokens, rows, d, *, q_block=512, wrong=None):
    """tokens [S], rows [R] (indices into the sequence) -> logits
    [R, vocab] of a full forward pass at those rows, float32."""
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    hidden = final_hidden(params, tokens, positions, d, q_block, wrong)
    return hidden[rows] @ head_weight(params, d)


def make_loss_fn(config, *, q_block=512, loss_chunk=1024, wrong=None,
                 with_gradients=False):
    """A jitted ``(params, tokens [S], targets [S], positions [S]) ->
    loss`` or ``-> (loss, global gradient norm, gradients of the norm
    gains)``, at ``highest`` matmul precision."""
    d = dims(config)
    fn = functools.partial(loss, d=d, q_block=q_block,
                           loss_chunk=loss_chunk, wrong=wrong)

    def loss_only(params, tokens, targets, positions):
        with jax.default_matmul_precision("highest"):
            return fn(params, tokens, targets, positions)

    def both(params, tokens, targets, positions):
        with jax.default_matmul_precision("highest"):
            return loss_and_gradients(
                params, tokens, targets, positions, d, q_block=q_block,
                loss_chunk=loss_chunk, wrong=wrong)

    return jax.jit(both if with_gradients else loss_only)


def make_logits_fn(config, *, q_block=512, wrong: Optional[str] = None):
    """A jitted ``(params, tokens [B, S], rows [B, R]) -> logits
    [B, R, vocab]``: the full forward pass of each sequence alone."""
    d = dims(config)

    def batch_logits(params, tokens, rows):
        with jax.default_matmul_precision("highest"):
            one = functools.partial(logits_at, params, d=d,
                                    q_block=q_block, wrong=wrong)
            return jax.lax.map(lambda tr: one(tr[0], tr[1]), (tokens, rows))

    return jax.jit(batch_logits)
