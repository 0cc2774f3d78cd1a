"""Plain float32 reference of the Jamba decoder (Mamba-1 layers between
rope-free attention layers).

The yardstick that decides ``correct`` for a ``jamba`` configuration:
straightforward ``jax.numpy``, float32 throughout, every matmul under
``jax.default_matmul_precision("highest")``, the scan a ``lax.scan``
over tokens, no cache, no chunking, no kernels. It follows the published
block (``transformers``' ``modeling_jamba.py``; the builder had no
network, so each line below is also in the configuration's ``assumed``).
With the plain gain ``N(x; w) = w * x / sqrt(mean(x^2) + eps)``:

    h <- h + Mix_l(N(h; w_in))        h <- h + MLP_l(N(h; w_ff))
    MLP(x) = (silu(x W_gate) * (x W_up)) W_down
    logits = N(h_L; w_f) E^T                       (tied embedding)

and no positional embedding anywhere. Layer ``l`` is an attention layer
where ``l % attn_layer_period == attn_layer_offset``, a Mamba layer
elsewhere.

*Attention layer.* ``q = x Wq`` (heads of ``hidden / heads``), ``k = x
Wk``, ``v = x Wv`` (``num_key_value_heads`` heads, shared by ``heads /
kv heads`` query heads each), no bias, no q/k norm, NO rotary embedding,
causal softmax of ``q k^T / sqrt(D)``, ``Wo``.

*Mamba layer*, ``C = mamba_expand * hidden`` channels, ``N =
mamba_d_state``, ``R = mamba_dt_rank``:

    [u, z] = x W_in                              (u first, no bias)
    u <- silu(conv(u) + b_conv)                  depthwise, causal, width K
    [dt_r, B, C] = u W_x                         (R + N + N, no bias)
    dt_r <- N(dt_r; w_dt)   B <- N(B; w_b)   C <- N(C; w_c)
    dt = softplus(dt_r W_dt + b_dt)
    S_t[n, c] = exp(dt_t[c] A[n, c]) S_{t-1}[n, c] + dt_t[c] B_t[n] u_t[c]
    y_t[c] = sum_n S_t[n, c] C_t[n] + D[c] u_t[c]          A = -exp(A_log)
    out = (y * silu(z)) W_out

from ``S = 0`` and rows before the first taken as zero in the
convolution. The three norms on ``dt_r``, ``B`` and ``C`` are Jamba's
addition to Mamba-1.

It imports nothing from ``scaletorch_tpu``; the plain norm, RoPE (for a
wrong variant only), the query-blocked attention and the chunked loss
are the ones ``reference/qwen3.py`` has, the causal convolution
``reference/olmo_hybrid.py``'s and the 3-bit ``operand``
``reference/trinity.py``'s. What it shares with the system
is the layout of the parameter tree it is handed: ``layers.mamba.*``
stacked ``[periods, mamba layers of a period, ...]``,
``layers.attention.*`` ``[periods, 1, ...]``, ``x @ W`` orientation,
``conv`` ``[K, C]``, and ``A_log`` ``[N, C]`` (the published ``[C, N]``
transposed: the system keeps the channels on the lanes).

Departures from the published description, none of them mathematics:
attention in query blocks of ``q_block``; the layer stack a ``lax.scan``
over the periods of the layer pattern with a period's layers written
out, weights widened to float32 a layer at a time; the sequences of a
batch ``seq_batch`` at a time (``lax.map`` over groups, a group
vectorised: the token loop of a Mamba layer is 3,136 dependent steps a
sequence and layer, and eight sequences side by side take the steps of
one); the cross entropy only in ``make_loss_fn``.

``wrong`` selects a deliberately wrong variant, there only to show that
the tolerance rejects it, each a temptation: ``"bf16_state"`` rounds the
scan's state to bfloat16 after every token (a state kept in the
serving dtype); ``"no_inner_norms"`` is Mamba-1 without Jamba's three
norms; ``"rope_on_attention"`` turns q and k by a rotary embedding
(theta 10,000) as every other family's attention does;
``"conv_bias_dropped"`` and ``"dt_bias_dropped"`` lose a bias;
``"fp8_activations"`` rounds the activation operand of every matmul
(the normed input of every sub-block and of the head, what ``W_x``,
``W_dt``, ``W_out``, ``Wo`` and the down projection read) to 3 bits of
mantissa, float8 e4m3's: the nearest precision below the bfloat16 such a
configuration is served in. The exponent keeps bfloat16's range, so
nothing underflows: the precision alone is lowered. Weights,
accumulation and the scan stay float32.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from benchmarks.lib.costs import dims
from benchmarks.reference.olmo_hybrid import short_conv as causal_conv
from benchmarks.reference.qwen3 import (
    _chunked_nll,
    _sum_squares,
    causal_attention,
    head_weight,
    rms_norm,
    rope,
)
from benchmarks.reference.trinity import operand

F32 = jnp.float32
MAMBA, ATTENTION = "mamba", "attention"

GAIN_KEYS = ("input_layernorm", "pre_ff_layernorm", "dt_norm", "b_norm",
             "c_norm")
WRONG = ("bf16_state", "no_inner_norms", "rope_on_attention",
         "conv_bias_dropped", "dt_bias_dropped", "fp8_activations")
# theta of the rotary embedding that "rope_on_attention" wrongly applies
WRONG_ROPE_THETA = 10000.0


def jamba_dims(config):
    d = dims(config)
    period = int(config["attn_layer_period"])
    offset = int(config["attn_layer_offset"])
    if d["layers"] % period or not 0 <= offset < period:
        raise ValueError(
            f"{d['layers']} layers in periods of {period} (offset {offset})")
    if int(config.get("num_experts", 1)) != 1:
        raise ValueError("routed experts are not built")
    if config.get("mamba_proj_bias", False):
        raise ValueError("mamba_proj_bias is not built")
    d.update(
        pattern=tuple(ATTENTION if i == offset else MAMBA
                      for i in range(period)),
        channels=int(config["mamba_expand"]) * d["hidden"],
        state=int(config["mamba_d_state"]),
        conv_k=int(config["mamba_d_conv"]),
        dt_rank=int(config["mamba_dt_rank"]),
        conv_bias=bool(config.get("mamba_conv_bias", True)))
    return d


def selective_scan(u, dt, a, bm, cm, wrong=None):
    """u, dt [S, C], a [N, C], bm, cm [S, N] -> y [S, C] without the
    ``D u`` skip: the recurrence from ``S = 0``, one token after
    another."""
    def token(state, row):
        u_t, dt_t, b_t, c_t = row
        state = (jnp.exp(dt_t[None, :] * a) * state
                 + (dt_t * u_t)[None, :] * b_t[:, None])
        if wrong == "bf16_state":
            state = jax.lax.reduce_precision(
                state, exponent_bits=8, mantissa_bits=7)
        return state, jnp.sum(state * c_t[:, None], axis=0)

    _, y = jax.lax.scan(token, jnp.zeros(a.shape, F32), (u, dt, bm, cm))
    return y


def mamba_part(x, lp, d, wrong=None):
    """The Mamba mixer of the normed ``x`` [S, hidden]."""
    c, n, r, eps = d["channels"], d["state"], d["dt_rank"], d["eps"]
    uz = x @ lp["in_proj"]
    u, z = uz[:, :c], uz[:, c:]
    u = causal_conv(u, lp["conv"])
    if d["conv_bias"] and wrong != "conv_bias_dropped":
        u = u + lp["conv_bias"]
    u = jax.nn.silu(u)
    dbc = operand(u, wrong) @ lp["x_proj"]
    dt_r, bm, cm = dbc[:, :r], dbc[:, r:r + n], dbc[:, r + n:]
    if wrong != "no_inner_norms":
        dt_r = rms_norm(dt_r, lp["dt_norm"], eps)
        bm = rms_norm(bm, lp["b_norm"], eps)
        cm = rms_norm(cm, lp["c_norm"], eps)
    dt = operand(dt_r, wrong) @ lp["dt_proj"]
    if wrong != "dt_bias_dropped":
        dt = dt + lp["dt_bias"]
    dt = jax.nn.softplus(dt)
    y = selective_scan(u, dt, -jnp.exp(lp["A_log"]), bm, cm, wrong)
    y = y + lp["D"] * u
    return operand(y * jax.nn.silu(z), wrong) @ lp["out_proj"]


def attention_part(x, lp, positions, d, q_block, wrong=None):
    """The attention mixer of the normed ``x`` [S, hidden]."""
    s = x.shape[0]
    hkv, g, hd = d["kv_heads"], d["heads"] // d["kv_heads"], d["head_dim"]
    q = (x @ lp["q_proj"]).reshape(s, hkv * g, hd)
    k = (x @ lp["k_proj"]).reshape(s, hkv, hd)
    v = (x @ lp["v_proj"]).reshape(s, hkv, hd)
    if wrong == "rope_on_attention":
        q = rope(q, positions, WRONG_ROPE_THETA)
        k = rope(k, positions, WRONG_ROPE_THETA)
    attn = causal_attention(q.reshape(s, hkv, g, hd), k, v, positions,
                            q_block)
    return operand(attn.reshape(s, hkv * g * hd), wrong) @ lp["o_proj"]


def mlp_part(x, lp, wrong=None):
    mid = jax.nn.silu(x @ lp["gate_proj"]) * (x @ lp["up_proj"])
    return operand(mid, wrong) @ lp["down_proj"]


def block(h, lp, kind, positions, d, q_block, wrong=None):
    x = operand(rms_norm(h, lp["input_layernorm"], d["eps"]), wrong)
    h = h + (mamba_part(x, lp, d, wrong) if kind == MAMBA
             else attention_part(x, lp, positions, d, q_block, wrong))
    x = operand(rms_norm(h, lp["pre_ff_layernorm"], d["eps"]), wrong)
    return h + mlp_part(x, lp, wrong)


def final_hidden(params, tokens, positions, d, q_block=512, wrong=None):
    """tokens [S] -> final-normed hidden states [S, hidden], float32."""
    if wrong is not None and wrong not in WRONG:
        raise ValueError(f"unknown wrong variant {wrong!r}")
    x = params["embed_tokens"][tokens].astype(F32)

    @jax.checkpoint
    def period(h, stacks):
        taken = {MAMBA: 0, ATTENTION: 0}
        for kind in d["pattern"]:
            # widened one layer at a time
            lp = {name: a[taken[kind]].astype(F32)
                  for name, a in stacks[kind].items()}
            taken[kind] += 1
            h = block(h, lp, kind, positions, d, q_block, wrong)
        return h, None

    x, _ = jax.lax.scan(period, x, params["layers"])
    return operand(rms_norm(x, params["norm"].astype(F32), d["eps"]), wrong)


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
    has (the family is served, not trained)."""
    d = jamba_dims(config)
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


def make_logits_fn(config, *, q_block=512, seq_batch=1,
                   wrong: Optional[str] = None):
    """A jitted ``(params, tokens [B, S], rows [B, R]) -> logits
    [B, R, vocab]``: the full forward pass of each sequence alone,
    ``seq_batch`` sequences side by side."""
    d = jamba_dims(config)

    def batch_logits(params, tokens, rows):
        with jax.default_matmul_precision("highest"):
            one = functools.partial(logits_at, params, d=d,
                                    q_block=q_block, wrong=wrong)
            return jax.lax.map(lambda tr: one(tr[0], tr[1]), (tokens, rows),
                               batch_size=seq_batch)

    return jax.jit(batch_logits)
