"""Plain float32 reference of the Llama decoder, for the toy tree.

The second family of the toy benchmark tree: ``toy.py`` copies this file
into the tree and a configuration names it by its path, which is all the
harness knows of it. Published Llama mathematics (HF
``LlamaForCausalLM``): pre-norm blocks, RMSNorm, multi-head or
grouped-query attention with RoPE (half-rotation) and **no** norm on q
and k, SwiGLU MLP, a final RMSNorm and an output head of its own where
the configuration does not tie it. Whole matrices and ``jax.grad``: at
toy size nothing needs blocks or chunks. It imports nothing of the
system and nothing of the benchmark; it shares with the system only the
layout of the parameter tree it is handed (``embed_tokens [V, H]``,
``layers.*`` stacked on a leading layer axis with ``x @ W``
orientation, ``norm``, ``lm_head [H, V]``).

The reference contract (``benchmarks/lib/modules.py``):
``make_loss_fn``, ``make_logits_fn``, ``GAIN_KEYS``. ``q_block`` and
``loss_chunk`` are accepted because the toy cells' ``check`` states
them; whole matrices need neither. ``wrong="no_rope"`` leaves the
rotation out, to show that a tolerance rejects something.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32

# the norm gains whose whole gradient is handed back (there is no
# q_norm / k_norm in this family)
GAIN_KEYS = ("input_layernorm", "post_attention_layernorm")


def _sizes(config):
    heads = int(config["num_attention_heads"])
    return {
        "heads": heads,
        "kv_heads": int(config.get("num_key_value_heads") or heads),
        "head_dim": int(config.get("head_dim")
                        or config["hidden_size"] // heads),
        "eps": float(config.get("rms_norm_eps", 1e-6)),
        "theta": float(config.get("rope_theta", 10000.0)),
        "tied": bool(config.get("tie_word_embeddings", False)),
    }


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def _rope(x, positions, theta):
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    angles = positions.astype(F32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _layer(x, lp, positions, d, wrong):
    s = x.shape[0]
    hq, hkv, hd = d["heads"], d["kv_heads"], d["head_dim"]
    h = _rms_norm(x, lp["input_layernorm"], d["eps"])
    q = (h @ lp["q_proj"]).reshape(s, hq, hd)
    k = (h @ lp["k_proj"]).reshape(s, hkv, hd)
    v = (h @ lp["v_proj"]).reshape(s, hkv, hd)
    if wrong != "no_rope":
        q, k = _rope(q, positions, d["theta"]), _rope(k, positions, d["theta"])
    k, v = (jnp.repeat(a, hq // hkv, axis=1) for a in (k, v))
    scores = jnp.einsum("qhd,khd->hqk", q, k) * hd ** -0.5
    visible = positions[None, :] <= positions[:, None]
    probs = jax.nn.softmax(jnp.where(visible[None], scores, -jnp.inf), -1)
    attn = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, hq * hd)
    x = x + attn @ lp["o_proj"]
    h = _rms_norm(x, lp["post_attention_layernorm"], d["eps"])
    return x + (jax.nn.silu(h @ lp["gate_proj"]) * (h @ lp["up_proj"])) \
        @ lp["down_proj"]


def _logits(params, tokens, positions, d, wrong):
    params = jax.tree.map(lambda a: a.astype(F32), params)
    x = params["embed_tokens"][tokens]
    layers = params["layers"]
    for i in range(layers["q_proj"].shape[0]):
        x = _layer(x, jax.tree.map(lambda a: a[i], layers), positions, d,
                   wrong)
    x = _rms_norm(x, params["norm"], d["eps"])
    head = params["embed_tokens"].T if d["tied"] else params["lm_head"]
    return x @ head


def _loss(params, tokens, targets, positions, d, wrong):
    logits = _logits(params, tokens, positions, d, wrong)
    gold = jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - gold)


def make_loss_fn(config, *, wrong=None, with_gradients=False, q_block=None,
                 loss_chunk=None):
    """``(params, tokens [S], targets [S], positions [S]) -> loss`` or
    ``-> (loss, global gradient norm, gradients of the norm gains)``."""
    d = _sizes(config)

    def loss_only(params, tokens, targets, positions):
        with jax.default_matmul_precision("highest"):
            return _loss(params, tokens, targets, positions, d, wrong)

    def both(params, tokens, targets, positions):
        with jax.default_matmul_precision("highest"):
            value, grads = jax.value_and_grad(_loss)(
                params, tokens, targets, positions, d, wrong)
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(F32)))
                            for g in jax.tree.leaves(grads)))
        gains = {k: grads["layers"][k].astype(F32) for k in GAIN_KEYS}
        return value, norm, {"layers": gains,
                             "norm": grads["norm"].astype(F32)}

    return jax.jit(both if with_gradients else loss_only)


def make_logits_fn(config, *, wrong=None, q_block=None):
    """``(params, tokens [B, S], rows [B, R]) -> logits [B, R, vocab]``:
    the full forward pass of each sequence alone."""
    d = _sizes(config)

    def batch_logits(params, tokens, rows):
        positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
        with jax.default_matmul_precision("highest"):
            return jnp.stack([
                _logits(params, tokens[b], positions, d, wrong)[rows[b]]
                for b in range(tokens.shape[0])])

    return jax.jit(batch_logits)
