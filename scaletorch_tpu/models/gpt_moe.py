"""GPT with capacity-based MoE — the self-contained educational model.

Parity with reference scaletorch/models/moe.py:40-903: ``GPTConfig`` with
the MoE knob surface (:40-133), noisy-top-k ``Router`` with z-loss + aux
loss and capacity-factor dispatch (:350-600), batched ``MLPExperts``
einsum experts (:269-347), einsum aggregation (:603-640), ``GPT`` with
learned positional embeddings, weight tying, ``generate`` and
``estimate_mfu`` (:659-871). Single-device by design in the reference
("Not EP-distributed — used by tests/benchmarks"); here the dispatch path
reuses parallel/expert_parallel, so passing ``ep_axis`` inside a
shard_map distributes it for free.

TPU-first notes: GELU MLP experts as batched einsums (MXU), ``generate``
is a ``lax.scan`` over positions on a fixed-size buffer (static shapes —
one compile, no per-token retrace), noise via explicit PRNG keys.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from scaletorch_tpu.models.layers import (
    DenseKVIO,
    normal_init,
    sdpa_attention,
)
from scaletorch_tpu.models.llama import scan_layers_cached, select_logit_rows
from scaletorch_tpu.parallel.expert_parallel import (
    combine_routed,
    dispatch_routed,
    expert_capacity,
    resolve_moe_dispatch,
    route_tokens,
)

Params = Dict[str, Any]


@dataclass(frozen=True)
class GPTMoEConfig:
    """Reference GPTConfig (moe.py:40-133) knob surface."""

    block_size: int = 256
    vocab_size: int = 65
    n_layer: int = 4
    n_head: int = 4
    n_embd: int = 128
    # MoE
    use_moe: bool = True
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    z_loss_weight: float = 0.001
    router_noise_std: float = 1.0  # noisy top-k (moe.py noisy routing)
    norm_topk_prob: bool = True
    # einsum | index token movement (see expert_parallel.route_tokens);
    # auto picks index at every E, like Qwen3MoEConfig
    # (AOT_DISPATCH_CROSSOVER.json: the one-hot cost never wins)
    moe_dispatch: str = "auto"
    dtype: Any = jnp.float32

    def resolved_moe_dispatch(self) -> str:
        # single source of truth for the auto crossover:
        # expert_parallel.resolve_moe_dispatch
        return resolve_moe_dispatch(self.moe_dispatch, self.num_experts)

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head


def config_from_args(args, common: dict) -> GPTMoEConfig:
    """The examples' tier (lenet, mingpt): its own main builds a config."""
    raise ValueError(
        f"model_type {args.model_type!r} trains via its example: "
        "examples/mnist/train_mnist.py (lenet) or "
        "examples/mingpt/train_mingpt.py (gpt_moe/mingpt)")


def init_params(key: jax.Array, cfg: GPTMoEConfig) -> Params:
    l, d, v = cfg.n_layer, cfg.n_embd, cfg.vocab_size
    e, i = cfg.num_experts, 4 * cfg.n_embd
    ks = jax.random.split(key, 12)
    pd = jnp.float32

    def stack(k, shape, std=0.02):
        return normal_init(k, (l,) + shape, std, pd)

    layers: Params = {
        "ln1": jnp.ones((l, d), pd),
        "attn_qkv": stack(ks[0], (d, 3 * d)),
        "attn_proj": stack(ks[1], (d, d), 0.02 / jnp.sqrt(2 * l)),
        "ln2": jnp.ones((l, d), pd),
    }
    if cfg.use_moe:
        layers["router"] = stack(ks[2], (d, e))
        layers["router_noise"] = stack(ks[3], (d, e))
        layers["expert_fc"] = normal_init(ks[4], (l, e, d, i), 0.02, pd)
        layers["expert_proj"] = normal_init(
            ks[5], (l, e, i, d), 0.02 / jnp.sqrt(2 * l), pd
        )
    else:
        layers["mlp_fc"] = stack(ks[6], (d, i))
        layers["mlp_proj"] = stack(ks[7], (i, d), 0.02 / jnp.sqrt(2 * l))
    return {
        "wte": normal_init(ks[8], (v, d), 0.02, pd),  # tied head (moe.py:659+)
        "wpe": normal_init(ks[9], (cfg.block_size, d), 0.02, pd),
        "layers": layers,
        "ln_f": jnp.ones((d,), pd),
    }


def _layer_norm(x: jax.Array, w: jax.Array) -> jax.Array:
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    return ((x32 - mu) / jnp.sqrt(var + 1e-5) * w).astype(x.dtype)


def _moe_ffn(
    h: jax.Array,
    layer: Params,
    cfg: GPTMoEConfig,
    noise_key: Optional[jax.Array],
    ep_axis: Optional[str],
) -> Tuple[jax.Array, jax.Array]:
    """Noisy-top-k routed GELU experts; returns (y, aux_loss_scalar)."""
    g, s, d = h.shape
    logits = jnp.einsum("gsh,he->gse", h, layer["router"])
    if noise_key is not None and cfg.router_noise_std > 0:
        # noisy top-k (reference Router noise head): learned per-token
        # noise scale, softplus'd, scaled standard-normal
        noise_scale = jax.nn.softplus(
            jnp.einsum("gsh,he->gse", h, layer["router_noise"])
        )
        noise = jax.random.normal(noise_key, logits.shape)
        logits = logits + cfg.router_noise_std * noise_scale * noise
    cap = expert_capacity(s, cfg.num_experts, cfg.top_k, cfg.capacity_factor)
    mode = cfg.resolved_moe_dispatch()
    state, aux = jax.vmap(
        lambda lg: route_tokens(
            lg, cfg.top_k, cap, mode=mode,
            normalize_weights=cfg.norm_topk_prob,
        )
    )(logits)
    slots = dispatch_routed(h, state, mode=mode,
                            num_experts=cfg.num_experts, capacity=cap,
                            axis=ep_axis)
    act = jax.nn.gelu(
        jnp.einsum("eth,ehi->eti", slots, layer["expert_fc"].astype(h.dtype))
    )
    out = jnp.einsum("eti,eih->eth", act,
                     layer["expert_proj"].astype(h.dtype))
    y = combine_routed(out, state, mode=mode,
                       num_experts=cfg.num_experts, capacity=cap,
                       axis=ep_axis)
    aux_loss = (
        cfg.aux_loss_weight * jnp.mean(aux["aux_loss"])
        + cfg.z_loss_weight * jnp.mean(aux["z_loss"])
    )
    return y, aux_loss


def forward(
    params: Params,
    input_ids: jax.Array,
    cfg: GPTMoEConfig,
    *,
    noise_key: Optional[jax.Array] = None,
    ep_axis: Optional[str] = None,
    return_aux: bool = False,
):
    """[B, S] -> logits [B, S, V] (and total aux loss with return_aux).

    ``noise_key`` enables noisy routing (training); omit for deterministic
    eval (the reference disables noise at eval, moe.py:350-600).
    """
    b, s = input_ids.shape
    cdt = cfg.dtype
    x = (params["wte"][input_ids] + params["wpe"][:s]).astype(cdt)

    def layer_body(carry, inp):
        h, key = carry
        layer = inp
        a = _layer_norm(h, layer["ln1"])
        qkv = a @ layer["attn_qkv"].astype(cdt)
        q, k, v = jnp.split(qkv, 3, axis=-1)

        def heads(t):
            return t.reshape(b, s, cfg.n_head, cfg.head_dim).transpose(0, 2, 1, 3)

        o = sdpa_attention(heads(q), heads(k), heads(v), causal=True)
        o = o.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_embd)
        h = h + o @ layer["attn_proj"].astype(cdt)

        m = _layer_norm(h, layer["ln2"])
        if cfg.use_moe:
            if key is not None:
                key, sub = jax.random.split(key)
            else:
                sub = None
            y, aux = _moe_ffn(m, layer, cfg, sub, ep_axis)
        else:
            y = jax.nn.gelu(m @ layer["mlp_fc"].astype(cdt))
            y = y @ layer["mlp_proj"].astype(cdt)
            aux = jnp.float32(0.0)
        return (h + y.astype(cdt), key), aux

    (x, _), aux_per_layer = jax.lax.scan(
        layer_body, (x, noise_key), params["layers"]
    )
    x = _layer_norm(x, params["ln_f"])
    logits = x @ params["wte"].astype(cdt).T  # weight tying
    if return_aux:
        return logits, jnp.sum(aux_per_layer)
    return logits


def init_cache(
    cfg: GPTMoEConfig, batch: int, dtype: Any = None
) -> Tuple[jax.Array, jax.Array]:
    """Zeroed per-layer KV cache in the scan layout
    [L, B, n_head, block_size, head_dim] (GPT attends with full per-head
    K/V — no GQA grouping)."""
    shape = (cfg.n_layer, batch, cfg.n_head, cfg.block_size, cfg.head_dim)
    dt = dtype or cfg.dtype
    return jnp.zeros(shape, dt), jnp.zeros(shape, dt)


def forward_cached(
    params: Params,
    input_ids: jax.Array,
    cfg: GPTMoEConfig,
    cache: Tuple[jax.Array, jax.Array],
    *,
    positions: jax.Array,
    write_mask: Optional[jax.Array] = None,
    kv_io: Optional[Any] = None,
    logit_rows: Optional[jax.Array] = None,
):
    """KV-cached forward: [B, S] tokens at absolute ``positions`` [B, S]
    -> (logits, new cache); ``logits`` [B, S, V], or [B, 1, V] for the
    row a sequence that ``logit_rows`` [B] names
    (``llama.select_logit_rows``). Positional signal is the learned
    ``wpe`` table looked up at the absolute positions (no RoPE). Routing
    is deterministic (no noise) — matching ``generate``'s eval-mode
    forward. ``kv_io`` is the cache layout (None: dense; the paged pool's
    adapter) and the layer loop ``llama.scan_layers_cached``, exactly as
    in ``llama.forward_cached``.
    """
    b, s = input_ids.shape
    cdt = cfg.dtype
    kv_io = kv_io or DenseKVIO()
    x = (params["wte"][input_ids] + params["wpe"][positions]).astype(cdt)

    def layer_fn(h, layer, index, kv):
        ck, cv = kv
        a = _layer_norm(h, layer["ln1"])
        qkv = a @ layer["attn_qkv"].astype(cdt)
        q, k, v = jnp.split(qkv, 3, axis=-1)

        def heads(t):
            return t.reshape(b, s, cfg.n_head, cfg.head_dim).transpose(0, 2, 1, 3)

        q, k, v = heads(q), heads(k), heads(v)
        ck = kv_io.write(ck, index, k, positions, write_mask)
        cv = kv_io.write(cv, index, v, positions, write_mask)
        o = kv_io.attend(q, ck, cv, index, positions, own=(k, v))
        o = o.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_embd)
        h = h + o @ layer["attn_proj"].astype(cdt)

        m = _layer_norm(h, layer["ln2"])
        if cfg.use_moe:
            y, _ = _moe_ffn(m, layer, cfg, None, None)
        else:
            y = jax.nn.gelu(m @ layer["mlp_fc"].astype(cdt))
            y = y @ layer["mlp_proj"].astype(cdt)
        return h + y.astype(cdt), (ck, cv), None

    x, cache, _ = scan_layers_cached(layer_fn, x, cache, params["layers"])
    x = _layer_norm(select_logit_rows(x, logit_rows), params["ln_f"])
    return x @ params["wte"].astype(cdt).T, cache


def generate(
    params: Params,
    prompt: jax.Array,
    cfg: GPTMoEConfig,
    *,
    max_new_tokens: int = 32,
    temperature: float = 1.0,
    key: Optional[jax.Array] = None,
) -> jax.Array:
    """Autoregressive sampling (reference GPT.generate, moe.py:659-871),
    KV-cached: one full prefill over the prompt, then a ``lax.scan`` of
    single-token decode steps against the cache — O(S·S_max) attention
    per emitted token instead of the old recompute path's O(S_max²·L)
    full forward per token (retained as ``generate_recompute``, the
    greedy parity oracle). Static shapes throughout — prefill + one
    decode-scan compile. prompt: [B, P]. Greedy when temperature == 0.

    Sampled continuations draw per-step keys from ``key`` exactly like
    before, but the stream is indexed from the prompt boundary — numeric
    parity with the recompute path holds for greedy decoding (same math,
    float-tolerance logits), not for the sampled RNG stream.
    """
    b, p = prompt.shape
    total = min(cfg.block_size, p + max_new_tokens)
    key = key if key is not None else jax.random.PRNGKey(0)
    cache = init_cache(cfg, b)

    buf = jnp.zeros((b, cfg.block_size), jnp.int32)
    buf = jax.lax.dynamic_update_slice(buf, prompt.astype(jnp.int32), (0, 0))

    def pick(logits_t, sub):
        if temperature == 0:
            return jnp.argmax(logits_t, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            sub, logits_t / temperature, axis=-1
        ).astype(jnp.int32)

    # Prefill: one causal pass over the prompt writes cache [0, p) and
    # yields the logits that sample token p.
    positions = jnp.broadcast_to(jnp.arange(p, dtype=jnp.int32), (b, p))
    logits, cache = forward_cached(params, prompt.astype(jnp.int32), cfg,
                                   cache, positions=positions)
    key, sub = jax.random.split(key)
    tok = pick(logits[:, -1, :], sub)
    if p < total:
        buf = jax.lax.dynamic_update_slice_in_dim(buf, tok[:, None], p, axis=1)

    def step(carry, t):
        buf, cache, key, tok = carry
        # feed the token at position t; its logits sample position t+1
        logits_t, cache = forward_cached(
            params, tok[:, None], cfg, cache,
            positions=jnp.broadcast_to(t, (b, 1)).astype(jnp.int32),
        )
        key, sub = jax.random.split(key)
        nxt = pick(logits_t[:, 0, :], sub)
        write = t + 1 < total
        col = jnp.where(write, nxt, buf[:, t + 1])
        buf = jax.lax.dynamic_update_slice_in_dim(buf, col[:, None], t + 1,
                                                  axis=1)
        return (buf, cache, key, jnp.where(write, nxt, tok)), None

    # total is a static Python int, so the scan length is exactly the
    # requested generation — no decode steps are spent on positions the
    # caller never asked for.
    if p < total - 1:
        (buf, _, _, _), _ = jax.lax.scan(
            step, (buf, cache, key, tok),
            jnp.arange(p, total - 1, dtype=jnp.int32),
        )
    return buf[:, :total]


def generate_recompute(
    params: Params,
    prompt: jax.Array,
    cfg: GPTMoEConfig,
    *,
    max_new_tokens: int = 32,
    temperature: float = 1.0,
    key: Optional[jax.Array] = None,
) -> jax.Array:
    """The original cache-less sampler: reruns the full O(S²·L) forward
    over the whole block buffer for every emitted token. Kept ONLY as the
    oracle ``generate`` is held to (tests/inference/test_decode_parity.py)
    — use ``generate``.
    """
    b, p = prompt.shape
    total = min(cfg.block_size, p + max_new_tokens)
    buf = jnp.zeros((b, cfg.block_size), jnp.int32)
    buf = jax.lax.dynamic_update_slice(buf, prompt.astype(jnp.int32), (0, 0))
    key = key if key is not None else jax.random.PRNGKey(0)

    def step(carry, t):
        buf, key = carry
        logits = forward(params, buf, cfg)  # [B, block, V]
        next_logits = jnp.take_along_axis(
            logits, (t - 1)[None, None, None].repeat(b, 0), axis=1
        )[:, 0, :]
        key, sub = jax.random.split(key)
        if temperature == 0:
            nxt = jnp.argmax(next_logits, axis=-1)
        else:
            nxt = jax.random.categorical(sub, next_logits / temperature, axis=-1)
        # only write positions >= p (keep the prompt intact)
        write = (t >= p) & (t < total)
        col = jnp.where(write, nxt.astype(jnp.int32), buf[:, t])
        buf = jax.lax.dynamic_update_slice_in_dim(
            buf, col[:, None], t, axis=1
        )
        return (buf, key), None

    (buf, _), _ = jax.lax.scan(
        step, (buf, key), jnp.arange(1, cfg.block_size)
    )
    return buf[:, :total]


def estimate_mfu(
    cfg: GPTMoEConfig, params: Params, tokens_per_second: float,
    peak_flops: float,
) -> float:
    """Model FLOPs utilisation (reference GPT.estimate_mfu, moe.py:826-871):
    active params only for MoE (top_k of num_experts)."""
    n = sum(x.size for x in jax.tree.leaves(params))
    if cfg.use_moe:
        expert_params = (
            params["layers"]["expert_fc"].size
            + params["layers"]["expert_proj"].size
        )
        n = n - expert_params + expert_params * cfg.top_k // cfg.num_experts
    l, h, q, t = cfg.n_layer, cfg.n_head, cfg.head_dim, cfg.block_size
    flops_per_token = 6 * n + 12 * l * h * q * t
    return flops_per_token * tokens_per_second / peak_flops


class GPTMoE:
    config_cls = GPTMoEConfig

    def __init__(self, config: GPTMoEConfig):
        self.config = config

    def init(self, key: jax.Array) -> Params:
        return init_params(key, self.config)

    def __call__(self, params: Params, input_ids: jax.Array, **kw):
        return forward(params, input_ids, self.config, **kw)
