"""Llama-family decoder LM — functional JAX implementation.

Capability parity with reference scaletorch/models/llama.py:65-556
(LlamaAttention with GQA, SwiGLU MLP, RMSNorm decoder layers, shared RoPE
tables computed once and CP-slicable, gradient checkpointing), re-designed
TPU-first:

  * parameters are a pytree with **layers stacked along axis 0** and the
    decoder loop is a ``lax.scan`` — compile time is O(1) in depth and XLA
    sees one fused layer body instead of L copies;
  * gradient checkpointing is ``jax.checkpoint`` around the scan body
    (reference uses torch.utils.checkpoint per layer, llama.py:534-545);
  * attention dispatches through the backend registry (sdpa / flash /
    ring), resolved statically before jit;
  * mixed precision: parameters live in fp32 (optimizer master copy),
    compute runs in ``cfg.dtype`` (bf16 on TPU) — norm/softmax internals
    stay fp32.

The same ``forward`` also serves Qwen3 (per-head q/k RMSNorm before RoPE,
tied embeddings, explicit head_dim — reference model_qwen3.py:139-350) via
config flags, so there is a single decoder implementation to optimise.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from scaletorch_tpu.models.layers import (
    DenseKVIO,
    apply_rotary_pos_emb,
    fan_in_uniform,
    get_cos_sin,
    rms_norm,
    sdpa_attention,
    swiglu,
)
from scaletorch_tpu.models.registry import (
    get_attention_backend,
    register_attention_backend,
)
from scaletorch_tpu.parallel.tensor_parallel import pvary_missing

Params = Dict[str, Any]


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_hidden_layers: int = 22
    num_attention_heads: int = 16
    num_key_value_heads: int = 4
    head_dim: Optional[int] = None  # defaults to hidden // heads
    max_position_embeddings: int = 32768
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    qk_norm: bool = False  # q/k RMSNorm before RoPE
    # what one q/k norm spans: "head" = each head's head_dim (Qwen3),
    # "projection" = the whole q (k) projection width before the split
    # into heads (OLMoE: gains [heads*head_dim] and [kv_heads*head_dim])
    qk_norm_scope: str = "head"
    dtype: Any = jnp.bfloat16  # compute dtype
    param_dtype: Any = jnp.float32

    @property
    def actual_head_dim(self) -> int:
        return self.head_dim or self.hidden_size // self.num_attention_heads

    @property
    def q_size(self) -> int:
        return self.num_attention_heads * self.actual_head_dim

    @property
    def kv_size(self) -> int:
        return self.num_key_value_heads * self.actual_head_dim

    @property
    def qk_norm_sizes(self) -> Tuple[int, int]:
        """Lengths of one layer's (q_norm, k_norm) gains; (0, 0) without
        the norm."""
        if not self.qk_norm:
            return 0, 0
        if self.qk_norm_scope == "projection":
            return self.q_size, self.kv_size
        if self.qk_norm_scope != "head":
            raise ValueError("qk_norm_scope must be 'head' or "
                             f"'projection', got {self.qk_norm_scope!r}")
        return self.actual_head_dim, self.actual_head_dim

    @classmethod
    def from_hf(cls, hf_config, **overrides) -> "LlamaConfig":
        """Build from a transformers AutoConfig (reference
        ModelArguments auto-fill, config.py:102-119)."""
        kw = dict(
            vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            intermediate_size=hf_config.intermediate_size,
            num_hidden_layers=hf_config.num_hidden_layers,
            num_attention_heads=hf_config.num_attention_heads,
            num_key_value_heads=getattr(
                hf_config, "num_key_value_heads", hf_config.num_attention_heads
            ),
            head_dim=getattr(hf_config, "head_dim", None),
            max_position_embeddings=hf_config.max_position_embeddings,
            rope_theta=getattr(hf_config, "rope_theta", 10000.0),
            rms_norm_eps=getattr(hf_config, "rms_norm_eps", 1e-6),
            tie_word_embeddings=getattr(hf_config, "tie_word_embeddings", False),
        )
        kw.update(overrides)
        return cls(**kw)

    def num_params(self) -> int:
        """Analytic parameter count (for MFU; matches get_num_params on an
        actual init)."""
        h, i, l, v = (
            self.hidden_size,
            self.intermediate_size,
            self.num_hidden_layers,
            self.vocab_size,
        )
        attn = h * self.q_size + 2 * h * self.kv_size + self.q_size * h
        mlp = 3 * h * i
        norms = 2 * h + sum(self.qk_norm_sizes)
        per_layer = attn + mlp + norms
        embed = v * h
        head = 0 if self.tie_word_embeddings else v * h
        return l * per_layer + embed + h + head


def init_params(key: jax.Array, cfg: LlamaConfig, *, mlp: bool = True) -> Params:
    """Random init: fan-in uniform for projections (reference
    attention_utils.py:160-167), ones for norms, normal(0.02) embeddings.

    ``mlp=False`` skips the dense MLP stacks (MoE models replace them with
    expert weights — no point materialising weights that are discarded).
    """
    l = cfg.num_hidden_layers
    h, i, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    dh = cfg.actual_head_dim
    keys = jax.random.split(key, 9)
    pd = cfg.param_dtype

    def stack_init(k, shape, fan_in):
        # one batched draw for all layers: fan-in-uniform bounds depend
        # only on fan_in, so [L, ...] in a single RNG call is
        # distributionally identical to per-layer slabs
        return fan_in_uniform(k, (l,) + shape, fan_in, pd)

    layers: Params = {
        "input_layernorm": jnp.ones((l, h), pd),
        "q_proj": stack_init(keys[0], (h, cfg.q_size), h),
        "k_proj": stack_init(keys[1], (h, cfg.kv_size), h),
        "v_proj": stack_init(keys[2], (h, cfg.kv_size), h),
        "o_proj": stack_init(keys[3], (cfg.q_size, h), cfg.q_size),
        "post_attention_layernorm": jnp.ones((l, h), pd),
    }
    if mlp:
        layers["gate_proj"] = stack_init(keys[4], (h, i), h)
        layers["up_proj"] = stack_init(keys[5], (h, i), h)
        layers["down_proj"] = stack_init(keys[6], (i, h), i)
    if cfg.qk_norm:
        q_gain, k_gain = cfg.qk_norm_sizes
        layers["q_norm"] = jnp.ones((l, q_gain), pd)
        layers["k_norm"] = jnp.ones((l, k_gain), pd)

    params: Params = {
        "embed_tokens": 0.02 * jax.random.normal(keys[7], (v, h), pd),
        "layers": layers,
        "norm": jnp.ones((h,), pd),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = fan_in_uniform(keys[8], (h, v), h, pd)
    return params


def tp_region_helpers(
    cfg: LlamaConfig,
    tp_axis: Optional[str],
    sequence_parallel: bool,
) -> Tuple[Callable, Callable, Callable, Callable]:
    """(pv, enter_full_seq, col, row) — the four region functions that
    parameterise a decoder block over its TP/SP mode. Shared by the dense
    decoder layer and the MoE decoder layer."""
    cdt = cfg.dtype
    tp = tp_axis

    if tp:
        from scaletorch_tpu.parallel.sequence_parallel import all_gather_sequence
        from scaletorch_tpu.parallel.tensor_parallel import (
            column_parallel_linear,
            row_parallel_linear,
        )

        def pv(t):
            return pvary_missing(t, tp)

        def enter_full_seq(h):
            # norm-region shard -> full sequence for attention/MLP
            return all_gather_sequence(h, tp) if sequence_parallel else pv(h)

        def col(h, w):
            return column_parallel_linear(h, w.astype(cdt), axis=tp)

        def row(h, w):
            return row_parallel_linear(
                h, w.astype(cdt), axis=tp, sequence_parallel=sequence_parallel
            )

    else:

        def pv(t):
            return t

        def enter_full_seq(h):
            return h

        def col(h, w):
            return h @ w.astype(cdt)

        def row(h, w):
            return h @ w.astype(cdt)

    return pv, enter_full_seq, col, row


def split_heads_qk_normed(q: jax.Array, k: jax.Array, layer: Params,
                          cfg: LlamaConfig,
                          pv: Callable = lambda t: t
                          ) -> Tuple[jax.Array, jax.Array]:
    """q [B, S, heads*D], k [B, S, kv_heads*D] as projected ->
    [B, S, heads, D] each, with the configuration's q/k RMSNorm (before
    RoPE): over each head's D after the split (Qwen3, reference
    model_qwen3.py:179-180,209-210) or over the whole projection width
    before it (OLMoE, HF ``OlmoeAttention``)."""
    b, s, _ = q.shape
    dh = cfg.actual_head_dim
    whole = cfg.qk_norm and cfg.qk_norm_scope == "projection"
    if whole:
        q = rms_norm(q, pv(layer["q_norm"]), cfg.rms_norm_eps)
        k = rms_norm(k, pv(layer["k_norm"]), cfg.rms_norm_eps)
    q = q.reshape(b, s, -1, dh)
    k = k.reshape(b, s, -1, dh)
    if cfg.qk_norm and not whole:
        q = rms_norm(q, pv(layer["q_norm"]), cfg.rms_norm_eps)
        k = rms_norm(k, pv(layer["k_norm"]), cfg.rms_norm_eps)
    return q, k


@jax.named_scope("attn")
def attention_block(
    x: jax.Array,
    layer: Params,
    cos: jax.Array,
    sin: jax.Array,
    cfg: LlamaConfig,
    attn_fn: Callable,
    helpers: Tuple[Callable, Callable, Callable, Callable],
) -> jax.Array:
    """Pre-norm attention sub-block with residual (reference
    LlamaAttention, llama.py:132-198). Shared by dense and MoE layers."""
    pv, enter_full_seq, col, row = helpers
    nh_l = layer["q_proj"].shape[-1]  # local q width (already tp-sliced)
    nkv_l = layer["k_proj"].shape[-1]
    dh = cfg.actual_head_dim

    h = rms_norm(x, pv(layer["input_layernorm"]), cfg.rms_norm_eps)
    h = enter_full_seq(h)
    b, s, _ = h.shape
    if cfg.qk_norm and cfg.qk_norm_scope == "projection" \
            and nh_l != cfg.q_size:
        raise NotImplementedError(
            "q/k RMSNorm over the whole projection width under tensor "
            "parallelism: each rank holds a slice of the width, so the "
            "mean of squares needs a psum over the tp axis that is not "
            "written yet; run this architecture with tp=1")
    q, k = split_heads_qk_normed(
        col(h, layer["q_proj"]), col(h, layer["k_proj"]), layer, cfg, pv)
    v = col(h, layer["v_proj"]).reshape(b, s, nkv_l // dh, dh)
    q = q.transpose(0, 2, 1, 3)  # [B, H_local, S, D]
    k = k.transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)
    q, k = apply_rotary_pos_emb(q, k, pv(cos), pv(sin))
    attn = attn_fn(q, k, v, causal=True)
    # Offer the attention output to the remat policy (the 'save_attn'
    # policy keeps it instead of recomputing the whole block in backward).
    attn = checkpoint_name(attn, "attn_out")
    attn = attn.transpose(0, 2, 1, 3).reshape(b, s, nh_l)
    return x + row(attn, layer["o_proj"])


def _decoder_layer(
    x: jax.Array,
    layer: Params,
    cos: jax.Array,
    sin: jax.Array,
    cfg: LlamaConfig,
    attn_fn: Callable,
    tp_axis: Optional[str] = None,
    sequence_parallel: bool = False,
) -> jax.Array:
    """One pre-norm decoder block. x: [B, S, H] in compute dtype.

    With ``tp_axis`` set (inside shard_map, weights arriving pre-sharded
    per llama_param_specs): q/k/v/gate/up are column-parallel, o/down are
    row-parallel (reference apply_tensor_parallel mapping,
    tensor_parallel.py:107-143). With ``sequence_parallel``, x is
    seq-sharded over tp; norm regions run on the shard, attention/MLP on
    the gathered sequence, and the row-parallel all-reduce becomes a
    reduce-scatter (reference llama.py:314-377, sp_comms.py:31-94).
    """
    helpers = tp_region_helpers(cfg, tp_axis, sequence_parallel)
    pv, enter_full_seq, col, row = helpers

    x = attention_block(x, layer, cos, sin, cfg, attn_fn, helpers)

    # ---- SwiGLU MLP (reference llama.py:207-249) ----------------------------
    with jax.named_scope("mlp"):
        h = rms_norm(x, pv(layer["post_attention_layernorm"]),
                     cfg.rms_norm_eps)
        h = enter_full_seq(h)
        gate = col(h, layer["gate_proj"])
        up = col(h, layer["up_proj"])
        x = x + row(swiglu(gate, up), layer["down_proj"])
    return x


@jax.named_scope("embed")
def embed(
    params: Params,
    input_ids: jax.Array,
    cfg: LlamaConfig,
    *,
    tp_axis: Optional[str] = None,
    sequence_parallel: bool = False,
) -> jax.Array:
    """Token embedding: [B, S] -> [B, S(/tp under SP), H] in compute dtype.

    Factored out of ``forward`` so pipeline parallelism can run it on the
    first stage only (reference PipelineParallel keeps the embedding on
    stage 0, pipeline_parallel.py:135-178).
    """
    cdt = cfg.dtype
    if sequence_parallel and tp_axis is None:
        raise ValueError("sequence_parallel requires tp_axis (run inside shard_map)")
    if tp_axis is None:
        return params["embed_tokens"][input_ids].astype(cdt)  # [B, S, H]
    from scaletorch_tpu.parallel.sequence_parallel import reduce_scatter_sequence
    from scaletorch_tpu.parallel.tensor_parallel import vocab_parallel_embedding

    if sequence_parallel:
        # Fused all-reduce + seq-scatter: the embedding's partial sums
        # are completed by the reduce-scatter that enters the SP region
        # (reference skips the embedding all-reduce under SP the same
        # way, tensor_parallel.py:238-240 + llama.py:530-552).
        partial = vocab_parallel_embedding(
            input_ids, params["embed_tokens"], axis=tp_axis, reduce="none"
        )
        return reduce_scatter_sequence(partial.astype(cdt), tp_axis)
    return vocab_parallel_embedding(
        input_ids, params["embed_tokens"], axis=tp_axis
    ).astype(cdt)


def final_hidden(
    params: Params,
    x: jax.Array,
    cfg: LlamaConfig,
    *,
    tp_axis: Optional[str] = None,
    sequence_parallel: bool = False,
) -> jax.Array:
    """Final RMSNorm (+ SP sequence all-gather): the last-stage epilogue
    before the LM head (reference keeps final_norm/final_proj on the last
    PP stage, pipeline_parallel.py:135-178)."""
    x = rms_norm(
        x,
        pvary_missing(params["norm"], tp_axis) if tp_axis else params["norm"],
        cfg.rms_norm_eps,
    )
    if sequence_parallel:
        from scaletorch_tpu.parallel.sequence_parallel import all_gather_sequence

        x = all_gather_sequence(x, tp_axis)
    return x


def resolve_remat_policy(name: str):
    """Map a config-level policy name to a jax.checkpoint policy.

    The reference's gradient checkpointing has exactly one mode — recompute
    the whole layer (torch.utils.checkpoint, llama.py:534-545). On TPU the
    policy is the main GC perf lever (VERDICT r1 #10): what gets saved
    decides how much of the flash/ring attention is recomputed in backward.
    """
    cp = jax.checkpoint_policies
    policies = {
        "nothing_saveable": cp.nothing_saveable,
        "dots_saveable": cp.dots_saveable,
        "dots_with_no_batch_dims_saveable": cp.dots_with_no_batch_dims_saveable,
        # Keeps the flash kernel's (out, lse) residuals (named in
        # ops/pallas/flash.py _flash_fwd) plus the layer-level attn output,
        # so backward under GC skips the flash-forward recompute and runs
        # the dq/dkv kernels directly off the saved statistics.
        "save_attn": cp.save_only_these_names("attn_out", "attn_lse"),
    }
    if name not in policies:
        raise ValueError(
            f"unknown remat_policy {name!r}; have {sorted(policies)}"
        )
    return policies[name]


def decoder_stack(
    x: jax.Array,
    layers: Params,
    cos: jax.Array,
    sin: jax.Array,
    cfg: LlamaConfig,
    attn_fn: Callable,
    *,
    tp_axis: Optional[str] = None,
    sequence_parallel: bool = False,
    gradient_checkpointing: bool = False,
    remat_policy: str = "nothing_saveable",
    active_layers: Optional[jax.Array] = None,
) -> jax.Array:
    """Scan ``_decoder_layer`` over a stack of layer params (leading axis =
    layer index). Used by ``forward`` for the whole model and by pipeline
    parallelism for one stage's layer subset.

    ``active_layers`` (scalar) marks the first k stacked slots as real;
    later slots are identity padding (uneven pipeline stages — reference
    PipelineParallel supports ragged layer counts, pipeline_parallel.py:
    83-133 — pad the stacked axis and mask here). Masked slots forward
    ``h`` unchanged, so their (zero-initialised) params get exactly zero
    gradient through the ``where``.
    """

    def layer_body(h, xs):
        layer_params, idx = xs
        out = _decoder_layer(
            h, layer_params, cos, sin, cfg, attn_fn,
            tp_axis=tp_axis, sequence_parallel=sequence_parallel,
        )
        if active_layers is not None:
            out = jnp.where(idx < active_layers, out, h)
        return out, None

    if gradient_checkpointing:
        layer_body = jax.checkpoint(
            layer_body, policy=resolve_remat_policy(remat_policy)
        )
    x, _ = jax.lax.scan(
        layer_body, x, (layers, scan_slot_indices(layers, active_layers))
    )
    return x


def scan_slot_indices(layers: Params, active_layers) -> jax.Array:
    """Per-slot indices [0..n_slots) for a stacked-layer scan. When an
    ``active_layers`` mask scalar is in play, the indices are broadcast
    onto its varying-mesh-axes (the ``+ 0 *`` trick) so the in-scan
    ``jnp.where`` compares vma-consistent operands under shard_map."""
    n_slots = jax.tree_util.tree_leaves(layers)[0].shape[0]
    idx = jnp.arange(n_slots, dtype=jnp.int32)
    if active_layers is not None:
        idx = idx + 0 * active_layers.astype(jnp.int32)
    return idx


def forward(
    params: Params,
    input_ids: jax.Array,
    cfg: LlamaConfig,
    *,
    positions: Optional[jax.Array] = None,
    attention_backend: str = "sdpa",
    gradient_checkpointing: bool = False,
    remat_policy: str = "nothing_saveable",
    tp_axis: Optional[str] = None,
    sequence_parallel: bool = False,
    return_hidden: bool = False,
) -> jax.Array:
    """Full decoder forward: [B, S] int tokens -> logits.

    Pure single-device semantics by default. With ``tp_axis`` (must run
    inside a shard_map over that mesh axis, params sharded per
    llama_param_specs) the decoder runs Megatron-style tensor parallel and
    the returned logits are **vocab-sharded** [B, S, V/tp] — pair with
    vocab_parallel_cross_entropy, or all-gather for dense logits.

    ``positions`` (shape [S]) overrides absolute positions for the RoPE
    table — CP passes this rank's sequence-shard positions (reference
    update_rope_for_context_parallel, context_parallel.py:427-473).
    """
    s = input_ids.shape[1]
    x = embed(params, input_ids, cfg, tp_axis=tp_axis,
              sequence_parallel=sequence_parallel)

    # RoPE tables computed once and shared across layers (reference
    # llama.py:476-491), fp32 then cast at application.
    cos, sin = get_cos_sin(s, cfg.actual_head_dim, cfg.rope_theta,
                           positions=positions)

    attn_fn = get_attention_backend(attention_backend)
    x = decoder_stack(
        x, params["layers"], cos, sin, cfg, attn_fn,
        tp_axis=tp_axis, sequence_parallel=sequence_parallel,
        gradient_checkpointing=gradient_checkpointing,
        remat_policy=remat_policy,
    )
    x = final_hidden(params, x, cfg, tp_axis=tp_axis,
                     sequence_parallel=sequence_parallel)
    if return_hidden:
        # Caller applies the LM head via lm_head_weight() (e.g. the fused
        # chunked CE in parallel/spmd.py).
        return x
    return x @ lm_head_weight(params, cfg, tp_axis)


def lm_head_weight(
    params: Params, cfg: LlamaConfig, tp_axis: Optional[str] = None
) -> jax.Array:
    """[H, V(/tp)] head weight in compute dtype (tied-embedding aware)."""
    head = (
        params["embed_tokens"].astype(cfg.dtype).T
        if cfg.tie_word_embeddings
        else params["lm_head"].astype(cfg.dtype)
    )
    return pvary_missing(head, tp_axis) if tp_axis else head


# ---- KV-cache inference path (scaletorch_tpu/inference) ---------------------
#
# The decode engine's two jitted steps (prefill / single-token decode,
# inference/decode.py) both lower onto ``forward_cached``: a full-sequence
# call with positions [B, 0..P) is prefill, a one-token call with positions
# [B, 1] = p is decode. TP runs via GSPMD — params and cache arrive as
# NamedSharding-placed global arrays (llama_param_specs +
# paged_kv_cache_specs) and XLA partitions the plain einsums; no shard_map/tp_axis threading.


def attention_mix_cached(
    h: jax.Array,
    layer: Params,
    index: jax.Array,
    cache_k: jax.Array,
    cache_v: jax.Array,
    cos: Optional[jax.Array],
    sin: Optional[jax.Array],
    positions: jax.Array,
    cfg: LlamaConfig,
    *,
    write_mask: Optional[jax.Array] = None,
    kv_io: Optional[Any] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The cache-aware attention mixer without its norm and residual:
    ``h`` [B, S, H] is what the projections multiply (the normed hidden
    state of a pre-norm block, the residual stream itself of a block
    that norms the mixer's output). q/k/v with the configuration's q/k
    norm, RoPE at the absolute positions (``cos`` None: no rotary
    embedding), K/V appended at ``index`` of the whole cache through
    ``kv_io``, attention against the cache, ``o_proj``. Returns (the
    residual's increment before any output norm, cache_k, cache_v)."""
    cdt = cfg.dtype
    dh = cfg.actual_head_dim
    kv_io = kv_io or DenseKVIO()
    b, s, _ = h.shape
    q, k = split_heads_qk_normed(
        h @ layer["q_proj"].astype(cdt), h @ layer["k_proj"].astype(cdt),
        layer, cfg)
    v = (h @ layer["v_proj"].astype(cdt)).reshape(b, s, -1, dh)
    q = q.transpose(0, 2, 1, 3)  # [B, Hq, S, D]
    k = k.transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)
    if cos is not None:
        q, k = apply_rotary_pos_emb(q, k, cos, sin)
    cache_k = kv_io.write(cache_k, index, k, positions, write_mask)
    cache_v = kv_io.write(cache_v, index, v, positions, write_mask)
    attn = kv_io.attend(q, cache_k, cache_v, index, positions, own=(k, v))
    attn = attn.transpose(0, 2, 1, 3).reshape(b, s, -1)
    return attn @ layer["o_proj"].astype(cdt), cache_k, cache_v


@jax.named_scope("attn")
def attention_block_cached(
    x: jax.Array,
    layer: Params,
    index: jax.Array,
    cache_k: jax.Array,
    cache_v: jax.Array,
    cos: jax.Array,
    sin: jax.Array,
    positions: jax.Array,
    cfg: LlamaConfig,
    *,
    write_mask: Optional[jax.Array] = None,
    kv_io: Optional[Any] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Cache-aware pre-norm attention sub-block with residual.

    x: [B, S, H]; cache_k/cache_v: the WHOLE stacked cache, all layers
    ([L, B, Hkv, S_max, D] dense), with ``index`` the layer this block
    is; cos/sin: [B, S, Dh] per-slot RoPE tables; positions: [B, S]
    absolute token positions (contiguous per slot — prefill passes
    [0..S), decode a single column p). K/V are computed with RoPE at the
    absolute positions, appended into the layer's cache at
    ``positions[:, 0]`` (see ``write_kv_cache``; ``write_mask`` [B]
    protects live slots during a mixed admit-prefill), and attention
    runs q-against-cache with the j <= p mask. Returns (out,
    new_cache_k, new_cache_v), the caches whole again.

    ``kv_io`` is the cache layout: an adapter with
    ``write(cache, layer, kv, positions, write_mask)`` and
    ``attend(q, cache_k, cache_v, layer, positions)``. None is the
    contiguous reference's ``layers.DenseKVIO``; the serving pool's
    ``inference.kv_cache.PagedKVIO`` carries [L, n_pages, Hkv, page, D]
    instead. The block never slices a layer out of the cache itself:
    whether that costs anything is the adapter's business. The mixer
    itself is ``attention_mix_cached``.
    """
    h = rms_norm(x, layer["input_layernorm"], cfg.rms_norm_eps)
    out, cache_k, cache_v = attention_mix_cached(
        h, layer, index, cache_k, cache_v, cos, sin, positions, cfg,
        write_mask=write_mask, kv_io=kv_io)
    return x + out, cache_k, cache_v


def swiglu_mlp(h: jax.Array, layer: Params, cfg: LlamaConfig) -> jax.Array:
    """The dense SwiGLU MLP of ``h`` without norm or residual."""
    cdt = cfg.dtype
    gate = h @ layer["gate_proj"].astype(cdt)
    up = h @ layer["up_proj"].astype(cdt)
    return swiglu(gate, up) @ layer["down_proj"].astype(cdt)


@jax.named_scope("mlp")
def _mlp_block(x: jax.Array, layer: Params, cfg: LlamaConfig) -> jax.Array:
    """Dense SwiGLU MLP sub-block with residual (single-device form; the
    TP/SP training path stays in ``_decoder_layer``)."""
    h = rms_norm(x, layer["post_attention_layernorm"], cfg.rms_norm_eps)
    return x + swiglu_mlp(h, layer, cfg)


def scan_layers_cached(
    layer_fn: Callable,
    x: jax.Array,
    cache: Any,
    layers: Params,
) -> Tuple[jax.Array, Any, Any]:
    """The one layer loop of the cache-aware forwards of one layer kind
    (Llama / Qwen3, Qwen3-MoE / OLMoE, GPT-MoE): a ``lax.scan`` whose
    CARRY is ``(h, cache)`` and whose scanned operands are the stacked
    layer parameters and the layer index.

    ``cache`` is any pytree of whole buffers (the ``(k, v)`` pair).
    ``layer_fn(h, layer, index, cache) -> (h, cache, out)`` sees the
    whole cache and the index of its layer; ``out`` is stacked over the
    layers (None for nothing). Returns ``(h, cache, outs)``.

    The cache is carried, never scanned: as ``xs`` / ``ys`` XLA slices a
    layer out, re-lays it for the write and re-stacks the layers into a
    new cache that the donated input cannot alias — 22.6 GB of copies a
    Qwen3-1.7B decode step to append 1.8 MB (PERF.md, PR 28). Carried
    whole, and touched only through the ``kv_io`` adapter at a layer
    index, the donated buffer is the loop's buffer from the first layer
    to the last. (``olmo_hybrid.forward_cached`` loops over periods of
    two layer kinds with the same carry, and scans no parameters.)
    """
    steps = jax.tree_util.tree_leaves(layers)[0].shape[0]

    def body(carry, xs):
        h, held = carry
        layer, index = xs
        h, held, out = layer_fn(h, layer, index, held)
        return (h, held), out

    (x, cache), outs = jax.lax.scan(
        body, (x, cache), (layers, jnp.arange(steps, dtype=jnp.int32)))
    return x, cache, outs


def select_logit_rows(
    x: jax.Array, logit_rows: Optional[jax.Array]
) -> jax.Array:
    """The hidden states a cached forward's final norm and head run on:
    ``x`` [B, S, H] whole (``logit_rows`` None: logits for every row),
    or row ``logit_rows[b]`` of each sequence, [B, 1, H]. A prefill step
    samples from one row a slot, so it names that row and the head
    multiplies [B, 1, H] as the decode step's does, never [B, S, H]
    (16 of 16,384 rows were used: PERF.md, PR 36). Shared by every
    family's ``forward_cached``."""
    if logit_rows is None:
        return x
    return jnp.take_along_axis(x, logit_rows[:, None, None], axis=1)


def forward_cached(
    params: Params,
    input_ids: jax.Array,
    cfg: LlamaConfig,
    cache: Tuple[jax.Array, jax.Array],
    *,
    positions: jax.Array,
    write_mask: Optional[jax.Array] = None,
    kv_io: Optional[Any] = None,
    logit_rows: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """KV-cached decoder forward: [B, S] tokens at absolute ``positions``
    [B, S] -> (logits, new (cache_k, cache_v)). ``logits`` is [B, S, V],
    or [B, 1, V] for the one row a sequence that ``logit_rows`` [B] int32
    names (``select_logit_rows``: the rows are taken before the final
    norm and the head).

    ``cache`` is a pair of [L, B, Hkv, S_max, D] stacked per-layer
    buffers (inference/kv_cache.py builds and shards them). One trace
    serves both engine steps: prefill (S = P, positions [0..P),
    ``write_mask`` selecting the admitted slots) and decode (S = 1,
    positions = current length per slot). The layer loop is
    ``scan_layers_cached``: the same ``lax.scan`` over the stacked
    parameters as the training forward, so compile time stays O(1) in
    depth, with the cache pair carried whole beside the hidden state and
    each layer writing and reading its own index of it. With ``kv_io``
    the cache pair is the adapter's layout instead (the paged pool's
    [L, n_pages, Hkv, page_size, D]), carried the same way.
    """
    x = embed(params, input_ids, cfg)
    cos, sin = get_cos_sin(
        input_ids.shape[1], cfg.actual_head_dim, cfg.rope_theta,
        positions=positions,
    )

    def layer_fn(h, layer, index, kv):
        h, ck, cv = attention_block_cached(
            h, layer, index, *kv, cos, sin, positions, cfg,
            write_mask=write_mask, kv_io=kv_io,
        )
        return _mlp_block(h, layer, cfg), (ck, cv), None

    x, cache, _ = scan_layers_cached(layer_fn, x, cache, params["layers"])
    x = rms_norm(select_logit_rows(x, logit_rows), params["norm"],
                 cfg.rms_norm_eps)
    logits = x @ lm_head_weight(params, cfg)
    return logits, cache


def config_from_args(args, common: dict) -> LlamaConfig:
    """The family's arm of ``families.build_model_config``."""
    return LlamaConfig(**common)


def config_from_hf(args, hf_config, overrides: dict) -> LlamaConfig:
    return LlamaConfig.from_hf(hf_config, **overrides)


class Llama:
    """Thin OO veneer matching the reference's ``Llama`` class API
    (llama.py:476+) over the functional init/forward pair."""

    config_cls = LlamaConfig

    def __init__(self, config: LlamaConfig):
        self.config = config

    def init(self, key: jax.Array) -> Params:
        return init_params(key, self.config)

    def __call__(self, params: Params, input_ids: jax.Array, **kw) -> jax.Array:
        return forward(params, input_ids, self.config, **kw)


# Default backends registered at import, like the reference registers
# ring/flash/sdpa at llama.py:38-57. ops.flash_attention and
# ops.ring_attention re-register 'flash'/'ring' with the real kernels when
# imported (scaletorch_tpu.ops does so eagerly).
register_attention_backend("sdpa", sdpa_attention)
