"""Qwen3-Next at a tiny size on the CPU: the system's forward and
``forward_cached`` against the plain reference
(``benchmarks/reference/qwen3_next.py``) on seeded random weights, both
layer kinds, the chunked scan against the sequential rule with fewer key
heads than value heads, the partial rotary embedding, the zero-centred
gain, the gates, and the share test: the routed partial results of all
shares plus the gated shared expert counted once add up to the uncut
reference's layer."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import qwen3_next as reference
from scaletorch_tpu.inference.decode import (
    counts_routing,
    teacher_forced_decode,
    teacher_forced_decode_paged,
)
from scaletorch_tpu.inference.kv_cache import (
    HybridCache,
    carries_state,
    init_paged_kv_cache,
)
from scaletorch_tpu.models import layers, olmo_hybrid, qwen3_moe, qwen3_next
from scaletorch_tpu.models.presets import preset
from scaletorch_tpu.ops.grouped_matmul import dropless_expert_mlp
from tests.inference.compiled import compiled_forward_cached

# the tiny preset: two periods, 2 key heads over 4 value heads, a
# quarter-rotary 32-wide head, 8 of 16 routed experts held from id 4
TINY = preset("qwen3-next-tiny")
# every expert held: the uncut layer
WHOLE = dict(TINY, num_experts=16, num_routed_experts=None,
             first_expert_id=0)
# every departure the reference offers (the real cell lists all but
# bf16_router, which the chip's comparison cannot tell: the test of the
# router's product below holds it here)
WRONG = ["no_output_gate", "rope_on_whole_head", "plain_norm_gain",
         "no_shared_expert_gate", "topk_not_renormalised",
         "key_heads_not_repeated", "bf16_router", "fp8_activations"]
# float32 on the CPU: the chunked scan against the reference's
# row-after-row recurrence lands near 1e-5 of the largest logit
RTOL_OF_MAX = 5e-4
GAINS = ("input_layernorm", "post_attention_layernorm", "q_norm", "k_norm",
         "o_norm", "norm")


def tiny_config(keys=None, **over):
    from scaletorch_tpu.config import ScaleTorchTPUArguments
    from scaletorch_tpu.trainer.trainer import build_model_config

    return build_model_config(ScaleTorchTPUArguments(
        **{**(keys or TINY), **over}, dtype="float32",
        param_dtype="float32"))


def seeded_params(cfg, seed=0):
    """The program's initialiser, then every norm gain moved off its
    initial value (a zero-centred gain starts at 0, where ``w`` for
    ``1 + w`` would be told by a zero output and nothing finer)."""
    params = qwen3_next.init_params(jax.random.PRNGKey(seed), cfg)
    key = jax.random.PRNGKey(seed + 100)

    def move(tree, key):
        out = {}
        for i, (name, leaf) in enumerate(sorted(tree.items())):
            k = jax.random.fold_in(key, i)
            if isinstance(leaf, dict):
                out[name] = move(leaf, k)
            elif name in GAINS:
                out[name] = leaf + 0.3 * jax.random.normal(k, leaf.shape)
            else:
                out[name] = leaf
        return out

    return move(params, key)


def _err_of_max(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def _forward(cfg, **kw):
    """The plain forward as one compiled program at ``highest`` matmul
    precision (op by op, every primitive of every layer compiles on its
    own, and a worker's mapped memory runs out)."""
    @jax.jit
    def run(params, toks):
        with jax.default_matmul_precision("highest"):
            return qwen3_next.forward(params, toks, cfg, **kw)

    return run


def _reference_logits(keys, params, tokens, wrong=None):
    rows = jnp.broadcast_to(jnp.arange(tokens.shape[1])[None], tokens.shape)
    return reference.make_logits_fn(
        keys, q_block=8, expert_chunk=4, wrong=wrong)(params, tokens, rows)


@pytest.fixture(scope="module", params=["share", "whole"])
def model(request):
    keys = TINY if request.param == "share" else WHOLE
    cfg = tiny_config(keys)
    return keys, cfg, seeded_params(cfg)


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0, 128)


@pytest.fixture(scope="module")
def reference_logits(model, tokens):
    keys, _, params = model
    return _reference_logits(keys, params, tokens)


# ---- the configuration --------------------------------------------------------

def test_the_preset_is_the_published_pattern_and_a_share():
    cfg = tiny_config()
    assert isinstance(cfg, qwen3_next.Qwen3NextConfig)
    assert cfg.layer_kinds == ("linear_attention",) * 3 + (
        "full_attention",) + ("linear_attention",) * 3 + ("full_attention",)
    assert (cfg.num_periods, cfg.num_linear_layers,
            cfg.num_kv_cache_layers) == (2, 6, 2)
    assert (cfg.rotary_dim, cfg.actual_head_dim) == (8, 32)
    assert (cfg.num_experts, cfg.router_width, cfg.first_expert_id) == (
        8, 16, 4)
    assert not cfg.holds_every_expert
    assert tiny_config(WHOLE).holds_every_expert
    assert cfg.sparse_layer_ids() == tuple(range(8))
    assert carries_state(cfg) and counts_routing(cfg)
    assert cfg.recurrent_state_shapes(3) == ((6, 3, 4, 8, 16),
                                             (6, 3, 3, 2 * 16 + 64))


def test_the_published_preset_counts_eighty_billion_parameters():
    cfg = tiny_config(preset("qwen3-next-80b-a3b"))
    assert cfg.layer_kinds.count("full_attention") == 12
    assert (cfg.rotary_dim, cfg.router_width, cfg.num_experts_per_tok) == (
        64, 512, 10)
    assert 79.6e9 < cfg.num_params() < 79.8e9


@pytest.mark.parametrize("keys", [TINY, WHOLE], ids=["share", "whole"])
def test_the_analytic_parameter_count_is_the_initialiser_s(keys):
    cfg = tiny_config(keys)
    shapes = jax.eval_shape(
        lambda: qwen3_next.init_params(jax.random.PRNGKey(0), cfg))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) \
        == cfg.num_params()
    moe = shapes["layers"]["moe"]
    assert moe["router"].shape == (8, 64, 16)
    assert moe["expert_gate_proj"].shape == (8, cfg.num_experts, 64, 32)
    assert shapes["layers"]["full"]["q_proj"].shape == (2, 1, 64, 2 * 4 * 32)


@pytest.mark.parametrize("over,error,match", [
    (dict(first_expert_id=12), ValueError, "are not among"),
    (dict(num_experts_per_tok=17), ValueError, "num_experts_per_tok"),
    (dict(linear_num_key_heads=3), ValueError, "key heads"),
    (dict(partial_rotary_factor=0.1), ValueError, "partial_rotary_factor"),
    (dict(mlp_only_layers=[1]), NotImplementedError, "dense-MLP layers"),
    (dict(decoder_sparse_step=2), NotImplementedError, "dense-MLP layers"),
    (dict(model_name_or_path="Qwen/Qwen3-Next-80B-A3B-Instruct"),
     NotImplementedError, "weight loading"),
], ids=["experts-outside-the-router", "top-k-over-the-router",
        "odd-key-heads", "odd-rotary", "mlp-only-layers", "sparse-step",
        "hf-weights"])
def test_what_is_not_written_refuses_by_name(over, error, match):
    with pytest.raises(error, match=match):
        tiny_config(**over)


def test_the_trainer_refuses_the_family_by_name():
    from scaletorch_tpu.config import ScaleTorchTPUArguments
    from scaletorch_tpu.trainer.trainer import Trainer

    with pytest.raises(NotImplementedError, match="no step for model_type"):
        Trainer(ScaleTorchTPUArguments(**TINY, dtype="float32"))


def test_a_share_under_capacity_dispatch_refuses_by_name():
    with pytest.raises(NotImplementedError, match="dropless routing only"):
        qwen3_moe.Qwen3MoEConfig(num_experts=4, num_routed_experts=8)
    with pytest.raises(NotImplementedError, match="dropless routing only"):
        qwen3_moe.Qwen3MoEConfig(shared_expert_intermediate_size=32)


# ---- the building blocks ------------------------------------------------------

def test_the_zero_centred_gain_is_one_plus_w():
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 16))
    w = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (16,))
    got = layers.rms_norm_zero_centered(x, w, 1e-6)
    want = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * (1 + w)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        layers.rms_norm_zero_centered(x, jnp.zeros(16), 1e-6),
        layers.rms_norm(x, jnp.ones(16), 1e-6), rtol=1e-6)
    # the gain's gradient flows through the sum
    g = jax.grad(lambda w_: jnp.sum(
        layers.rms_norm_zero_centered(x, w_, 1e-6) ** 2))(w)
    assert float(jnp.max(jnp.abs(g))) > 0


def test_partial_rotary_turns_the_first_dims_and_passes_the_rest():
    q = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 6, 32))
    k = jax.random.normal(jax.random.PRNGKey(1), (2, 2, 6, 32))
    positions = jnp.asarray([[3, 4, 5, 6, 7, 8], [0, 1, 2, 3, 4, 5]])
    cos, sin = layers.get_cos_sin(6, 8, 1e4, positions=positions)
    q_rot, k_rot = layers.apply_rotary_pos_emb(q, k, cos, sin)
    np.testing.assert_array_equal(q_rot[..., 8:], q[..., 8:])
    np.testing.assert_array_equal(k_rot[..., 8:], k[..., 8:])
    q_head, k_head = layers.apply_rotary_pos_emb(
        q[..., :8], k[..., :8], cos, sin)
    np.testing.assert_allclose(q_rot[..., :8], q_head, rtol=1e-6)
    np.testing.assert_allclose(k_rot[..., :8], k_head, rtol=1e-6)
    assert float(jnp.max(jnp.abs(q_rot[..., :8] - q[..., :8]))) > 0.1
    # tables as wide as the head: the whole head, as before
    cos, sin = layers.get_cos_sin(6, 32, 1e4, positions=positions)
    whole, _ = layers.apply_rotary_pos_emb(q, k, cos, sin)
    assert float(jnp.max(jnp.abs(whole[..., 8:] - q[..., 8:]))) > 0.1


def _mixer_inputs(cfg, seed, b, s):
    stack = qwen3_next.init_params(
        jax.random.PRNGKey(seed), cfg)["layers"]["linear"]
    layer = olmo_hybrid.layer_of(stack, jnp.int32(1), 2)
    u = jax.random.normal(jax.random.PRNGKey(seed + 1), (b, s, 64))
    state_shape, tail_shape = cfg.recurrent_state_shapes(b)
    state = 0.3 * jax.random.normal(
        jax.random.PRNGKey(seed + 2), state_shape[1:])
    tail = jax.random.normal(jax.random.PRNGKey(seed + 3), tail_shape[1:])
    return u, layer, state, tail


@pytest.mark.parametrize("s", [1, 37, 130], ids=["step", "short", "chunks"])
def test_fewer_key_heads_the_chunked_scan_is_the_sequential_rule(s):
    """2 key heads over 4 value heads, from a non-zero state: one row is
    the recurrence itself, more rows its chunked form."""
    cfg = tiny_config()
    u, layer, state, tail = _mixer_inputs(cfg, 3, 2, s)
    def mix(sequential):
        @jax.jit
        def run(u, layer, state, tail):
            with jax.default_matmul_precision("highest"):
                return olmo_hybrid.linear_attention_mix(
                    u, layer, cfg, state, tail, sequential=sequential)
        return run(u, layer, state, tail)

    out, new, new_tail = mix(False)
    out_seq, new_seq, tail_seq = mix(True)
    np.testing.assert_allclose(out, out_seq, atol=2e-5)
    np.testing.assert_allclose(new, new_seq, atol=2e-5)
    np.testing.assert_array_equal(new_tail, tail_seq)
    assert new.shape == (2, 4, 8, 16)


def test_a_key_head_serves_consecutive_value_heads():
    """Value heads 2j and 2j + 1 read key head j: with the two value
    heads of a pair given the same v, z, decay and beta they return the
    same rows, and heads of different pairs do not."""
    cfg = tiny_config()
    u, layer, state, tail = _mixer_inputs(cfg, 7, 1, 12)
    dv = cfg.linear_value_head_dim

    def pair_up(w):                       # [..., heads(4) * x] columns
        x = w.shape[-1] // 4
        w = w.reshape(w.shape[:-1] + (2, 2, x))
        return jnp.broadcast_to(w[..., :1, :], w.shape).reshape(
            w.shape[:-3] + (4 * x,))

    layer = dict(layer, **{n: pair_up(layer[n]) for n in (
        "v_proj", "g_proj", "a_proj", "b_proj", "A_log", "dt_bias")})
    conv = layer["conv"]
    layer["conv"] = jnp.concatenate(
        [conv[:, :32], pair_up(conv[:, 32:])], axis=1)
    eye = jnp.eye(4 * dv)
    @jax.jit
    def mix(u, layer, state, tail):
        with jax.default_matmul_precision("highest"):
            return olmo_hybrid.linear_attention_mix(
                u, layer, cfg, state, tail)

    out, _, _ = mix(u, dict(layer, o_proj=eye), jnp.zeros_like(state),
                    jnp.zeros_like(tail))
    heads = out.reshape(1, 12, 4, dv)
    np.testing.assert_allclose(heads[:, :, 0], heads[:, :, 1], atol=1e-6)
    np.testing.assert_allclose(heads[:, :, 2], heads[:, :, 3], atol=1e-6)
    assert float(jnp.max(jnp.abs(heads[:, :, 0] - heads[:, :, 2]))) > 1e-3


# ---- the expert layer on a share ---------------------------------------------

def _moe_layer(cfg, params, index):
    moe = params["layers"]["moe"]
    return {name: a[index] for name, a in moe.items()}


def _block_increment(cfg, layer, x, row_mask=None):
    """What the block adds to the residual stream, and its routing."""
    @jax.jit
    def run(layer, x):
        normed = layers.rms_norm_zero_centered(
            x, layer["post_attention_layernorm"], cfg.rms_norm_eps)
        with jax.default_matmul_precision("highest"):
            out, _, _, routing = qwen3_moe.dropless_block(
                x, normed, layer, cfg, row_mask, None)
        return out - x, normed, routing

    return run(layer, x)


def test_four_shares_add_up_to_the_uncut_layer():
    """THE share test. 16 routed experts in four shares of four; every
    share routes over all 16, computes its own experts' choices under
    the uncut layer's weights and adds the gated shared expert. The four
    routed partial sums plus the shared expert counted ONCE are the
    uncut reference's layer; no share's weights are renormalised."""
    whole_cfg = tiny_config(WHOLE)
    params = seeded_params(whole_cfg, seed=4)
    layer = _moe_layer(whole_cfg, params, 5)
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 24, 64))
    d = reference.next_dims(dict(WHOLE))
    wide = {k: v for k, v in layer.items() if not k.startswith("expert_")}
    experts = {k: params["layers"]["moe"][k] for k in (
        "expert_gate_proj", "expert_up_proj", "expert_down_proj")}

    total, seen = 0.0, 0
    for r in range(4):
        cfg = dataclasses.replace(
            whole_cfg, num_experts=4, num_routed_experts=16,
            first_expert_id=4 * r)
        mine = dict(layer, **{k: layer[k][4 * r:4 * r + 4] for k in experts})
        y, normed, routing = _block_increment(cfg, mine, x)
        flat = normed.reshape(-1, 64)
        with jax.default_matmul_precision("highest"):
            shared = jax.jit(
                lambda f, l: qwen3_moe.shared_expert(f, l, cfg))(
                    flat, layer).reshape(x.shape)
        total = total + (y - shared)
        assert int(routing["dropped"]) == 0
        assert int(jnp.sum(routing["expert_rows"])) \
            + int(routing["elsewhere"]) == 2 * 24 * 3
        seen += int(jnp.sum(routing["expert_rows"]))
        # each share alone is the reference's share
        share_d = dict(d, held=4, first=4 * r)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(jax.vmap(lambda h: reference.moe_part(
                h, wide, {k: v[:, 4 * r:4 * r + 4]
                          for k, v in experts.items()},
                5, share_d, 2)))(normed)
        assert _err_of_max(y, want) < 1e-5
    assert seen == 2 * 24 * 3          # every choice computed exactly once
    with jax.default_matmul_precision("highest"):
        uncut = jax.jit(jax.vmap(lambda h: reference.moe_part(
            h, wide, experts, 5, d, 4)))(normed)
    assert _err_of_max(total + shared, uncut) < 1e-5
    # and the program's own uncut layer says the same
    y_whole, _, routing = _block_increment(whole_cfg, layer, x)
    assert _err_of_max(y_whole, uncut) < 1e-5
    assert int(routing["elsewhere"]) == 0


def test_a_choice_held_elsewhere_costs_no_rows_and_adds_zeros():
    n, k, hid, e = 6, 3, 16, 4
    key = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(key[0], (n, hid))
    gate = jax.random.normal(key[1], (e, hid, 8))
    up = jax.random.normal(key[2], (e, hid, 8))
    down = jax.random.normal(key[3], (e, 8, hid))
    idx = jax.random.randint(key[4], (n, k), -3, e + 3)     # some outside
    held = (idx >= 0) & (idx < e)
    w = jax.random.uniform(key[5], (n, k))
    live = jnp.asarray([True, True, False, True, True, True])
    y, rows = jax.jit(lambda *a: dropless_expert_mlp(
        *a, live=live, held=held))(x, idx, w, gate, up, down)
    assert int(jnp.sum(rows)) == int(jnp.sum(held & live[:, None]))
    want = np.zeros((n, hid), np.float32)
    for t in range(n):
        for c in range(k):
            if bool(held[t, c]) and bool(live[t]):
                i = int(idx[t, c])
                mid = jax.nn.silu(x[t] @ gate[i]) * (x[t] @ up[i])
                want[t] += float(w[t, c]) * np.asarray(mid @ down[i])
    np.testing.assert_allclose(y, want, rtol=2e-4, atol=2e-4)
    assert not np.any(np.asarray(y[2]))                      # the dead token
    assert bool(jnp.all(jnp.isfinite(y)))


def test_the_router_s_product_is_float32_in_a_bfloat16_model():
    """What the cell on the chip cannot attest (its ``check_why``): the
    largest logit error of a bf16 system reads the same under a router
    that accumulates in bf16. Here the block is handed the REFERENCE's
    own input, so a tie is flipped only by the router's product: on the
    tokens whose k choices the bf16 product changes, the bf16 system
    agrees with the float32-router reference and not with
    ``wrong="bf16_router"``; and the traced program holds the product's
    operands and result to float32 whatever the serving dtype."""
    f32_cfg = tiny_config(WHOLE)
    cfg = dataclasses.replace(f32_cfg, dtype=jnp.bfloat16,
                              param_dtype=jnp.bfloat16)
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                          seeded_params(f32_cfg, seed=6))
    layer = _moe_layer(cfg, params, 2)
    x = jax.random.normal(jax.random.PRNGKey(3), (8, 512, 64),
                          jnp.bfloat16)
    y, normed, _ = _block_increment(cfg, layer, x)
    assert normed.dtype == jnp.bfloat16

    d = reference.next_dims(dict(WHOLE))
    wide = {k: v.astype(jnp.float32) for k, v in layer.items()
            if not k.startswith("expert_")}
    experts = {k: params["layers"]["moe"][k] for k in (
        "expert_gate_proj", "expert_up_proj", "expert_down_proj")}
    flat = normed.astype(jnp.float32).reshape(-1, 64)

    def ref(wrong):
        with jax.default_matmul_precision("highest"):
            return (jax.jit(lambda h: reference.moe_part(
                h, wide, experts, 2, d, 4, wrong))(flat),
                    reference.expert_weights(flat, wide["router"], d, wrong))

    (sound, w_sound), (off, w_off) = ref(None), ref("bf16_router")
    flipped = np.flatnonzero(np.any(
        np.asarray((w_sound > 0) != (w_off > 0)), axis=1))
    assert len(flipped) >= 3, "no near-tie among 4,096 tokens: another seed"
    got = np.asarray(y.astype(jnp.float32).reshape(-1, 64))[flipped]
    near = np.max(np.abs(got - np.asarray(sound)[flipped]), axis=1)
    far = np.max(np.abs(got - np.asarray(off)[flipped]), axis=1)
    assert np.all(near < 0.25 * far), (near, far)

    jaxpr = jax.make_jaxpr(lambda weights, h: qwen3_moe.dropless_block(
        h, h, weights, cfg, None, None)[0])(layer, x)
    router_dots = [e for e in jaxpr.eqns if e.primitive.name == "dot_general"
                   and e.invars[1].aval.shape == layer["router"].shape]
    assert len(router_dots) == 1
    assert {v.aval.dtype for v in router_dots[0].invars
            + router_dots[0].outvars} == {jnp.dtype(jnp.float32)}


# ---- the whole model against the reference -------------------------------------

def test_forward_matches_the_reference(model, tokens, reference_logits):
    _, cfg, params = model
    logits = _forward(cfg)(params, tokens)
    assert _err_of_max(logits, reference_logits) < RTOL_OF_MAX


def test_the_chunked_forward_is_the_row_by_row_forward(model, tokens):
    _, cfg, params = model
    chunked = _forward(cfg)(params, tokens)
    rows = _forward(cfg, sequential=True)(params, tokens)
    assert _err_of_max(chunked, rows) < RTOL_OF_MAX


@pytest.mark.parametrize("prefill_len", [1, 21, 40])
def test_prefill_then_decode_through_the_cache_matches_the_reference(
        model, tokens, reference_logits, prefill_len):
    _, cfg, params = model
    with jax.default_matmul_precision("highest"):
        cached = teacher_forced_decode(
            params, cfg, tokens, prefill_len=prefill_len,
            forward_fn=compiled_forward_cached(
                qwen3_next.forward_cached, cfg))
    assert _err_of_max(cached, reference_logits) < RTOL_OF_MAX


def test_the_paged_pool_matches_the_reference(model, tokens,
                                              reference_logits):
    _, cfg, params = model
    with jax.default_matmul_precision("highest"):
        paged = teacher_forced_decode_paged(
            params, cfg, tokens, page_size=8, prefill_len=13,
            forward_fn=compiled_forward_cached(
                qwen3_next.forward_cached, cfg))
    assert _err_of_max(paged, reference_logits) < RTOL_OF_MAX
    pool = init_paged_kv_cache(cfg, 7, 8, dtype=jnp.float32, slots=3)
    assert isinstance(pool, HybridCache)
    assert pool.k.shape == (2, 7, 2, 8, 32)
    assert pool.state.shape == (6, 3, 4, 8, 16)


@pytest.mark.parametrize("variant", WRONG)
def test_each_wrong_variant_is_far_from_the_system(model, tokens, variant):
    """What the cell's tolerance is shown to reject: each departure the
    reference offers moves the logits by far more than the system is off
    the reference."""
    keys, cfg, params = model
    logits = _forward(cfg)(params, tokens)
    off = _reference_logits(keys, params, tokens, wrong=variant)
    # a bf16 router flips a few near-ties in 80 rows: the weakest
    # departure, still a thousand times the system's own 1e-5
    factor = 20 if variant in ("bf16_router", "fp8_activations") else 50
    assert _err_of_max(logits, off) > factor * RTOL_OF_MAX


def test_return_routing_counts_held_and_elsewhere(tokens):
    cfg = tiny_config()
    params = seeded_params(cfg)
    cache = init_paged_kv_cache(cfg, 2 * 5 + 1, 8, dtype=jnp.float32, slots=2)
    from scaletorch_tpu.inference.kv_cache import PagedKVIO

    tables = jnp.arange(1, 11, dtype=jnp.int32).reshape(2, 5)
    positions = jnp.broadcast_to(jnp.arange(40, dtype=jnp.int32), (2, 40))
    row_mask = positions < jnp.asarray([[40], [17]])
    _, _, counts = jax.jit(lambda p, t, c: qwen3_next.forward_cached(
        p, t, cfg, c, positions=positions,
        kv_io=PagedKVIO(tables, 8, seq_limit=40), row_mask=row_mask,
        return_routing=True))(params, tokens, tuple(cache))
    live = 40 + 17
    assert int(counts["routed"]) + int(counts["elsewhere"]) == live * 3 * 8
    assert int(counts["dropped"]) == 0
    assert 0 < int(counts["routed"]) < live * 3 * 8
    assert int(counts["expert_visits"]) <= 8 * 8


def test_the_reference_gradients_cover_every_gain():
    cfg = tiny_config()
    params = seeded_params(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(2), (16,), 0, 128)
    fn = reference.make_loss_fn(TINY, q_block=8, loss_chunk=8,
                                expert_chunk=4, with_gradients=True)
    loss, norm, gains = fn(params, toks, jnp.roll(toks, -1),
                           jnp.arange(16, dtype=jnp.int32))
    assert np.isfinite(float(loss)) and float(norm) > 0
    assert set(gains["layers"]["linear"]) == {"input_layernorm", "o_norm"}
    assert set(gains["layers"]["full"]) == {
        "input_layernorm", "q_norm", "k_norm"}
    assert set(gains["layers"]["moe"]) == {"post_attention_layernorm"}
    assert set(reference.GAIN_KEYS) == set(GAINS) - {"norm"}
