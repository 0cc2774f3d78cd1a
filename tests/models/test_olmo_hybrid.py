"""Olmo-Hybrid at a tiny size on the CPU: the chunked delta rule against
its definition at lengths that are no multiple of the chunk, the model's
forward and its cache (contiguous and paged) against the plain float32
reference on logits, what masked rows and masked slots may touch, every
``wrong=`` variant of the reference, and the dispatch from the published
``config.json`` keys."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import olmo_hybrid as reference
from scaletorch_tpu.inference.decode import (
    teacher_forced_decode,
    teacher_forced_decode_paged,
)
from scaletorch_tpu.inference.kv_cache import (
    HybridCache,
    PagedKVIO,
    carries_state,
    init_kv_cache,
    init_paged_kv_cache,
    recurrent_state_bytes,
)
from scaletorch_tpu.models import olmo_hybrid
from scaletorch_tpu.models.olmo_hybrid import FULL, LINEAR, OlmoHybridConfig
from tests.inference.compiled import compiled_forward_cached

# the published key names at toy widths: two periods of (3 linear, 1
# full); key width 8, value width 16 (the published 96 / 192 ratio)
TINY = {
    "model_type": "olmo_hybrid", "vocab_size": 128, "hidden_size": 64,
    "intermediate_size": 96, "num_hidden_layers": 8,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "layer_types": [LINEAR, LINEAR, LINEAR, FULL] * 2,
    "linear_num_key_heads": 4, "linear_num_value_heads": 4,
    "linear_key_head_dim": 8, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None},
    "max_position_embeddings": 4096, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False,
}
WRONG = ["beta_unscaled", "no_decay", "no_short_conv",
         "rope_on_full_layers", "bf16_state"]
# float32 against float32: the chunked form solves a triangular system
# per 64 rows where the reference goes row after row; the logits differ
# by 1e-4 of the largest (largest |logit| ~2.3 at this size). The
# nearest wrong variant, bf16_state, is 0.3 of it.
RTOL_OF_MAX = 5e-4


def tiny_config(**over):
    from scaletorch_tpu.config import ScaleTorchTPUArguments
    from scaletorch_tpu.trainer.trainer import build_model_config

    keys = {k: v for k, v in TINY.items()}
    keys.update(over)
    return build_model_config(ScaleTorchTPUArguments(
        **keys, dtype="float32", param_dtype="float32"))


def seeded_params(cfg, seed=0):
    return olmo_hybrid.init_params(jax.random.PRNGKey(seed), cfg)


@pytest.fixture(scope="module")
def model():
    cfg = tiny_config()
    return cfg, seeded_params(cfg)


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(1), (2, 150), 0, 128)


@pytest.fixture(scope="module")
def reference_logits(model, tokens):
    """The reference's full forward of each sequence alone, at every
    row (buffer padded to a multiple of its query block)."""
    _, params = model
    padded = jnp.pad(tokens, ((0, 0), (0, 192 - tokens.shape[1])))
    rows = jnp.broadcast_to(jnp.arange(tokens.shape[1])[None], tokens.shape)

    def logits(wrong=None):
        return reference.make_logits_fn(TINY, q_block=64, wrong=wrong)(
            params, padded, rows)

    return logits(), logits


def _err_of_max(system, ref):
    return float(jnp.max(jnp.abs(system - ref)) / jnp.max(jnp.abs(ref)))


# ---- the dispatch ------------------------------------------------------------

def test_published_keys_build_the_two_kinds_in_order(model):
    cfg, params = model
    assert isinstance(cfg, OlmoHybridConfig)
    assert cfg.layer_kinds == tuple(TINY["layer_types"])
    assert cfg.period_pattern == (LINEAR, LINEAR, LINEAR, FULL)
    assert (cfg.num_periods, cfg.num_linear_layers,
            cfg.num_kv_cache_layers) == (2, 6, 2)
    assert cfg.rope_theta is None and cfg.qk_norm_scope == "projection"
    linear, full = params["layers"]["linear"], params["layers"]["full"]
    assert linear["q_proj"].shape == (2, 3, 64, 32)
    assert linear["v_proj"].shape == (2, 3, 64, 64)
    assert linear["conv"].shape == (2, 3, 4, 128)
    assert linear["A_log"].shape == (2, 3, 4)
    assert full["q_proj"].shape == (2, 1, 64, 64)
    assert full["q_norm"].shape == (2, 1, 64)
    assert "input_layernorm" not in full     # the block norms its output
    n = sum(x.size for x in jax.tree.leaves(params))
    assert n == cfg.num_params()
    assert carries_state(cfg)


def test_omitted_layer_types_are_the_published_pattern():
    cfg = tiny_config(layer_types=None)
    assert cfg.layer_kinds == tuple(TINY["layer_types"])


@pytest.mark.parametrize("kinds, error", [
    ([LINEAR] * 8, "both"),
    ([FULL] * 8, "both"),
    ([LINEAR, FULL, "sliding_attention", FULL] * 2, "unknown"),
    ([LINEAR, FULL], "num_hidden_layers"),
])
def test_a_layer_stack_that_is_no_repeated_period_is_refused(kinds, error):
    with pytest.raises(ValueError, match=error):
        tiny_config(layer_types=kinds)


def test_a_stack_that_repeats_nothing_is_one_period():
    cfg = tiny_config(layer_types=[
        LINEAR, LINEAR, FULL, LINEAR, FULL, LINEAR, LINEAR, LINEAR])
    assert cfg.num_periods == 1 and len(cfg.period_pattern) == 8


def test_any_period_of_both_kinds_is_served():
    """Not only 3 + 1: a period is whatever repeats."""
    cfg = tiny_config(layer_types=[LINEAR, FULL, FULL, LINEAR] * 2)
    params = seeded_params(cfg)
    assert params["layers"]["full"]["q_proj"].shape[:2] == (2, 2)
    toks = jax.random.randint(jax.random.PRNGKey(3), (1, 40), 0, 128)
    config = dict(TINY, layer_types=list(cfg.layer_kinds))
    ref = reference.make_logits_fn(config, q_block=8)(
        params, toks, jnp.arange(40)[None])
    with jax.default_matmul_precision("highest"):
        cached = teacher_forced_decode(
            params, cfg, toks, prefill_len=21,
            forward_fn=compiled_forward_cached(
                olmo_hybrid.forward_cached, cfg))
    assert _err_of_max(cached, ref) < RTOL_OF_MAX


def test_value_heads_no_multiple_of_the_key_heads_are_refused_by_name():
    """Fewer key heads than value heads are repeated over them
    (tests/models/test_qwen3_next.py runs 2 over 4); 3 over 4 cannot."""
    with pytest.raises(ValueError, match="key heads"):
        tiny_config(linear_num_key_heads=3)
    assert tiny_config(linear_num_key_heads=2).linear_key_size == 2 * 8


# ---- the delta rule ----------------------------------------------------------

def _rule_inputs(seed, b, s, h=3, dk=8, dv=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = olmo_hybrid.l2norm(jax.random.normal(ks[0], (b, s, h, dk))) / dk ** .5
    k = olmo_hybrid.l2norm(jax.random.normal(ks[1], (b, s, h, dk)))
    v = jax.random.normal(ks[2], (b, s, h, dv))
    log_alpha = -jnp.exp(jax.random.normal(ks[3], (b, s, h)) - 2.0)
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)))
    state = 0.3 * jax.random.normal(ks[5], (b, h, dk, dv))
    return q, k, v, log_alpha, beta, state


@pytest.mark.parametrize("length", [1, 7, 63, 64, 65, 130, 200])
def test_chunked_equals_sequential_at_any_length(length):
    """Lengths under, at and over the chunk, none a multiple but one,
    from a non-zero state."""
    args = _rule_inputs(length, 2, length)
    with jax.default_matmul_precision("highest"):
        o_seq, s_seq = olmo_hybrid.gated_delta_sequential(*args)
        o_chk, s_chk = olmo_hybrid.gated_delta_chunked(*args)
    np.testing.assert_allclose(o_chk, o_seq, atol=2e-5)
    np.testing.assert_allclose(s_chk, s_seq, atol=2e-5)


def test_a_row_with_beta_zero_and_alpha_one_is_no_token():
    """What ``row_mask`` relies on: such rows leave the state as it
    was, wherever they stand, in both forms."""
    q, k, v, log_alpha, beta, state = _rule_inputs(9, 1, 100)
    live = jnp.arange(100) < 37
    beta = jnp.where(live[None, :, None], beta, 0.0)
    log_alpha = jnp.where(live[None, :, None], log_alpha, 0.0)
    with jax.default_matmul_precision("highest"):
        _, s_all = olmo_hybrid.gated_delta_chunked(
            q, k, v, log_alpha, beta, state)
        _, s_cut = olmo_hybrid.gated_delta_sequential(
            q[:, :37], k[:, :37], v[:, :37], log_alpha[:, :37],
            beta[:, :37], state)
    np.testing.assert_allclose(s_all, s_cut, atol=2e-5)


def test_the_step_is_the_recurrence_written_out():
    q, k, v, log_alpha, beta, state = _rule_inputs(4, 2, 1)
    o, new = olmo_hybrid.gated_delta_step(
        q[:, 0], k[:, 0], v[:, 0], log_alpha[:, 0], beta[:, 0], state)
    alpha = np.exp(np.asarray(log_alpha[:, 0], np.float64))
    for b in range(2):
        for h in range(3):
            s0 = alpha[b, h] * np.asarray(state[b, h], np.float64)
            kk, vv = np.asarray(k[b, 0, h]), np.asarray(v[b, 0, h])
            s1 = s0 + float(beta[b, 0, h]) * np.outer(kk, vv - s0.T @ kk)
            np.testing.assert_allclose(new[b, h], s1, atol=1e-6)
            np.testing.assert_allclose(
                o[b, h], s1.T @ np.asarray(q[b, 0, h]), atol=1e-6)


def test_short_conv_is_causal_and_continues_from_its_tail():
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 11, 6))
    w = jax.random.normal(jax.random.PRNGKey(3), (4, 6))
    zeros = jnp.zeros((2, 3, 6))
    whole, rows = olmo_hybrid.short_conv(x, w, zeros)
    assert rows.shape == (2, 14, 6)
    want = sum(np.pad(np.asarray(x), ((0, 0), (3, 0), (0, 0)))[:, j:j + 11]
               * np.asarray(w)[j] for j in range(4))
    np.testing.assert_allclose(whole, want, atol=1e-6)
    head, head_rows = olmo_hybrid.short_conv(x[:, :5], w, zeros)
    tail, _ = olmo_hybrid.short_conv(x[:, 5:], w, head_rows[:, -3:])
    np.testing.assert_allclose(
        jnp.concatenate([head, tail], axis=1), whole, atol=1e-6)


# ---- the model against the plain reference -----------------------------------

def test_forward_is_the_reference_in_both_forms(model, tokens,
                                                reference_logits):
    cfg, params = model
    ref, _ = reference_logits
    with jax.default_matmul_precision("highest"):
        chunked = olmo_hybrid.forward(params, tokens, cfg)
        row_by_row = olmo_hybrid.forward(params, tokens, cfg, sequential=True)
    assert _err_of_max(chunked, ref) < RTOL_OF_MAX
    assert _err_of_max(row_by_row, ref) < RTOL_OF_MAX


@pytest.mark.parametrize("prefill_len", [1, 70, 129])
def test_prefill_then_decode_through_the_cache_is_the_reference(
        model, tokens, reference_logits, prefill_len):
    """A prompt of 1, 70 or 129 rows (no multiple of 64), then every
    later token through the state and the convolution tail: the
    reference's full forward, on logits, contiguous and paged."""
    cfg, params = model
    ref, _ = reference_logits
    with jax.default_matmul_precision("highest"):
        dense = teacher_forced_decode(
            params, cfg, tokens, prefill_len=prefill_len,
            forward_fn=compiled_forward_cached(
                olmo_hybrid.forward_cached, cfg))
        paged = teacher_forced_decode_paged(
            params, cfg, tokens, page_size=16, prefill_len=prefill_len,
            forward_fn=compiled_forward_cached(
                olmo_hybrid.forward_cached, cfg))
    assert _err_of_max(dense, ref) < RTOL_OF_MAX
    assert _err_of_max(paged, ref) < RTOL_OF_MAX
    np.testing.assert_allclose(paged, dense, atol=1e-5)


@pytest.mark.parametrize("variant", WRONG)
def test_every_wrong_variant_differs_from_the_honest_reference(
        reference_logits, variant):
    ref, logits = reference_logits
    assert _err_of_max(logits(variant), ref) > 100 * RTOL_OF_MAX


def test_reference_offers_its_loss_and_the_gain_gradients(model, tokens):
    cfg, params = model
    seq = tokens[0, :64]
    loss, norm, gains = reference.make_loss_fn(
        TINY, q_block=32, loss_chunk=32, with_gradients=True)(
            params, seq, jnp.roll(seq, -1), jnp.arange(64))
    assert np.isfinite(float(loss)) and float(norm) > 0
    assert set(gains["layers"]["linear"]) == {
        "post_attention_layernorm", "post_feedforward_layernorm", "o_norm"}
    assert set(gains["layers"]["full"]) == {
        "post_attention_layernorm", "post_feedforward_layernorm",
        "q_norm", "k_norm"}
    with jax.default_matmul_precision("highest"):
        logits = olmo_hybrid.forward(params, seq[None], cfg)
    nll = -jnp.mean(jnp.take_along_axis(
        jax.nn.log_softmax(logits[0]), jnp.roll(seq, -1)[:, None], axis=1))
    assert abs(float(nll) - float(loss)) < 1e-4


def test_reference_imports_nothing_from_the_system():
    import ast
    import inspect

    tree = ast.parse(inspect.getsource(reference))
    names = [n.module or "" for n in ast.walk(tree)
             if isinstance(n, ast.ImportFrom)] + [
        a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
        for a in n.names]
    assert not [n for n in names if n.startswith("scaletorch_tpu")], names


# ---- what masked rows and masked slots may touch ------------------------------

def _paged_cache(cfg, slots, pages_per_slot=4, page=16):
    pool = init_paged_kv_cache(cfg, slots * pages_per_slot + 1, page,
                               dtype=jnp.float32, slots=slots)
    tables = (np.arange(slots * pages_per_slot, dtype=np.int32) + 1
              ).reshape(slots, pages_per_slot)
    return pool, PagedKVIO(jnp.asarray(tables), page, seq_limit=64)


def test_the_cache_is_one_pytree_of_two_kinds_of_memory(model):
    cfg, _ = model
    pool, _ = _paged_cache(cfg, 3)
    assert isinstance(pool, HybridCache)
    assert pool.k.shape == (2, 13, 4, 16, 16)      # full layers only
    assert pool.state.shape == (6, 3, 4, 8, 16)
    assert pool.state.dtype == jnp.float32
    assert pool.conv.shape == (6, 3, 3, 128)
    assert recurrent_state_bytes(pool) == pool.state.nbytes + pool.conv.nbytes
    dense = init_kv_cache(cfg, 3, 32)
    assert isinstance(dense, HybridCache) and dense.k.shape[:2] == (2, 3)
    with pytest.raises(ValueError, match="slots"):
        init_paged_kv_cache(cfg, 13, 16)


def test_rows_past_the_tail_and_slots_outside_the_mask_touch_nothing(model):
    """A fixed-shape prefill call over three slots: slot 0 admits 20
    rows of its 32, slot 1 is not admitted and holds another request's
    state, slot 2 admits all 32. Slot 1's state and tail stay bit for
    bit; slot 0's are those of its 20 rows alone."""
    cfg, params = model
    pool, kv_io = _paged_cache(cfg, 3)
    dirty = pool._replace(
        state=jax.random.normal(jax.random.PRNGKey(5), pool.state.shape),
        conv=jax.random.normal(jax.random.PRNGKey(6), pool.conv.shape))
    toks = jax.random.randint(jax.random.PRNGKey(7), (3, 32), 0, 128)
    rows = jnp.broadcast_to(jnp.arange(32, dtype=jnp.int32), (3, 32))
    tail_lens = jnp.asarray([20, 9, 32])
    admit = jnp.asarray([True, False, True])
    with jax.default_matmul_precision("highest"):
        _, (_, _, state, conv) = olmo_hybrid.forward_cached(
            params, toks, cfg, tuple(dirty), positions=rows,
            write_mask=admit, kv_io=kv_io,
            row_mask=admit[:, None] & (rows < tail_lens[:, None]))
        # slot 0's 20 rows alone, from a clean cache
        clean, kv_io1 = _paged_cache(cfg, 1)
        _, (_, _, state1, conv1) = olmo_hybrid.forward_cached(
            params, toks[:1, :20], cfg, tuple(clean),
            positions=rows[:1, :20], kv_io=kv_io1)
    np.testing.assert_array_equal(state[:, 1], dirty.state[:, 1])
    np.testing.assert_array_equal(conv[:, 1], dirty.conv[:, 1])
    # float32 sums in another order (three slots a call, one a call)
    np.testing.assert_allclose(state[:, 0], state1[:, 0], atol=3e-4)
    np.testing.assert_allclose(conv[:, 0], conv1[:, 0], atol=3e-4)
    assert float(jnp.max(jnp.abs(state[:, 2] - dirty.state[:, 2]))) > 1e-2


def test_an_inactive_slot_of_a_decode_step_keeps_its_state(model):
    cfg, params = model
    pool, kv_io = _paged_cache(cfg, 2)
    held = pool._replace(
        state=jax.random.normal(jax.random.PRNGKey(8), pool.state.shape),
        conv=jax.random.normal(jax.random.PRNGKey(9), pool.conv.shape))
    active = jnp.asarray([True, False])
    _, (_, _, state, conv) = olmo_hybrid.forward_cached(
        params, jnp.asarray([[3], [4]]), cfg, tuple(held),
        positions=jnp.asarray([[5], [5]]), write_mask=active, kv_io=kv_io,
        row_mask=active[:, None])
    np.testing.assert_array_equal(state[:, 1], held.state[:, 1])
    np.testing.assert_array_equal(conv[:, 1], held.conv[:, 1])
    assert float(jnp.max(jnp.abs(state[:, 0] - held.state[:, 0]))) > 1e-3
    # the tail moved on by one row: its last two are the old last two
    np.testing.assert_array_equal(conv[:, 0, :2], held.conv[:, 0, 1:])


def test_named_scopes_are_in_the_lowered_program(model):
    """``gdn.conv``, ``gdn.recurrence``, ``gdn.gate_norm``: what a
    profile of the step shows for the linear layers' parts."""
    cfg, params = model
    pool, kv_io = _paged_cache(cfg, 2)
    text = jax.jit(
        lambda p, t, c: olmo_hybrid.forward_cached(
            p, t, cfg, c, positions=jnp.full((2, 1), 5, jnp.int32),
            kv_io=kv_io)
    ).lower(params, jnp.zeros((2, 1), jnp.int32), tuple(pool)).as_text(
        debug_info=True)
    for scope in ("gdn.conv", "gdn.recurrence", "gdn.gate_norm", "attn",
                  "mlp"):
        assert scope in text, scope
