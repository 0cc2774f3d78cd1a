"""Jamba at a tiny size on the CPU: the selective scan's forms (one
token, chunked XLA, the Mosaic kernel in interpret mode) against its
definition and against the plain reference's ``lax.scan``, at lengths
that are no multiple of a chunk and with padded rows; the model's
forward and its paged cache against the plain float32 reference on
logits; what masked rows and masked slots may touch; every ``wrong=``
variant of the reference; and the dispatch from the published
``config.json`` keys."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import jamba as reference
from scaletorch_tpu.inference.decode import teacher_forced_decode_paged
from scaletorch_tpu.inference.kv_cache import (
    HybridCache,
    carries_state,
    init_paged_kv_cache,
    recurrent_state_bytes,
)
from scaletorch_tpu.models import jamba
from scaletorch_tpu.models.jamba import ATTENTION, MAMBA, JambaConfig
from scaletorch_tpu.models.olmo_hybrid import short_conv
from scaletorch_tpu.ops.pallas import ssm_scan
from tests.inference.compiled import compiled_forward_cached
from tests.models.test_olmo_hybrid import _err_of_max, _paged_cache

# the published key names at toy widths: two periods of (mamba,
# attention, mamba, mamba), 128 channels of 8 states, ONE K/V head
TINY = {
    "model_type": "jamba", "vocab_size": 128, "hidden_size": 64,
    "intermediate_size": 96, "num_hidden_layers": 8,
    "num_attention_heads": 4, "num_key_value_heads": 1,
    "attn_layer_period": 4, "attn_layer_offset": 1,
    "num_experts": 1, "num_experts_per_tok": 1,
    "mamba_d_state": 8, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_dt_rank": 8, "mamba_conv_bias": True, "mamba_proj_bias": False,
    "max_position_embeddings": 4096, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": True,
}
WRONG = list(reference.WRONG)
# float32 against float32: the same recurrence in another order of
# float32 sums (a chunk's decays computed at once; the reference's state
# [N, C], the system's [N, R, 128]); the logits differ by 2e-6 of the
# largest (~0.6 at this size). The nearest wrong variant, bf16_state,
# is 5e-3 of it.
RTOL_OF_MAX = 2e-5


def tiny_config(**over):
    from scaletorch_tpu.config import ScaleTorchTPUArguments
    from scaletorch_tpu.trainer.trainer import build_model_config

    keys = {k: v for k, v in TINY.items()}
    keys.update(over)
    return build_model_config(ScaleTorchTPUArguments(
        **keys, dtype="float32", param_dtype="float32"))


def seeded_params(cfg, seed=0):
    return jamba.init_params(jax.random.PRNGKey(seed), cfg)


@pytest.fixture(scope="module")
def model():
    cfg = tiny_config()
    return cfg, seeded_params(cfg)


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(1), (2, 72), 0, 128)


@pytest.fixture(scope="module")
def reference_logits(model, tokens):
    """The reference's full forward of each sequence alone, at every
    row."""
    _, params = model
    rows = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)

    def logits(wrong=None):
        return reference.make_logits_fn(TINY, q_block=8, wrong=wrong)(
            params, tokens, rows)

    return logits(), logits


# ---- the dispatch ------------------------------------------------------------

def test_published_keys_build_the_two_kinds_in_order(model):
    cfg, params = model
    assert isinstance(cfg, JambaConfig) and carries_state(cfg)
    assert cfg.layer_kinds == (MAMBA, ATTENTION, MAMBA, MAMBA) * 2
    assert jamba.period_runs(cfg.period_pattern) == (
        (MAMBA, 0, 1), (ATTENTION, 0, 1), (MAMBA, 1, 2))
    assert (cfg.num_periods, cfg.num_mamba_layers,
            cfg.num_kv_cache_layers) == (2, 6, 2)
    assert cfg.rope_theta is None and not cfg.qk_norm
    assert (cfg.mamba_inner, cfg.channel_view) == (128, (1, 128))
    mamba, attn = params["layers"]["mamba"], params["layers"]["attention"]
    assert mamba["in_proj"].shape == (2, 3, 64, 256)
    assert mamba["conv"].shape == (2, 3, 4, 128)
    assert mamba["conv_bias"].shape == (2, 3, 128)
    assert mamba["x_proj"].shape == (2, 3, 128, 8 + 8 + 8)
    assert mamba["A_log"].shape == (2, 3, 8, 128)     # channels last
    assert attn["q_proj"].shape == (2, 1, 64, 64)
    assert attn["k_proj"].shape == (2, 1, 64, 16)     # one K/V head
    assert "q_norm" not in attn
    n = sum(x.size for x in jax.tree.leaves(params))
    assert n == cfg.num_params()


def test_the_published_keys_put_attention_at_layers_7_and_21():
    """The benchmark's configuration file through the program's own
    dispatch: two whole periods of (7 mamba, attention, 6 mamba), 3.03 B
    parameters, the state ``[16, 40, 128]`` a slot and layer."""
    import json
    import os

    from benchmarks.lib.program import serving_model

    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(repo, "benchmarks", "configs",
                           "jamba2-3b-serve.json")) as f:
        config = json.load(f)
    cfg, init = serving_model(config, "bfloat16")
    assert init is jamba.init_params
    kinds = cfg.layer_kinds
    assert [i for i, k in enumerate(kinds) if k == ATTENTION] == [7, 21]
    assert jamba.period_runs(cfg.period_pattern) == (
        (MAMBA, 0, 7), (ATTENTION, 0, 1), (MAMBA, 7, 6))
    assert cfg.num_params() == 3_029_337_472
    assert cfg.recurrent_state_shapes(8) == (
        (26, 8, 16, 40, 128), (26, 8, 3, 5120))
    assert (cfg.num_key_value_heads, cfg.actual_head_dim,
            cfg.tie_word_embeddings) == (1, 128, True)


def test_the_initialisers_are_the_published_ones(model):
    cfg, params = model
    mamba = params["layers"]["mamba"]
    np.testing.assert_allclose(
        np.exp(mamba["A_log"][1, 2, :, 5]), np.arange(1, 9), rtol=1e-6)
    np.testing.assert_array_equal(mamba["D"], 1.0)
    step = jax.nn.softplus(mamba["dt_bias"])
    assert 1e-3 * 0.999 <= float(step.min()) and float(step.max()) <= 0.1001
    assert float(jnp.abs(mamba["dt_proj"]).max()) <= 8 ** -0.5


def test_q_and_k_are_drawn_at_twice_their_fan_in_bound(model):
    """``QK_INIT_SCALE``: ``W_q`` and ``W_k`` of the attention layers
    fill ``+-2 / sqrt(hidden)`` (so ``q k^T`` is 4 times the fan-in
    draw's: random attention that a check of logits can see), ``W_v``
    and ``W_o`` keep their fan-in bound; a constant of the initialiser,
    no field of the configuration and no launch argument."""
    from scaletorch_tpu.config import ScaleTorchTPUArguments

    cfg, params = model
    assert jamba.QK_INIT_SCALE == 2.0
    assert not hasattr(cfg, "attn_score_init_gain")
    assert not hasattr(ScaleTorchTPUArguments(), "attn_score_init_gain")
    attention = params["layers"]["attention"]
    bound = cfg.hidden_size ** -0.5
    for name in ("q_proj", "k_proj"):
        top = float(jnp.abs(attention[name]).max())
        assert 1.5 * bound < top <= 2.0 * bound * (1 + 1e-6), name
    assert float(jnp.abs(attention["v_proj"]).max()) <= bound * (1 + 1e-6)
    assert float(jnp.abs(attention["o_proj"]).max()) <= \
        cfg.q_size ** -0.5 * (1 + 1e-6)


@pytest.mark.parametrize("over, error, match", [
    (dict(num_hidden_layers=6), ValueError, "multiple of attn_layer_period"),
    (dict(attn_layer_offset=4), ValueError, "attn_layer_offset"),
    (dict(mamba_proj_bias=True), NotImplementedError, "mamba_proj_bias"),
    (dict(num_experts=16, num_experts_per_tok=2), NotImplementedError,
     "routed"),
    (dict(model_name_or_path="ai21labs/AI21-Jamba2-3B"),
     NotImplementedError, "weight loading"),
])
def test_what_is_not_written_is_refused_by_name(over, error, match):
    with pytest.raises(error, match=match):
        tiny_config(**over)


def test_the_trainer_refuses_the_family_by_name():
    from scaletorch_tpu.config import ScaleTorchTPUArguments
    from scaletorch_tpu.trainer.trainer import Trainer

    with pytest.raises(NotImplementedError, match="selective scan has no "
                                                  "backward"):
        Trainer(ScaleTorchTPUArguments(**TINY))


# ---- the scan ------------------------------------------------------------------

def _scan_inputs(seed, b, s, rows=1, lanes=128, n=8):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    u = jax.random.normal(k[0], (b, s, rows, lanes))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, s, rows, lanes)) - 2.0)
    a = -jnp.exp(jax.random.normal(k[2], (n, rows, lanes)))
    bm = jax.random.normal(k[3], (b, s, n))
    cm = jax.random.normal(k[4], (b, s, n))
    state = jax.random.normal(k[5], (b, n, rows, lanes))
    return u, dt, a, bm, cm, state


@pytest.mark.parametrize("length", [2, 15, 16, 17, 40, 100])
def test_chunked_equals_sequential_at_any_length(length):
    args = _scan_inputs(length, 2, length)
    y, s = jax.jit(jamba.selective_scan_chunked)(*args)
    y0, s0 = jax.jit(jamba.selective_scan_sequential)(*args)
    np.testing.assert_allclose(y, y0, atol=2e-5)
    np.testing.assert_allclose(s, s0, atol=2e-5)


@pytest.mark.parametrize("length, block_t", [(8, 128), (37, 16), (64, 16)])
def test_the_kernel_in_interpret_mode_equals_sequential(length, block_t):
    """1,024 channels as one ``[8, 128]`` register a state index (u, dt
    and y as ``[B, S, 1024]``, a token's row re-laid in the kernel),
    from a state that is not zero, at a length that is no multiple of
    the block: the padded rows' ``dt = 0`` must leave the state
    alone."""
    args = _scan_inputs(3, 2, length, rows=8, n=16)
    u, dt = (x.reshape(2, length, 1024) for x in args[:2])
    y, s = ssm_scan.ssm_scan_fwd(u, dt, *args[2:], block_t=block_t,
                                 interpret=True)
    y0, s0 = jax.jit(jamba.selective_scan_sequential)(*args)
    np.testing.assert_allclose(y.reshape(y0.shape), y0, atol=2e-5)
    np.testing.assert_allclose(s, s0, atol=2e-5)


def test_the_kernel_refuses_channels_that_fill_no_register():
    assert ssm_scan.kernel_serves(40, 128)
    assert not ssm_scan.kernel_serves(1, 128)
    assert not ssm_scan.kernel_serves(8, 64)
    u, dt, *rest = _scan_inputs(0, 1, 4)
    with pytest.raises(ValueError, match=r"\[8k, 128\]"):
        ssm_scan.ssm_scan_fwd(u.reshape(1, 4, 128), dt.reshape(1, 4, 128),
                              *rest)


def test_every_form_is_the_reference_s_scan_over_tokens():
    """The system's forms on ``[B, S, R, L]`` against the plain
    reference's ``lax.scan`` on ``[S, C]``, from an empty state."""
    u, dt, a, bm, cm, _ = _scan_inputs(9, 1, 45)
    zero = jnp.zeros((1, 8, 1, 128))
    want = reference.selective_scan(
        u[0, :, 0], dt[0, :, 0], a[:, 0], bm[0], cm[0])
    for form in (jamba.selective_scan_sequential,
                 jamba.selective_scan_chunked):
        y, _ = form(u, dt, a, bm, cm, zero)
        np.testing.assert_allclose(y[0, :, 0], want, atol=2e-5)


def test_a_row_with_dt_zero_is_no_token():
    """``exp(0) = 1`` and ``dt B u = 0``: rows of ``dt = 0`` between and
    after the tokens leave the state where the tokens alone put it."""
    u, dt, a, bm, cm, state = _scan_inputs(4, 2, 24)
    live = jnp.arange(24) < 13
    masked = jnp.where(live[None, :, None, None], dt, 0.0)
    for form in (jamba.selective_scan_sequential,
                 jamba.selective_scan_chunked):
        _, s_all = form(u, masked, a, bm, cm, state)
        _, s_live = form(u[:, :13], dt[:, :13], a, bm[:, :13], cm[:, :13],
                         state)
        np.testing.assert_allclose(s_all, s_live, atol=1e-6)


def test_the_step_is_the_recurrence_written_out():
    u, dt, a, bm, cm, state = _scan_inputs(2, 2, 1)
    y, new = jamba.selective_scan_step(
        u[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0], state)
    b, n, c = 1, 3, 77
    want = (np.exp(dt[b, 0, 0, c] * a[n, 0, c]) * state[b, n, 0, c]
            + dt[b, 0, 0, c] * bm[b, 0, n] * u[b, 0, 0, c])
    np.testing.assert_allclose(new[b, n, 0, c], want, rtol=1e-6)
    np.testing.assert_allclose(
        y[b, 0, c], jnp.sum(new[b, :, 0, c] * cm[b, 0]), rtol=1e-5)


def test_short_conv_takes_a_bias_and_is_the_same_without():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 6))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 6))
    tail = jax.random.normal(jax.random.PRNGKey(2), (2, 3, 6))
    bias = jnp.arange(6.0)
    plain, rows = short_conv(x, w, tail)
    biased, rows_b = short_conv(x, w, tail, bias)
    np.testing.assert_array_equal(rows, rows_b)
    np.testing.assert_allclose(biased, plain + bias, atol=1e-6)
    np.testing.assert_array_equal(short_conv(x, w, tail, None)[0], plain)


# ---- the model against the plain reference -----------------------------------

def test_forward_is_the_reference_in_both_forms(model, tokens,
                                                reference_logits):
    cfg, params = model
    ref, _ = reference_logits
    with jax.default_matmul_precision("highest"):
        chunked = jamba.forward(params, tokens, cfg, scan="chunked")
        row_by_row = jamba.forward(params, tokens, cfg, scan="sequential")
    assert _err_of_max(chunked, ref) < RTOL_OF_MAX
    assert _err_of_max(row_by_row, ref) < RTOL_OF_MAX


@pytest.mark.parametrize("prefill_len", [1, 23, 40])
def test_prefill_then_decode_through_the_cache_is_the_reference(
        model, tokens, reference_logits, prefill_len):
    """A prompt of 1, 23 or 40 rows (no multiple of the chunk of 16),
    then every later token through the state, the convolution tail and
    the one-K/V-head page pool: the reference's full forward, on
    logits."""
    cfg, params = model
    ref, _ = reference_logits
    with jax.default_matmul_precision("highest"):
        paged = teacher_forced_decode_paged(
            params, cfg, tokens, page_size=8, prefill_len=prefill_len,
            forward_fn=compiled_forward_cached(jamba.forward_cached, cfg))
    assert _err_of_max(paged, ref) < RTOL_OF_MAX


@pytest.mark.parametrize("variant", WRONG)
def test_every_wrong_variant_differs_from_the_honest_reference(
        reference_logits, variant):
    ref, logits = reference_logits
    assert _err_of_max(logits(variant), ref) > 100 * RTOL_OF_MAX


def test_an_unknown_wrong_variant_is_refused():
    with pytest.raises(ValueError, match="unknown wrong variant"):
        reference.make_logits_fn(TINY, q_block=8, wrong="nothing")(
            seeded_params(tiny_config()), jnp.zeros((1, 8), jnp.int32),
            jnp.zeros((1, 1), jnp.int32))


def test_reference_offers_its_loss_and_the_gain_gradients(model, tokens):
    cfg, params = model
    seq = tokens[0, :64]
    loss, norm, gains = reference.make_loss_fn(
        TINY, q_block=32, loss_chunk=32, with_gradients=True)(
            params, seq, jnp.roll(seq, -1), jnp.arange(64))
    assert np.isfinite(float(loss)) and float(norm) > 0
    assert set(gains["layers"]["mamba"]) == set(reference.GAIN_KEYS)
    assert set(gains["layers"]["attention"]) == {
        "input_layernorm", "pre_ff_layernorm"}
    with jax.default_matmul_precision("highest"):
        logits = jamba.forward(params, seq[None], cfg)
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

def test_the_cache_is_one_pytree_of_two_kinds_of_memory(model):
    """The hook carries a state of any rank after ``[layers, slots]``:
    this family's is ``[N, R, lanes]``, three axes where the delta
    rule's ``[H, d_k, d_v]`` happens to have three too, and a tail."""
    cfg, _ = model
    pool, _ = _paged_cache(cfg, 3)
    assert isinstance(pool, HybridCache)
    assert pool.k.shape == (2, 13, 1, 16, 16)      # attention layers only
    assert pool.state.shape == (6, 3, 8, 1, 128)
    assert pool.state.dtype == jnp.float32
    assert pool.conv.shape == (6, 3, 3, 128)
    assert recurrent_state_bytes(pool) == pool.state.nbytes + pool.conv.nbytes
    with pytest.raises(ValueError, match="slots"):
        init_paged_kv_cache(cfg, 13, 16)


def test_a_state_of_another_rank_goes_through_the_hook_and_the_fill():
    """What ``recurrent_state_shapes`` may return: any shapes that begin
    ``[layers, slots]``. A rank-3 state and a rank-6 one are built,
    zeroed and filled by slot like the ranks the families have."""
    from scaletorch_tpu.inference.decode import make_fill_slots_step

    class Odd(JambaConfig):
        def recurrent_state_shapes(self, slots):
            return (2, slots, 5), (2, slots, 1, 2, 3, 4)

    cfg = Odd(**{k: getattr(tiny_config(), k) for k in (
        "vocab_size", "hidden_size", "intermediate_size",
        "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
        "attn_layer_period", "attn_layer_offset", "mamba_d_state",
        "mamba_dt_rank")})
    pool = init_paged_kv_cache(cfg, 5, 8, dtype=jnp.float32, slots=2)
    assert pool.state.shape == (2, 2, 5)
    assert pool.conv.shape == (2, 2, 1, 2, 3, 4)
    filled = make_fill_slots_step(donate_cache=False)(
        pool, jnp.zeros((5,), bool), 7.0, jnp.asarray([False, True]))
    np.testing.assert_array_equal(filled.state[:, 0], 0.0)
    np.testing.assert_array_equal(filled.state[:, 1], 7.0)
    np.testing.assert_array_equal(filled.conv[:, 1], 7.0)
    np.testing.assert_array_equal(filled.k, 0.0)


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
        _, (_, _, state, conv) = jamba.forward_cached(
            params, toks, cfg, tuple(dirty), positions=rows,
            write_mask=admit, kv_io=kv_io,
            row_mask=admit[:, None] & (rows < tail_lens[:, None]))
        # slot 0's 20 rows alone, from a clean cache
        clean, kv_io1 = _paged_cache(cfg, 1)
        _, (_, _, state1, conv1) = jamba.forward_cached(
            params, toks[:1, :20], cfg, tuple(clean),
            positions=rows[:1, :20], kv_io=kv_io1)
    np.testing.assert_array_equal(state[:, 1], dirty.state[:, 1])
    np.testing.assert_array_equal(conv[:, 1], dirty.conv[:, 1])
    np.testing.assert_allclose(state[:, 0], state1[:, 0], atol=1e-5)
    np.testing.assert_allclose(conv[:, 0], conv1[:, 0], atol=1e-5)
    assert float(jnp.max(jnp.abs(state[:, 2] - dirty.state[:, 2]))) > 1e-2


def test_an_inactive_slot_of_a_decode_step_keeps_its_state(model):
    cfg, params = model
    pool, kv_io = _paged_cache(cfg, 2)
    held = pool._replace(
        state=jax.random.normal(jax.random.PRNGKey(8), pool.state.shape),
        conv=jax.random.normal(jax.random.PRNGKey(9), pool.conv.shape))
    active = jnp.asarray([True, False])
    _, (_, _, state, conv) = jamba.forward_cached(
        params, jnp.asarray([[3], [4]]), cfg, tuple(held),
        positions=jnp.asarray([[5], [5]]), write_mask=active, kv_io=kv_io,
        row_mask=active[:, None])
    np.testing.assert_array_equal(state[:, 1], held.state[:, 1])
    np.testing.assert_array_equal(conv[:, 1], held.conv[:, 1])
    assert float(jnp.max(jnp.abs(state[:, 0] - held.state[:, 0]))) > 1e-3
    # the tail moved on by one row: its last two are the old last two
    np.testing.assert_array_equal(conv[:, 0, :2], held.conv[:, 0, 1:])


def test_a_contiguous_cache_is_refused_by_name(model):
    cfg, params = model
    pool, _ = _paged_cache(cfg, 1)
    with pytest.raises(NotImplementedError, match="paged cache"):
        jamba.forward_cached(
            params, jnp.zeros((1, 4), jnp.int32), cfg, tuple(pool),
            positions=jnp.arange(4, dtype=jnp.int32)[None])


def test_named_scopes_are_in_the_lowered_program(model):
    """``ssm.conv``, ``ssm.params``, ``ssm.scan``, ``ssm.gate``,
    ``attn.full``, ``mlp.dense``: what a profile of either step shows
    for the layers' parts."""
    cfg, params = model
    pool, kv_io = _paged_cache(cfg, 2)
    for width in (1, 8):
        text = jax.jit(
            lambda p, t, c: jamba.forward_cached(
                p, t, cfg, c,
                positions=jnp.arange(width, dtype=jnp.int32)[None]
                + jnp.zeros((2, 1), jnp.int32), kv_io=kv_io)
        ).lower(params, jnp.zeros((2, width), jnp.int32),
                tuple(pool)).as_text(debug_info=True)
        for scope in ("ssm.conv", "ssm.params", "ssm.scan", "ssm.gate",
                      "attn.full", "mlp.dense"):
            assert scope in text, (width, scope)
