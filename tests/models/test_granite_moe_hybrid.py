"""granitemoehybrid (Granite 4.0-H) at a tiny size on the CPU: the Mamba-2
recurrence (step, sequential and chunked forms against each other at
mild and at strong decays and at lengths that are no multiple of the
chunk; the Mosaic state update interpreted against the step), the whole
forward against the plain reference
(``benchmarks/reference/granite_moe_hybrid.py``) on seeded random
weights (logits, not tokens), every wrong variant and each of the four
multipliers told, the refusals by name, and the share test: the routed
partial results of the two shares plus the ungated shared expert and
the mixer counted once add up to the uncut reference's layer, and the
two vocabulary slices side by side are the uncut logits."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import granite_moe_hybrid as reference
from scaletorch_tpu.inference.decode import counts_routing, rows_name_slots
from scaletorch_tpu.inference.kv_cache import (
    carries_state,
    latent_of,
    no_prefix_reason,
    window_of,
)
from scaletorch_tpu.models import granite_moe_hybrid as granite
from scaletorch_tpu.models import qwen3_moe
from scaletorch_tpu.models.presets import preset
from scaletorch_tpu.ops.pallas.ssd_update import (
    kernel_serves,
    ssd_state_update,
)

# the tiny preset: m m a m m (4 Mamba-2 layers of 4 heads x 16 on a state
# of 8, chunks of 8; one attention layer of 4 heads on 2 K/V heads), 4 of
# 8 routed experts held from id 4, top 3
TINY = preset("granite-moe-hybrid-tiny")
# every expert held: the uncut layer
WHOLE = dict(TINY, num_local_experts=8, num_routed_experts=None,
             first_expert_id=0)
WRONG = list(reference.WRONG)
# float32 on the CPU: the chunked scan reassociates the recurrence, the
# grouped matmul sums in another order; all float32 rounding (measured
# 3e-7 of the largest logit over 5 layers). The weakest departure, a
# state rounded to bfloat16 after each of 27 tokens, reads 3.1e-5
RTOL_OF_MAX = 2e-6
F32 = jnp.float32


def tiny_config(keys=None, **over):
    from scaletorch_tpu.config import ScaleTorchTPUArguments
    from scaletorch_tpu.trainer.trainer import build_model_config

    return build_model_config(ScaleTorchTPUArguments(
        **{**(keys or TINY), **over}, dtype="float32",
        param_dtype="float32"))


def seeded_params(cfg, seed=3):
    return jax.jit(granite.init_params, static_argnums=1)(
        jax.random.PRNGKey(seed), cfg)


@pytest.fixture(scope="module")
def model():
    cfg = tiny_config()
    return cfg, seeded_params(cfg)


def ref_config(keys=None):
    """The reference reads the published key names, which the preset
    has."""
    return dict(keys or TINY)


def _tokens(shape, seed=0):
    return np.random.default_rng(seed).integers(0, TINY["vocab_size"], shape)


def _close(got, want, rtol=RTOL_OF_MAX):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.abs(got - want).max() <= rtol * np.abs(want).max(), (
        np.abs(got - want).max(), np.abs(want).max())


_forward = jax.jit(granite.forward, static_argnums=2,
                   static_argnames=("return_hidden", "sequential"))
_chunked = jax.jit(granite.ssd_chunked, static_argnames=("chunk",))
_sequential = jax.jit(granite.ssd_sequential)


@pytest.fixture(scope="module")
def full(model):
    """Two sequences of 27 tokens (three chunks and a part of one)
    through the uncached forward and through the reference's, at every
    row."""
    cfg, params = model
    tokens = jnp.asarray(_tokens((2, 27), seed=1))
    rows = jnp.broadcast_to(jnp.arange(27), (2, 27))
    with jax.default_matmul_precision("highest"):
        system = _forward(params, tokens, cfg)

    made = {}

    def ref(wrong=None):
        if wrong not in made:       # one compile a variant
            made[wrong] = reference.make_logits_fn(
                ref_config(), q_block=9, expert_chunk=2, wrong=wrong)(
                    params, tokens, rows)
        return made[wrong]

    return tokens, system, ref(), ref


# ---- the recurrence ------------------------------------------------------------

def _rule_inputs(s, scale, seed=0, b=2, h=3, p=8, n=4):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (b, s, h, p), F32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h), F32))
    log_a = -dt * scale * jnp.exp(jax.random.normal(ks[2], (h,), F32))
    bm = jax.random.normal(ks[3], (b, s, n), F32)
    cm = jax.random.normal(ks[4], (b, s, n), F32)
    state = jax.random.normal(ks[5], (b, n, h * p), F32)
    return x, dt, log_a, bm, cm, state


@pytest.mark.parametrize("s,chunk,scale", [
    (24, 8, 1.0), (24, 16, 1.0), (24, 32, 1.0), (24, 16, 30.0),
    (24, 16, 0.01)],
    ids=["whole-chunks", "a-part-of-one", "under-a-chunk", "decay-e-30",
         "hardly-any-decay"])
def test_the_chunked_scan_is_the_recurrence_row_after_row(s, chunk, scale):
    with jax.default_matmul_precision("highest"):
        inputs = _rule_inputs(s, scale)
        y, state = _chunked(*inputs, chunk=chunk)
        want_y, want_state = _sequential(*inputs)
    _close(y, want_y, 2e-6)
    _close(state, want_state, 2e-6)


def test_a_row_that_is_no_token_leaves_the_state_alone():
    """``dt = 0`` and ``log_a = 0``: what the mixer makes of a row
    outside ``row_mask``, and what the chunked form pads with."""
    x, dt, log_a, bm, cm, state = _rule_inputs(1, 1.0)
    _, after = jax.jit(granite.ssd_step)(
        x[:, 0], 0.0 * dt[:, 0], 0.0 * log_a[:, 0], bm[:, 0], cm[:, 0], state)
    np.testing.assert_array_equal(np.asarray(after), np.asarray(state))


def test_the_state_update_kernel_is_the_step_in_place():
    """``ops/pallas/ssd_update.py`` interpreted: one layer of the whole
    ``[layers, slots, N, C]`` buffer advanced as ``ssd_step`` advances
    it, every other layer bit for bit; a slot told not to keep its state
    starts from an empty one whatever the buffer holds (a NaN too), and
    a slot told ``a = 1, dx = 0`` keeps its own bit for bit."""
    layers, b, h, p, n = 3, 3, 4, 64, 8
    x, dt, log_a, bm, cm, _ = (a[:, 0] if a.ndim > 2 and i < 5 else a
                               for i, a in enumerate(_rule_inputs(
                                   1, 1.0, b=b, h=h, p=p, n=n)))
    states = jax.random.normal(jax.random.PRNGKey(9), (layers, b, n, h * p))
    a = granite._by_channel(jnp.exp(log_a), p)
    dx = (dt[..., None] * x).reshape(b, h * p)
    want_y, want = granite.ssd_step(x, dt, log_a, bm, cm, states[1])
    y, after = ssd_state_update(states, a, dx, jnp.ones(b, bool), bm, cm,
                                layer=1, interpret=True)
    _close(y.reshape(want_y.shape), want_y, 1e-6)
    _close(after[1], want, 1e-6)
    for other in (0, 2):
        np.testing.assert_array_equal(np.asarray(after[other]),
                                      np.asarray(states[other]))
    # slot 1 fresh over a NaN buffer; slot 2 not written
    poisoned = states.at[1, 1].set(jnp.nan)
    written = jnp.array([True, True, False])
    y, after = ssd_state_update(
        poisoned, jnp.where(written[:, None], a, 1.0),
        jnp.where(written[:, None], dx, 0.0),
        jnp.array([True, False, True]), bm, cm, layer=1, interpret=True)
    want_y, want = granite.ssd_step(x, dt, log_a, bm, cm,
                                    states[1].at[1].set(0.0))
    _close(y[:2].reshape(2, h, p), want_y[:2], 1e-6)
    _close(after[1, :2], want[:2], 1e-6)
    np.testing.assert_array_equal(np.asarray(after[1, 2]),
                                  np.asarray(states[1, 2]))
    assert kernel_serves(128, 8192) and not kernel_serves(8, 64)
    with pytest.raises(ValueError, match="8k, 128m"):
        ssd_state_update(states[:, :, :, :64], a[:, :64], dx[:, :64],
                         jnp.ones(b, bool), bm, cm, layer=0, interpret=True)


def test_a_decode_row_through_the_kernel_is_the_mixer_s_own():
    """``mamba2_decode`` (the kernel, interpreted) against ``mamba2_mix``
    of one row: output, state and tail, on a configuration wide enough
    for the kernel (2 heads x 64)."""
    cfg = tiny_config(mamba_n_heads=2, mamba_d_head=64, hidden_size=64,
                      num_attention_heads=4)
    layer = jax.tree.map(lambda a: a[1],
                         seeded_params(cfg)["layers"]["mamba"])
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    u = jax.random.normal(ks[0], (3, 1, 64))
    states = jax.random.normal(ks[1], cfg.recurrent_state_shapes(3)[0])
    tail = jax.random.normal(ks[2], cfg.recurrent_state_shapes(3)[1][1:])
    fresh = jnp.array([False, True, False])
    written = jnp.array([True, True, False])
    row_mask = written[:, None]
    out, after, new_tail = granite.mamba2_decode(
        u, layer, cfg, states, 2, tail, fresh, written, row_mask=row_mask,
        interpret=True)
    want_out, want_state, want_tail = granite.mamba2_mix(
        u, layer, cfg, jnp.where(fresh[:, None, None], 0.0, states[2]), tail,
        row_mask=row_mask)
    _close(out[:2], want_out[:2], 1e-5)
    _close(after[2, :2], want_state[:2], 1e-6)
    np.testing.assert_array_equal(np.asarray(after[2, 2]),
                                  np.asarray(states[2, 2]))
    np.testing.assert_array_equal(np.asarray(new_tail),
                                  np.asarray(want_tail))


# ---- the whole forward against the reference -----------------------------------

def test_the_forward_is_the_reference_s(full):
    _, system, ref, _ = full
    _close(system, ref)


@pytest.mark.parametrize("variant", WRONG)
def test_the_reference_tells_each_wrong_variant(full, variant):
    """Each departure a later PR would be tempted by moves the logits by
    many times what separates the system from the reference."""
    _, _, ref, make = full
    off = float(jnp.max(jnp.abs(make(variant) - ref)))
    assert off / float(jnp.max(jnp.abs(ref))) > 5 * RTOL_OF_MAX, variant


def test_the_embedding_and_the_logits_take_their_multipliers(model):
    """``h0 = embedding_multiplier * E[ids]`` and ``logits = x E^T /
    logits_scaling``, on the functions themselves."""
    cfg, params = model
    ids = jnp.asarray(_tokens((2, 5)))
    _close(granite.embed(params, ids, cfg),
           12.0 * params["embed_tokens"][ids], 1e-7)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 32))
    with jax.default_matmul_precision("highest"):
        _close(granite._logits(x, params, cfg),
               x @ params["embed_tokens"].T / 16.0, 1e-6)


@pytest.mark.parametrize("key,variant", [
    ("attention_multiplier", "sqrt_d_attention_scale"),
    ("residual_multiplier", "no_residual_multiplier")])
def test_the_other_two_multipliers_are_the_reference_s(model, full, key,
                                                        variant):
    """The system is the reference's function (above) and is NOT the
    reference's with the multiplier taken for what every other family
    has in its place: the file's value reaches the mixer."""
    cfg, _ = model
    _, system, ref, make = full
    assert getattr(cfg, key) == TINY[key]
    assert float(jnp.max(jnp.abs(system - make(variant)))) > 1000 * \
        RTOL_OF_MAX * float(jnp.max(jnp.abs(ref)))


# ---- a chip's share --------------------------------------------------------------

def test_the_two_shares_add_up_to_the_uncut_layer():
    """Chip 0 holds experts [0, 4) and chip 1 [4, 8) of the same layer:
    each computes the mixer, the router over all 8 and the shared expert
    alike and its own experts' part. Their MLP results less one shared
    expert are the uncut reference's MLP, and with the mixer counted
    once the layer's output is the uncut reference's layer output."""
    whole_cfg = tiny_config(WHOLE)
    params = seeded_params(whole_cfg)
    d = reference.granite_dims(ref_config(WHOLE))
    shares = [tiny_config(dict(WHOLE, num_local_experts=4,
                               num_routed_experts=8, first_expert_id=first))
              for first in (0, 4)]
    eps, res = 1e-5, TINY["residual_multiplier"]

    @jax.jit
    def both(params, h):
        layers = params["layers"]
        block, moe, mamba = ({k: v[0] for k, v in layers[name].items()}
                             for name in ("block", "moe", "mamba"))
        u = reference.rms_norm(h, block["input_layernorm"], eps)
        h1 = h + res * reference.mamba_part(u, mamba, d)
        m = reference.rms_norm(h1, block["post_attention_layernorm"], eps)
        want = h1 + res * reference.moe_part(
            m, {k: v for k, v in moe.items()
                if k not in reference._EXPERT_KEYS},
            {k: layers["moe"][k] for k in reference._EXPERT_KEYS}, 0, d, 2)
        parts, counted = [], []
        for cfg in shares:
            first = cfg.first_expert_id
            held = {k: (v[first:first + 4] if k in qwen3_moe.EXPERT_KEYS
                        else v) for k, v in moe.items()}
            y, _, _, routing = qwen3_moe.dropless_mlp(
                m[None], held, cfg, None, None)
            parts.append(y[0])
            counted.append((routing["dropped"], routing["elsewhere"]
                            + jnp.sum(routing["expert_rows"])))
        shared = qwen3_moe.shared_expert(m, moe, whole_cfg)
        return h1 + res * (parts[0] + parts[1] - shared), want, counted

    with jax.default_matmul_precision("highest"):
        got, want, counted = both(
            params, jax.random.normal(jax.random.PRNGKey(4), (12, 32), F32))
    _close(got, want, 1e-5)
    for dropped, seen in counted:
        assert (int(dropped), int(seen)) == (0, 12 * 3)


def test_the_two_vocabulary_slices_side_by_side_are_the_uncut_logits(
        model, full):
    """The head is the chip's rows of the tied embedding: a chip with
    rows [0, 64) and one with rows [64, 128) hand back, side by side,
    the uncut model's logits."""
    cfg, params = model
    tokens, _, ref, _ = full
    with jax.default_matmul_precision("highest"):
        hidden = _forward(params, tokens, cfg, return_hidden=True)
        halves = [granite._logits(
            hidden, {"embed_tokens": params["embed_tokens"][rows]},
            tiny_config(vocab_size=64))
            for rows in (slice(0, 64), slice(64, 128))]
    _close(jnp.concatenate(halves, axis=-1), ref)


# ---- the family's row and its refusals -------------------------------------------

def test_the_family_s_row_and_cache_kind(model):
    cfg, _ = model
    assert counts_routing(cfg) and rows_name_slots(cfg)
    assert carries_state(cfg) and not latent_of(cfg)
    assert window_of(cfg) is None
    assert "recurrent state" in no_prefix_reason(cfg)
    assert cfg.layer_kinds == ("mamba", "mamba", "attention", "mamba",
                               "mamba")
    assert (cfg.num_mamba_layers, cfg.num_kv_cache_layers) == (4, 1)
    assert cfg.recurrent_state_shapes(3) == ((4, 3, 8, 64), (4, 3, 3, 80))
    assert (cfg.num_experts, cfg.router_width, cfg.first_expert_id) == (
        4, 8, 4)
    assert cfg.sparse_layer_ids() == (0, 1, 2, 3, 4)
    assert not granite.update_kernel_serves(cfg)


def test_the_published_preset_is_the_published_model():
    cfg = tiny_config(preset("granite-4.0-h-small"))
    assert 32.1e9 < cfg.num_params() < 32.3e9
    assert cfg.layer_kinds == (("mamba",) * 5 + ("attention",)
                               + ("mamba",) * 4) * 4
    assert (cfg.mamba_params(), cfg.attention_params()) == (
        102_286_976, 41_943_040)
    assert cfg.recurrent_state_shapes(64) == (
        (36, 64, 128, 8192), (36, 64, 3, 8448))
    assert (cfg.attention_multiplier, cfg.embedding_multiplier,
            cfg.residual_multiplier, cfg.logits_scaling) == (
        0.0078125, 12.0, 0.22, 16.0)
    assert cfg.rope_theta is None and cfg.holds_every_expert


def test_init_params_counts_what_the_config_counts(model):
    cfg, params = model
    assert sum(x.size for x in jax.tree.leaves(params)) == cfg.num_params()
    mamba = params["layers"]["mamba"]
    # Mamba-2's own initialisers: a decay that remembers
    assert float(jnp.min(mamba["A_log"])) >= 0.0
    assert float(jnp.max(mamba["A_log"])) <= np.log(16.0) + 1e-6
    step = jax.nn.softplus(mamba["dt_bias"])
    assert 1e-3 * 0.99 <= float(jnp.min(step)) and float(
        jnp.max(step)) <= 1e-1 * 1.01
    assert float(jnp.min(mamba["D"])) == float(jnp.max(mamba["D"])) == 1.0


@pytest.mark.parametrize("over,error,match", [
    ({"mamba_n_groups": 2}, NotImplementedError, "mamba_n_groups 2"),
    ({"mamba_proj_bias": True}, NotImplementedError, "mamba_proj_bias"),
    ({"position_embedding_type": "rope"}, NotImplementedError,
     "position_embedding_type 'rope'"),
    ({"layer_types": ["mamba", "attention"]}, ValueError, "layer_types"),
    ({"layer_types": ["mamba"] * 5}, ValueError, "both kinds"),
    ({"layer_types": None, "num_hidden_layers": 7}, ValueError,
     "published period"),
    ({"mamba_n_heads": 3}, ValueError, "mamba_expand"),
    ({"first_expert_id": 6}, ValueError, "are not among"),
    ({"moe_dispatch": "einsum"}, NotImplementedError, "dropless"),
    ({"embed_init_std": 1.0}, NotImplementedError, "embed_init_std"),
], ids=["groups", "proj-bias", "rope", "too-few-kinds", "one-kind",
        "no-whole-period", "heads-x-width", "share-outside", "capacity",
        "another-family-s-draw"])
def test_what_is_not_written_is_refused_by_name(over, error, match):
    with pytest.raises(error, match=match):
        tiny_config(**over)


@pytest.mark.parametrize("m,tm", [
    (64, 128), (128, 128), (160, 256), (256, 256), (384, 128), (640, 128),
    (1024, 128), (1025, 512), (4096, 512), (20480, 512)])
def test_a_decode_call_past_256_rows_takes_128_row_tiles(m, tm):
    """64 slots x 10 choices is the first decode call past 256 rows:
    its row tile is 128 (megablox multiplies a whole row tile a group:
    at 512 the call is bound by the MXU); every shape the older cells
    call (64-256 rows a decode step, 4,096 and more a prefill call)
    keeps the tile it had."""
    from scaletorch_tpu.ops import grouped_matmul

    assert grouped_matmul._gmm_tiling(m, 4096, 768) == (tm, 1024, 768)


def test_layer_types_omitted_is_the_published_period():
    cfg = tiny_config(layer_types=None, num_hidden_layers=10)
    assert cfg.layer_kinds == ("mamba",) * 5 + ("attention",) + (
        "mamba",) * 4


def test_the_cached_forward_refuses_a_contiguous_cache(model):
    cfg, params = model
    with pytest.raises(NotImplementedError, match="paged cache"):
        granite.forward_cached(
            params, jnp.zeros((1, 1), jnp.int32), cfg, (None,) * 4,
            positions=jnp.zeros((1, 1), jnp.int32))
    with pytest.raises(ValueError, match="at least two tokens"):
        granite.forward(params, jnp.zeros((1, 1), jnp.int32), cfg)
