"""afmoe (Trinity) at a tiny size on the CPU: every sub-block, the whole
forward and the cached path (prefill, then decode across the window's
edge and across the ring's wrap) against the plain reference
(``benchmarks/reference/trinity.py``) on seeded random weights, the
sigmoid router (the bias steers the choice and never the weight, the
product float32 under bf16), a slot reused by a shorter request, and the
share test: the routed partial results of all shares plus the ungated
shared expert counted once add up to the uncut reference's layer."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import trinity as reference
from scaletorch_tpu.inference.decode import (
    counts_routing,
    make_fill_slots_step,
)
from scaletorch_tpu.inference.kv_cache import (
    PagedKVIO,
    RingKVIO,
    WindowCache,
    carries_state,
    init_paged_kv_cache,
    no_prefix_reason,
    window_of,
    window_ring_pages,
)
from scaletorch_tpu.models import afmoe, qwen3_moe
from scaletorch_tpu.models.layers import rms_norm
from scaletorch_tpu.models.presets import preset
from tests.inference.compiled import compiled_forward_cached

# the tiny preset: two periods of (three window layers, one full), a
# window of 24 tokens, 2 leading dense layers, 8 of 16 routed experts
# held from id 4
TINY = preset("afmoe-tiny")
# every expert held: the uncut layer
WHOLE = dict(TINY, num_experts=16, num_routed_experts=None,
             first_expert_id=0)
WRONG = list(reference.WRONG)
# float32 on the CPU
RTOL_OF_MAX = 2e-4
PAGE = 8
F32 = jnp.float32


def tiny_config(keys=None, **over):
    from scaletorch_tpu.config import ScaleTorchTPUArguments
    from scaletorch_tpu.trainer.trainer import build_model_config

    return build_model_config(ScaleTorchTPUArguments(
        **{**(keys or TINY), **over}, dtype="float32",
        param_dtype="float32"))


@pytest.fixture(scope="module")
def model():
    cfg = tiny_config()
    params = jax.jit(afmoe.init_params, static_argnums=1)(
        jax.random.PRNGKey(3), cfg)
    return cfg, params


def ref_config(keys=None):
    """The reference reads the published key names: the window is
    ``sliding_window`` there, ``sliding_window_size`` in a preset."""
    keys = dict(keys or TINY)
    return dict(keys, tie_word_embeddings=False,
                sliding_window=keys["sliding_window_size"])


def _tokens(shape, seed=0):
    return np.random.default_rng(seed).integers(0, TINY["vocab_size"], shape)


def _close(got, want, rtol=RTOL_OF_MAX):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.abs(got - want).max() <= rtol * np.abs(want).max(), (
        np.abs(got - want).max(), np.abs(want).max())


# the uncached forward as one program: run operation by operation, the
# period loop is traced and dispatched piece by piece
_forward = jax.jit(afmoe.forward, static_argnums=2,
                   static_argnames=("return_hidden",))


def _ref_layer(params, index):
    return {k: v[index].astype(F32)
            for k, v in params["layers"]["block"].items()}


# ---- the configuration --------------------------------------------------------

def test_the_program_builds_the_family_from_its_published_keys(model):
    cfg, params = model
    assert isinstance(cfg, afmoe.AfmoeConfig)
    assert cfg.period_pattern == (afmoe.SLIDING,) * 3 + (afmoe.FULL,)
    assert (cfg.num_window_layers, cfg.num_kv_cache_layers) == (6, 2)
    assert cfg.sparse_layer_ids() == tuple(range(2, 8))
    assert (cfg.router_width, cfg.num_experts, cfg.first_expert_id) == (
        16, 8, 4)
    assert cfg.score_func == "sigmoid" and not cfg.shared_expert_gated
    assert cfg.norm_topk_prob and cfg.route_scale == 2.826
    assert cfg.shared_expert_intermediate_size == 32
    assert window_of(cfg) == 24 and not carries_state(cfg)
    assert counts_routing(cfg)
    assert "window-attention layers" in no_prefix_reason(cfg)
    n = sum(x.size for x in jax.tree.leaves(params))
    assert n == cfg.num_params()
    assert "shared_expert_gate" not in params["layers"]["moe"]
    assert params["layers"]["moe"]["expert_bias"].dtype == jnp.float32
    assert params["layers"]["moe"]["expert_bias"].shape == (6, 16)


def test_the_published_sizes_are_the_26b_model():
    from scaletorch_tpu.config import ScaleTorchTPUArguments
    from scaletorch_tpu.trainer.trainer import build_model_config

    cfg = build_model_config(ScaleTorchTPUArguments(**preset("trinity-mini")))
    assert cfg.layer_kinds.count(afmoe.FULL) == 8
    assert cfg.layer_kinds[3] == afmoe.FULL
    block = 2048 * (3 * 4096 + 2 * 512) + 2 * 128 + 4 * 2048
    moe = (2048 * 128 + 128 + 129 * 3 * 2048 * 1024)
    assert cfg.num_params() == (32 * block + 2 * 3 * 2048 * 6144 + 30 * moe
                                + 2 * 200192 * 2048 + 2048)
    assert 26.0e9 < cfg.num_params() < 26.2e9


@pytest.mark.parametrize("over,error,match", [
    (dict(n_group=2), NotImplementedError, "n_group"),
    (dict(topk_group=2), NotImplementedError, "topk_group"),
    (dict(model_name_or_path="arcee-ai/Trinity-Mini"), NotImplementedError,
     "model_name_or_path"),
    (dict(mlp_only_layers=[0]), NotImplementedError, "mlp_only_layers"),
    (dict(moe_dispatch="einsum"), NotImplementedError, "capacity dispatch"),
    (dict(moe_capacity_factor=2.0), NotImplementedError,
     "capacity dispatch"),
    (dict(num_dense_layers=8), ValueError, "num_dense_layers"),
    (dict(layer_types=["sliding_attention"] * 7 + ["linear_attention"]),
     ValueError, "layer_types"),
    (dict(score_func="tanh"), ValueError, "score_func"),
    (dict(first_expert_id=12), ValueError, "first_expert_id"),
])
def test_refusals_by_name(over, error, match):
    with pytest.raises(error, match=match):
        tiny_config(**over)


def test_a_scaled_rotary_embedding_is_refused_by_name():
    """The published ``rope_scaling`` is null and has no launch
    argument; the configuration class refuses any other."""
    with pytest.raises(NotImplementedError, match="rope_scaling"):
        dataclasses.replace(tiny_config(), rope_scaling={"type": "yarn"})


@pytest.mark.parametrize("asked,std", [(None, 0.02), (1.0, 1.0)])
def test_the_embedding_s_scale_is_the_initialiser_s_or_the_one_asked(
        asked, std):
    """``--embed_init_std`` is a property of random weights a benchmark
    may state (its ``check_data``); the family's own draw is 0.02 like
    every family's, and the stream starts at that times sqrt(hidden)."""
    cfg = tiny_config(**({} if asked is None else {"embed_init_std": asked}))
    assert cfg.embed_init_std == std
    params = jax.jit(afmoe.init_params, static_argnums=1)(
        jax.random.PRNGKey(0), cfg)
    assert abs(float(params["embed_tokens"].std()) / std - 1) < 0.05
    h0 = afmoe._embed(params, jnp.arange(64)[None], cfg)
    assert abs(float(jnp.sqrt(jnp.mean(h0 * h0))) / (std * 8) - 1) < 0.1


def test_the_trainer_refuses_the_family():
    from scaletorch_tpu.config import ScaleTorchTPUArguments
    from scaletorch_tpu.trainer.trainer import Trainer

    with pytest.raises(NotImplementedError, match="afmoe"):
        Trainer(ScaleTorchTPUArguments(**TINY))


def test_a_contiguous_cache_is_refused_by_name(model):
    cfg, params = model
    with pytest.raises(NotImplementedError, match="contiguous cache"):
        afmoe.forward_cached(
            params, jnp.zeros((1, 4), jnp.int32), cfg, (None,) * 4,
            positions=jnp.arange(4)[None])


# ---- sub-blocks against the reference -----------------------------------------

@pytest.mark.parametrize("index,kind", [(0, afmoe.SLIDING), (3, afmoe.FULL)])
def test_attention_mixer_against_the_reference(model, index, kind):
    cfg, params = model
    d = reference.trinity_dims(ref_config())
    assert cfg.layer_kinds[index] == kind
    s = 48                                     # twice the window
    u = jax.random.normal(jax.random.PRNGKey(index), (2, s, 64), F32)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (2, s))
    from scaletorch_tpu.models.layers import get_cos_sin

    rope = get_cos_sin(s, 32, cfg.rope_theta, positions=positions)
    layer = {k: v[index] for k, v in params["layers"]["block"].items()}
    got, _, _ = afmoe.attention_mix(
        u, layer, kind, 0, None, None, rope, positions, cfg, afmoe.SelfKV(),
        None)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([reference.attention_part(
            u[b], _ref_layer(params, index), jnp.asarray(kind == afmoe.SLIDING),
            positions[b], d, 16) for b in range(2)])
    _close(got, want)


def test_dense_mlp_against_the_reference(model):
    from scaletorch_tpu.models.llama import swiglu_mlp

    cfg, params = model
    m = jax.random.normal(jax.random.PRNGKey(5), (3, 7, 64), F32)
    lp = {k: v[1] for k, v in params["layers"]["dense"].items()}
    with jax.default_matmul_precision("highest"):
        want = reference.swiglu(m, lp["gate_proj"], lp["up_proj"],
                                lp["down_proj"])
    _close(swiglu_mlp(m, lp, cfg), want)


def _moe_layer(params, place):
    moe = params["layers"]["moe"]
    small = {k: v[place] for k, v in moe.items()
             if k not in qwen3_moe.EXPERT_KEYS}
    return small, {k: moe[k] for k in qwen3_moe.EXPERT_KEYS}


@pytest.mark.parametrize("place", [0, 5])
def test_sparse_mlp_on_a_share_against_the_reference(model, place):
    cfg, params = model
    d = reference.trinity_dims(ref_config())
    m = jax.random.normal(jax.random.PRNGKey(place), (2, 9, 64), F32)
    small, experts = _moe_layer(params, place)
    got, _, _, routing = qwen3_moe.dropless_mlp(
        m, small, cfg, None, (experts, place))
    with jax.default_matmul_precision("highest"):
        want = reference.moe_part(
            m.reshape(18, 64), small, experts, place, d, 4).reshape(2, 9, 64)
    _close(got, want)
    # 18 tokens x 3 choices: on the held experts or elsewhere, none lost
    assert int(jnp.sum(routing["expert_rows"])) + int(
        routing["elsewhere"]) == 54
    assert int(routing["dropped"]) == 0 and int(routing["elsewhere"]) > 0


# ---- the router ---------------------------------------------------------------

def _route(cfg, small, m):
    """(chosen ids [N, k] over all routed experts, weights [N, k])."""
    scores = jax.nn.sigmoid(m @ small["router"])
    _, choice = jax.lax.top_k(scores + small["expert_bias"],
                              cfg.num_experts_per_tok)
    kept = jnp.take_along_axis(scores, choice, axis=-1)
    return choice, kept / kept.sum(-1, keepdims=True) * cfg.route_scale


def test_the_bias_steers_the_choice_and_never_the_weight():
    """A bias that lifts one expert makes every token choose it, and
    its weight is still its sigmoid score's share of the chosen scores;
    a bias that is the same for all experts changes nothing at all."""
    cfg = tiny_config(WHOLE)
    params = afmoe.init_params(jax.random.PRNGKey(0), cfg)
    small, experts = _moe_layer(params, 0)
    m = jax.random.normal(jax.random.PRNGKey(1), (1, 32, 64), F32)

    def run(bias):
        y, _, _, routing = qwen3_moe.dropless_mlp(
            m, dict(small, expert_bias=bias), cfg, None, (experts, 0))
        return y, routing["expert_rows"]

    base, rows = run(jnp.zeros(16, F32))
    shifted, rows_shifted = run(jnp.full(16, 0.7, F32))
    np.testing.assert_array_equal(rows, rows_shifted)
    np.testing.assert_allclose(base, shifted, rtol=0, atol=0)
    lifted, rows_lifted = run(jnp.zeros(16, F32).at[11].set(5.0))
    assert int(rows_lifted[11]) == 32 > int(rows[11])
    # the reference agrees on the lifted layer, and a reference that
    # lets the bias into the weight does not
    d = reference.trinity_dims(ref_config(WHOLE))
    small_l = dict(small, expert_bias=jnp.zeros(16, F32).at[11].set(5.0))
    with jax.default_matmul_precision("highest"):
        want = reference.moe_part(m[0], small_l, experts, 0, d, 4)
        off = reference.moe_part(m[0], small_l, experts, 0, d, 4,
                                 wrong="bias_in_weights")
    _close(lifted[0], want)
    assert np.abs(np.asarray(off - want)).max() > 0.1 * np.abs(
        np.asarray(want)).max()


def test_the_drawn_bias_changes_a_stated_share_of_the_choices():
    """``init_params`` draws ``expert_bias`` wide enough to matter: at
    the published router width (128, top 8) and the initialiser's own
    scales (the top scores lie ~0.01 apart) it replaces a quarter to a
    third of the chosen experts, and some choice of nearly every
    token."""
    key = jax.random.PRNGKey(0)
    m = jax.random.normal(key, (4096, 256), F32)
    m = m / jnp.sqrt(jnp.mean(m * m, -1, keepdims=True))
    router = 0.02 * jax.random.normal(jax.random.fold_in(key, 1),
                                      (256, 128), F32) * (2048 / 256) ** 0.5
    bias = afmoe.EXPERT_BIAS_INIT_STD * jax.random.normal(
        jax.random.fold_in(key, 2), (128,), F32)
    scores = jax.nn.sigmoid(m @ router)
    plain = jax.nn.one_hot(jax.lax.top_k(scores, 8)[1], 128).sum(1)
    steered = jax.nn.one_hot(jax.lax.top_k(scores + bias, 8)[1], 128).sum(1)
    replaced = jnp.sum(jnp.maximum(plain - steered, 0), -1)
    assert 0.2 < float(jnp.mean(replaced)) / 8 < 0.35
    assert float(jnp.mean(replaced > 0)) > 0.9


def test_the_router_product_is_float32_under_bf16():
    """Served in bfloat16 the router's logits still come from a float32
    product of the (bfloat16-rounded) hidden states: the chosen experts
    are those of a float32 router given the same input."""
    cfg = dataclasses.replace(tiny_config(WHOLE), dtype=jnp.bfloat16)
    params = afmoe.init_params(jax.random.PRNGKey(0), cfg)
    small, experts = _moe_layer(params, 1)
    m = jax.random.normal(jax.random.PRNGKey(2), (1, 64, 64), F32).astype(
        jnp.bfloat16)
    _, _, _, routing = qwen3_moe.dropless_mlp(
        m, small, cfg, None, (experts, 1))
    choice, _ = _route(cfg, small, m[0].astype(F32))
    want = jnp.sum(choice[..., None] == jnp.arange(16), axis=(0, 1))
    np.testing.assert_array_equal(routing["expert_rows"], want)
    lowered = jax.jit(lambda x: qwen3_moe.dropless_mlp(
        x, small, cfg, None, (experts, 1))[0]).lower(m).as_text()
    assert "tensor<64x16xf32>" in lowered


def test_four_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """The deployment's arithmetic: the router is the uncut layer's on
    every chip, each chip computes its own experts' choices under the
    uncut weights, and the partial sums of all shares plus the shared
    expert ONCE are what the uncut reference computes."""
    whole = tiny_config(WHOLE)
    params = afmoe.init_params(jax.random.PRNGKey(4), whole)
    small, experts = _moe_layer(params, 2)
    m = jax.random.normal(jax.random.PRNGKey(6), (2, 16, 64), F32)
    shared = qwen3_moe.shared_expert(
        m.reshape(32, 64), small, whole).reshape(2, 16, 64)
    total, held_rows, elsewhere = shared, 0, 0
    for first in (0, 4, 8, 12):
        cfg = tiny_config(dict(WHOLE, num_experts=4, num_routed_experts=16,
                               first_expert_id=first))
        share = {k: v[:, first:first + 4] for k, v in experts.items()}
        y, _, _, routing = qwen3_moe.dropless_mlp(
            m, small, cfg, None, (share, 2))
        total = total + (y - shared)
        held_rows += int(jnp.sum(routing["expert_rows"]))
        elsewhere += int(routing["elsewhere"])
    assert held_rows == 32 * 3 and elsewhere == 3 * held_rows
    d = reference.trinity_dims(ref_config(WHOLE))
    with jax.default_matmul_precision("highest"):
        want = reference.moe_part(
            m.reshape(32, 64), small, experts, 2, d, 8).reshape(2, 16, 64)
    _close(total, want)


# ---- the whole forward --------------------------------------------------------

def _reference_logits(params, tokens, rows, keys=None, wrong=None, **sizes):
    sizes = {"q_block": 8, "expert_chunk": 4, **sizes}
    fn = reference.make_logits_fn(ref_config(keys), wrong=wrong, **sizes)
    return fn(params, jnp.asarray(tokens), jnp.asarray(rows))


def test_forward_against_the_reference(model):
    cfg, params = model
    tokens = _tokens((2, 64))
    rows = np.broadcast_to(np.arange(64), (2, 64))
    _close(_forward(params, jnp.asarray(tokens), cfg),
           _reference_logits(params, tokens, rows))


@pytest.mark.parametrize("variant", WRONG)
def test_each_wrong_variant_differs(model, variant):
    """Every departure the reference offers moves the logits by far
    more than the float32 system is off: the comparison can tell it."""
    _, params = model
    tokens = _tokens((1, 64), seed=1)
    rows = np.arange(32, 64)[None]
    sound = _reference_logits(params, tokens, rows)
    off = _reference_logits(params, tokens, rows, wrong=variant)
    err = float(jnp.abs(off - sound).max() / jnp.abs(sound).max())
    assert err > 25 * RTOL_OF_MAX, (variant, err)


def test_an_unknown_wrong_variant_is_refused(model):
    _, params = model
    with pytest.raises(ValueError, match="unknown wrong variant"):
        _reference_logits(params, _tokens((1, 8)), np.arange(8)[None],
                          wrong="not_a_variant")


def test_the_reference_loss_and_gain_gradients(model):
    """The contract of ``benchmarks/lib/modules.py``: ``make_loss_fn``
    with gradients of the norm gains (test sizes only: the family is
    served)."""
    _, params = model
    fn = reference.make_loss_fn(ref_config(), q_block=8, loss_chunk=8,
                                expert_chunk=4, with_gradients=True)
    tokens = jnp.asarray(_tokens((17,), seed=2))
    loss, norm, gains = fn(params, tokens[:-1], tokens[1:],
                           jnp.arange(16, dtype=jnp.int32))
    assert np.isfinite(float(loss)) and float(norm) > 0
    assert set(gains["layers"]["block"]) == set(reference.GAIN_KEYS)
    assert abs(float(loss) - np.log(TINY["vocab_size"])) < 1.0


# ---- the cached path ----------------------------------------------------------

def _paged(cfg, slots, max_seq):
    pages = -(-max_seq // PAGE)
    cache = init_paged_kv_cache(cfg, slots * pages + 1, PAGE,
                                dtype=jnp.float32, slots=slots)
    tables = (np.arange(slots * pages, dtype=np.int32) + 1).reshape(
        slots, pages)
    return cache, jnp.asarray(tables)


def _cached(cfg):
    """``afmoe.forward_cached`` compiled (one program a shape): a loop
    of decode steps is one compile and as many calls, not the period
    loop dispatched operation by operation."""
    return compiled_forward_cached(afmoe.forward_cached, cfg)


def _prefill(cfg, params, cache, tables, buf, lens, write=None):
    slots, width = buf.shape
    write = np.ones(slots, bool) if write is None else write
    rows = np.arange(width)[None]
    logits, cache, counts = _cached(cfg)(
        params, jnp.asarray(buf), cfg, tuple(cache),
        positions=jnp.broadcast_to(jnp.arange(width, dtype=jnp.int32),
                                   (slots, width)),
        write_mask=jnp.asarray(write), kv_io=PagedKVIO(tables, PAGE),
        row_mask=jnp.asarray(write[:, None] & (rows < lens[:, None])),
        logit_rows=jnp.asarray(lens - 1, jnp.int32), return_routing=True)
    return logits[:, 0], WindowCache(*cache), counts


def _decode(cfg, params, cache, tables, feed, positions, active=None):
    slots = len(feed)
    active = np.ones(slots, bool) if active is None else active
    logits, cache, counts = _cached(cfg)(
        params, jnp.asarray(feed)[:, None], cfg, tuple(cache),
        positions=jnp.asarray(positions, jnp.int32)[:, None],
        write_mask=jnp.asarray(active), kv_io=PagedKVIO(tables, PAGE),
        row_mask=jnp.asarray(active)[:, None], return_routing=True)
    return logits[:, 0], WindowCache(*cache), counts


def test_the_cache_is_a_pool_and_rings_by_slot(model):
    cfg, _ = model
    cache, _ = _paged(cfg, 3, 96)
    assert isinstance(cache, WindowCache)
    assert window_ring_pages(24, PAGE) == 4
    assert cache.k.shape == (2, 3 * 12 + 1, 2, PAGE, 32)
    assert cache.wk.shape == (6, 3 * 4 + 1, 2, PAGE, 32)
    assert window_ring_pages(2048, 16) == 129


def test_prefill_then_decode_across_the_window_and_the_ring(model):
    """Prompts under and over the window (24) and over the ring (32
    tokens), then 40 decode steps each: positions pass the window's
    edge and wrap the ring twice; every step's logits are the uncached
    forward's at that position, which are the reference's."""
    cfg, params = model
    lens = np.array([10, 30, 48], np.int32)
    depth, slots, width = 40, 3, 48
    tokens = _tokens((slots, width + depth), seed=3)
    full = _forward(params, jnp.asarray(tokens), cfg)
    rows = np.broadcast_to(np.arange(width + depth), tokens.shape)
    _close(full, _reference_logits(params, tokens, rows))
    cache, tables = _paged(cfg, slots, width + depth)
    buf = np.zeros((slots, width), np.int32)
    for i, n in enumerate(lens):
        buf[i, :n] = tokens[i, :n]
    logits, cache, counts = _prefill(cfg, params, cache, tables, buf, lens)
    _close(logits, np.stack([full[i, n - 1] for i, n in enumerate(lens)]))
    # 88 live rows x 3 choices x 6 sparse layers, held or elsewhere
    assert int(counts["routed"]) + int(counts["elsewhere"]) == 88 * 18
    for t in range(depth):
        feed = np.array([tokens[i, n + t] for i, n in enumerate(lens)])
        logits, cache, _ = _decode(cfg, params, cache, tables, feed,
                                   lens + t)
        _close(logits,
               np.stack([full[i, n + t] for i, n in enumerate(lens)]))


def test_a_slot_reused_by_a_shorter_request_reads_nothing_of_the_last(model):
    """Slot 0 serves a 48-token request (its rings full and wrapped),
    then a 9-token one: the second request's logits are those of the
    same request in a fresh cache, bit for bit, with no fill between."""
    cfg, params = model
    tokens = _tokens((2, 64), seed=4)

    def serve(cache, tables, row, n, steps):
        buf = np.zeros((1, 48), np.int32)
        buf[0, :n] = tokens[row, :n]
        out, cache, _ = _prefill(cfg, params, cache, tables, buf,
                                 np.array([n], np.int32))
        seen = [out]
        for t in range(steps):
            out, cache, _ = _decode(cfg, params, cache, tables,
                                    tokens[row, n + t:n + t + 1],
                                    np.array([n + t]))
            seen.append(out)
        return jnp.stack(seen), cache

    cache, tables = _paged(cfg, 1, 64)
    _, used = serve(cache, tables, 0, 48, 12)
    assert float(jnp.abs(used.wk).max()) > 0
    after, _ = serve(used, tables, 1, 9, 30)
    fresh, _ = serve(_paged(cfg, 1, 64)[0], tables, 1, 9, 30)
    np.testing.assert_array_equal(np.asarray(after), np.asarray(fresh))


def test_a_masked_slot_s_ring_is_left_as_it_was(model):
    """An admission prefills slot 1 only: slot 0's pages and rings come
    back bit for bit (its writes went to TRASH)."""
    cfg, params = model
    cache, tables = _paged(cfg, 2, 64)
    buf = _tokens((2, 48), seed=5).astype(np.int32)
    lens = np.array([40, 40], np.int32)
    _, cache, _ = _prefill(cfg, params, cache, tables, buf, lens)
    _, after, _ = _prefill(cfg, params, cache, tables, buf[::-1].copy(), lens,
                           write=np.array([False, True]))
    ring = window_ring_pages(24, PAGE)
    np.testing.assert_array_equal(after.wk[:, 1:1 + ring],
                                  cache.wk[:, 1:1 + ring])
    np.testing.assert_array_equal(after.k[:, 1:9], cache.k[:, 1:9])
    assert not np.array_equal(after.wk[:, 1 + ring:], cache.wk[:, 1 + ring:])


def test_a_prompt_s_dead_rows_never_land_on_its_live_ring_pages(model):
    """The fixed-shape buffer holds rows past the prompt's end; their
    pages wrap onto ring pages that hold the window's live keys, so
    they are written to TRASH: the ring holds exactly the prompt's last
    pages."""
    cfg, _ = model
    tables = jnp.zeros((1, 12), jnp.int32)
    io = RingKVIO(PagedKVIO(tables, PAGE), 24, jnp.array([0]),
                  jnp.array([41]))
    # 41 live rows: logical pages 0-5, the newest four (2-5) are kept
    np.testing.assert_array_equal(
        io.write_tables[0], [0, 0, 3, 4, 1, 2, 0, 0, 0, 0, 0, 0])
    np.testing.assert_array_equal(
        io.tables[0], [1, 2, 3, 4, 1, 2, 3, 4, 1, 2, 3, 4])
    # a decode step at position 70: logical page 8, ring page 1
    io = RingKVIO(PagedKVIO(tables, PAGE), 24, jnp.array([70]),
                  jnp.array([1]))
    assert int(io.write_tables[0, 8]) == 1
    assert int(jnp.sum(io.write_tables != 0)) == 4


def test_the_masked_fill_covers_a_slot_s_ring(model):
    """The quarantine's fill: a slot's pages by the page mask, its
    rings by the slot mask, TRASH and the neighbours untouched."""
    cfg, _ = model
    cache, _ = _paged(cfg, 2, 64)
    cache = WindowCache(*(jnp.ones_like(a) for a in cache))
    pages = np.zeros(cache.k.shape[1], bool)
    pages[9:17] = True
    filled = make_fill_slots_step(donate_cache=False)(
        cache, jnp.asarray(pages), jnp.asarray(0.0, F32),
        jnp.asarray([False, True]))
    assert float(filled.k[:, 9:17].sum()) == 0
    assert float(filled.k[:, :9].min()) == 1
    assert float(filled.wk[:, 5:].sum()) == 0 == float(filled.wv[:, 5:].sum())
    assert float(filled.wk[:, :5].min()) == 1


@pytest.mark.parametrize("window", [None, 24])
def test_the_decode_kernel_with_a_window_in_interpret_mode(window):
    """The Mosaic decode kernel against the gather + softmax pair,
    through a ring's table, at positions under the window, at its edge
    and past two wraps; without a window the walk is the parent's."""
    from scaletorch_tpu.ops.pallas.paged_attention import paged_attention

    slots, hkv, d, pages = 3, 2, 128, 12
    ring = window_ring_pages(24, PAGE) if window else pages
    key = jax.random.PRNGKey(0)
    pool_k = jax.random.normal(key, (2, slots * ring + 1, hkv, PAGE, d), F32)
    pool_v = jax.random.normal(jax.random.fold_in(key, 1), pool_k.shape, F32)
    q = jax.random.normal(jax.random.fold_in(key, 2), (slots, 4, 1, d), F32)
    tables = (1 + ring * jnp.arange(slots)[:, None]
              + jnp.arange(pages)[None, :] % ring).astype(jnp.int32)
    for positions in ([3, 23, 24], [31, 64, 95]):
        pos = jnp.asarray(positions, jnp.int32)[:, None]
        kw = dict(page_size=PAGE, layer=jnp.int32(1), window=window)
        got = paged_attention(q, pool_k, pool_v, tables, pos, kernel=True,
                              interpret=True, **kw)
        want = paged_attention(q, pool_k, pool_v, tables, pos, kernel=False,
                               **kw)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("sq,block,window,live", [
    (3072, 512, None, 21), (3072, 512, 2048, 20), (3072, 256, None, 78),
    (3072, 256, 2048, 72), (8192, 512, None, 136), (8192, 512, 2048, 70),
    (1024, 512, 2048, 3)])
def test_the_block_plan_drops_what_a_window_cannot_see(sq, block, window,
                                                       live):
    """Live (query block, key block) pairs a head: at the cell's 3,072
    rows a window of 2,048 drops 1 of 21 (6 of 78 at 256-wide blocks),
    at 8,192 rows 66 of 136; without a window the plan is the
    parent's, table for table."""
    from scaletorch_tpu.ops.pallas.flash import causal_block_plan

    plan = causal_block_plan(sq, sq, block, block, window)
    assert plan.live == live and plan.dead == 0
    assert len(plan.by_query[0]) == live
    if window is None:
        same = causal_block_plan(sq, sq, block, block)
        for walk in ("by_query", "by_key"):
            for mine, parents in zip(getattr(plan, walk), getattr(same, walk)):
                np.testing.assert_array_equal(mine, parents)
    else:
        q_blk, k_blk, _ = plan.by_query
        # every kept block holds a visible pair, every dropped one none
        assert np.all(k_blk <= q_blk)
        assert np.all((q_blk - k_blk) * block - (block - 1) < window)
        assert live == sum(
            min(i + 1, -(-(window - 1) // block) + 1)
            for i in range(sq // block))


@pytest.mark.parametrize("window", [None, 24, 40])
def test_the_flash_forward_with_a_window_in_interpret_mode(window):
    from scaletorch_tpu.models.layers import sdpa_attention
    from scaletorch_tpu.ops.pallas.flash import flash_forward_with_lse

    key = jax.random.PRNGKey(1)
    q = jax.random.normal(key, (1, 4, 64, 32), F32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 2, 64, 32), F32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 2, 64, 32), F32)
    got, _ = flash_forward_with_lse(
        q, k, v, causal=True, block_q=16, block_kv=16, interpret=True,
        window=window)
    gap = jnp.arange(64)[:, None] - jnp.arange(64)[None, :]
    bias = None if window is None else jnp.where(
        gap >= window, jnp.finfo(F32).min, 0.0)
    want = sdpa_attention(q, k, v, causal=True, bias=bias)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_the_output_norm_is_applied_before_the_residual(model):
    """Four norms a layer: with the two post norms' gains at zero a
    layer adds nothing, whatever its mixers compute."""
    cfg, params = model
    block = dict(params["layers"]["block"])
    for name in ("post_attention_layernorm", "post_mlp_layernorm"):
        block[name] = jnp.zeros_like(block[name])
    muted = dict(params, layers=dict(params["layers"], block=block))
    tokens = jnp.asarray(_tokens((1, 16), seed=6))
    got = _forward(muted, tokens, cfg, return_hidden=True)
    x = params["embed_tokens"][tokens] * 8.0          # sqrt(64)
    _close(got, rms_norm(x, params["norm"], cfg.rms_norm_eps), rtol=1e-6)
