"""mimo_v2_flash (MiMo-V2-Flash) at a tiny size on the CPU: the whole
forward and the cached path (a one-row prefill that names its slot,
then decode through the pool and the rings of two shapes) against the
plain reference (``benchmarks/reference/mimo_v2_flash.py``) on seeded
random weights (logits, not tokens); the sink against a softmax over
one appended column, in the lax forms and in the two kernels
interpreted; a key wider than its value; the two rope bases on the
first third of a head; the published 48-entry lists accepted whole;
every wrong variant rejected; the refusals by name; and the share
test: the routed partial results of sixteen shares of one expert add
up to the uncut reference's layer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import mimo_v2_flash as reference
from scaletorch_tpu.inference.decode import counts_routing, rows_name_slots
from scaletorch_tpu.inference.kv_cache import (
    PagedKVIO,
    WindowCache,
    cache_nbytes,
    init_kv_cache,
    init_paged_kv_cache,
    kv_cache_bytes,
    kv_head_shapes,
    no_prefix_reason,
    stored_key_width,
    window_cache_bytes,
    window_of,
    window_ring_pages,
)
from scaletorch_tpu.models import mimo_v2_flash as mimo
from scaletorch_tpu.models import qwen3_moe
from scaletorch_tpu.models.layers import (
    apply_rotary_pos_emb,
    cached_sdpa_attention,
    get_cos_sin,
    sdpa_attention,
    softmax_with_sink,
)
from scaletorch_tpu.models.presets import preset
from scaletorch_tpu.ops.flash_attention import prefill_self_attention
from scaletorch_tpu.ops.pallas.flash import flash_forward_with_lse
from scaletorch_tpu.ops.pallas.paged_attention import (
    paged_attention,
    paged_write,
)
from tests.inference.compiled import compiled_forward_cached

# the tiny preset: the published pattern's first seven layers (a dense
# full layer, then 5 window + 1 full sparse layers), keys 24 wide on
# values 16 with 8 dims turned, a window of 20 (a ring of 4 pages of 8),
# 2 and 4 K/V heads, 4 of 16 routed experts held from id 4
TINY = preset("mimo-v2-flash-tiny")
# every expert held: the uncut layer
WHOLE = dict(TINY, n_routed_experts=16, num_routed_experts=None,
             first_expert_id=0)
WRONG = list(reference.WRONG)
# float32 on the CPU: the grouped matmul sums in another order than the
# reference's dense expert sum, the key blocks of the prefill another
# than the full softmax; all float32 rounding (measured 7e-7 of the
# largest logit over 7 layers)
RTOL_OF_MAX = 2e-5
PAGE = 8
F32 = jnp.float32


def tiny_config(keys=None, **over):
    from scaletorch_tpu.config import ScaleTorchTPUArguments
    from scaletorch_tpu.trainer.trainer import build_model_config

    return build_model_config(ScaleTorchTPUArguments(
        **{**(keys or TINY), **over}, dtype="float32",
        param_dtype="float32"))


def seeded_params(cfg, seed=3):
    return jax.jit(mimo.init_params, static_argnums=1)(
        jax.random.PRNGKey(seed), cfg)


@pytest.fixture(scope="module")
def model():
    cfg = tiny_config()
    return cfg, seeded_params(cfg)


def ref_config(keys=None):
    """The reference reads the published key names: the preset's, with
    the window under ``sliding_window`` (the launch arguments carry it
    as ``sliding_window_size``; the published file has both)."""
    keys = dict(keys or TINY)
    return dict(keys, sliding_window=keys["sliding_window_size"])


def _tokens(shape, seed=0):
    return np.random.default_rng(seed).integers(0, TINY["vocab_size"], shape)


def _close(got, want, rtol=RTOL_OF_MAX):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.abs(got - want).max() <= rtol * np.abs(want).max(), (
        np.abs(got - want).max(), np.abs(want).max())


_forward = jax.jit(mimo.forward, static_argnums=2,
                   static_argnames=("return_hidden",))


@pytest.fixture(scope="module")
def full(model):
    """Two sequences of 72 tokens (three and a half windows) through the
    uncached forward and through the reference's, at every row."""
    cfg, params = model
    tokens = jnp.asarray(_tokens((2, 72), seed=1))
    rows = jnp.broadcast_to(jnp.arange(72), (2, 72))
    with jax.default_matmul_precision("highest"):
        system = _forward(params, tokens, cfg)

    def ref(wrong=None):
        return reference.make_logits_fn(
            ref_config(), q_block=8, expert_chunk=2, wrong=wrong)(
                params, tokens, rows)

    return tokens, system, ref(), ref


# ---- the family, from its published keys ------------------------------------------

def test_the_program_builds_the_family_from_its_published_keys(model):
    cfg, params = model
    assert type(cfg) is mimo.MimoV2FlashConfig
    assert (cfg.hybrid_layer_pattern, cfg.moe_layer_freq) == (
        (0, 1, 1, 1, 1, 0, 1), (0, 1, 1, 1, 1, 1, 1))
    assert (cfg.num_kv_cache_layers, cfg.num_window_layers) == (2, 5)
    assert (cfg.kv_heads(mimo.FULL), cfg.kv_heads(mimo.WINDOW)) == (2, 4)
    assert (cfg.actual_head_dim, cfg.v_head_dim, cfg.rotary_dim) == (24, 16, 8)
    assert (cfg.rope_theta, cfg.swa_rope_theta) == (5e6, 1e4)
    assert (cfg.sliding_window, cfg.rms_norm_eps) == (20, 1e-5)
    assert cfg.route_scale == 1.0 and cfg.shared_expert_intermediate_size == 0
    assert (cfg.num_experts, cfg.router_width, cfg.first_expert_id) == (
        4, 16, 4)
    assert cfg.sparse_layer_ids() == (1, 2, 3, 4, 5, 6)
    assert counts_routing(cfg) and rows_name_slots(cfg)
    assert window_of(cfg) == 20 and no_prefix_reason(cfg)
    layers = params["layers"]
    assert layers["full"]["k_proj"].shape == (2, 64, 2 * 24)
    assert layers["window"]["v_proj"].shape == (5, 64, 4 * 16)
    sinks = layers["window"]["attention_sink_bias"]
    assert sinks.shape == (5, 8) and sinks.dtype == F32
    assert "attention_sink_bias" not in layers["full"]
    assert layers["moe"]["expert_bias"].shape == (6, 16)
    assert sum(x.size for x in jax.tree.leaves(params)) == cfg.num_params()


def test_the_published_sizes_are_the_309b_model():
    from scaletorch_tpu.config import ScaleTorchTPUArguments
    from scaletorch_tpu.trainer.trainer import build_model_config

    cfg = build_model_config(ScaleTorchTPUArguments(
        **preset("mimo-v2-flash"), dtype="bfloat16", param_dtype="bfloat16"))
    assert (cfg.num_kv_cache_layers, cfg.num_window_layers) == (9, 39)
    assert len(cfg.sparse_layer_ids()) == 47
    assert cfg.kv_head_shapes == ((4, 256, 128), (8, 256, 128))
    assert cfg.rotary_dim == 64 and cfg.holds_every_expert
    assert (cfg.mixer_params(mimo.FULL), cfg.mixer_params(mimo.WINDOW)) == (
        89_128_960, 94_371_904)
    assert 308e9 < cfg.num_params() < 310e9


def test_the_published_48_entry_lists_are_accepted_whole():
    """The published stack (``0 1 1 1 1 0`` then ``1 1 1 1 1 0`` seven
    times: the first period one window layer short; a dense layer and
    47 sparse ones) at the tiny widths: 9 full and 39 window layers, the
    parameter stacks by kind, and a forward that runs (operation by
    operation: nothing of 48 unrolled layers is compiled; the
    seven-layer stack is the one held to the reference)."""
    lists = preset("mimo-v2-flash")
    keys = dict(TINY, num_hidden_layers=48,
                hybrid_layer_pattern=lists["hybrid_layer_pattern"],
                moe_layer_freq=lists["moe_layer_freq"])
    cfg = tiny_config(keys)
    assert (cfg.num_kv_cache_layers, cfg.num_window_layers) == (9, 39)
    assert cfg.sparse_layer_ids() == tuple(range(1, 48))
    params = seeded_params(cfg)
    layers = params["layers"]
    assert layers["full"]["k_proj"].shape[0] == 9
    assert layers["window"]["k_proj"].shape[0] == 39
    assert layers["window"]["attention_sink_bias"].shape == (39, 8)
    assert layers["moe"]["router"].shape[0] == 47
    assert layers["dense"]["gate_proj"].shape[0] == 1
    assert sum(x.size for x in jax.tree.leaves(params)) == cfg.num_params()
    logits = mimo.forward(params, jnp.asarray(_tokens((1, 8), seed=4)), cfg)
    assert logits.shape == (1, 8, 128) and bool(jnp.all(jnp.isfinite(logits)))
    assert reference.mimo_dims(ref_config(keys))["kinds"].count(1) == 39


@pytest.mark.parametrize("over,error,match", [
    (dict(n_group=2), NotImplementedError, "n_group 2"),
    (dict(topk_group=2), NotImplementedError, "topk_group 2"),
    (dict(swa_head_dim=32), NotImplementedError, "swa_head_dim 32"),
    (dict(swa_v_head_dim=24), NotImplementedError, "swa_v_head_dim 24"),
    (dict(add_full_attention_sink_bias=True), NotImplementedError,
     "add_full_attention_sink_bias"),
    (dict(n_shared_experts=1), NotImplementedError, "n_shared_experts 1"),
    (dict(hybrid_layer_pattern=[0, 1, 1]), ValueError,
     "hybrid_layer_pattern names 3 layers"),
    (dict(moe_layer_freq=[0] * 7), ValueError, "no sparse layer"),
    (dict(moe_layer_freq=[0, 2, 1, 1, 1, 1, 1]), ValueError, "0 / 1"),
    (dict(first_expert_id=14), ValueError, "are not among"),
    (dict(moe_dispatch="einsum"), NotImplementedError, "capacity dispatch"),
    (dict(mlp_only_layers=[1]), NotImplementedError, "moe_layer_freq"),
    (dict(model_name_or_path="XiaomiMiMo/MiMo-V2-Flash"),
     NotImplementedError, "HF config"),
])
def test_what_the_family_refuses_it_refuses_by_name(over, error, match):
    with pytest.raises(error, match=match):
        tiny_config(**over)


def test_a_repeated_window_head_width_that_agrees_is_accepted():
    cfg = tiny_config(swa_head_dim=24, swa_v_head_dim=16)
    assert cfg.kv_head_shapes == ((2, 128, 16), (4, 128, 16))


def test_the_trainer_refuses_the_family_and_says_what_is_missing():
    from scaletorch_tpu.models.families import FAMILIES

    row = FAMILIES["mimo_v2_flash"]
    assert row.counts_routing and row.rows_name_slots and not row.loads_hf
    for word in ("window", "sink", "multi-token-prediction", "HF weight",
                 "--preset mimo-v2-flash"):
        assert word in row.untrained, word


def test_a_contiguous_cache_is_refused_by_name(model):
    cfg, params = model
    with pytest.raises(TypeError, match="stores keys 128 wide beside"):
        init_kv_cache(cfg, 1, 16)
    with pytest.raises(NotImplementedError, match="contiguous cache"):
        mimo.forward_cached(params, jnp.zeros((1, 4), jnp.int32), cfg,
                            (None,) * 4, positions=jnp.arange(4)[None])


def test_the_draw_s_scales_move_their_own_leaves_alone(model):
    cfg, params = model
    other = seeded_params(tiny_config(
        embed_init_std=1.0, routed_expert_init_scale=0.25,
        query_init_scale=3.0, sink_init_mean=0.5))
    scaled = {("embed_tokens",): 50.0,
              ("layers", "moe", "expert_down_proj"): 0.25,
              ("layers", "block", "q_proj"): 3.0}
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    for path, leaf in flat:
        names = tuple(k.key for k in path)
        got = other
        for name in names:
            got = got[name]
        if names == ("layers", "window", "attention_sink_bias"):
            np.testing.assert_allclose(got - 0.5, leaf - 2.5, atol=1e-6)
            assert abs(float(leaf.mean()) - 2.5) < 0.4   # the preset's mean
        else:
            np.testing.assert_allclose(
                got, leaf * scaled.get(names, 1.0), rtol=1e-6, err_msg=names)


# ---- the sink, the two widths, the two rope bases -----------------------------------

def test_the_sink_is_one_more_column_of_the_softmax_that_carries_no_value():
    rng = np.random.default_rng(0)
    scores = jnp.asarray(rng.normal(size=(2, 3, 5, 7)) * 2, F32)
    sink = jnp.asarray([0.5, -1.0, 3.0], F32)
    got = softmax_with_sink(scores, sink)
    e = np.exp(np.asarray(scores, np.float64))
    want = e / (np.exp(np.asarray(sink, np.float64))[None, :, None, None]
                + e.sum(-1, keepdims=True))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert np.all(np.asarray(got).sum(-1) < 1.0)
    np.testing.assert_allclose(softmax_with_sink(scores),
                               jax.nn.softmax(scores, -1))


def _qkv(rng, b, hq, hkv, s, dk, dv, dtype=F32):
    return (jnp.asarray(rng.normal(size=(b, hq, s, dk)), dtype),
            jnp.asarray(rng.normal(size=(b, hkv, s, dk)), dtype),
            jnp.asarray(rng.normal(size=(b, hkv, s, dv)), dtype))


def _plain(q, k, v, window, sink):
    """Causal banded attention with the sink as an appended column,
    written out in numpy float64."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    n_rep = q.shape[1] // k.shape[1]
    k, v = np.repeat(k, n_rep, 1), np.repeat(v, n_rep, 1)
    s = np.einsum("bhqd,bhkd->bhqk", q, k) * q.shape[-1] ** -0.5
    i, j = np.arange(q.shape[2])[:, None], np.arange(k.shape[2])[None, :]
    seen = (j <= i) & ((i - j < window) if window else True)
    s = np.where(seen, s, -np.inf)
    if sink is not None:
        col = np.broadcast_to(np.asarray(sink, np.float64)[None, :, None, None],
                              s.shape[:-1] + (1,))
        s = np.concatenate([s, col], -1)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = (p / p.sum(-1, keepdims=True))[..., :k.shape[2]]
    return np.einsum("bhqk,bhkd->bhqd", p, v)


@pytest.mark.parametrize("window,sinked", [
    (20, True), (None, False), (128, True)],
    ids=["window-sink", "full", "window-128"])
def test_the_flash_forward_with_a_sink_and_two_widths_interpreted(
        window, sinked):
    """``flash_fwd`` in interpret mode at keys 192 on values 128, 8
    query heads on 2 K/V heads, in 32 x 32 blocks (a window of 20 under
    a block) and, for the published window of 128, in 256 x 256 blocks;
    against the softmax written out.
    Float32 in key blocks: 2e-6 of outputs of size 1."""
    rng = np.random.default_rng(1)
    s = 512 if window == 128 else 96
    q, k, v = _qkv(rng, 1, 8, 2, s, 192, 128)
    sink = jnp.asarray(rng.normal(size=8) + 1.0, F32) if sinked else None
    blocks = dict(block_q=256, block_kv=256) if window == 128 else dict(
        block_q=32, block_kv=32)
    out, lse = flash_forward_with_lse(
        q, k, v, causal=True, window=window, sink=sink, interpret=True,
        **blocks)
    assert out.shape == (1, 8, s, 128) and lse.shape == (1, 8, s)
    np.testing.assert_allclose(out, _plain(q, k, v, window, sink),
                               atol=2e-6 * 4)
    if sinked:   # the sink takes mass: a row's weights no longer sum to 1
        bare, _ = flash_forward_with_lse(
            q, k, v, causal=True, window=window, interpret=True, **blocks)
        assert float(jnp.abs(bare - out).max()) > 1e-2


def test_the_lax_prefill_form_takes_the_sink_and_a_narrower_value():
    rng = np.random.default_rng(2)
    q, k, v = _qkv(rng, 2, 4, 2, 40, 24, 16)
    sink = jnp.asarray([0.0, 1.0, 2.0, -1.0], F32)
    got = prefill_self_attention(q, k, v, window=20, sink=sink)
    np.testing.assert_allclose(got, _plain(q, k, v, 20, sink), atol=1e-5)
    got = sdpa_attention(q, k, v, causal=True, sink=sink)
    np.testing.assert_allclose(got, _plain(q, k, v, None, sink), atol=1e-5)


@pytest.mark.parametrize("window,sinked", [(20, True), (None, False)],
                         ids=["ring-sink", "pool"])
def test_the_decode_kernel_with_a_sink_and_two_widths_interpreted(
        window, sinked):
    """The paged-decode kernel in interpret mode against the lax pair on
    the same pool: keys stored 256 wide (192 and 64 zeros) beside values
    of 128, 8 query heads on 2 K/V heads, 3 slots at positions under a
    page, past the window and past the ring (a ring of 4 pages of 8
    where there is a window), a dead slot between them."""
    rng = np.random.default_rng(3)
    slots, pages, page = 4, 6, 8
    dk, stored, dv = 192, stored_key_width(192), 128
    assert stored == 256
    ring = window_ring_pages(window, page) if window else pages
    tables = 1 + ring * np.arange(slots)[:, None] + (
        np.arange(pages)[None, :] % ring)
    tables = jnp.asarray(tables, jnp.int32)
    pool_k = jnp.zeros((2, 1 + slots * ring, 2, page, stored), F32)
    pool_v = jnp.zeros((2, 1 + slots * ring, 2, page, dv), F32)
    lengths = np.array([5, 30, 0, 47])
    for t in range(int(lengths.max())):
        live = jnp.asarray(t < lengths)
        k = jnp.asarray(rng.normal(size=(slots, 2, 1, dk)), F32)
        v = jnp.asarray(rng.normal(size=(slots, 2, 1, dv)), F32)
        k = jnp.pad(k, ((0, 0),) * 3 + ((0, stored - dk),))
        at = jnp.full((slots, 1), t, jnp.int32)
        pool_k = paged_write(pool_k, k, at, tables, live, layer=1,
                             kernel=False)
        pool_v = paged_write(pool_v, v, at, tables, live, layer=1,
                             kernel=False)
    q = jnp.asarray(rng.normal(size=(slots, 8, 1, dk)), F32)
    q = jnp.pad(q, ((0, 0),) * 3 + ((0, stored - dk),))
    sink = jnp.asarray(rng.normal(size=8) + 1.0, F32) if sinked else None
    positions = jnp.asarray(lengths - 1, jnp.int32)[:, None]
    kw = dict(page_size=page, layer=1, scale=dk ** -0.5, window=window,
              sink=sink)
    got = paged_attention(q, pool_k, pool_v, tables, positions,
                          kernel=True, interpret=True, **kw)
    want = paged_attention(q, pool_k, pool_v, tables, positions,
                           kernel=False, **kw)
    assert got.shape == (slots, 8, 1, dv)
    live = lengths > 0
    np.testing.assert_allclose(got[live], want[live], atol=2e-6 * 4)
    if sinked:
        bare = paged_attention(q, pool_k, pool_v, tables, positions,
                               kernel=False, **dict(kw, sink=None))
        assert float(jnp.abs(bare[live] - want[live]).max()) > 1e-2
        np.testing.assert_array_equal(np.asarray(got[~live]), 0.0)


def test_the_cached_lax_form_reads_a_ring_under_a_sink():
    """``cached_sdpa_attention`` with a window and a sink is the
    written-out softmax over the window's keys."""
    rng = np.random.default_rng(4)
    q, k, v = _qkv(rng, 1, 4, 2, 48, 24, 16)
    sink = jnp.asarray([1.0, 0.0, -0.5, 2.0], F32)
    want = _plain(q, k, v, 20, sink)
    got = cached_sdpa_attention(q[:, :, 40:41], k, v, jnp.asarray([[40]]),
                                window=20, sink=sink)
    np.testing.assert_allclose(got[:, :, 0], want[:, :, 40], atol=1e-5)


@pytest.mark.parametrize("theta", [5e6, 1e4])
def test_the_rotary_embedding_turns_a_head_s_first_third_at_each_base(theta):
    """64 of 192 dims at the published widths (8 of 24 here): the
    program's tables at the rotary width against the reference's partial
    rope, at both bases; dims past the rotary share pass unchanged, and
    the two bases differ."""
    rng = np.random.default_rng(5)
    for dk, rotary in ((192, 64), (24, 8)):
        assert int(dk * 0.334) == rotary
        q = jnp.asarray(rng.normal(size=(1, 3, 16, dk)), F32)
        k = jnp.asarray(rng.normal(size=(1, 2, 16, dk)), F32)
        positions = jnp.arange(100, 116)
        cos, sin = get_cos_sin(16, rotary, theta, positions=positions[None])
        got_q, got_k = apply_rotary_pos_emb(q, k, cos, sin)
        want_q = reference.partial_rope(
            q[0].transpose(1, 0, 2), positions, theta, rotary)
        np.testing.assert_allclose(got_q[0].transpose(1, 0, 2), want_q,
                                   atol=2e-5)
        np.testing.assert_array_equal(got_k[..., rotary:], k[..., rotary:])
        assert float(jnp.abs(got_k[..., :rotary] - k[..., :rotary]).max()) > .1
        other = reference.partial_rope(
            q[0].transpose(1, 0, 2), positions, 3e5, rotary)
        assert float(jnp.abs(other - want_q).max()) > 0.1


# ---- the whole forward against the reference ------------------------------------------

def test_the_forward_is_the_reference_s(full):
    _, system, ref, _ = full
    _close(system, ref)


@pytest.mark.parametrize("wrong", WRONG)
def test_every_wrong_variant_is_rejected_at_the_toy_size(full, wrong):
    """Each departure the reference offers moves the logits by more than
    fifty times the tolerance the forward is held to."""
    _, _, ref, make = full
    off = np.asarray(make(wrong))
    assert np.abs(off - np.asarray(ref)).max() > 50 * RTOL_OF_MAX * np.abs(
        np.asarray(ref)).max(), wrong


def test_the_reference_refuses_an_unknown_variant(model):
    cfg, params = model
    with pytest.raises(ValueError, match="unknown wrong variant"):
        reference.make_logits_fn(ref_config(), q_block=8, wrong="typo")(
            params, jnp.zeros((1, 8), jnp.int32), jnp.zeros((1, 1), jnp.int32))


def test_the_reference_imports_nothing_of_the_program():
    import inspect

    source = inspect.getsource(reference)
    assert "import scaletorch_tpu" not in source
    assert "from scaletorch_tpu" not in source


# ---- the cached path: a one-row prefill that names its slot, then decode ------------

def _paged(cfg, slots, pages_per_slot, **kw):
    pool = init_paged_kv_cache(cfg, 1 + slots * pages_per_slot, PAGE,
                               slots=slots)
    tables = jnp.asarray(
        1 + np.arange(slots * pages_per_slot).reshape(slots, pages_per_slot),
        jnp.int32)
    return pool, tables, dict(seq_limit=pages_per_slot * PAGE, **kw)


def test_the_cache_holds_two_shapes_of_k_v_and_its_bytes_add_up(model):
    cfg, _ = model
    assert kv_head_shapes(cfg) == ((2, 128, 16), (4, 128, 16))
    pool, _, _ = _paged(cfg, 3, 10)
    assert isinstance(pool, WindowCache)
    ring = window_ring_pages(20, PAGE)
    assert ring == 4
    assert pool.k.shape == (2, 31, 2, PAGE, 128)
    assert pool.v.shape == (2, 31, 2, PAGE, 16)
    assert pool.wk.shape == (5, 1 + 3 * ring, 4, PAGE, 128)
    assert pool.wv.shape == (5, 1 + 3 * ring, 4, PAGE, 16)
    assert kv_cache_bytes(cfg, 31, PAGE) == pool.k.nbytes + pool.v.nbytes
    assert window_cache_bytes(pool) == pool.wk.nbytes + pool.wv.nbytes
    assert cache_nbytes(pool) == sum(a.nbytes for a in pool)


def test_prefill_then_decode_is_the_full_forward(model, full):
    """Two prompts, each a ONE-row call that names its slot (slot 1
    first, then slot 0), of 19 tokens in a buffer of 40 (under the ring
    of 32) and of 37 in 40 (past it: its first page goes to TRASH), then
    both decoded to position 71: across the rings' wraps at 32 and 64.
    Through the lax pair; the decode kernel at the stored widths, with
    the sink, past a ring's wrap is the interpreted kernel test's above
    (seven layers of interpreted kernels in one program cost half a
    minute of compile here)."""
    cfg, params = model
    tokens, _, ref, _ = full
    fwd = compiled_forward_cached(mimo.forward_cached, cfg)
    pool, tables, io_kw = _paged(cfg, 2, 9)
    cache = tuple(pool)
    buffer, prompts = 40, {1: 19, 0: 37}
    positions = jnp.arange(buffer)[None]
    with jax.default_matmul_precision("highest"):
        for slot, prompt in prompts.items():
            rows = positions < prompt
            logits, cache, counts = fwd(
                params, jnp.where(rows, tokens[slot:slot + 1, :buffer], 0),
                cfg, cache, positions=positions,
                kv_io=PagedKVIO(tables[slot:slot + 1], PAGE, **io_kw),
                row_mask=rows, return_routing=True,
                logit_rows=jnp.asarray([prompt - 1]),
                slot_ids=jnp.asarray([slot], jnp.int32))
            _close(logits[0, 0], ref[slot, prompt - 1])
            assert int(counts["dropped"]) == 0
            assert int(counts["routed"]) + int(counts["elsewhere"]) == (
                prompt * 6 * 3)                       # tokens x layers x k
        io = PagedKVIO(tables, PAGE, **io_kw)
        at = np.array([prompts[0], prompts[1]])
        end = 72
        while np.any(at < end):
            live = jnp.asarray(at < end)
            t = np.minimum(at, 71)
            step = jnp.asarray(tokens[np.arange(2), t])[:, None]
            logits, cache = fwd(
                params, step, cfg, cache, positions=jnp.asarray(t)[:, None],
                kv_io=io, write_mask=live, row_mask=live[:, None])
            for slot in np.flatnonzero(at < end):
                _close(logits[slot, 0], ref[slot, at[slot]])
            at = at + 1


def test_a_slot_reused_by_a_second_request_reads_nothing_of_the_last(model):
    """Request A fills a slot's pages and rings to position 50; request
    B (12 tokens, under a window) is prefilled into the same slot and
    decoded: its logits are those of B in a fresh cache, whatever A left
    in the ring's pages that B does not write."""
    cfg, params = model
    fwd = compiled_forward_cached(mimo.forward_cached, cfg)
    a, b = jnp.asarray(_tokens((1, 56), 5)), jnp.asarray(_tokens((1, 16), 6))
    step = jnp.asarray(_tokens((1, 1), 7))
    pool, tables, io_kw = _paged(cfg, 1, 8)
    io = PagedKVIO(tables, PAGE, **io_kw)

    def run(cache, prompt, live):
        positions = jnp.arange(prompt.shape[1])[None]
        return fwd(params, prompt, cfg, cache, positions=positions,
                   kv_io=io, row_mask=positions < live,
                   slot_ids=jnp.zeros((1,), jnp.int32))[1]

    def decode(cache, at):
        return fwd(params, step, cfg, cache,
                   positions=jnp.full((1, 1), at), kv_io=io)[0]

    used = run(run(tuple(pool), a, 50), b, 12)
    fresh = run(tuple(_paged(cfg, 1, 8)[0]), b, 12)
    assert np.abs(np.asarray(used[2]) - np.asarray(fresh[2])).max() > 0
    np.testing.assert_array_equal(np.asarray(decode(used, 12)),
                                  np.asarray(decode(fresh, 12)))


# ---- the expert layer -------------------------------------------------------------

def test_the_sixteen_shares_of_one_expert_layer_add_up_to_the_uncut_layer():
    """Sixteen shares of one expert (16 routed experts; the cell holds
    16 of 256 on a chip of 16): each share's routed partial result
    through ``dropless_mlp`` under the selection bias, nothing standing
    in for the absent chips, against the uncut reference's layer on the
    same input."""
    whole = tiny_config(WHOLE)
    params = seeded_params(whole, seed=5)
    place = 1                                    # the second sparse layer
    moe = dict(params["layers"]["moe"])
    # a bias that changes the choice: the weights must not see it
    moe["expert_bias"] = jnp.asarray(
        np.random.default_rng(9).normal(size=moe["expert_bias"].shape) * 0.3,
        F32)
    m = jnp.asarray(np.random.default_rng(2).normal(size=(2, 24, 64)), F32)
    d = reference.mimo_dims(ref_config(WHOLE))
    small = {k: v[place].astype(F32) for k, v in moe.items()
             if k not in qwen3_moe.EXPERT_KEYS}
    experts = {k: moe[k] for k in qwen3_moe.EXPERT_KEYS}
    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda rows: reference.moe_part(
            rows, small, experts, place, d, 4))(m)
        unbiased = jax.vmap(lambda rows: reference.moe_part(
            rows, dict(small, expert_bias=0 * small["expert_bias"]),
            experts, place, d, 4))(m)
        total, held_rows = 0.0, 0
        for first in range(16):
            cfg = tiny_config(dict(WHOLE, n_routed_experts=1,
                                   num_routed_experts=16,
                                   first_expert_id=first))
            stack = {k: v[:, first:first + 1] for k, v in experts.items()}
            out, _, _, routing = qwen3_moe.dropless_mlp(
                m, dict(small), cfg, None, (stack, place))
            counts = qwen3_moe.routing_counts(routing)
            assert int(counts["dropped"]) == 0
            held_rows += int(counts["routed"])
            total = total + out
    assert held_rows == 2 * 24 * 3               # every choice, once
    _close(total, want, rtol=1e-5)
    # the bias did change the choice
    assert np.abs(np.asarray(unbiased) - np.asarray(want)).max() > 1e-3
