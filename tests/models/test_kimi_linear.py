"""kimi_linear (Kimi-Linear) at a tiny size on the CPU: the delta rule
with a decay per key channel (step, sequential and chunked forms against
each other at mild and at ``e^-30`` decays and at lengths that are no
multiple of the chunk; the scalar forms untouched), the whole forward and
the cached path (one-row prefill, then decode through the latent pages
and the by-slot state) against the plain reference
(``benchmarks/reference/kimi_linear.py``) on seeded random weights
(logits, not tokens), the published 27-entry layer lists accepted, every
wrong variant rejected, the K / N tiles of the expert kernel and the
latent kernel's block by the shapes, and the share test: the routed
partial results of the four shares plus the ungated shared expert
counted once add up to the uncut reference's layer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import kimi_linear as reference
from scaletorch_tpu.inference.decode import counts_routing, rows_name_slots
from scaletorch_tpu.inference.kv_cache import (
    HybridCache,
    PagedKVIO,
    carries_state,
    init_kv_cache,
    init_paged_kv_cache,
    kv_cache_bytes,
    latent_cache_bytes,
    latent_of,
    latent_row_width,
    no_prefix_reason,
    recurrent_state_bytes,
    window_of,
)
from scaletorch_tpu.models import kimi_linear as kimi
from scaletorch_tpu.models import olmo_hybrid, qwen3_moe
from scaletorch_tpu.models.presets import preset
from scaletorch_tpu.ops import grouped_matmul
from scaletorch_tpu.ops.pallas import paged_attention
from tests.inference.compiled import compiled_forward_cached

# the tiny preset: a dense layer, two whole periods and the published
# list's short last one (8 KDA layers of 2 heads x 16, 3 latent layers of
# 4 heads over a 32 + 8 row), 4 of 16 routed experts held from id 4
TINY = preset("kimi-linear-tiny")
# every expert held: the uncut layer
WHOLE = dict(TINY, num_experts=16, num_routed_experts=None,
             first_expert_id=0)
WRONG = list(reference.WRONG)
# float32 on the CPU: the chunked scan reassociates the recurrence, the
# absorbed form two matmuls, the grouped matmul sums in another order;
# all float32 rounding (measured 2e-5 of the largest logit over 11
# layers)
RTOL_OF_MAX = 2e-4
PAGE = 8
F32 = jnp.float32


def tiny_config(keys=None, **over):
    from scaletorch_tpu.config import ScaleTorchTPUArguments
    from scaletorch_tpu.trainer.trainer import build_model_config

    return build_model_config(ScaleTorchTPUArguments(
        **{**(keys or TINY), **over}, dtype="float32",
        param_dtype="float32"))


def seeded_params(cfg, seed=3):
    return jax.jit(kimi.init_params, static_argnums=1)(
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


_forward = jax.jit(kimi.forward, static_argnums=2,
                   static_argnames=("return_hidden", "sequential"))


@pytest.fixture(scope="module")
def full(model):
    """Two sequences of 72 tokens (a chunk and a part of one) through
    the uncached forward and through the reference's, at every row."""
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


# ---- the delta rule with a decay per key channel ------------------------------

def _rule_inputs(s, scale, seed=0, b=2, h=3, dk=8, dv=16, constant=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = olmo_hybrid.l2norm(jax.random.normal(ks[0], (b, s, h, dk))) * dk ** -.5
    k = olmo_hybrid.l2norm(jax.random.normal(ks[1], (b, s, h, dk)))
    v = jax.random.normal(ks[2], (b, s, h, dv))
    draw = (jnp.ones((b, s, h, dk)) if constant
            else jax.random.uniform(ks[3], (b, s, h, dk)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)))
    state = jax.random.normal(ks[5], (b, h, dk, dv))
    return q, k, v, -scale * draw, beta, state


_chunked = jax.jit(olmo_hybrid.gated_delta_chunked)


@pytest.mark.parametrize("constant", [False, True], ids=["drawn", "flat"])
@pytest.mark.parametrize("scale", [0.1, 2.0, 30.0])
@pytest.mark.parametrize("s", [5, 64, 100, 200])
def test_the_chunked_form_is_the_recurrence_at_every_decay(s, scale,
                                                           constant):
    """Decays from mild (``e^-0.1`` a step) down to ``alpha = e^-30`` a
    step on every channel (``flat``) or up to it (``drawn``: the
    channels of one key differ by e^30), at lengths under a chunk, of
    one chunk and of no whole number of chunks: ``-g`` reaches 1,900
    inside one chunk, where the naive split ``(k e^g) . (k e^-g)``
    overflows float32 at 88. The tolerance is float32's: the forms sum
    in different orders (measured 5e-6 of outputs of size 1)."""
    args = _rule_inputs(s, scale, constant=constant)
    want_o, want_s = olmo_hybrid.gated_delta_sequential(*args)
    got_o, got_s = _chunked(*args)
    assert np.isfinite(np.asarray(got_o)).all()
    np.testing.assert_allclose(got_o, want_o, atol=2e-5)
    np.testing.assert_allclose(got_s, want_s, atol=2e-5)


def test_a_step_with_a_vector_decay_is_the_rule_written_out():
    q, k, v, log_alpha, beta, state = (
        a[:, 0] if a.ndim > 3 and i < 5 else a
        for i, a in enumerate(_rule_inputs(1, 2.0)))
    beta = beta[:, 0] if beta.ndim == 3 else beta
    o, new = olmo_hybrid.gated_delta_step(q, k, v, log_alpha, beta, state)
    decayed = np.exp(np.asarray(log_alpha))[..., None] * np.asarray(state)
    u = np.asarray(beta)[..., None] * (
        np.asarray(v) - np.einsum("bhkv,bhk->bhv", decayed, k))
    want = decayed + np.einsum("bhk,bhv->bhkv", k, u)
    np.testing.assert_allclose(new, want, atol=1e-6)
    np.testing.assert_allclose(
        o, np.einsum("bhkv,bhk->bhv", want, q), atol=1e-6)


def test_a_vector_decay_that_is_one_number_a_head_is_the_scalar_rule():
    """The per-channel forms fed a decay that does not vary over the
    channels compute what the scalar forms compute (to float32
    rounding: other programs), and the scalar forms themselves are the
    parent's code, reached by the rank of ``log_alpha``."""
    q, k, v, log_alpha, beta, state = _rule_inputs(100, 1.0)
    scalar = log_alpha[..., 0]
    spread = jnp.broadcast_to(scalar[..., None], log_alpha.shape)
    for form in (olmo_hybrid.gated_delta_sequential, _chunked):
        want_o, want_s = form(q, k, v, scalar, beta, state)
        got_o, got_s = form(q, k, v, spread, beta, state)
        np.testing.assert_allclose(got_o, want_o, atol=2e-5)
        np.testing.assert_allclose(got_s, want_s, atol=2e-5)


# ---- the configuration --------------------------------------------------------

def test_the_program_builds_the_family_from_its_published_keys(model):
    cfg, params = model
    assert isinstance(cfg, kimi.KimiLinearConfig)
    assert cfg.layer_kinds == ("kda",) * 3 + ("full",) + ("kda",) * 3 + (
        "full", "kda", "kda", "full")
    assert (cfg.num_kda_layers, cfg.num_kv_cache_layers) == (8, 3)
    assert cfg.sparse_layer_ids() == tuple(range(1, 11))
    assert (cfg.router_width, cfg.num_experts, cfg.first_expert_id) == (
        16, 4, 4)
    assert cfg.score_func == "sigmoid" and not cfg.shared_expert_gated
    assert cfg.norm_topk_prob and cfg.route_scale == 2.446
    assert cfg.num_experts_per_tok == 3
    assert cfg.shared_expert_intermediate_size == 32
    assert cfg.attn_scale == pytest.approx(24 ** -0.5)
    assert latent_of(cfg) and carries_state(cfg) and window_of(cfg) is None
    assert counts_routing(cfg) and rows_name_slots(cfg)
    why = no_prefix_reason(cfg)
    assert "snapshots of the recurrent state" in why
    assert "expanded through W_ukv" in why
    assert cfg.recurrent_state_shapes(5) == ((8, 5, 2, 16, 16),
                                             (8, 5, 3, 96))
    n = sum(x.size for x in jax.tree.leaves(params))
    assert n == cfg.num_params()
    moe = params["layers"]["moe"]
    assert moe["expert_bias"].shape == (10, 16)
    assert moe["expert_bias"].dtype == F32
    assert "shared_expert_gate" not in moe
    assert params["layers"]["mla"]["kv_b_proj"].shape == (3, 32, 4, 32)
    assert "q_a_proj" not in params["layers"]["mla"]
    assert params["layers"]["kda"]["dt_bias"].shape == (8, 32)
    assert params["layers"]["kda"]["A_log"].shape == (8, 2)


def test_the_published_sizes_are_the_48b_model():
    """ISSUE 54's hand counts: 39.51 M a KDA mixer, 29.11 M a latent
    mixer, 7.078 M an expert, 49.1 B in all (published as 48 B), and the
    27-entry layer lists taken whole, the short last period with them."""
    from scaletorch_tpu.config import ScaleTorchTPUArguments
    from scaletorch_tpu.trainer.trainer import build_model_config

    cfg = build_model_config(ScaleTorchTPUArguments(
        **preset("kimi-linear-48b-a3b")))
    assert cfg.kda_params() == 39_514_272
    assert cfg.attention_params() == 29_114_880
    assert 3 * 2304 * 1024 == 7_077_888
    assert 48e9 < cfg.num_params() < 50e9
    assert (cfg.num_kda_layers, cfg.num_kv_cache_layers) == (20, 7)
    assert cfg.layer_kinds[24:] == ("kda", "kda", "full")
    assert latent_row_width(cfg) == 640        # 512 + 64 in whole tiles
    assert kv_cache_bytes(cfg, 2, 16, jnp.bfloat16) == 7 * 2 * 16 * 1280
    assert cfg.recurrent_state_shapes(32)[0] == (20, 32, 32, 128, 128)


@pytest.mark.parametrize("kinds,n_dense", [
    ("KKKFKKKF", 1),        # the cell's cut
    ("KFKFKFKF", 0),
    ("KKF", 1),
    ("FK", 0),              # a latent layer first
    ("FFKFK", 2),           # no period at all, two dense layers
])
def test_a_layer_list_in_any_order_of_kinds_is_the_reference_s(kinds,
                                                               n_dense):
    """``linear_attn_config`` names the layers of each kind, 1-based, in
    any order: every layer finds its own mixer among those of its kind
    and its own MLP (the forward against the reference's, which reads
    the same lists)."""
    lists = dict(
        TINY["linear_attn_config"],
        kda_layers=[i + 1 for i, k in enumerate(kinds) if k == "K"],
        full_attn_layers=[i + 1 for i, k in enumerate(kinds) if k == "F"])
    keys = dict(TINY, num_hidden_layers=len(kinds),
                linear_attn_config=lists, first_k_dense_replace=n_dense)
    cfg = tiny_config(keys)
    assert cfg.layer_kinds == tuple(
        "kda" if k == "K" else "full" for k in kinds)
    assert cfg.recurrent_state_shapes(3)[0][0] == kinds.count("K")
    assert cfg.num_kv_cache_layers == kinds.count("F")
    params = seeded_params(cfg, seed=len(kinds))
    assert params["layers"]["dense"]["up_proj"].shape[0] == n_dense
    tokens = jnp.asarray(_tokens((1, 72), seed=2))   # a chunk and a part
    rows = jnp.arange(72)[None]
    with jax.default_matmul_precision("highest"):
        system = _forward(params, tokens, cfg)
    want = reference.make_logits_fn(keys, q_block=8, expert_chunk=2)(
        params, tokens, rows)
    _close(system, want)


@pytest.mark.parametrize("over,error,match", [
    (dict(q_lora_rank=48), NotImplementedError, "q_lora_rank"),
    (dict(topk_group=2), NotImplementedError, "group-limited"),
    (dict(mlp_only_layers=[1]), NotImplementedError, "mlp_only_layers"),
    (dict(decoder_sparse_step=2), NotImplementedError,
     "decoder_sparse_step"),
    (dict(moe_capacity_factor=2.0), NotImplementedError,
     "capacity dispatch"),
    (dict(model_name_or_path="x/Kimi-Linear"), NotImplementedError,
     "model_name_or_path"),
    (dict(moe_dispatch="einsum"), NotImplementedError, "capacity dispatch"),
    (dict(first_k_dense_replace=11), ValueError, "first_k_dense_replace"),
    (dict(num_key_value_heads=2), ValueError, "num_key_value_heads"),
    (dict(num_hidden_layers=10), ValueError, "name each of the layers"),
    (dict(linear_attn_config=dict(
        TINY["linear_attn_config"], kda_layers=list(range(1, 12)),
        full_attn_layers=[])), ValueError, "name each of the layers|both"),
    (dict(first_expert_id=14), ValueError, "first_expert_id"),
])
def test_what_the_family_refuses_it_refuses_by_name(over, error, match):
    with pytest.raises(error, match=match):
        tiny_config(**over)


def test_the_trainer_refuses_the_family_and_says_what_is_missing():
    from scaletorch_tpu.models.families import FAMILIES

    row = FAMILIES["kimi_linear"]
    assert row.rows_name_slots and row.counts_routing and not row.loads_hf
    for missing in ("backward", "sharding rules", "exchange",
                    "HF weight loading"):
        assert missing in row.untrained, missing


def test_a_contiguous_cache_is_refused_by_name(model):
    cfg, params = model
    with pytest.raises(TypeError, match="LatentCache"):
        init_kv_cache(cfg, 1, 16)
    with pytest.raises(NotImplementedError, match="paged latent cache"):
        kimi.forward_cached(
            params, jnp.zeros((1, 4), jnp.int32), cfg,
            (None, None, None, None),
            positions=jnp.zeros((1, 4), jnp.int32))
    with pytest.raises(ValueError, match="number of slots"):
        init_paged_kv_cache(cfg, 5, PAGE)


def test_the_draw_s_scales_move_their_own_leaves_alone(model):
    """The three launch arguments for random weights reach this
    family's initialiser: the embedding's deviation, the held routed
    experts' down projection and the latent layers' ``q_proj`` are the
    unscaled draw times them, every other leaf the same draw bit for
    bit; unset, the draw is the family's own."""
    cfg, params = model
    assert (cfg.embed_init_std, cfg.routed_expert_init_scale,
            cfg.query_init_scale) == (0.02, 1.0, 1.0)
    other = tiny_config(routed_expert_init_scale=0.25, query_init_scale=4.0,
                        embed_init_std=0.08)
    scaled = dict(jax.tree_util.tree_leaves_with_path(seeded_params(other)))
    factors = {"expert_down_proj": 0.25, "['mla']['q_proj']": 4.0,
               "embed_tokens": 4.0}
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        name = jax.tree_util.keystr(path)
        factor = next((f for key, f in factors.items() if key in name), 1.0)
        np.testing.assert_allclose(
            np.asarray(scaled[path]), np.asarray(leaf * factor),
            rtol=1e-6 if factor != 1.0 else 0, err_msg=name)


# ---- the forward against the reference ------------------------------------------

def test_the_forward_is_the_reference_s(full):
    _, system, ref, _ = full
    _close(system, ref)


def test_the_chunked_forward_is_the_forward_row_after_row(model, full):
    cfg, params = model
    tokens, system, _, _ = full
    with jax.default_matmul_precision("highest"):
        _close(_forward(params, tokens, cfg, sequential=True), system)


@pytest.mark.parametrize("wrong", WRONG)
def test_every_wrong_variant_is_rejected_at_the_toy_size(full, wrong):
    _, system, _, ref = full
    other = np.asarray(ref(wrong), np.float64)
    err = np.abs(np.asarray(system, np.float64) - other)
    # (no_qk_l2norm may overflow: an un-normed key's update diverges)
    assert not np.isfinite(err).all() or \
        err.max() > 50 * RTOL_OF_MAX * np.abs(other).max(), err.max()


def test_the_reference_refuses_an_unknown_variant(model):
    cfg, params = model
    with pytest.raises(ValueError, match="unknown wrong variant"):
        reference.make_logits_fn(ref_config(), wrong="no_such")(
            params, jnp.zeros((1, 8), jnp.int32),
            jnp.zeros((1, 1), jnp.int32))


def test_the_reference_imports_nothing_of_the_program():
    import inspect

    source = inspect.getsource(reference)
    assert "import scaletorch_tpu" not in source
    assert "from scaletorch_tpu" not in source


# ---- the cached path: one-row prefill, then decode --------------------------------

def _paged(cfg, slots, pages_per_slot, **kw):
    pool = init_paged_kv_cache(cfg, 1 + slots * pages_per_slot, PAGE,
                               slots=slots)
    tables = jnp.asarray(
        1 + np.arange(slots * pages_per_slot).reshape(slots, pages_per_slot),
        jnp.int32)
    return pool, PagedKVIO(tables, PAGE,
                           seq_limit=pages_per_slot * PAGE, **kw)


@pytest.mark.parametrize("kernel", [None, True], ids=["xla", "interpret"])
def test_prefill_then_decode_is_the_full_forward(model, full, kernel):
    """A prompt of 19 tokens in a buffer of 24 (a padded row: its last
    five rows are no tokens, neither for the state nor for the routing
    counts), then decode from position 19 to 45: across the page
    boundaries at 24, 32 and 40. The absorbed form on the gathered rows,
    and the latent kernel in interpret mode."""
    cfg, params = model
    tokens, _, ref, _ = full
    fwd = compiled_forward_cached(kimi.forward_cached, cfg)
    kw = {} if kernel is None else dict(kernel=True, interpret=True)
    pool, io = _paged(cfg, 2, 6, **kw)
    assert isinstance(pool, HybridCache) and pool.v is None
    assert pool.k.shape == (3, 13, 1, 8, 128)
    assert pool.state.shape == (8, 2, 2, 16, 16) and pool.state.dtype == F32
    assert pool.conv.shape == (8, 2, 3, 96)
    assert latent_cache_bytes(pool) == pool.k.nbytes == kv_cache_bytes(
        cfg, 13, PAGE)
    assert recurrent_state_bytes(pool) == pool.state.nbytes + pool.conv.nbytes
    prompt, buffer = 19, 24
    positions = jnp.broadcast_to(jnp.arange(buffer), (2, buffer))
    rows = positions < prompt
    with jax.default_matmul_precision("highest"):
        padded = jnp.where(rows, tokens[:, :buffer], 0)
        logits, cache, counts = fwd(
            params, padded, cfg, tuple(pool), positions=positions,
            kv_io=io, row_mask=rows, return_routing=True,
            logit_rows=jnp.full((2,), prompt - 1))
        _close(logits[:, 0], ref[:, prompt - 1])
        assert int(counts["dropped"]) == 0
        assert int(counts["routed"]) + int(counts["elsewhere"]) == (
            2 * prompt * 10 * 3)                  # tokens x layers x k
        for t in range(prompt, 46):
            logits, cache = fwd(
                params, tokens[:, t:t + 1], cfg, cache,
                positions=jnp.full((2, 1), t), kv_io=io)
            _close(logits[:, 0], ref[:, t])
    assert cache[1] is None


def test_a_slot_reused_by_a_second_request_reads_nothing_of_the_last(model):
    """Request A fills a slot's pages and its state to position 30;
    request B (12 tokens) is prefilled into the same slot and decoded:
    its logits are those of B in a fresh cache, whatever A left."""
    cfg, params = model
    fwd = compiled_forward_cached(kimi.forward_cached, cfg)
    a, b = jnp.asarray(_tokens((1, 32), 5)), jnp.asarray(_tokens((1, 16), 6))
    step = jnp.asarray(_tokens((1, 1), 7))

    def run(cache, io, prompt):
        positions = jnp.arange(prompt.shape[1])[None]
        return fwd(params, prompt, cfg, cache, positions=positions,
                   kv_io=io)[1]

    def decode(cache, io, at):
        return fwd(params, step, cfg, cache,
                   positions=jnp.full((1, 1), at), kv_io=io)[0]

    pool, io = _paged(cfg, 1, 5)
    used = run(run(tuple(pool), io, a), io, b[:, :12])
    fresh = run(tuple(_paged(cfg, 1, 5)[0]), io, b[:, :12])
    assert np.abs(np.asarray(used[0]) - np.asarray(fresh[0])).max() > 0
    np.testing.assert_array_equal(np.asarray(used[2]), np.asarray(fresh[2]))
    np.testing.assert_array_equal(np.asarray(decode(used, io, 12)),
                                  np.asarray(decode(fresh, io, 12)))


def test_an_unwritten_slot_keeps_state_tail_and_pages_bit_for_bit(model):
    """A decode step whose second slot is outside ``write_mask`` (and
    ``row_mask``): that slot's state, tail and pages pass through."""
    cfg, params = model
    pool, io = _paged(cfg, 2, 3)
    rng = np.random.default_rng(0)
    cache = (pool.k, None,
             jnp.asarray(rng.normal(size=pool.state.shape), F32),
             jnp.asarray(rng.normal(size=pool.conv.shape), F32))
    mask = jnp.asarray([True, False])
    _, new = kimi.forward_cached(
        params, jnp.asarray(_tokens((2, 1), 2)), cfg, cache,
        positions=jnp.full((2, 1), 5), kv_io=io, write_mask=mask,
        row_mask=mask[:, None])
    for old, got in ((cache[2], new[2]), (cache[3], new[3])):
        np.testing.assert_array_equal(np.asarray(old[:, 1]),
                                      np.asarray(got[:, 1]))
        assert np.abs(np.asarray(old[:, 0]) - np.asarray(got[:, 0])).max() > 0
    np.testing.assert_array_equal(np.asarray(new[0][:, 4:]),
                                  np.asarray(cache[0][:, 4:]))


# ---- the kernels' shapes ----------------------------------------------------------

@pytest.mark.parametrize("width,tile", [
    (2304, 1152),     # Kimi-Linear's hidden size: 2.25 tiles of 1,024
    (7680, 1024),     # openPangu's: 7.5 tiles, a sixteenth empty: as it was
    (2048, 1024), (1024, 1024), (512, 512), (768, 768), (4096, 1024),
    (2200, 1024),     # nothing up to the cap divides it
])
def test_the_expert_kernel_s_tiles_divide_the_width_where_1024_does_not(
        width, tile):
    assert grouped_matmul._width_tile(width) == tile
    assert grouped_matmul._gmm_tiling(256, width, 1024) == (256, tile, 1024)
    assert grouped_matmul._gmm_tiling(65536, 1024, width) == (512, 1024, tile)


@pytest.mark.parametrize("m,k,n", [
    (96, 2304, 1024),     # up / gate of a decode step: the K tile 1,152
    (96, 1024, 2304),     # down: the N tile 1,152
    (700, 2304, 1024),    # a prefill-shaped call: two row tiles of 512
])
def test_the_expert_kernel_under_the_1152_tile_gives_the_plain_product(
        m, k, n):
    """The megablox kernel under the tiles ``_gmm_tiling`` picks at
    hidden 2,304, interpreted on the CPU, against ``ragged_matmul`` on
    bfloat16 operands with a float32 result: an empty group, groups that
    end inside a row tile, rows of no group at the end (unspecified, not
    compared). Both sum bfloat16 products in float32, in another order:
    1e-5 of outputs of size ~5 (measured 3e-6)."""
    rng = np.random.default_rng(k + n)
    sizes = jnp.asarray([m // 3, 0, m // 4, m // 8], jnp.int32)
    live = int(sizes.sum())
    rows = jnp.asarray(rng.normal(size=(m, k)), jnp.bfloat16)
    weights = jnp.asarray(rng.normal(size=(4, k, n)) / np.sqrt(k),
                          jnp.bfloat16)
    assert 1152 in grouped_matmul._gmm_tiling(m, k, n)
    got = grouped_matmul.pallas_matmul(rows, weights, sizes, out_dtype=F32,
                                       interpret=True)
    want = grouped_matmul.ragged_matmul(rows, weights, sizes, out_dtype=F32)
    assert got.shape == (m, n)
    assert float(jnp.abs(want[:live]).max()) > 1.0
    np.testing.assert_allclose(np.asarray(got[:live]),
                               np.asarray(want[:live]), atol=1e-5 * 5)


@pytest.mark.parametrize("heads,keys", [
    (128, 256), (256, 256), (64, 512), (32, 1024), (4, 1024)])
def test_the_latent_kernel_s_block_follows_the_head_count(heads, keys):
    assert paged_attention._latent_block_keys(heads) == keys
    # the score tile [heads, keys] in float32 stays within 128 KB, the
    # double-buffered landing zone of 640-wide bfloat16 rows within 2.6 MB
    assert min(heads, 128) * keys * 4 <= 128 * 1024
    assert 2 * keys * 640 * 2 <= 2.7e6


# ---- the expert layer -------------------------------------------------------------

def test_the_four_shares_of_one_expert_layer_add_up_to_the_uncut_layer():
    """Four shares of four experts (16 routed experts; the cell holds 64
    of 256 on a chip of 4): each share's routed partial result through
    ``dropless_mlp`` under the selection bias, the ungated shared
    expert counted once, against the uncut reference's layer on the same
    input."""
    whole = tiny_config(WHOLE)
    params = seeded_params(whole, seed=5)
    place = 1                                    # the second sparse layer
    moe = dict(params["layers"]["moe"])
    # a bias that changes the choice: the weights must not see it
    moe["expert_bias"] = jnp.asarray(
        np.random.default_rng(9).normal(size=moe["expert_bias"].shape) * 0.3,
        F32)
    m = jnp.asarray(np.random.default_rng(2).normal(size=(2, 24, 64)), F32)
    d = reference.kimi_dims(ref_config(WHOLE))
    small = {k: v[place].astype(F32) for k, v in moe.items()
             if k not in qwen3_moe.EXPERT_KEYS}
    experts = {k: moe[k] for k in qwen3_moe.EXPERT_KEYS}
    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda rows: reference.moe_part(
            rows, small, experts, place, d, 4))(m)
        unbiased = jax.vmap(lambda rows: reference.moe_part(
            rows, dict(small, expert_bias=0 * small["expert_bias"]),
            experts, place, d, 4))(m)
        shared = qwen3_moe.shared_expert(
            m.reshape(-1, 64), small, whole).reshape(m.shape)
        total = shared
        held_rows = 0
        for first in range(0, 16, 4):
            cfg = tiny_config(dict(WHOLE, num_experts=4,
                                   num_routed_experts=16,
                                   first_expert_id=first))
            stack = {k: moe[k][:, first:first + 4]
                     for k in qwen3_moe.EXPERT_KEYS}
            y, _, _, routing = qwen3_moe.dropless_mlp(
                m, dict(small), cfg, None, (stack, place))
            total = total + (y - shared)          # its routed part alone
            counts = qwen3_moe.routing_counts(routing)
            assert int(counts["dropped"]) == 0
            held_rows += int(counts["routed"])
    assert held_rows == 2 * 24 * 3               # every choice held once
    _close(total, want)
    assert np.abs(np.asarray(want) - np.asarray(unbiased)).max() > 1e-3


def test_the_router_is_steered_by_its_bias_and_scaled_by_2_446(model):
    cfg, params = model
    rng = np.random.default_rng(4)
    m = jnp.asarray(rng.normal(size=(12, 64)), F32)
    small = {k: v[0].astype(F32) for k, v in params["layers"]["moe"].items()
             if k not in qwen3_moe.EXPERT_KEYS}
    small["expert_bias"] = jnp.asarray(rng.normal(size=16) * 0.3, F32)
    d = reference.kimi_dims(ref_config())
    w = np.asarray(reference.expert_weights(m, small, d))
    scores = np.asarray(jax.nn.sigmoid(m @ small["router"]))
    steered = scores + np.asarray(small["expert_bias"])
    chosen = steered >= np.sort(steered, axis=-1)[:, -3:-2]
    kept = np.where(chosen, scores, 0)
    want = kept / kept.sum(-1, keepdims=True) * 2.446
    np.testing.assert_allclose(w, want[:, 4:8], rtol=1e-5)
