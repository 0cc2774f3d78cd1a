"""pangu_ultra_moe (openPangu-Ultra-MoE) at a tiny size on the CPU: the
whole forward and the cached path (the expanded prefill, then the
absorbed decode through the latent pages, across a page boundary and
with a padded row) against the plain reference
(``benchmarks/reference/pangu_ultra_moe.py``) on seeded random weights
(logits, not tokens), the latent decode kernel in interpret mode against
the XLA form, a page reused by a second request, every wrong variant
rejected, the expert layer's token blocks, and the share test: the
routed partial results of all shares plus the ungated shared expert
counted once add up to the uncut reference's layer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import pangu_ultra_moe as reference
from scaletorch_tpu.inference.decode import counts_routing
from scaletorch_tpu.inference.kv_cache import (
    LatentCache,
    PagedKVIO,
    carries_state,
    init_kv_cache,
    init_paged_kv_cache,
    kv_cache_bytes,
    latent_cache_bytes,
    latent_of,
    latent_row_width,
    no_prefix_reason,
    window_of,
)
from scaletorch_tpu.models import pangu_ultra_moe as pangu
from scaletorch_tpu.models import qwen3_moe
from scaletorch_tpu.models.layers import rms_norm
from scaletorch_tpu.models.presets import preset
from scaletorch_tpu.ops import grouped_matmul
from scaletorch_tpu.ops.pallas import paged_attention
from tests.inference.compiled import compiled_forward_cached

# the tiny preset: one dense layer and three sparse ones, 4 heads over a
# 32 + 8 latent row, 4 of 16 routed experts held from id 4
TINY = preset("pangu-tiny")
# every expert held: the uncut layer
WHOLE = dict(TINY, n_routed_experts=16, num_routed_experts=None,
             first_expert_id=0)
WRONG = list(reference.WRONG)
# float32 on the CPU: the absorbed form reassociates two matmuls, the
# grouped matmul sums in another order; both float32 rounding
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
    return jax.jit(pangu.init_params, static_argnums=1)(
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


_forward = jax.jit(pangu.forward, static_argnums=2,
                   static_argnames=("return_hidden",))


@pytest.fixture(scope="module")
def full(model):
    """Two sequences of 40 tokens through the uncached forward and
    through the reference's, at every row."""
    cfg, params = model
    tokens = jnp.asarray(_tokens((2, 40), seed=1))
    rows = jnp.broadcast_to(jnp.arange(40), (2, 40))
    with jax.default_matmul_precision("highest"):
        system = _forward(params, tokens, cfg)

    def ref(wrong=None):
        return reference.make_logits_fn(
            ref_config(), q_block=8, expert_chunk=2, wrong=wrong)(
                params, tokens, rows)

    return tokens, system, ref(), ref


# ---- the configuration --------------------------------------------------------

def test_the_program_builds_the_family_from_its_published_keys(model):
    cfg, params = model
    assert isinstance(cfg, pangu.PanguUltraMoEConfig)
    assert cfg.sparse_layer_ids() == (1, 2, 3)
    assert (cfg.router_width, cfg.num_experts, cfg.first_expert_id) == (
        16, 4, 4)
    assert cfg.score_func == "sigmoid" and not cfg.shared_expert_gated
    assert cfg.norm_topk_prob and cfg.route_scale == 2.5
    assert cfg.shared_expert_intermediate_size == 32
    assert cfg.qk_head_dim == 24
    assert cfg.attn_scale == pytest.approx(24 ** -0.5)
    assert latent_of(cfg) and window_of(cfg) is None
    assert not carries_state(cfg) and counts_routing(cfg)
    assert "expanded through W_ukv" in no_prefix_reason(cfg)
    n = sum(x.size for x in jax.tree.leaves(params))
    assert n == cfg.num_params()
    moe = params["layers"]["moe"]
    assert "expert_bias" not in moe and "shared_expert_gate" not in moe
    assert params["layers"]["block"]["kv_b_proj"].shape == (4, 32, 4, 32)


def test_the_published_sizes_are_the_718b_model():
    from scaletorch_tpu.config import ScaleTorchTPUArguments
    from scaletorch_tpu.trainer.trainer import build_model_config

    cfg = build_model_config(ScaleTorchTPUArguments(
        **preset("openpangu-ultra-moe-718b")))
    # ISSUE 51's hand count: 196.58 M of attention a layer, 47.19 M an
    # expert, 719 B in all (published as 718 B)
    assert cfg.attention_params() == 196_577_280
    assert 3 * 7680 * 2048 == 47_185_920
    assert 718e9 < cfg.num_params() < 720e9
    assert latent_row_width(cfg) == 640        # 512 + 64 in whole tiles
    assert kv_cache_bytes(cfg, 2, 16, jnp.bfloat16) == 61 * 2 * 16 * 1280


@pytest.mark.parametrize("over,error,match", [
    (dict(sandwich_norm=False), NotImplementedError, "sandwich_norm"),
    (dict(model_name_or_path="x/openPangu"), NotImplementedError,
     "model_name_or_path"),
    (dict(mlp_only_layers=[0]), NotImplementedError, "mlp_only_layers"),
    (dict(moe_dispatch="einsum"), NotImplementedError, "capacity dispatch"),
    (dict(first_k_dense_replace=4), ValueError, "first_k_dense_replace"),
    (dict(num_key_value_heads=2), ValueError, "num_key_value_heads"),
    (dict(head_dim=16), ValueError, "head_dim"),
    (dict(qk_rope_head_dim=7), ValueError, "odd"),
    (dict(first_expert_id=14), ValueError, "first_expert_id"),
])
def test_what_the_family_refuses_it_refuses_by_name(over, error, match):
    with pytest.raises(error, match=match):
        tiny_config(**over)


def test_the_trainer_refuses_the_family_and_says_what_is_missing():
    from scaletorch_tpu.models.families import FAMILIES

    why = FAMILIES["pangu_ultra_moe"].untrained
    for missing in ("sharding rules", "exchange",
                    "prediction module", "HF weight loading"):
        assert missing in why, missing


def test_a_contiguous_cache_is_refused_by_name(model):
    cfg, params = model
    with pytest.raises(TypeError, match="LatentCache"):
        init_kv_cache(cfg, 1, 16)
    with pytest.raises(NotImplementedError, match="paged latent cache"):
        pangu.forward_cached(
            params, jnp.zeros((1, 4), jnp.int32), cfg, (None,),
            positions=jnp.zeros((1, 4), jnp.int32))


def test_the_draw_s_two_scales_move_their_own_leaves_alone(model):
    """``--routed_expert_init_scale`` and ``--query_init_scale`` are
    properties of random weights a benchmark file hands the program
    (the openPangu cell: 1/16, so that a router's near-tie on a held
    expert moves a logit by less than bfloat16's rounding does; and 6,
    so that attention is a token's own and routing with it): the held
    experts' down projection and ``q_b_proj`` are the unscaled draw
    times them, every other leaf the same draw bit for bit; unset, the
    draw is the family's own."""
    cfg, params = model
    assert (cfg.routed_expert_init_scale, cfg.query_init_scale) == (1.0, 1.0)
    other = tiny_config(routed_expert_init_scale=0.0625, query_init_scale=4.0)
    assert (other.routed_expert_init_scale, other.query_init_scale) == (
        0.0625, 4.0)
    scaled = dict(jax.tree_util.tree_leaves_with_path(seeded_params(other)))
    flat = jax.tree_util.tree_leaves_with_path(params)
    assert len(flat) == len(scaled)
    factors = {"expert_down_proj": 0.0625, "q_b_proj": 4.0}
    for path, leaf in flat:
        name = jax.tree_util.keystr(path)
        factor = next((f for key, f in factors.items() if key in name), 1.0)
        np.testing.assert_array_equal(
            np.asarray(scaled[path]), np.asarray(leaf * factor), err_msg=name)


# ---- the forward against the reference ------------------------------------------

def test_the_expanded_forward_is_the_reference_s(full):
    _, system, ref, _ = full
    _close(system, ref)


@pytest.mark.parametrize("wrong", WRONG)
def test_every_wrong_variant_is_rejected_at_the_toy_size(full, wrong):
    _, system, _, ref = full
    other = np.asarray(ref(wrong), np.float64)
    err = np.abs(np.asarray(system, np.float64) - other).max()
    assert err > 50 * RTOL_OF_MAX * np.abs(other).max(), err


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


# ---- the cached path: expanded prefill, absorbed decode -----------------------------

def _paged(cfg, slots, pages_per_slot, **kw):
    pool = init_paged_kv_cache(cfg, 1 + slots * pages_per_slot, PAGE)
    tables = jnp.asarray(
        1 + np.arange(slots * pages_per_slot).reshape(slots, pages_per_slot),
        jnp.int32)
    return pool, PagedKVIO(tables, PAGE,
                           seq_limit=pages_per_slot * PAGE, **kw)


@pytest.mark.parametrize("kernel", [None, True], ids=["xla", "interpret"])
def test_prefill_then_absorbed_decode_is_the_full_forward(model, full, kernel):
    """A prompt of 19 tokens in a buffer of 24 (a padded row: its last
    five rows are no tokens), then decode from position 19 to 39: across
    the page boundaries at 24 and 32. The absorbed form on the gathered
    rows, and the Mosaic kernel in interpret mode."""
    cfg, params = model
    tokens, _, ref, _ = full
    fwd = compiled_forward_cached(pangu.forward_cached, cfg)
    kw = {} if kernel is None else dict(kernel=True, interpret=True)
    pool, io = _paged(cfg, 2, 5, **kw)
    assert isinstance(pool, LatentCache) and pool.k.shape == (4, 11, 1, 8, 128)
    assert latent_cache_bytes(pool) == pool.k.nbytes == kv_cache_bytes(
        cfg, 11, PAGE)
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
            2 * prompt * 3 * 3)                   # tokens x layers x k
        for t in range(prompt, 40):
            logits, cache = fwd(
                params, tokens[:, t:t + 1], cfg, cache,
                positions=jnp.full((2, 1), t), kv_io=io)
            _close(logits[:, 0], ref[:, t])


def test_the_cache_holds_the_normed_latent_and_the_rotated_key(model, full):
    """What a page holds after a prefill: ``[c | k_r | 0...]``, ``c``
    after ``g_kv``'s norm and ``k_r`` after the rotation, 32 + 8 numbers
    of a row stored 128 wide."""
    cfg, params = model
    tokens, _, _, _ = full
    pool, io = _paged(cfg, 2, 5)
    positions = jnp.broadcast_to(jnp.arange(16), (2, 16))
    with jax.default_matmul_precision("highest"):
        _, (rows,) = pangu.forward_cached(
            params, tokens[:, :16], cfg, tuple(pool), positions=positions,
            kv_io=io)
        block = {k: v[0] for k, v in params["layers"]["block"].items()}
        x = rms_norm(params["embed_tokens"][tokens[:, :16]],
                     block["input_layernorm"], cfg.rms_norm_eps)
        kv_a = x @ block["kv_a_proj_with_mqa"]
        c = rms_norm(kv_a[..., :32], block["kv_a_layernorm"],
                     cfg.rms_norm_eps)
    held = np.asarray(rows[0, 1:3, 0]).reshape(16, 128)   # slot 0, layer 0
    _close(held[:, :32], c[0])
    assert np.abs(held[:, 32:40]).max() > 0               # the rotary key
    assert not held[:, 40:].any()                         # the padding
    raw = np.asarray(kv_a[0, :, 32:])
    assert np.allclose(held[0, 32:40], raw[0], atol=1e-6)  # position 0
    assert not np.allclose(held[5, 32:40], raw[5], atol=1e-3)  # rotated
    assert not rows[:, 0].any()                            # TRASH untouched


def test_a_page_reused_by_a_second_request_reads_nothing_of_the_last(model):
    """Request A fills a slot's pages to position 30; request B (12
    tokens) is prefilled into the same pages and decoded: its logits are
    those of B on fresh pages, whatever A left past B's positions."""
    cfg, params = model
    fwd = compiled_forward_cached(pangu.forward_cached, cfg)
    a, b = jnp.asarray(_tokens((1, 32), 5)), jnp.asarray(_tokens((1, 16), 6))
    step = jnp.asarray(_tokens((1, 1), 7))

    def run(cache, io, prompt):
        n = prompt.shape[1]
        positions = jnp.arange(n)[None]
        _, cache = fwd(params, prompt, cfg, cache, positions=positions,
                       kv_io=io)
        return cache

    def decode(cache, io, at):
        logits, _ = fwd(params, step, cfg, cache,
                        positions=jnp.full((1, 1), at), kv_io=io)
        return logits

    pool, io = _paged(cfg, 1, 5)
    used = run(run(tuple(pool), io, a), io, b[:, :12])
    fresh = run(tuple(_paged(cfg, 1, 5)[0]), io, b[:, :12])
    assert np.abs(np.asarray(used[0]) - np.asarray(fresh[0])).max() > 0
    np.testing.assert_array_equal(np.asarray(decode(used, io, 12)),
                                  np.asarray(decode(fresh, io, 12)))


def test_the_latent_kernel_in_interpret_mode_is_the_xla_form():
    """``latent_decode`` against the gathered form on one pool: slots at
    position 0, at a page's last row, one past it, and past a block of
    keys (the block is cut to 32 keys so that the double buffer turns
    over)."""
    rng = np.random.default_rng(0)
    pool = jnp.asarray(rng.normal(size=(2, 13, 1, 8, 128)), F32)
    q = jnp.asarray(rng.normal(size=(4, 6, 128)), F32)
    tables = jnp.asarray(1 + rng.permutation(12).reshape(4, 3), jnp.int32)
    positions = jnp.asarray([0, 7, 8, 23], jnp.int32)
    kw = dict(layer=jnp.int32(1), value_width=32, scale=0.2)
    want = paged_attention.latent_attention(
        q, pool, tables, positions, kernel=False, **kw)
    for keys in (16, 512):
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(paged_attention, "_latent_block_keys",
                          lambda heads, keys=keys: keys)
            got = paged_attention.latent_attention(
                q, pool, tables, positions, kernel=True, interpret=True,
                **kw)
        assert got.shape == (4, 6, 32)
        _close(got, want, 1e-5)
    with pytest.raises(ValueError, match="one head of the query's width"):
        paged_attention.pallas_latent_decode_attention(
            q[..., :64], pool, tables, positions, interpret=True, **kw)


def test_heads_in_groups_attend_as_all_heads_at_once(model, full,
                                                     monkeypatch):
    """The full-shape prefill call expands and attends its heads in
    groups (``head_groups``: 4 of 32 at 8 x 3,072 x 128 x 192 in
    bfloat16, 1 for the one-row shape and for a decode step); here two
    groups of two heads against the one-group forward."""
    cfg, params = model
    tokens, system, _, _ = full
    assert pangu.head_groups(8 * 3072, 128, 192, 2) == 4
    assert pangu.head_groups(1536, 128, 192, 2) == 1
    assert pangu.head_groups(8, 128, 192, 2) == 1
    monkeypatch.setattr(pangu, "_EXPANDED_Q_BYTES", 2 * 40 * 2 * 24 * 4)
    assert pangu.head_groups(2 * 40, 4, 24, 4) == 2
    with jax.default_matmul_precision("highest"):
        grouped = jax.jit(pangu.forward, static_argnums=2)(
            params, tokens, cfg)
    _close(grouped, system, 1e-5)


# ---- the expert layer -------------------------------------------------------------

def test_the_32_shares_of_one_expert_layer_add_up_to_the_uncut_layer():
    """Eight shares of two experts here (16 routed experts; the cell
    holds 8 of 256 on a chip of 32): each share's routed partial result
    through ``dropless_mlp``, the ungated shared expert counted once,
    against the uncut reference's layer on the same input."""
    whole = tiny_config(WHOLE)
    params = seeded_params(whole, seed=5)
    place = 1                                    # the second sparse layer
    moe = params["layers"]["moe"]
    m = jnp.asarray(np.random.default_rng(2).normal(size=(2, 24, 64)), F32)
    d = reference.pangu_dims(ref_config(WHOLE))
    small = {k: v[place].astype(F32) for k, v in moe.items()
             if k not in qwen3_moe.EXPERT_KEYS}
    experts = {k: moe[k] for k in qwen3_moe.EXPERT_KEYS}
    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda rows: reference.moe_part(
            rows, small, experts, place, d, 4))(m)
        shared = qwen3_moe.shared_expert(
            m.reshape(-1, 64), small, whole).reshape(m.shape)
        total = shared
        held_rows = 0
        for first in range(0, 16, 2):
            cfg = tiny_config(dict(WHOLE, n_routed_experts=2,
                                   num_routed_experts=16,
                                   first_expert_id=first))
            layer = dict(small)
            stack = {k: moe[k][:, first:first + 2]
                     for k in qwen3_moe.EXPERT_KEYS}
            y, _, _, routing = qwen3_moe.dropless_mlp(
                m, layer, cfg, None, (stack, place))
            total = total + (y - shared)          # its routed part alone
            counts = qwen3_moe.routing_counts(routing)
            assert int(counts["dropped"]) == 0
            held_rows += int(counts["routed"])
    assert held_rows == 2 * 24 * 3               # every choice held once
    _close(total, want)


def test_the_router_has_no_selection_bias_and_scales_by_2_5(model):
    cfg, params = model
    m = jnp.asarray(np.random.default_rng(4).normal(size=(12, 64)), F32)
    small = {k: v[0].astype(F32) for k, v in params["layers"]["moe"].items()
             if k not in qwen3_moe.EXPERT_KEYS}
    d = reference.pangu_dims(ref_config())
    w = np.asarray(reference.expert_weights(m, small, d))
    scores = np.asarray(jax.nn.sigmoid(m @ small["router"]))
    top = np.sort(scores, axis=-1)[:, -3:]
    chosen = scores >= top[:, :1]
    want = np.where(chosen, scores, 0) / top.sum(-1, keepdims=True) * 2.5
    np.testing.assert_allclose(w, want[:, 4:8], rtol=1e-5)


@pytest.mark.parametrize("n,k,hidden,blocks", [
    (24576, 8, 7680, 12),      # the cell's prefill call: 3.02 GB of rows
    (24576, 8, 2048, 1),       # Trinity-Mini's: 0.81 GB, as it was
    (16384, 8, 2048, 1),       # the longgen cells' full shape
    (8, 8, 7680, 1),           # a decode step
    (1536, 8, 7680, 1),        # the one-row prefill shape
])
def test_the_expert_layer_bounds_its_sorted_rows_by_the_shapes(n, k, hidden,
                                                                blocks):
    assert grouped_matmul.token_blocks(n, k, hidden, 2) == blocks
    rows = n // blocks * k * hidden * 2
    assert rows <= (grouped_matmul._SORTED_ROWS_BLOCK if blocks > 1
                    else grouped_matmul._SORTED_ROWS_WHOLE)


def test_token_blocks_compute_what_one_call_computes(monkeypatch):
    """The same rows through one call and through four blocks of
    sixteen tokens: every held, live choice computed once, none
    dropped."""
    rng = np.random.default_rng(1)
    n, k, h, i, e = 64, 3, 16, 8, 4
    x = jnp.asarray(rng.normal(size=(n, h)), F32)
    idx = jnp.asarray(rng.integers(-2, 6, size=(n, k)), jnp.int32)
    held = (idx >= 0) & (idx < e)
    w = jnp.asarray(rng.uniform(size=(n, k)), F32)
    live = jnp.asarray(rng.uniform(size=n) < 0.8)
    stacks = [jnp.asarray(rng.normal(size=s), F32) for s in (
        (2, e, h, i), (2, e, h, i), (2, e, i, h))]
    args = (x, idx, w, *stacks)
    kw = dict(live=live, held=held, layer=jnp.int32(1))
    one, sizes = grouped_matmul.dropless_expert_mlp(*args, **kw)
    limit = 16 * k * h * 4
    monkeypatch.setattr(grouped_matmul, "_SORTED_ROWS_WHOLE", limit)
    monkeypatch.setattr(grouped_matmul, "_SORTED_ROWS_BLOCK", limit)
    assert grouped_matmul.token_blocks(n, k, h, 4) == 4
    four, sizes4 = grouped_matmul.dropless_expert_mlp(*args, **kw)
    _close(four, one, 1e-5)
    np.testing.assert_array_equal(np.asarray(sizes), np.asarray(sizes4))
    assert int(sizes.sum()) == int((held & live[:, None]).sum())
