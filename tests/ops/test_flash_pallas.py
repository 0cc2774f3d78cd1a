"""Golden tests for the Pallas flash-attention kernel (interpret mode on
CPU) against the dense sdpa reference — the same strategy the reference
uses for its ring-attention math (reference
tests/parallel/test_context_parallel.py:72-106)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scaletorch_tpu.models.layers import sdpa_attention, sdpa_attention_with_lse
from scaletorch_tpu.ops.pallas.flash import (
    _FIRST,
    _LAST,
    MAX_CAUSAL_STEPS,
    causal_block_plan,
    flash_block_backward,
    flash_blocks,
    flash_forward_with_lse,
    pallas_flash_attention,
)


def _qkv(b=2, hq=4, hkv=2, s=256, d=64, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(jax.random.key(0), 3)
    return (
        jax.random.normal(kq, (b, hq, s, d), dtype),
        jax.random.normal(kk, (b, hkv, s, d), dtype),
        jax.random.normal(kv, (b, hkv, s, d), dtype),
    )


@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_sdpa(causal):
    q, k, v = _qkv()
    out = pallas_flash_attention(
        q, k, v, causal=causal, block_q=128, block_kv=128, interpret=True
    )
    ref = sdpa_attention(q, k, v, causal=causal)
    assert jnp.max(jnp.abs(out - ref)) < 1e-5


@pytest.mark.slow
@pytest.mark.parametrize("causal", [True, False])
def test_gradients_match_sdpa(causal):
    q, k, v = _qkv(s=128, d=32)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    gp = jax.grad(
        loss(lambda q, k, v: pallas_flash_attention(
            q, k, v, causal=causal, block_q=64, block_kv=64, interpret=True
        )),
        argnums=(0, 1, 2),
    )(q, k, v)
    gr = jax.grad(
        loss(lambda q, k, v: sdpa_attention(q, k, v, causal=causal)),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(gp, gr):
        assert jnp.max(jnp.abs(a - b)) < 1e-4


def test_mqa_single_kv_head():
    q, k, v = _qkv(hq=4, hkv=1, s=128, d=32)
    out = pallas_flash_attention(
        q, k, v, causal=True, block_q=64, block_kv=64, interpret=True
    )
    ref = sdpa_attention(q, k, v, causal=True)
    assert jnp.max(jnp.abs(out - ref)) < 1e-5


@pytest.mark.slow
@pytest.mark.parametrize("bq,bkv", [(64, 32), (32, 64)])
def test_mismatched_block_sizes_causal(bq, bkv):
    # regression: the causal DMA clamp must convert between query- and
    # key-block units, not compare raw block indices
    q, k, v = _qkv(s=128, d=32)
    out = pallas_flash_attention(
        q, k, v, causal=True, block_q=bq, block_kv=bkv, interpret=True
    )
    ref = sdpa_attention(q, k, v, causal=True)
    assert jnp.max(jnp.abs(out - ref)) < 1e-5

    gp = jax.grad(
        lambda q, k, v: jnp.sum(pallas_flash_attention(
            q, k, v, causal=True, block_q=bq, block_kv=bkv, interpret=True
        ) ** 2),
        argnums=(0, 1, 2),
    )(q, k, v)
    gr = jax.grad(
        lambda q, k, v: jnp.sum(sdpa_attention(q, k, v, causal=True) ** 2),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(gp, gr):
        assert jnp.max(jnp.abs(a - b)) < 1e-4


def test_uneven_block_fallback():
    # seq not divisible by the preferred block: _pick_block halves it
    q, k, v = _qkv(s=192, d=32)
    out = pallas_flash_attention(
        q, k, v, causal=True, block_q=128, block_kv=128, interpret=True
    )
    ref = sdpa_attention(q, k, v, causal=True)
    assert jnp.max(jnp.abs(out - ref)) < 1e-5


def test_flash_jax_rejects_nondivisible_gqa_heads():
    """flash_attention_jax mirrors the in-repo entry points' explicit
    guard: hq % hkv != 0 must raise up front instead of floor-dividing
    into an obscure head-count mismatch inside jax's kernel."""
    from scaletorch_tpu.ops.flash_attention import flash_attention_jax

    q, k, v = _qkv(hq=4, hkv=3, s=64, d=32)
    with pytest.raises(ValueError, match="multiple of key/value heads"):
        flash_attention_jax(q, k, v)


# ---------------------------------------------------------------------------
# the causal structure on the grid (PR 33): the plan, then the kernels on it
# ---------------------------------------------------------------------------
PLAN_SHAPES = [
    # sq, skv, bq, bkv -> live blocks, key blocks no row reaches
    ((8192, 8192, 512, 512), (136, 0)),   # train-0.6b-seq8k
    ((128, 128, 64, 32), (6, 0)),
    ((128, 128, 32, 64), (6, 0)),
    ((64, 64, 64, 64), (1, 0)),           # one block
    ((256, 256, 64, 64), (10, 0)),
    ((192, 192, 64, 64), (6, 0)),
    ((128, 64, 32, 32), (7, 0)),          # more rows than keys
    ((64, 128, 32, 32), (3, 2)),          # keys no row reaches
]
PLAN_IDS = ["x".join(map(str, shape)) for shape, _ in PLAN_SHAPES]


def _visible(shape, i, j):
    """Block (i, j) of the triangle the kernels' mask draws (the block
    alone: the whole 8,192 x 8,192 triangle a call was 17 s of a test)."""
    _, _, bq, bkv = shape
    return (np.arange(i * bq, (i + 1) * bq)[:, None]
            >= np.arange(j * bkv, (j + 1) * bkv)[None, :])


@pytest.mark.parametrize("shape,counts", PLAN_SHAPES, ids=PLAN_IDS)
def test_plan_counts(shape, counts):
    plan = causal_block_plan(*shape)
    assert (plan.live, plan.dead) == counts
    sq, skv, bq, bkv = shape
    # no grid step but a live block's (and one for a key block that has
    # none, to write its zeros): the rectangle had nq * nkv of them
    assert len(plan.by_query[0]) == plan.live
    assert len(plan.by_key[0]) == plan.live + plan.dead
    assert plan.live + plan.dead <= (sq // bq) * (skv // bkv)
    for table in plan.by_query + plan.by_key:  # the cache shares them
        assert table.dtype == np.int32 and not table.flags.writeable


@pytest.mark.parametrize("shape", [s for s, _ in PLAN_SHAPES], ids=PLAN_IDS)
def test_plan_visits_the_blocks_tril_has_something_in(shape):
    """Every live pair once, in the forward's walk, and no other: a block
    is walked iff the triangle has a visible element inside it."""
    sq, skv, bq, bkv = shape
    q_blk, k_blk, _ = causal_block_plan(*shape).by_query
    pairs = list(zip(q_blk.tolist(), k_blk.tolist()))
    assert len(set(pairs)) == len(pairs)
    for i in range(sq // bq):
        for j in range(skv // bkv):
            assert ((i, j) in pairs) == bool(_visible(shape, i, j).any())


@pytest.mark.parametrize("shape", [s for s, _ in PLAN_SHAPES], ids=PLAN_IDS)
def test_plan_walks_open_and_close_each_accumulation_once(shape):
    """Both walks: an outer block at a time, its inner blocks ascending,
    flagged where its accumulation opens and closes. The dk/dv walk
    holds the forward's pairs, and for a key block with none, one block
    the mask empties (so dk = dv = 0 is computed, not special-cased)."""
    sq, skv, bq, bkv = shape
    plan = causal_block_plan(*shape)
    live = set(zip(*(t.tolist() for t in plan.by_query[:2])))
    for tables, n_outer in ((plan.by_query, sq // bq),
                            (plan.by_key, skv // bkv)):
        outer, inner, flags = (t.tolist() for t in tables)
        assert outer == sorted(outer) and set(outer) == set(range(n_outer))
        for o in range(n_outer):
            steps = [n for n, b in enumerate(outer) if b == o]
            assert [inner[n] for n in steps] == sorted(
                inner[n] for n in steps)
            assert [flags[n] for n in steps] == [
                _FIRST * (n == steps[0]) | _LAST * (n == steps[-1])
                for n in steps]
    k_blk, q_blk, _ = (t.tolist() for t in plan.by_key)
    walked = set(zip(q_blk, k_blk))
    assert live <= walked and len(walked) == len(k_blk)
    for i, j in walked - live:
        assert i == 0 and not _visible(shape, i, j).any()


def test_plan_refuses_more_blocks_than_its_tables_hold():
    """The tables lie in SMEM whole: past ``MAX_CAUSAL_STEPS`` a call
    says so, with the way out, before Mosaic is asked. 361 blocks a
    side is the last that fits, whatever the heads."""
    assert causal_block_plan(
        128 * 361, 128 * 361, 128, 128).live == 65341 <= MAX_CAUSAL_STEPS
    with pytest.raises(ValueError, match="larger blocks"):
        causal_block_plan(128 * 362, 128 * 362, 128, 128)
    q = jax.ShapeDtypeStruct((1, 8, 128 * 362, 32), jnp.float32)
    kv = jax.ShapeDtypeStruct((1, 1, 128 * 362, 32), jnp.float32)
    with pytest.raises(ValueError, match="walks 65703 live blocks"):
        jax.eval_shape(lambda q, k, v: pallas_flash_attention(
            q, k, v, block_q=128, block_kv=128, interpret=True), q, kv, kv)
    jax.eval_shape(lambda q, k, v: pallas_flash_attention(
        q, k, v, block_q=256, block_kv=256, interpret=True), q, kv, kv)


def _sq_loss(fn):
    return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)


def _softmax_reference(q, k, v, causal):
    """Plain softmax attention and the log-sum-exp of its scores; causal
    is row >= column from the top left, as the kernels draw it."""
    n_rep = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(x, n_rep, axis=1) for x in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(float(q.shape[-1]))
    if causal:
        visible = (jnp.arange(q.shape[2])[:, None]
                   >= jnp.arange(k.shape[2])[None, :])
        s = jnp.where(visible, s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", jnp.exp(s - lse[..., None]), v), lse


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("hq,hkv", [(4, 2), (4, 1), (2, 2)],
                         ids=["gqa2", "mqa", "mha"])
def test_four_by_four_blocks_forward_and_gradients(hq, hkv, causal):
    """4 x 4 blocks: causal has dead, interior and diagonal blocks at
    once (6 + 6 + 4); ``causal=False`` keeps the rectangle, unmasked."""
    q, k, v = _qkv(b=1, hq=hq, hkv=hkv, s=128, d=32)

    def flash(q, k, v):
        return pallas_flash_attention(
            q, k, v, causal=causal, block_q=32, block_kv=32, interpret=True)

    def ref(q, k, v):
        return sdpa_attention(q, k, v, causal=causal)

    assert jnp.max(jnp.abs(flash(q, k, v) - ref(q, k, v))) < 1e-5
    gp = jax.grad(_sq_loss(flash), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(_sq_loss(ref), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        assert jnp.max(jnp.abs(a - b)) < 1e-4


@pytest.mark.parametrize("bq,bkv", [(64, 32), (32, 64), (16, 64)])
def test_mismatched_blocks_gradients_gqa(bq, bkv):
    """bq != bkv: a diagonal block is then not square and a query block
    has more than one (or a key block several query blocks') of them."""
    q, k, v = _qkv(b=1, hq=4, hkv=2, s=128, d=32)

    def flash(q, k, v):
        return pallas_flash_attention(
            q, k, v, causal=True, block_q=bq, block_kv=bkv, interpret=True)

    gp = jax.grad(_sq_loss(flash), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(_sq_loss(sdpa_attention), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        assert jnp.max(jnp.abs(a - b)) < 1e-4


@pytest.mark.parametrize("sq,skv", [(64, 128), (128, 64)],
                         ids=["keys-past-the-rows", "rows-past-the-keys"])
def test_causal_rectangle_row_ge_column(sq, skv):
    """The kernels' causal is row >= column from the top left, whatever
    the two lengths. Keys that no row reaches get dk = dv = 0 (their
    blocks have no live pair, so the plan walks one the mask empties)."""
    kq, kk, kv = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(kq, (1, 4, sq, 32))
    k = jax.random.normal(kk, (1, 2, skv, 32))
    v = jax.random.normal(kv, (1, 2, skv, 32))

    def ref(q, k, v):
        return _softmax_reference(q, k, v, causal=True)[0]

    def flash(q, k, v):
        return pallas_flash_attention(
            q, k, v, causal=True, block_q=32, block_kv=32, interpret=True)

    assert jnp.max(jnp.abs(flash(q, k, v) - ref(q, k, v))) < 1e-5
    gp = jax.grad(_sq_loss(flash), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(_sq_loss(ref), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        assert jnp.max(jnp.abs(a - b)) < 1e-4
    if skv > sq:
        assert not jnp.any(gp[1][:, :, sq:]) and not jnp.any(gp[2][:, :, sq:])


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_raw_entries_match_the_differentiable_op(causal):
    """Ring attention's pair: ``flash_forward_with_lse`` (causal on the
    diagonal hop, full on the others) and ``flash_block_backward`` give
    what ``pallas_flash_attention`` and its VJP give, and the lse is the
    dense one."""
    q, k, v = _qkv(b=1, hq=4, hkv=2, s=128, d=32)
    kw = dict(causal=causal, block_q=32, block_kv=32, interpret=True)
    out, lse = flash_forward_with_lse(q, k, v, **kw)
    want, vjp = jax.vjp(
        lambda q, k, v: pallas_flash_attention(q, k, v, **kw), q, k, v)
    assert jnp.array_equal(out, want)
    _, ref_lse = sdpa_attention_with_lse(q, k, v, causal=causal)
    assert jnp.max(jnp.abs(lse - ref_lse)) < 1e-5
    dout = jnp.cos(out)
    for a, b in zip(flash_block_backward(q, k, v, out, lse, dout, **kw),
                    vjp(dout)):
        assert jnp.array_equal(a, b)


# ---------------------------------------------------------------------------
# the forward's statistics, lane-replicated (PR 38): m and l are held
# [bq, 128]; what widens them to the key block and to the head is chosen
# from the static shapes, so each branch gets a shape that selects it
# ---------------------------------------------------------------------------
STATISTICS_CASES = [
    # hq, hkv, sq, skv, d, bq, bkv, causal
    # key blocks under a register's width: the leading lanes
    (4, 2, 128, 128, 64, 32, 32, True),
    (4, 1, 128, 128, 128, 64, 64, False),
    (2, 2, 128, 64, 256, 32, 32, True),
    (4, 2, 64, 128, 64, 16, 64, True),
    # one register wide: as held
    (4, 2, 256, 256, 128, 128, 128, True),
    (4, 1, 128, 256, 64, 64, 128, False),
    (2, 2, 256, 256, 256, 64, 128, True),
    # wider: repeated whole registers
    (4, 2, 512, 512, 64, 128, 256, True),
    (2, 2, 256, 512, 128, 128, 256, False),
    (4, 1, 512, 512, 256, 256, 256, True),
    (4, 2, 256, 512, 128, 64, 512, True),
    # neither: 192 = a register and a half
    (4, 2, 192, 384, 64, 64, 192, False),
]


@pytest.mark.parametrize(
    "hq,hkv,sq,skv,d,bq,bkv,causal", STATISTICS_CASES,
    ids=[f"h{c[0]}-{c[1]}_s{c[2]}-{c[3]}_d{c[4]}_b{c[5]}-{c[6]}_"
         + ("causal" if c[7] else "full") for c in STATISTICS_CASES])
def test_lane_replicated_statistics_at_every_width(
        hq, hkv, sq, skv, d, bq, bkv, causal):
    """``out``, ``lse`` and the three gradients against plain softmax,
    over key blocks under, at and over 128 lanes, heads 64 / 128 / 256
    wide, GQA / MQA / MHA, causal and full, ``sq != skv``. The lse that
    ring attention merges across hops is finite on every row and is the
    log-sum-exp of the reference's scores."""
    kq, kk, kv = jax.random.split(jax.random.key(bkv + d + hkv), 3)
    q = jax.random.normal(kq, (1, hq, sq, d))
    k = jax.random.normal(kk, (1, hkv, skv, d))
    v = jax.random.normal(kv, (1, hkv, skv, d))
    kw = dict(causal=causal, block_q=bq, block_kv=bkv, interpret=True)

    out, lse = flash_forward_with_lse(q, k, v, **kw)
    want_out, want_lse = _softmax_reference(q, k, v, causal)
    assert lse.shape == (1, hq, sq) and bool(jnp.all(jnp.isfinite(lse)))
    assert jnp.max(jnp.abs(lse - want_lse)) < 1e-5
    assert jnp.max(jnp.abs(out - want_out)) < 1e-5

    def flash(q, k, v):
        return pallas_flash_attention(q, k, v, **kw)

    assert jnp.array_equal(flash(q, k, v), out)
    gp = jax.grad(_sq_loss(flash), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(_sq_loss(
        lambda q, k, v: _softmax_reference(q, k, v, causal)[0]),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        assert jnp.max(jnp.abs(a - b)) < 1e-4


# ---------------------------------------------------------------------------
# blocks from shapes (PR 62): each kernel takes its own (bq, bkv)
# ---------------------------------------------------------------------------
RULE_ROWS = [
    # id, (sq, skv), causal, window, fwd, dq, dkv
    ("row-8192-training-cell-mimo-kimi-linear", (8192, 8192), True, None,
     (512, 512), (1024, 512), (1024, 1024)),
    ("rows-3072-trinity-mini-openpangu-jamba2", (3072, 3072), True, None,
     (512, 512), (512, 512), (1024, 1024)),
    ("trinity-mini-window-2048", (3072, 3072), True, 2048,
     (512, 512), (512, 512), (1024, 1024)),
    ("one-row-512", (512, 512), True, None,
     (512, 512), (512, 512), (512, 512)),
    ("ring-diagonal-hop-2k", (2048, 2048), True, None,
     (512, 512), (512, 512), (1024, 1024)),
    ("ring-unmasked-hop-4k", (4096, 4096), False, None,
     (1024, 512), (1024, 1024), (1024, 1024)),
    ("ring-unmasked-hop-1k", (1024, 1024), False, None,
     (512, 512), (512, 512), (512, 512)),
    ("ulysses-128k", (131072, 131072), True, None,
     (1024, 1024), (1024, 512), (1024, 1024)),
    ("window-narrower-than-a-block", (16384, 16384), True, 128,
     (512, 512), (1024, 512), (1024, 1024)),
    ("long-row-16k", (16384, 16384), True, None,
     (1024, 1024), (1024, 512), (1024, 1024)),
    ("side-1024-does-not-divide", (8704, 8704), True, None,
     (512, 512), (512, 512), (512, 512)),
    ("rows-shorter-than-keys", (2048, 8192), True, None,
     (512, 512), (512, 512), (1024, 1024)),
    ("a-cpu-test-s-shape", (192, 128), True, None,
     (192, 128), (192, 128), (192, 128)),
]


@pytest.mark.parametrize("shape,causal,window,fwd,dq,dkv",
                         [row[1:] for row in RULE_ROWS],
                         ids=[row[0] for row in RULE_ROWS])
def test_the_rule_s_blocks_by_shape(shape, causal, window, fwd, dq, dkv):
    """``flash_blocks`` at the training cell's sides (MiMo-V2-Flash's
    and Kimi-Linear's 8,192-token rows have the same: the head's width
    is no argument), at the sides the other serving prefills, the ring
    and Ulysses hand the kernels, under a window narrower than a block
    and at a side 1,024 does not divide: the three pairs a sweep on the
    v5e chose (PERF.md, PR 62). Every pair divides its sides."""
    got = {kind: flash_blocks(kind, *shape, causal=causal, window=window)
           for kind in ("fwd", "dq", "dkv")}
    assert got == {"fwd": fwd, "dq": dq, "dkv": dkv}
    sq, skv = shape[:2]
    assert all(sq % bq == 0 and skv % bkv == 0 for bq, bkv in got.values())


def test_the_rule_knows_three_kernels_and_a_hand_set_pair_overrides_all(
        monkeypatch):
    with pytest.raises(ValueError, match="one of fwd, dq, dkv"):
        flash_blocks("bwd", 8192, 8192)
    q = jax.ShapeDtypeStruct((1, 16, 8192, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 8, 8192, 128), jnp.bfloat16)

    def blocks():
        # a new function a call: the pair is read when a call is traced
        grad = jax.grad(lambda q, k, v: pallas_flash_attention(
            q, k, v, interpret=True).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))
        return flash_call_blocks(jax.make_jaxpr(grad)(q, k, k).jaxpr)

    assert blocks() == {"flash_fwd": (512, 512), "flash_dq": (1024, 512),
                        "flash_dkv": (1024, 1024)}
    monkeypatch.setenv("SCALETORCH_TPU_FLASH_BLOCK_Q", "2048")
    assert blocks() == {"flash_fwd": (2048, 512), "flash_dq": (2048, 512),
                        "flash_dkv": (2048, 1024)}
    monkeypatch.setenv("SCALETORCH_TPU_FLASH_BLOCK_KV", "256")
    assert set(blocks().values()) == {(2048, 256)}
    # an empty value is an unset one
    monkeypatch.setenv("SCALETORCH_TPU_FLASH_BLOCK_Q", "")
    monkeypatch.setenv("SCALETORCH_TPU_FLASH_BLOCK_KV", "")
    assert blocks()["flash_dkv"] == (1024, 1024)


THREE_PAIRS_CASES = [
    # hq, hkv, sq, skv, d, causal, fwd, dq, dkv
    (4, 2, 128, 128, 32, True, (64, 32), (32, 128), (128, 64)),
    (2, 1, 64, 128, 32, True, (32, 64), (64, 128), (16, 32)),
    (2, 2, 128, 64, 32, False, (128, 32), (32, 64), (64, 16)),
]


@pytest.mark.parametrize(
    "hq,hkv,sq,skv,d,causal,fwd,dq,dkv", THREE_PAIRS_CASES,
    ids=[f"h{c[0]}-{c[1]}_s{c[2]}-{c[3]}_" + ("causal" if c[5] else "full")
         for c in THREE_PAIRS_CASES])
def test_three_kernels_in_three_different_blocks_in_one_grad(
        monkeypatch, hq, hkv, sq, skv, d, causal, fwd, dq, dkv):
    """One ``jax.grad`` whose forward, ``flash_dq`` and ``flash_dkv`` each
    run another rectangular pair (the rule's answer, stood in for here:
    at these sizes the real one says one pair for all three): ``lse`` is
    ``[B, Hq, 1, S]`` whatever the forward's blocks, so the backward
    kernels read it in theirs. Against plain softmax, GQA / MQA / MHA,
    ``sq != skv`` both ways, causal and full."""
    from scaletorch_tpu.ops.pallas import flash as flash_module

    asked = []

    def rule(kind, *shape, **mask):
        asked.append((kind, shape, mask))
        return {"fwd": fwd, "dq": dq, "dkv": dkv}[kind]

    monkeypatch.setattr(flash_module, "flash_blocks", rule)
    kq, kk, kv = jax.random.split(jax.random.key(sq + skv + d), 3)
    q = jax.random.normal(kq, (1, hq, sq, d))
    k = jax.random.normal(kk, (1, hkv, skv, d))
    v = jax.random.normal(kv, (1, hkv, skv, d))

    def flash(q, k, v):
        return pallas_flash_attention(q, k, v, causal=causal, interpret=True)

    def ref(q, k, v):
        return _softmax_reference(q, k, v, causal)[0]

    traced = jax.make_jaxpr(jax.grad(_sq_loss(flash), argnums=(0, 1, 2)))(
        q, k, v)
    assert flash_call_blocks(traced.jaxpr) == {
        "flash_fwd": fwd, "flash_dq": dq, "flash_dkv": dkv}
    assert {kind for kind, _, _ in asked} == {"fwd", "dq", "dkv"}
    assert all(shape == (sq, skv) and mask == dict(
        causal=causal, window=None) for _, shape, mask in asked)

    out, vjp = jax.vjp(flash, q, k, v)
    want, want_vjp = jax.vjp(ref, q, k, v)
    assert jnp.max(jnp.abs(out - want)) < 1e-5
    for a, b in zip(vjp(2 * out), want_vjp(2 * want)):
        assert jnp.max(jnp.abs(a - b)) < 1e-4


def pallas_calls(jaxpr):
    """Every ``pallas_call`` equation under ``jaxpr``, the ones inside a
    ``custom_vjp`` or a ``jit`` too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for param in eqn.params.values():
            inner = getattr(param, "jaxpr", param)
            if hasattr(inner, "eqns"):
                yield from pallas_calls(inner)


def flash_call_blocks(jaxpr):
    """``{kernel name: (bq, bkv)}`` of the flash calls traced under
    ``jaxpr``: the rows of the ``q`` and of the ``k`` block, the first
    two operands of all three kernels."""
    return {
        call.params["name"]: tuple(
            mapping.block_shape[2].block_size
            for mapping in call.params["grid_mapping"].block_mappings[:2])
        for call in pallas_calls(jaxpr)}
