"""The prefill step names the row a slot it samples from
(``logit_rows = tail_lens - 1``) and every family's ``forward_cached``
takes those rows BEFORE its final norm and head: the step's
``last_logits`` / ``first_token`` are the all-rows forward's row
``tail_len - 1``, whatever the tail length, a prefix hit or the write
mask say, and a forward that names no rows returns ``[B, S, V]`` as it
always did. CPU, float32, the tiny size of each of the five families.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scaletorch_tpu.inference import SamplingParams
from scaletorch_tpu.inference.decode import (
    counts_routing,
    make_paged_prefill_step,
    resolve_forward_cached,
    rows_name_slots,
)
from scaletorch_tpu.inference.kv_cache import (
    PagedKVIO,
    carries_state,
    init_paged_kv_cache,
    no_prefix_reason,
)
from scaletorch_tpu.inference.routing_counters import ROUTING_COUNTERS
from scaletorch_tpu.models import gpt_moe, llama

FAMILIES = ["llama", "qwen3_moe", "olmo_hybrid", "qwen3_next", "gpt_moe"]
GREEDY = SamplingParams(temperature=0.0)
SLOTS, PREFILL, PAGE, MAX_PAGES = 5, 8, 4, 4
SEQ = PAGE * MAX_PAGES
#           differ by slot ...   one row  the whole buffer  prefix hit  unwritten
TAIL_LENS = np.array([3,         1,       PREFILL,          5,          6], np.int32)
STARTS = np.array([0,            0,       0,                PAGE,       0], np.int32)
WRITTEN = np.array([True,        True,    True,             True,       False])
CASES = ["short_tail", "one_row", "whole_buffer", "prefix_hit",
         "outside_write_mask"]
TOL = dict(rtol=1e-6, atol=1e-6)


@functools.lru_cache(maxsize=None)
def _model(family):
    if family == "llama":
        cfg = llama.LlamaConfig(
            vocab_size=64, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, dtype=jnp.float32)
        return cfg, llama.init_params(jax.random.PRNGKey(0), cfg)
    if family == "gpt_moe":
        cfg = gpt_moe.GPTMoEConfig(
            vocab_size=64, block_size=SEQ, n_layer=2, n_head=2, n_embd=32,
            use_moe=True, num_experts=2, top_k=1)
        return cfg, gpt_moe.init_params(jax.random.PRNGKey(0), cfg)
    if family == "qwen3_moe":
        from tests.models import test_olmoe as tiny
    elif family == "olmo_hybrid":
        from tests.models import test_olmo_hybrid as tiny
    else:
        from tests.models import test_qwen3_next as tiny
    cfg = tiny.tiny_config()
    return cfg, tiny.seeded_params(cfg)


def _inputs(cfg):
    tokens = jax.random.randint(
        jax.random.PRNGKey(7), (SLOTS, PREFILL), 0, cfg.vocab_size)
    tables = jnp.asarray(
        (np.arange(SLOTS * MAX_PAGES, dtype=np.int32) + 1).reshape(
            SLOTS, MAX_PAGES))
    keys = jnp.zeros((SLOTS, 2), jnp.uint32)
    return tokens, tables, keys


def _pool(cfg):
    return init_paged_kv_cache(
        cfg, SLOTS * MAX_PAGES + 1, PAGE, dtype=jnp.float32, slots=SLOTS)


def _all_rows_step(cfg, routing):
    """The step as it was before the forwards learnt ``logit_rows``: the
    family's forward over every row, then the row ``tail_len - 1``."""
    fwd = resolve_forward_cached(cfg)

    @jax.jit
    def run(params, tokens, tail_lens, starts, write_mask, tables, pool):
        rows = jnp.broadcast_to(
            jnp.arange(PREFILL, dtype=jnp.int32), tokens.shape)
        kw = {}
        if routing or carries_state(cfg):
            kw["row_mask"] = write_mask[:, None] & (rows < tail_lens[:, None])
        # the step's own choice of attention: it differs from the step
        # in the rows its head multiplies alone
        hit = (no_prefix_reason(cfg) is None
               and jnp.any(write_mask & (starts > 0)))
        logits, new_pool = fwd(
            params, tokens, cfg, tuple(pool),
            positions=starts[:, None] + rows, write_mask=write_mask,
            kv_io=PagedKVIO(tables, PAGE, seq_limit=SEQ, prefix_hit=hit),
            **kw)[:2]
        assert logits.shape == (SLOTS, PREFILL, cfg.vocab_size)
        last = jnp.take_along_axis(
            logits, (tail_lens - 1)[:, None, None], axis=1)[:, 0]
        return last, new_pool

    return run


@pytest.fixture(scope="module", params=FAMILIES)
def stepped(request):
    """One prefill call of the engine's step beside the all-rows
    forward on the same pool: the prefix of the ``prefix_hit`` slot is
    put into the pool (and, for a state-carrying model, into that slot's
    state) by a first call of the same step."""
    cfg, params = _model(request.param)
    routing = counts_routing(cfg)
    step = make_paged_prefill_step(
        cfg, GREEDY, page_size=PAGE, seq_limit=SEQ, routing_counts=routing)
    tokens, tables, keys = _inputs(cfg)
    pool = _pool(cfg)
    extra = ((jnp.zeros(len(ROUTING_COUNTERS), jnp.uint32),)
             if routing else ())
    hit = CASES.index("prefix_hit")
    prefix = jax.random.randint(
        jax.random.PRNGKey(8), (SLOTS, PREFILL), 0, cfg.vocab_size)
    out = step(params, prefix, jnp.full((SLOTS,), PAGE, jnp.int32),
               jnp.zeros((SLOTS,), jnp.int32), jnp.arange(SLOTS) == hit,
               tables, pool, keys, *extra)
    pool = out[3]
    # a family that refuses prefix sharing starts every row at 0 (its
    # step holds no read of a prefix): the slot is one more cold tail
    starts = STARTS * (no_prefix_reason(cfg) is None)
    args = (params, tokens, jnp.asarray(TAIL_LENS), jnp.asarray(starts),
            jnp.asarray(WRITTEN), tables, pool)
    got = step(*args, keys, *extra)
    want_last, want_pool = _all_rows_step(cfg, routing)(*args)
    return cfg, got, want_last, want_pool, pool


@pytest.mark.parametrize("case", CASES)
def test_the_step_s_row_is_the_all_rows_forward_s(stepped, case):
    cfg, (first, last, finite, *_), want_last, _, _ = stepped
    slot = CASES.index(case)
    assert last.shape == (SLOTS, cfg.vocab_size) and last.dtype == jnp.float32
    if rows_name_slots(cfg) and not WRITTEN[slot]:
        # handed no slot ids the step runs its one-row program once a
        # WRITTEN row (``decode.SlotRows``): the row nothing reads is
        # blank and says so
        assert not bool(finite[slot]) and not np.asarray(last[slot]).any()
        return
    np.testing.assert_allclose(
        np.asarray(last[slot]), np.asarray(want_last[slot]), **TOL)
    assert int(first[slot]) == int(jnp.argmax(want_last[slot]))
    assert bool(finite[slot])
    # the rows differ from each other: a wrong row would be seen
    others = [s for s in range(SLOTS) if s != slot]
    assert all(float(jnp.max(jnp.abs(want_last[slot] - want_last[s]))) > 1e-3
               for s in others)


def test_the_cache_it_returns_is_the_all_rows_forward_s(stepped):
    """Naming rows touches nothing before the final norm: the pool (and
    a recurrent state) come back as from the all-rows forward, and the
    slot outside ``write_mask`` keeps what it held."""
    cfg, got, _, want_pool, before = stepped
    new_pool = got[3]
    assert type(new_pool) is type(before)
    for name, new, want in zip(before._fields, new_pool, want_pool):
        if rows_name_slots(cfg):
            # one row a program, not five: equal to float32's last
            # places, and the row that is not written is not run, so
            # its K/V never reaches the TRASH page
            keep = slice(None) if name in ("state", "conv") else slice(1, None)
            np.testing.assert_allclose(
                np.asarray(new[:, keep]), np.asarray(want[:, keep]), **TOL)
        else:
            np.testing.assert_array_equal(np.asarray(new), np.asarray(want))
    slot = CASES.index("outside_write_mask")
    for name, new, old in zip(before._fields, new_pool, before):
        if name in ("state", "conv"):
            np.testing.assert_array_equal(
                np.asarray(new[:, slot]), np.asarray(old[:, slot]))
        else:
            own = slice(1 + slot * MAX_PAGES, 1 + (slot + 1) * MAX_PAGES)
            np.testing.assert_array_equal(
                np.asarray(new[:, own]), np.asarray(old[:, own]))


@pytest.mark.parametrize("family", FAMILIES)
def test_a_forward_that_names_no_rows_returns_every_row(family):
    """``logit_rows=None`` is the forward as it was, ``[B, S, V]``; the
    named rows are rows of it."""
    cfg, params = _model(family)
    fwd = resolve_forward_cached(cfg)
    tokens, tables, _ = _inputs(cfg)
    pool = tuple(_pool(cfg))
    positions = jnp.broadcast_to(
        jnp.arange(PREFILL, dtype=jnp.int32), tokens.shape)
    rows = jnp.asarray(TAIL_LENS - 1)

    @functools.partial(jax.jit, static_argnames="named")
    def run(params, tokens, pool, named):
        return fwd(params, tokens, cfg, pool, positions=positions,
                   kv_io=PagedKVIO(tables, PAGE, seq_limit=SEQ),
                   **({"logit_rows": rows} if named else {}))[0]

    every = run(params, tokens, pool, named=False)
    one = run(params, tokens, pool, named=True)
    assert every.shape == (SLOTS, PREFILL, cfg.vocab_size)
    assert one.shape == (SLOTS, 1, cfg.vocab_size)
    np.testing.assert_allclose(
        np.asarray(one[:, 0]),
        np.asarray(every[np.arange(SLOTS), TAIL_LENS - 1]), **TOL)


def test_the_rows_are_taken_or_the_hidden_states_pass_untouched():
    x = jnp.arange(2 * 3 * 4, dtype=jnp.float32).reshape(2, 3, 4)
    assert llama.select_logit_rows(x, None) is x
    np.testing.assert_array_equal(
        np.asarray(llama.select_logit_rows(x, jnp.asarray([2, 0]))),
        np.asarray(x)[[0, 1], [2, 0]][:, None])


def test_a_forward_fn_that_cannot_take_the_rows_fails_at_the_trace():
    cfg, params = _model("llama")

    def all_rows_only(params, tokens, cfg, cache, *, positions,
                      write_mask=None, kv_io=None):
        return llama.forward_cached(
            params, tokens, cfg, cache, positions=positions,
            write_mask=write_mask, kv_io=kv_io)

    step = make_paged_prefill_step(
        cfg, GREEDY, page_size=PAGE, seq_limit=SEQ, forward_fn=all_rows_only)
    tokens, tables, keys = _inputs(cfg)
    with pytest.raises(TypeError, match="logit_rows"):
        step(params, tokens, jnp.asarray(TAIL_LENS), jnp.asarray(STARTS),
             jnp.asarray(WRITTEN), tables, _pool(cfg), keys)
