"""A multi-row prefill call none of whose rows continues a prefix in
the pool attends each prompt to itself (``PagedKVIO``'s ``prefix_hit``
-> ``ops/flash_attention.prefill_self_attention``); a call that holds a
prefix hit reads the pool through the gather, as every call did. The
two agree on the same prompts in the four families that come to
``PagedKVIO.attend`` with several rows, the gather branch gives what
it gave, and the engine counts which calls took which. Quick
tier, CPU (where the key-block attention is the plain softmax)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scaletorch_tpu.inference import InferenceEngine, SamplingParams
from scaletorch_tpu.inference.decode import (
    make_paged_prefill_step,
    resolve_forward_cached,
    rows_name_slots,
)
from scaletorch_tpu.inference.kv_cache import (
    PagedKVIO,
    init_paged_kv_cache,
    no_prefix_reason,
)
from tests.inference.oracle import assert_greedy
from tests.inference.test_paged_cache import PAGED_DENSE_LOGIT_TOL
from tests.inference.test_paged_engine import family as paged_family
from tests.inference.test_prefill_logit_rows import _model as tiny_model

GREEDY = SamplingParams(temperature=0.0)
SLOTS, LENGTH, PAGE, MAX_PAGES = 3, 12, 4, 7
FAMILIES = ["qwen3", "olmoe", "olmo_hybrid", "qwen3_next"]


def family(name):
    """(cfg, params) of a family at its tiny size."""
    if name in ("olmo_hybrid", "qwen3_next"):
        return tiny_model(name)
    return paged_family(name)


def reads_the_pool(forward_cached):
    """``forward_cached`` with the adapter's ``prefix_hit`` taken away:
    every multi-row call goes through the gather, as at the parent."""
    def fwd(*args, kv_io, **kw):
        return forward_cached(*args, kv_io=PagedKVIO(
            kv_io.page_tables, kv_io.page_size, seq_limit=kv_io.seq_limit),
            **kw)

    return fwd


def operands(cfg, starts=(0, 0, 0), tails=(12, 7, 3), admitted=(1, 1, 1)):
    """One full-shape call's arguments after ``params``: three rows of
    twelve tokens on their own pages (row b owns pages ``1 + 7b ..``)."""
    tokens = jax.random.randint(
        jax.random.PRNGKey(3), (SLOTS, LENGTH), 0, cfg.vocab_size)
    tables = (np.arange(SLOTS * MAX_PAGES, dtype=np.int32) + 1).reshape(
        SLOTS, MAX_PAGES)
    pool = init_paged_kv_cache(cfg, SLOTS * MAX_PAGES + 1, PAGE,
                               dtype=jnp.float32, slots=SLOTS)
    return (tokens, jnp.asarray(tails, jnp.int32),
            jnp.asarray(starts, jnp.int32), jnp.asarray(admitted, bool),
            jnp.asarray(tables), pool, jnp.zeros((SLOTS, 2), jnp.uint32))


def step(cfg, forward_fn=None):
    return make_paged_prefill_step(
        cfg, GREEDY, page_size=PAGE, seq_limit=PAGE * MAX_PAGES,
        forward_fn=forward_fn, donate_cache=False)


@pytest.mark.parametrize("name", FAMILIES)
def test_a_call_of_cold_prompts_agrees_with_the_gather(name):
    """Rows of 12, 7 and 3 tokens, all from position 0: the step as the
    engine builds it (each prompt to itself) and the same step made to
    read the pool sample the same first tokens from the same logits,
    and leave the same pool behind."""
    cfg, params = family(name)
    args = operands(cfg)
    first, logits, finite, pool = step(cfg)(params, *args)
    want_first, want_logits, _, want_pool = step(
        cfg, reads_the_pool(resolve_forward_cached(cfg)))(params, *args)
    assert bool(finite.all())
    np.testing.assert_array_equal(np.asarray(first), np.asarray(want_first))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want_logits),
                               **PAGED_DENSE_LOGIT_TOL)
    for got, want in zip(pool, want_pool):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   **PAGED_DENSE_LOGIT_TOL)


@pytest.mark.parametrize("name", FAMILIES)
def test_the_program_holds_the_gather_only_where_a_prefix_can_lie(name):
    """A family that shares prefixes chooses on the device, once a
    layer body; one that refuses them has no choice to make, and the
    score array over the whole cache is in no program of it."""
    cfg, params = family(name)
    args = operands(cfg)
    if rows_name_slots(cfg):        # the program takes its rows' slot ids
        args += (np.arange(SLOTS, dtype=np.int32),)
    text = step(cfg).lower(params, *args).as_text()
    parent = step(cfg, reads_the_pool(resolve_forward_cached(cfg))).lower(
        params, *args).as_text()
    scores = f"x{LENGTH}x{PAGE * MAX_PAGES}xf32"     # [.., rows, max_seq]
    choices = text.count("stablehlo.case") - parent.count("stablehlo.case")
    assert scores in parent
    if no_prefix_reason(cfg) is None:
        assert choices > 0 and scores in text
    else:
        assert choices == 0 and scores not in text


@pytest.mark.parametrize("name", ["qwen3", "olmoe"])
def test_a_call_with_a_hit_reads_the_pool(name):
    """Row 1 continues two pages that lie in the pool (another call
    wrote them), rows 0 and 2 are cold: one hit sends all three rows
    through the gather, and the result is the parent's (to the
    tolerance of two compiled programs: a branch is fused on its own)."""
    cfg, params = family(name)
    warm = operands(cfg)
    pool = step(cfg)(params, *warm)[3]          # every row's pages hold K/V
    args = operands(cfg, starts=(0, 2 * PAGE, 0), tails=(12, 4, 3))
    args = args[:5] + (pool,) + args[6:]
    got = step(cfg)(params, *args)
    want = step(cfg, reads_the_pool(resolve_forward_cached(cfg)))(
        params, *args)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   **PAGED_DENSE_LOGIT_TOL)
    # a row outside the mask does not count as a hit
    masked = operands(cfg, starts=(0, 2 * PAGE, 0), admitted=(1, 0, 1))
    cold = operands(cfg, admitted=(1, 0, 1))
    for a, b in zip(step(cfg)(params, *masked)[:2],
                    step(cfg)(params, *cold)[:2]):
        np.testing.assert_array_equal(np.asarray(a)[[0, 2]],
                                      np.asarray(b)[[0, 2]])


SYSTEM = [7, 7, 7, 7, 3, 3, 3, 3]   # two full pages at page_size 4


@pytest.mark.parametrize("name", ["qwen3", "olmoe", "gpt_moe"])
def test_the_engine_counts_the_calls_that_held_no_hit(name):
    """Shared-prefix traffic on two slots: a cold prompt registers two
    pages; then a hit and a cold prompt share ONE full-shape call, then
    a hit alone takes a call, then a cold one. Every request's tokens
    are the plain forward's; ``prefill_calls_self_attended`` is the
    calls less those that held a hit, and the spans say which."""
    from scaletorch_tpu.telemetry.spans import SpanTracer

    cfg, params = paged_family(name)
    tracer = SpanTracer(path=None, role="serve", tail_size=4096)
    eng = InferenceEngine(params, cfg, max_slots=2, max_seq=32,
                          prefill_len=12, sampling=GREEDY, page_size=PAGE,
                          tracer=tracer)
    rounds = [[(SYSTEM + [1], 3)],
              [(SYSTEM + [2], 3), ([9, 8, 5], 3)],
              [(SYSTEM + [5, 6], 2)],
              [([4, 5, 6, 7, 8], 2)]]
    for schedule in rounds:
        ids = [eng.submit(p, max_new_tokens=n, trace_id=f"{p[-1]:032x}")
               for p, n in schedule]
        done = eng.run()
        for (prompt, n), i in zip(schedule, ids):
            assert done[i].outcome == "ok"
            assert_greedy(params, cfg, prompt, done[i].tokens)
    m = eng.metrics.snapshot()
    assert m["prefill_calls"] == 4 and eng.metrics.prefix_hits == 2
    assert m["prefill_calls_self_attended"] == 2
    spans = [e["args"] for e in tracer.tail()
             if e["name"] == "req.prefill" and e["ph"] == "b"]
    assert [(s["prefix_hit"], s["self_attended"]) for s in spans] == [
        (False, True), (True, False), (False, False), (True, False),
        (False, True)]
    assert eng.prefill_compile_count <= len(eng.prefill_shapes)


@pytest.mark.parametrize("name", ["olmo_hybrid", "qwen3_next"])
def test_a_family_without_prefixes_self_attends_every_call(name):
    cfg, params = family(name)
    eng = InferenceEngine(params, cfg, max_slots=2, max_seq=48,
                          prefill_len=16, sampling=GREEDY, page_size=8,
                          strict_submit=False)
    for prompt in ([3, 1, 4, 1, 5, 9, 2, 6, 5], [3, 1, 4, 1, 5, 9, 2, 6, 7]):
        rid = eng.submit(prompt, max_new_tokens=3)
        assert_greedy(params, cfg, prompt, eng.run()[rid].tokens)
    m = eng.metrics.snapshot()
    assert m["prefill_calls"] == m["prefill_calls_self_attended"] == 2
