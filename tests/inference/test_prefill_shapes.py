"""A prefill call takes the shape of its admission: where the cache is
addressed by page the engine picks the call's ``(rows, length)`` from
``engine.prefill_shapes``, a short list fixed at construction
(``decode.prefill_shapes``), and a row of the call is an admitted slot
only through its row of the page tables and of the sampling keys. Held
here, on the CPU in float32 at the tiny size of each family:

  * the list the rule gives, at the serving cells' shapes and at toy
    ones, and which shape an admission takes;
  * that a call at ANY ``(rows, length)`` (rows 1 / 4 / all, lengths a
    quarter / half / whole: more than the rule lists, so the mechanism
    is held and not the list) gives what the full ``(max_slots,
    prefill_len)`` call gives (first tokens, the sampled-from logits,
    K/V at the prompts' positions) and touches no page but the admitted
    slots' own and TRASH, padding rows included;
  * that a sampled request's tokens do not depend on the shape that
    admitted it (a slot's key stays its own);
  * that a cache by slot whose row IS its slot (jamba's state, window
    rings) still makes exactly the one fixed-shape call;
  * that where a row NAMES its slot (the delta-rule families) the one
    program is one row: a call writes its slot's state and tail at
    ``slot_ids`` and no other, as the by-row call of every slot did;
    two admissions in a tick are two calls; and the step handed the
    full shape without ids (the benchmark's check) runs that program
    once a written row;
  * that ``scripts/serve.py``'s ``build_engine`` leaves every listed
    program compiled, nothing written, no counter moved, and that no
    admission compiles another;
  * that the two counters add up.
"""

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scaletorch_tpu.inference import InferenceEngine, SamplingParams
from scaletorch_tpu.inference.decode import (
    SlotRows,
    make_paged_prefill_step,
    prefill_shapes,
    rows_name_slots,
)
from scaletorch_tpu.inference.engine import EngineMetrics
from scaletorch_tpu.inference.kv_cache import TRASH_PAGE
from scaletorch_tpu.inference.routing_counters import ROUTING_COUNTERS
from tests.inference.oracle import assert_greedy
from tests.inference.test_decode_parity import ATOL
from tests.inference.test_paged_engine import family

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
GREEDY = SamplingParams(temperature=0.0)
# the toy engine of the shape tests: 6 slots x 32 tokens, pages of 4
SLOTS, LENGTH, PAGE, MAX_SEQ = 6, 32, 4, 48


def wide(slots, length):
    """Every ``(rows, length)`` of ISSUE 44's widest list, fewest
    positions first: more shapes than the rule lists."""
    shapes = {(r, -(-length // d)) for r in (1, min(4, slots), slots)
              for d in (4, 2, 1)}
    return tuple(sorted(shapes, key=lambda s: (s[0] * s[1], s[0])))


WIDE = wide(SLOTS, LENGTH)


@functools.lru_cache(maxsize=None)
def model(name):
    if name == "olmo_hybrid":
        from tests.models.test_olmo_hybrid import seeded_params, tiny_config

        cfg = tiny_config()
        return cfg, seeded_params(cfg)
    if name == "afmoe":
        from scaletorch_tpu.models import afmoe
        from tests.models.test_afmoe import tiny_config

        cfg = tiny_config()
        return cfg, jax.jit(afmoe.init_params, static_argnums=1)(
            jax.random.PRNGKey(3), cfg)
    if name in ("qwen3_next", "jamba"):
        import importlib

        tiny = importlib.import_module(f"tests.models.test_{name}")
        cfg = tiny.tiny_config()
        return cfg, tiny.seeded_params(cfg)
    return family(name)


def admit(engine):
    """An admission on its own, outside a tick: the prefill call
    dispatched, then read back and its first tokens emitted (a tick
    dispatches the next decode step between the two)."""
    engine._read_admission(engine._admit())


def prompt(n, seed):
    rng = np.random.default_rng(seed)
    return [int(t) for t in rng.integers(1, 60, size=n)]


# ---- the list ----------------------------------------------------------------

@pytest.mark.parametrize("name,shapes", [
    # addressed by page: one row of half the length before the full shape
    ("qwen3-1.7b-serve", ((1, 512), (16, 1024))),
    ("olmoe-1b-7b-serve", ((1, 512), (16, 1024))),
    ("openpangu-ultra-moe-718b-serve", ((1, 1536), (8, 3072))),
    # state by slot, a row names its slot: ONE row, one program
    ("olmo-hybrid-7b-serve", ((1, 512),)),
    ("qwen3-next-80b-a3b-serve", ((1, 512),)),
    # state or rings by slot, a row IS its slot: the one fixed call
    ("trinity-mini-serve", ((8, 3072),)),
    ("jamba2-3b-serve", ((8, 3072),)),
])
def test_the_list_at_the_serving_cells_shapes(name, shapes):
    """What an engine built as the benchmark builds it would list, from
    the three tests the engine makes (``rows_name_slots``: the family's
    column; ``carries_state``, ``window_of``): never from a model's
    name."""
    from benchmarks.lib import spec as spec_lib
    from benchmarks.lib.program import serving_model
    from scaletorch_tpu.inference.kv_cache import carries_state, window_of

    config = spec_lib.Spec().config(name)
    serve = config["serve"]
    cfg, _ = serving_model(config, serve["dtype"])
    top = (serve["max_slots"], serve["prefill_len"])
    by_slot = carries_state(cfg) or window_of(cfg) is not None
    assert (((1, top[1]),) if rows_name_slots(cfg)
            else (top,) if by_slot else prefill_shapes(*top)) == shapes
    assert len(shapes) - 1 <= 6


@pytest.mark.parametrize("slots,length,shapes", [
    (2, 16, ((1, 8), (2, 16))),
    (1, 16, ((1, 8), (1, 16))),
    (4, 7, ((1, 4), (4, 7))),
    (4, 1, ((1, 1), (4, 1))),
    (1, 1, ((1, 1),)),
    (16, 1024, ((1, 512), (16, 1024))),
])
def test_the_list_is_a_rule_of_slots_and_length(slots, length, shapes):
    got = prefill_shapes(slots, length)
    assert got == shapes
    assert got[-1] == (slots, length)
    assert list(got) == sorted(got, key=lambda s: s[0] * s[1])
    assert len(got) - 1 <= 6


# ---- which shape an admission takes ------------------------------------------

class Recorder:
    """In the place of ``engine._prefill``, as the benchmark's harness
    wraps it: keeps each call's operands and, with a step behind it,
    its first tokens and logits."""

    def __init__(self, step=None):
        self.step, self.calls = step, []

    def __call__(self, params, tokens, tail_lens, starts, write_mask,
                 tables, cache, keys, *slot_ids):
        rows = tokens.shape[0]
        assert (tail_lens.shape == starts.shape == write_mask.shape
                == (rows,))
        assert tables.shape[0] == keys.shape[0] == rows
        call = dict(shape=tokens.shape, tail_lens=np.asarray(tail_lens),
                    starts=np.asarray(starts),
                    write_mask=np.asarray(write_mask),
                    tables=np.asarray(tables), keys=np.asarray(keys),
                    slot_ids=[np.asarray(ids).tolist() for ids in slot_ids])
        self.calls.append(call)
        if self.step is None:       # no model: shapes only
            return (jnp.zeros(rows, jnp.int32), None,
                    jnp.ones(rows, bool), cache)
        first, logits, finite, cache = self.step(
            params, tokens, tail_lens, starts, write_mask, tables, cache,
            keys, *slot_ids)
        call["logits"], call["first"] = np.asarray(logits), np.asarray(first)
        return first, logits, finite, cache

    def __getattr__(self, item):
        return getattr(self.step, item)


def shape_engine(name, shapes=None, **kw):
    cfg, params = model(name)
    kw = dict(dict(max_slots=SLOTS, max_seq=MAX_SEQ, prefill_len=LENGTH,
                   page_size=PAGE, prefix_cache=False), **kw)
    engine = InferenceEngine(params, cfg, sampling=GREEDY, **kw)
    if shapes is not None:
        engine.prefill_shapes = shapes
    engine._prefill = Recorder()
    return engine


@pytest.mark.parametrize("admitted,tail,shape", [
    (1, 1, (1, 16)), (1, 16, (1, 16)), (1, 17, (6, 32)), (1, 32, (6, 32)),
    (2, 3, (6, 32)), (2, 32, (6, 32)), (6, 16, (6, 32)), (6, 32, (6, 32)),
])
def test_an_admission_takes_the_smallest_shape_the_rule_lists(
        admitted, tail, shape):
    engine = shape_engine("llama")
    assert engine.prefill_shapes == ((1, 16), (6, 32))
    check_the_call(engine, admitted, tail, shape)


@pytest.mark.parametrize("admitted,tail,shape", [
    (1, 8, (1, 8)), (1, 9, (1, 16)), (1, 17, (1, 32)),
    (2, 8, (4, 8)), (4, 8, (4, 8)), (3, 9, (4, 16)), (4, 17, (4, 32)),
    (5, 8, (6, 8)), (6, 16, (6, 16)), (5, 17, (6, 32)), (6, 32, (6, 32)),
])
def test_an_admission_takes_the_smallest_shape_of_any_list(
        admitted, tail, shape):
    """k requests with longest tail t: the listed shape of fewest
    positions with rows >= k and length >= t."""
    check_the_call(shape_engine("llama", shapes=WIDE), admitted, tail, shape)


def check_the_call(engine, admitted, tail, shape):
    rows, length = shape
    for n in range(admitted):
        engine.submit(prompt(tail if n == 0 else 1, seed=n),
                      max_new_tokens=2, seed=100 + n)
    admit(engine)
    (call,) = engine._prefill.calls
    assert call["shape"] == (rows, length)
    holds = [s for s in engine.prefill_shapes
             if s[0] >= admitted and s[1] >= tail]
    assert rows * length == min(r * n for r, n in holds)
    # rows in admission order; past them padding: masked, one token, a
    # TRASH table, a zero key
    assert call["write_mask"].tolist() == \
        [True] * admitted + [False] * (rows - admitted)
    assert call["tail_lens"].tolist() == \
        [tail] + [1] * (admitted - 1) + [1] * (rows - admitted)
    assert (call["tables"][admitted:] == TRASH_PAGE).all()
    assert (call["keys"][admitted:] == 0).all()
    for row in range(admitted):
        assert (call["tables"][row] == engine._tables[row]).all()
        assert (call["keys"][row] == np.asarray(
            jax.random.PRNGKey(100 + row), np.uint32)).all()
    snap = engine.metrics.snapshot()
    assert snap["prefill_calls"] == 1
    assert snap["prefill_positions_run"] == rows * length
    assert snap["prefill_positions_admitted"] == tail + (admitted - 1)


BY_SLOT = dict(max_slots=3, max_seq=160, prefill_len=128, page_size=8)


@pytest.mark.parametrize("name,kw", [
    ("jamba", BY_SLOT),
    ("afmoe", dict(BY_SLOT, max_slots=2)),
])
def test_a_cache_by_slot_still_makes_exactly_the_fixed_shape_call(name, kw):
    """Jamba's state and convolution tail and the window rings are
    indexed by the row: the list is the one full shape, a row is its
    slot whether admitted or not, and a live slot's row carries its own
    table, masked."""
    engine = shape_engine(name, **kw)
    slots, length = kw["max_slots"], kw["prefill_len"]
    assert engine._by_slot and not engine._rows_name_slots
    assert engine.prefill_shapes == ((slots, length),)
    engine.submit(prompt(5, 0), max_new_tokens=2)
    admit(engine)                 # slot 0 is live
    engine.submit(prompt(9, 1), max_new_tokens=2)
    admit(engine)
    first, second = engine._prefill.calls
    assert first["shape"] == second["shape"] == (slots, length)
    assert second["write_mask"].tolist() == \
        [False, True] + [False] * (slots - 2)
    assert second["tail_lens"].tolist() == [1, 9] + [1] * (slots - 2)
    assert (second["tables"] == engine._tables).all()
    assert (second["keys"] == engine._base_keys).all()
    assert engine.metrics.prefill_positions_run == 2 * slots * length
    assert engine.metrics.prefill_positions_admitted == 5 + 9


def slot_rows(engine):
    """The engine's prefill step if it is the one that takes slot ids
    (under an MoE model's counting wrapper)."""
    step = engine._prefill
    if not isinstance(step, SlotRows):
        step = getattr(step, "_step", None)
    return step if isinstance(step, SlotRows) else None


NAMED = ["olmo_hybrid", "qwen3_next"]


# two compiled programs of one recurrence (one row, every row): the
# delta rule carries float32's reduction-order noise (6e-7 a layer:
# tests/inference/test_paged_cache.py) to 3e-5 on |logit| ~ 1.7 and on
# the state; a wrong slot, row or tail moves either by >= 1e-2
BY_ROW_TOL = dict(rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("name", NAMED)
def test_a_row_that_names_its_slot_makes_one_call_a_prompt(name):
    """The delta-rule families: the list is ONE row of the whole
    length; an admission of two prompts in one tick is two calls back
    to back, each row carrying its slot's table, key and id; a live
    slot is in no call."""
    engine = shape_engine(name, **BY_SLOT)
    length = BY_SLOT["prefill_len"]
    assert engine._by_slot and engine._rows_name_slots
    assert engine.prefill_shapes == ((1, length),)
    engine.submit(prompt(5, 0), max_new_tokens=2)
    admit(engine)                 # slot 0 is live
    engine.submit(prompt(9, 1), max_new_tokens=2, seed=7)
    engine.submit(prompt(120, 2), max_new_tokens=2, seed=8)
    admit(engine)                 # one tick admits two
    assert [c["shape"] for c in engine._prefill.calls] == [(1, length)] * 3
    for call, slot, tail in zip(engine._prefill.calls, (0, 1, 2),
                                (5, 9, 120)):
        assert call["slot_ids"] == [[slot]]
        assert call["write_mask"].tolist() == [True]
        assert call["tail_lens"].tolist() == [tail]
        assert call["starts"].tolist() == [0]
        assert (call["tables"] == engine._tables[[slot]]).all()
        assert (call["keys"] == engine._base_keys[[slot]]).all()
    snap = engine.metrics.snapshot()
    assert snap["prefill_calls"] == snap["prefill_calls_self_attended"] == 3
    assert snap["prefill_positions_run"] == 3 * length
    assert snap["prefill_positions_admitted"] == 5 + 9 + 120
    assert snap["recurrent_state_resets"] == 3
    assert engine._state_owner == [0, 1, 2]


def by_row_oracle(cfg, kw):
    """The parent's program: every slot a row of ONE call, state and
    tail read and written at the row (``forward_cached`` without slot
    ids, through the step a family whose rows are its slots still
    gets)."""
    from scaletorch_tpu.inference import decode

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(decode, "rows_name_slots", lambda cfg: False)
        step = make_paged_prefill_step(
            cfg, GREEDY, page_size=kw["page_size"], seq_limit=kw["max_seq"],
            routing_counts=decode.counts_routing(cfg))
    assert not isinstance(step, SlotRows)
    return step


@pytest.mark.parametrize("name", NAMED)
def test_a_one_row_call_writes_its_slot_and_no_other(name):
    """Three slots hold noise. The one-row program with ``slot_ids =
    [1]`` leaves slots 0 and 2 bit for bit (state, tail, pages) and
    gives slot 1 what the by-row call over every slot gives it (the
    oracle: the parent's semantics): state, tail, K/V, first token and
    logits; a row that is masked, or whose id is past the last slot,
    writes nothing at all."""
    cfg, params = model(name)
    kw = dict(BY_SLOT, max_seq=48, prefill_len=40)
    engine = InferenceEngine(params, cfg, sampling=GREEDY,
                             prefix_cache=False, **kw)
    assert engine.prefill_shapes == ((1, 40),)
    rng = np.random.default_rng(0)
    noise = type(engine.cache)(*(
        jnp.asarray(rng.normal(size=buf.shape), buf.dtype)
        for buf in engine.cache))
    slot, tail = 1, prompt(23, 4)
    pps = engine._pages_per_slot
    tables = (np.arange(3 * pps, dtype=np.int32) + 1).reshape(3, pps)
    keys = np.asarray(jax.random.split(jax.random.PRNGKey(1), 3), np.uint32)
    tokens = np.zeros((3, 40), np.int32)
    tokens[slot, :23] = tail
    tail_lens = np.array([1, 23, 1], np.int32)
    starts = np.zeros(3, np.int32)
    mask = np.arange(3) == slot
    counts = () if not hasattr(engine._prefill, "uncounted") else (
        engine.metrics.routing.accumulator,)
    want = by_row_oracle(cfg, kw)(
        engine.params, tokens, tail_lens, starts, mask, tables, noise,
        keys, *counts)
    step = slot_rows(engine)
    at = slice(slot, slot + 1)

    def one_row(written, ids):
        return step(engine.params, tokens[at], tail_lens[at], starts[at],
                    np.array([written]), tables[at], noise, keys[at],
                    np.array(ids, np.int32), *counts)

    got = one_row(True, [slot])
    assert int(got[0][0]) == int(want[0][slot])
    np.testing.assert_allclose(got[1][0], want[1][slot], **BY_ROW_TOL)
    own = tables[slot]
    for field, new, ref, old in zip(
            type(noise)._fields, got[3], want[3], noise):
        new, ref, old = (np.asarray(a, np.float32) for a in (new, ref, old))
        if field in ("state", "conv"):
            keep = [0, 2]
            np.testing.assert_allclose(new[:, slot], ref[:, slot],
                                       **BY_ROW_TOL, err_msg=field)
            assert np.abs(new[:, slot] - old[:, slot]).max() > 0.1
        else:
            keep = [p for p in range(engine.num_pages)
                    if p not in own and p != TRASH_PAGE]
            np.testing.assert_allclose(new[:, own[:2]], ref[:, own[:2]],
                                       **BY_ROW_TOL, err_msg=field)
        assert (new[:, keep] == old[:, keep]).all(), field
    for written, ids in ((False, [slot]), (True, [3])):
        cache = one_row(written, ids)[3]
        for field, new, old in zip(type(noise)._fields, cache, noise):
            new, old = np.asarray(new), np.asarray(old)
            if field in ("state", "conv"):
                keep = slice(None)
            else:       # K/V goes to the row's pages if written, or TRASH
                keep = [p for p in range(engine.num_pages) if p != TRASH_PAGE
                        and not (written and p in own)]
            assert (new[:, keep] == old[:, keep]).all(), (field, ids)


@pytest.mark.parametrize("name", NAMED)
def test_the_full_shape_without_ids_runs_the_one_row_program(name):
    """The benchmark's check hands ``engine._prefill`` eight operands at
    ``[max_slots, prefill_len]``, row b = slot b: it gets ``[max_slots,
    V]`` logits from the ONE program the admissions run (nothing else
    is compiled), a written row's are the oracle's by-row call's, its
    state lands at its row's slot, and an MoE model's counters count
    the written rows."""
    cfg, params = model(name)
    kw = dict(BY_SLOT, max_seq=48, prefill_len=40)
    engine = InferenceEngine(params, cfg, sampling=GREEDY,
                             prefix_cache=False, **kw)
    rid = engine.submit(prompt(7, 0), max_new_tokens=3)
    engine.run()
    assert engine.prefill_compile_count == 1
    pps = engine._pages_per_slot
    tables = (np.arange(3 * pps, dtype=np.int32) + 1).reshape(3, pps)
    tokens = np.zeros((3, 40), np.int32)
    lens = np.array([11, 1, 40], np.int32)
    tokens[0, :11], tokens[2] = prompt(11, 5), prompt(40, 6)
    mask = np.array([True, False, True])
    operands = (jnp.asarray(tokens), jnp.asarray(lens),
                jnp.zeros(3, jnp.int32), jnp.asarray(mask),
                jnp.asarray(tables))
    keys = jnp.zeros((3, 2), jnp.uint32)
    counts = () if not hasattr(engine._prefill, "uncounted") else (
        engine.metrics.routing.accumulator,)
    want = by_row_oracle(cfg, kw)(
        engine.params, *operands, engine.cache, keys, *counts)
    before = engine.metrics.snapshot()
    with engine.on_device():
        first, logits, finite, engine.cache = engine._prefill(
            engine.params, *operands, engine.cache, keys)
    assert engine.prefill_compile_count == 1
    assert logits.shape == (3, cfg.vocab_size) and first.shape == (3,)
    assert np.asarray(finite).tolist() == mask.tolist()
    for row in (0, 2):
        assert int(first[row]) == int(want[0][row])
        np.testing.assert_allclose(logits[row], want[1][row], **BY_ROW_TOL)
        for field in ("state", "conv"):
            np.testing.assert_allclose(
                np.asarray(getattr(engine.cache, field)[:, row], np.float32),
                np.asarray(getattr(want[3], field)[:, row], np.float32),
                **BY_ROW_TOL, err_msg=field)
    if counts:      # the accumulator went from written row to written row
        key = "moe_prefill_assignments"
        want_moved = np.asarray(want[4] - counts[0])[
            ROUTING_COUNTERS.index(key)]
        assert engine.metrics.snapshot()[key] - before[key] == want_moved > 0
    assert engine._results[rid].outcome == "ok"


@pytest.mark.parametrize("name", NAMED)
def test_a_successor_starts_from_zero_whatever_the_slot_held(name):
    """A retired slot's state and tail are noise when the next prompt
    takes the slot: its tokens are a fresh engine's, two admitted in
    one tick are two calls of one program, and no step ran on another
    request's state."""
    cfg, params = model(name)
    kw = dict(BY_SLOT, max_slots=2, max_seq=48, prefill_len=40)
    asked = [prompt(13, 1), prompt(31, 2)]
    tokens = []
    for dirty in (False, True):
        engine = InferenceEngine(params, cfg, sampling=GREEDY,
                                 prefix_cache=False, **kw)
        if dirty:
            engine.cache = engine.cache._replace(
                state=jnp.full_like(engine.cache.state, 3.0),
                conv=jnp.full_like(engine.cache.conv, -2.0))
        ids = [engine.submit(p, max_new_tokens=6) for p in asked]
        done = engine.run()
        tokens.append([done[i].tokens for i in ids])
        snap = engine.metrics.snapshot()
        assert snap["prefill_calls"] == 2
        assert snap["prefill_calls_behind_flight"] == 0   # one cold tick
        assert snap["recurrent_state_owner_mismatches"] == 0
        assert engine.prefill_compile_count == 1
        assert engine.decode_compile_count == 1
    assert tokens[0] == tokens[1]
    for p, got in zip(asked, tokens[0]):
        assert_greedy(params, cfg, p, got)


# ---- a call at any listed shape against the full one -------------------------

@pytest.fixture(scope="module", params=["qwen3", "olmoe"])
def pair(request):
    """Two engines on one model: ``full`` calls the prefill step at
    ``(max_slots, prefill_len)`` whatever it admits, as every engine
    did; ``ladder`` takes the wide list. Both record their calls; a
    test leaves them drained."""
    cfg, params = model(request.param)
    engines = []
    for shapes in ((SLOTS, LENGTH),), WIDE:
        engine = InferenceEngine(
            params, cfg, sampling=GREEDY, max_slots=SLOTS, max_seq=MAX_SEQ,
            prefill_len=LENGTH, page_size=PAGE, prefix_cache=False)
        engine.prefill_shapes = shapes
        engine._prefill = Recorder(engine._prefill)
        engines.append(engine)
    return (cfg, params, *engines)


def kv_at(engine, slot, n):
    """K and V of ``slot`` at its first ``n`` positions, through its
    page table: [2, L, Hkv, n, D]."""
    pages = engine._tables[slot, np.arange(n) // PAGE]
    return np.stack([np.asarray(pool)[:, pages, :, np.arange(n) % PAGE]
                     for pool in (engine.cache.k, engine.cache.v)])


def pools(engine):
    return np.stack([np.asarray(engine.cache.k), np.asarray(engine.cache.v)])


def admit_same(engines, prompts, new=3):
    """Submit the same prompts to both, admit them in one call each;
    returns the slots they went to (the same in both)."""
    went = []
    for engine in engines:
        before = {i for i, s in enumerate(engine._slots) if s.active}
        calls = len(engine._prefill.calls)
        for p in prompts:
            engine.submit(p, max_new_tokens=new)
        admit(engine)
        assert len(engine._prefill.calls) == calls + 1
        went.append(sorted(
            {i for i, s in enumerate(engine._slots) if s.active} - before))
    assert went[0] == went[1] and len(went[0]) == len(prompts)
    return went[0]


@pytest.mark.parametrize("shape,padding", [
    (shape, padding) for shape in WIDE for padding in (0, 1)
    if padding < shape[0]])       # a one-row call has no padding row
def test_a_call_at_any_shape_gives_what_the_full_call_gives(
        pair, shape, padding):
    """One slot live; then ``rows - padding`` prompts, the longest as
    long as the shape, admitted by one call: at ``shape`` in the ladder
    (``padding`` of its rows masked over TRASH tables), at ``(6, 32)``
    in the other. First tokens, the sampled-from logits and the K/V
    written are the full call's; every page that is not the admitted
    slots' own or TRASH is bit for bit what it was; run out, every
    token is the plain forward's."""
    cfg, params, full, ladder = pair
    rows, length = shape
    admitted = rows - padding
    batches = []
    if admitted < SLOTS:
        batches.append([prompt(5, 99)])        # a live slot to leave alone
    lens = [length] + [1 + (3 * n) % length for n in range(1, admitted)]
    batches.append([prompt(n, i) for i, n in enumerate(lens)])
    for batch in batches[:-1]:
        admit_same((full, ladder), batch, new=8)
    before = pools(ladder)
    slots = admit_same((full, ladder), batches[-1])
    a, b = full._prefill.calls[-1], ladder._prefill.calls[-1]
    assert a["shape"] == (SLOTS, LENGTH) and b["shape"] == shape
    assert b["write_mask"].sum() == admitted
    # a row is an admitted slot, in admission order, at either shape
    for row, (slot, p) in enumerate(zip(slots, batches[-1])):
        assert a["first"][row] == b["first"][row]
        np.testing.assert_allclose(b["logits"][row], a["logits"][row],
                                   atol=ATOL)
        np.testing.assert_allclose(
            kv_at(ladder, slot, len(p)), kv_at(full, slot, len(p)),
            atol=ATOL)
    own = {TRASH_PAGE} | {int(page) for slot in slots
                          for page in ladder._tables[slot]}
    others = [page for page in range(ladder.num_pages) if page not in own]
    assert (pools(ladder)[:, :, others] == before[:, :, others]).all(), (
        "a page of a slot that was not admitted changed")
    done_full, done_ladder = full.run(), ladder.run()
    assert not any(s.active for s in ladder._slots)
    ids = sorted(done_ladder)[-len(batches[-1]):]
    for rid, p in zip(ids, batches[-1]):
        assert done_ladder[rid].tokens == done_full[rid].tokens
        assert_greedy(params, cfg, p, done_ladder[rid].tokens)
    assert ladder.decode_compile_count == 1
    assert ladder.prefill_compile_count <= len(WIDE)
    assert full.prefill_compile_count == 1


def test_a_prefix_hit_runs_its_tail_in_a_short_row():
    """A prompt that shares its leading pages with a cached one
    prefills its tail alone: the row starts at the shared length, and
    the one-row call reads the shared pages through its table."""
    cfg, params = model("qwen3")
    kw = dict(sampling=GREEDY, max_slots=4, max_seq=48, prefill_len=32,
              page_size=4)
    ladder = InferenceEngine(params, cfg, **kw)
    ladder._prefill = Recorder(ladder._prefill)
    assert ladder.prefill_shapes == ((1, 16), (4, 32))
    head = prompt(20, 1)                   # over half: the full shape
    shared = head[:8] + prompt(9, 2)       # two pages shared, a tail of 9
    ids = [ladder.submit(head, max_new_tokens=4)]
    admit(ladder)
    ids.append(ladder.submit(shared, max_new_tokens=4))
    admit(ladder)
    first, second = ladder._prefill.calls
    assert first["shape"] == (4, 32) and second["shape"] == (1, 16)
    assert second["starts"].tolist() == [8]
    assert second["tail_lens"].tolist() == [9]
    assert ladder.metrics.prefix_hits == 1
    assert ladder.metrics.prefill_positions_admitted == 20 + 9
    done = ladder.run()
    for rid, p in zip(ids, (head, shared)):
        assert_greedy(params, cfg, p, done[rid].tokens)


def test_a_sampled_request_does_not_depend_on_the_shape_that_admitted_it():
    """Temperature 1: a slot's key is folded from the request's seed and
    the position, whatever row of whatever shape carried it."""
    cfg, params = model("llama")
    hot = SamplingParams(temperature=1.0)
    tokens = []
    for shapes in ((SLOTS, LENGTH),), WIDE:
        engine = InferenceEngine(
            params, cfg, sampling=hot, max_slots=SLOTS, max_seq=MAX_SEQ,
            prefill_len=LENGTH, page_size=PAGE, prefix_cache=False)
        engine.prefill_shapes = shapes
        ids = []
        for wave in ([7], [3, 12, 5], [30]):
            ids += [engine.submit(prompt(n, n), max_new_tokens=6, seed=40 + n)
                    for n in wave]
            admit(engine)
        done = engine.run()
        tokens.append([done[i].tokens for i in ids])
    assert tokens[0] == tokens[1]
    assert len({tuple(t) for t in tokens[0]}) > 1


# ---- every program before the first request ----------------------------------

def serve_module():
    spec = importlib.util.spec_from_file_location(
        "_shapes_serve", os.path.join(REPO, "scripts", "serve.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class BackendCompiles:
    """jax's own count of programs handed to the backend compiler, as
    the benchmark's harness counts them (``benchmarks/lib/device.py``)."""

    def __init__(self):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


@pytest.fixture(scope="module")
def backend_compiles():
    return BackendCompiles()


def _leaves(cache):
    return {name: np.asarray(leaf)
            for name, leaf in zip(type(cache)._fields, cache)}


@pytest.mark.parametrize("name,flags,count", [
    ("llama", ["--max_slots", "4", "--max_seq", "24", "--prefill_len", "16",
               "--page_size", "4"], 2),
    ("olmoe", ["--max_slots", "2", "--max_seq", "24", "--prefill_len", "16",
               "--page_size", "4"], 2),
    ("olmo_hybrid", ["--max_slots", "2", "--max_seq", "160",
                     "--prefill_len", "128", "--page_size", "8"], 1),
    ("jamba", ["--max_slots", "2", "--max_seq", "160",
               "--prefill_len", "128", "--page_size", "8"], 1),
])
def test_build_engine_warms_every_shape_and_no_admission_compiles_another(
        backend_compiles, name, flags, count):
    """``scripts/serve.py build_engine`` on one device, as the harness
    and the gateway call it: every listed program of a page-addressed
    engine exists before the first request, the cache is still zero but
    for the TRASH page, no counter has moved, and 50 mixed admissions
    hand the backend compiler nothing and add no entry to the prefill
    step's cache. A cache by slot has the one shape (the full one, or
    one row where a row names its slot) and compiles it at its first
    call, as before."""
    serve = serve_module()
    cfg, params = model(name)
    args = serve.parse_args(flags)
    fresh = EngineMetrics(num_slots=args.max_slots).snapshot()
    engine = serve.build_engine(args, cfg, params, device=jax.devices()[0])
    assert len(engine.prefill_shapes) == count
    assert engine.prefill_compile_count == (count if count > 1 else 0)
    for field, leaf in _leaves(engine.cache).items():
        keep = slice(1, None) if leaf.ndim == 5 else slice(None)
        assert not leaf[:, keep].any(), field
    snap = engine.metrics.snapshot()
    for key, value in fresh.items():
        # (the ``host_gc_*`` counters are the process's, not the
        # engine's: building an engine allocates, and the collector runs)
        if key not in ("page_pool_free", "paged_pool_in_place") \
                and not key.startswith("host_gc_"):
            assert snap[key] == value, key
    for key in ("moe_routed_assignments", "moe_dropped_assignments",
                "moe_prefill_assignments"):
        assert snap.get(key, 0) == 0, key
    with engine.on_device():           # as the gateway's worker ticks it
        engine.submit(prompt(3, 0), max_new_tokens=2)
        engine.run()                   # the decode program, and a by-slot
        compiled = backend_compiles.count    # cache's one prefill program
        rng = np.random.default_rng(0)
        admissions = 0
        while admissions < 50:
            for _ in range(int(rng.integers(1, engine.max_slots + 1))):
                engine.submit(
                    prompt(int(rng.integers(1, engine.prefill_len + 1)),
                           admissions), max_new_tokens=2)
                admissions += 1
            engine.run()
    assert backend_compiles.count == compiled
    assert {r.outcome for r in engine._results.values()} == {"ok"}
    assert engine.prefill_compile_count == count
    assert engine.decode_compile_count == 1
    snap = engine.metrics.snapshot()
    calls = snap["prefill_calls"]
    rows, length = engine.prefill_shapes[-1]
    assert (rows, length) == (
        (1 if name == "olmo_hybrid" else engine.max_slots),
        engine.prefill_len)
    assert 0 < snap["prefill_positions_admitted"] \
        <= snap["prefill_positions_run"] <= calls * rows * length
    if count > 1:                      # some calls were under the full shape
        assert snap["prefill_positions_run"] < calls * rows * length
    else:
        assert snap["prefill_positions_run"] == calls * rows * length
    assert snap.get("moe_dropped_assignments", 0) == 0


def test_the_disaggregated_engine_keeps_its_one_call():
    from scaletorch_tpu.inference.disagg import DisaggregatedEngine

    cfg, params = model("llama")
    engine = DisaggregatedEngine(
        params, cfg, disagg_split=(1, 1), sampling=GREEDY, max_slots=2,
        max_seq=24, prefill_len=16, page_size=4)
    assert engine.prefill_shapes == ((2, 16),)
    with pytest.raises(NotImplementedError):
        engine.warm_prefill_shapes()
    engine.submit(prompt(5, 0), max_new_tokens=3)
    engine.run()
    assert engine.metrics.prefill_positions_run == 2 * 16
    assert engine.metrics.prefill_positions_admitted == 5


# ---- the benchmark's two readers ---------------------------------------------

def test_the_benchmark_reads_the_counter_the_same_in_both_halves():
    """``serve_prefill_positions_run`` is split by the end-to-end metric
    a cell is held to; both halves read ``engine.prefill_positions_run``
    and list the three cells whose cache is addressed by page."""
    from benchmarks.lib.spec import Spec

    spec = Spec()
    chat = "serve-1.7b-chat"
    (mine,) = [m for m in spec.per_layer(chat)
               if m["name"] == "serve_prefill_positions_run.p995"]
    (theirs,) = [m for m in spec.per_layer("serve-1.7b-longgen")
                 if m["name"] == "serve_prefill_positions_run"]
    assert mine["reducer"] == theirs["reducer"] == {
        "kind": "counter", "key": "engine.prefill_positions_run"}
    for key in ("unit", "better", "source", "layer"):
        assert mine[key] == theirs[key]
    assert (mine["moves"], theirs["moves"]) == ("serve_itl_p995_ms",
                                                "serve_itl_p99_ms")
    assert mine["workloads"] == [chat]
    assert theirs["workloads"] == ["serve-1.7b-longgen",
                                   "serve-olmoe-longgen"]
    snap = EngineMetrics().snapshot()
    assert snap["prefill_positions_run"] == 0
    assert snap["prefill_positions_admitted"] == 0
