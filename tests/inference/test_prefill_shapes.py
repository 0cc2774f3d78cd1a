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
  * that a cache by slot (recurrent state, window rings) still makes
    exactly the one fixed-shape call;
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
from scaletorch_tpu.inference.decode import prefill_shapes
from scaletorch_tpu.inference.engine import EngineMetrics
from scaletorch_tpu.inference.kv_cache import TRASH_PAGE
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
    # state or rings by slot: the one fixed call
    ("olmo-hybrid-7b-serve", ((16, 512),)),
    ("qwen3-next-80b-a3b-serve", ((16, 512),)),
    ("trinity-mini-serve", ((8, 3072),)),
])
def test_the_list_at_the_serving_cells_shapes(name, shapes):
    """What an engine built as the benchmark builds it would list, from
    the two tests the engine makes (``carries_state``, ``window_of``):
    never from a model's name."""
    from benchmarks.lib import spec as spec_lib
    from benchmarks.lib.program import serving_model
    from scaletorch_tpu.inference.kv_cache import carries_state, window_of

    config = spec_lib.Spec().config(name)
    serve = config["serve"]
    cfg, _ = serving_model(config, serve["dtype"])
    top = (serve["max_slots"], serve["prefill_len"])
    by_slot = carries_state(cfg) or window_of(cfg) is not None
    assert ((top,) if by_slot else prefill_shapes(*top)) == shapes
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
                 tables, cache, keys):
        rows = tokens.shape[0]
        assert (tail_lens.shape == starts.shape == write_mask.shape
                == (rows,))
        assert tables.shape[0] == keys.shape[0] == rows
        call = dict(shape=tokens.shape, tail_lens=np.asarray(tail_lens),
                    starts=np.asarray(starts),
                    write_mask=np.asarray(write_mask),
                    tables=np.asarray(tables), keys=np.asarray(keys))
        self.calls.append(call)
        if self.step is None:       # no model: shapes only
            return (jnp.zeros(rows, jnp.int32), None,
                    jnp.ones(rows, bool), cache)
        first, logits, finite, cache = self.step(
            params, tokens, tail_lens, starts, write_mask, tables, cache,
            keys)
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


@pytest.mark.parametrize("name,kw", [
    ("olmo_hybrid", dict(max_slots=3, max_seq=160, prefill_len=128,
                         page_size=8)),
    ("afmoe", dict(max_slots=2, max_seq=160, prefill_len=128, page_size=8)),
])
def test_a_cache_by_slot_still_makes_exactly_the_fixed_shape_call(name, kw):
    """State, convolution tail and rings are indexed by the row: the
    list is the one full shape, a row is its slot whether admitted or
    not, and a live slot's row carries its own table, masked."""
    engine = shape_engine(name, **kw)
    slots, length = kw["max_slots"], kw["prefill_len"]
    assert engine._by_slot
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
])
def test_build_engine_warms_every_shape_and_no_admission_compiles_another(
        backend_compiles, name, flags, count):
    """``scripts/serve.py build_engine`` on one device, as the harness
    and the gateway call it: every listed program of a page-addressed
    engine exists before the first request, the cache is still zero but
    for the TRASH page, no counter has moved, and 50 mixed admissions
    hand the backend compiler nothing and add no entry to the prefill
    step's cache. A cache by slot has the one shape and compiles it at
    its first call, as before."""
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
        if key not in ("page_pool_free", "paged_pool_in_place"):
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
    assert 0 < snap["prefill_positions_admitted"] \
        <= snap["prefill_positions_run"] \
        <= calls * engine.max_slots * engine.prefill_len
    if count > 1:                      # some calls were under the full shape
        assert snap["prefill_positions_run"] < \
            calls * engine.max_slots * engine.prefill_len
    else:
        assert snap["prefill_positions_run"] == \
            calls * engine.max_slots * engine.prefill_len
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
