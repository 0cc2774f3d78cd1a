"""The engine places its parameters once, in the order of dimensions
the compiler chooses for the decode program
(``decode.compile_decode_for_layouts`` + ``decode.chosen_orders`` +
``decode.place_params``; PERF.md, PR 48).

On a CPU the compiler answers with the layouts the arrays have and no
leaf moves, so most of this file hands the rule a STAND-IN for the
executable (the real one's ``input_formats`` with another layout named
for some leaves), which is all the rule needs to be walked end to end
here: the leaf is stored transposed as a new array, the steps are built
to read it (one decode program, one prefill program a shape), and the
tokens are those of the bare jitted steps and of the teacher-forced
paged harness on the caller's own arrays. What the v5e's compiler asks
for at the cells' shapes is in ``tests/aot/``.

A stack that is asked for AND that the program reads only one static
layer at a time is stored as its layers (PERF.md, PR 60): the rule reads
the traced program, so toy steps that index, scan and mix the two say
which qualify, and a tiny ``mimo_v2_flash`` engine (an unrolled forward)
serves from such a tree.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.layout import Format, Layout
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from scaletorch_tpu.inference import InferenceEngine, SamplingParams
from scaletorch_tpu.inference import engine as engine_module
from scaletorch_tpu.inference.decode import (
    ByLayer,
    abstract,
    chosen_orders,
    compile_decode_for_layouts,
    in_model_order,
    load_orders,
    make_paged_decode_step,
    make_paged_prefill_step,
    place_params,
    resolve_forward_cached,
    store_orders,
    teacher_forced_decode_paged,
)
from scaletorch_tpu.inference.kv_cache import (
    HybridCache,
    init_paged_kv_cache,
)
from scaletorch_tpu.models import llama
from scaletorch_tpu.models import mimo_v2_flash as mimo
from tests.conftest import compile_cache_at
from tests.inference.compiled import compiled_forward_cached
from tests.models.test_mimo_v2_flash import tiny_config as tiny_mimo_config
from tests.models.test_olmo_hybrid import seeded_params, tiny_config

GREEDY = SamplingParams(temperature=0.0)
SHAPES = dict(max_slots=3, max_seq=48, prefill_len=16, page_size=8)
TINY = dict(
    vocab_size=64, hidden_size=32, intermediate_size=64,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    dtype=jnp.float32,
)


MOVED = ("['q_proj']", "['o_proj']")


class StandIn:
    """An executable as ``chosen_orders`` reads one: ``executable``'s
    ``input_formats`` with the leaves of the parameters whose path ends
    in one of ``moved`` asked for with their last two dimensions
    swapped (a matrix contraction-minor, as the v5e asks for the
    attention projections)."""

    def __init__(self, executable, moved):
        (params, *rest), kwargs = executable.input_formats

        def ask(path, chosen):
            if jax.tree_util.keystr(path).endswith(moved):
                order = chosen.layout.major_to_minor
                return Format(
                    Layout(major_to_minor=order[:-2] + order[:-3:-1]),
                    chosen.sharding)
            return chosen

        self.input_formats = (
            (jax.tree_util.tree_map_with_path(ask, params), *rest), kwargs)


def stand_in(program, moved):
    """``compile_decode_for_layouts``' answer with the stand-in for its
    executable and the program's own jaxpr."""
    executable, jaxpr = program
    return StandIn(executable, moved), jaxpr


def forward_fn(*args, **kwargs):
    """A ``forward_fn`` in the llama forward's place."""
    return llama.forward_cached(*args, **kwargs)


@pytest.fixture(scope="module")
def dense():
    cfg = llama.LlamaConfig(**TINY)
    return cfg, llama.init_params(jax.random.PRNGKey(0), cfg), forward_fn


@pytest.fixture(scope="module")
def hybrid():
    cfg = tiny_config()
    return cfg, seeded_params(cfg), None


@pytest.fixture(params=["dense", "hybrid"])
def model(request):
    """A ``forward_fn`` engine, and one family with a ``HybridCache``."""
    return request.getfixturevalue(request.param)


@pytest.fixture
def moving(monkeypatch):
    """Engines built inside ask for ``q_proj`` and ``o_proj`` in another
    layout than they come in."""
    real = engine_module.compile_decode_for_layouts
    monkeypatch.setattr(
        engine_module, "compile_decode_for_layouts",
        lambda *a, **kw: stand_in(real(*a, **kw), MOVED))
    # whatever an earlier process of this checkout kept is not asked
    monkeypatch.setattr(engine_module, "load_orders",
                        lambda key, params: (False, None))
    monkeypatch.setattr(engine_module, "store_orders", lambda *a: None)


def make_engine(model, **kw):
    cfg, params, fwd = model
    return InferenceEngine(params, cfg, sampling=GREEDY, forward_fn=fwd,
                           strict_submit=False, **{**SHAPES, **kw})


@functools.cache
def bare_steps(cfg, fwd):
    """The two jitted steps as their builders hand them out."""
    build = dict(page_size=SHAPES["page_size"], seq_limit=SHAPES["max_seq"],
                 forward_fn=fwd)
    return (make_paged_prefill_step(cfg, GREEDY, **build),
            make_paged_decode_step(cfg, GREEDY, **build))


def bare_steps_greedy(model, prompt, n):
    """``n`` greedy tokens after ``prompt`` from the two jitted steps
    alone, on the caller's arrays: slot 0 of an identity page table."""
    cfg, params, fwd = model
    slots, max_seq = SHAPES["max_slots"], SHAPES["max_seq"]
    page = SHAPES["page_size"]
    pages = max_seq // page
    prefill, decode = bare_steps(cfg, fwd)
    pool = init_paged_kv_cache(cfg, slots * pages + 1, page, slots=slots)
    tables = jnp.asarray(
        np.arange(slots * pages, dtype=np.int32).reshape(slots, pages) + 1)
    keys = jnp.zeros((slots, 2), jnp.uint32)
    first = np.zeros(slots, bool)
    first[0] = True
    buf = np.zeros((slots, SHAPES["prefill_len"]), np.int32)
    buf[0, :len(prompt)] = prompt
    tail = np.ones(slots, np.int32)
    tail[0] = len(prompt)
    token, _, _, pool = prefill(
        params, jnp.asarray(buf), jnp.asarray(tail),
        jnp.zeros(slots, jnp.int32), jnp.asarray(first), tables, pool, keys)
    tokens = [int(token[0])]
    for t in range(n - 1):
        feed = np.zeros(slots, np.int32)
        feed[0] = tokens[-1]
        at = np.zeros(slots, np.int32)
        at[0] = len(prompt) + t
        token, _, _, pool = decode(
            params, jnp.asarray(feed), jnp.asarray(at), jnp.asarray(first),
            tables, pool, keys)
        tokens.append(int(token[0]))
    return tokens


def teacher_forced_greedy(model, prompt, tokens):
    """The argmax after ``prompt`` and after each of ``tokens`` but the
    last, from the teacher-forced paged harness on the caller's arrays."""
    cfg, params, fwd = model
    seq = jnp.asarray([list(prompt) + list(tokens[:-1])], jnp.int32)
    logits = teacher_forced_decode_paged(
        params, cfg, seq, page_size=SHAPES["page_size"],
        max_seq=SHAPES["max_seq"], prefill_len=len(prompt),
        forward_fn=compiled_forward_cached(
            fwd or resolve_forward_cached(cfg), cfg))
    return [int(t) for t in np.argmax(
        np.asarray(logits[0, len(prompt) - 1:], np.float32), axis=-1)]


PROMPTS = [([5, 9, 2, 41, 7], 9), ([11, 3, 3, 60, 1, 8, 22, 4, 13], 7),
           ([2, 2], 6), ([17, 40, 6, 9, 9, 31], 8)]


# ---- the rule -----------------------------------------------------------------

def decode_program(model):
    cfg, params, fwd = model
    engine = make_engine(model)
    step = make_paged_decode_step(
        cfg, GREEDY, page_size=SHAPES["page_size"],
        seq_limit=SHAPES["max_seq"], forward_fn=fwd)
    slots = SHAPES["max_slots"]
    ints = jax.ShapeDtypeStruct((slots,), jnp.int32)
    operands = (
        ints, ints, jax.ShapeDtypeStruct((slots,), jnp.bool_),
        jax.ShapeDtypeStruct(engine._tables.shape, jnp.int32),
        abstract(engine.cache), jax.ShapeDtypeStruct((slots, 2), jnp.uint32))
    return compile_decode_for_layouts(step, params, operands)


def test_on_a_cpu_the_compiler_asks_for_nothing(model):
    """Every leaf is handed on by identity: 0 leaves, 0 bytes."""
    _, params, _ = model
    orders = chosen_orders(params, *decode_program(model))
    assert orders is None
    placed, moved = place_params(params, orders)
    assert set(moved.values()) == {0}
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(placed)):
        assert a is b


def test_the_rule_moves_the_leaf_the_executable_names_and_no_other(dense):
    """A stand-in asks for ``q_proj`` ``[L, in, out]`` as ``(0, 2, 1)``:
    that leaf is a new array ``[L, out, in]`` with the same values,
    every other leaf is the caller's own array, and the caller's
    ``q_proj`` is alive and as it was."""
    _, params, _ = dense
    before = jax.tree.map(np.asarray, params)
    orders = chosen_orders(
        params, *stand_in(decode_program(dense), ("['q_proj']",)))
    placed, moved = place_params(params, orders)
    q = params["layers"]["q_proj"]
    # the llama forward scans its layers: a stack stays a stack
    assert moved == {
        "params_relaid_leaves": 1, "params_relaid_bytes": q.nbytes,
        "params_layered_leaves": 0, "params_layered_bytes": 0}
    assert orders["layers"]["q_proj"] == (0, 2, 1)
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    for (path, leaf), new in zip(flat, jax.tree.leaves(placed)):
        if jax.tree_util.keystr(path).endswith("['q_proj']"):
            assert new is not leaf
            assert new.shape == (leaf.shape[0], leaf.shape[2], leaf.shape[1])
            assert new.unsafe_buffer_pointer() != leaf.unsafe_buffer_pointer()
            np.testing.assert_array_equal(
                np.asarray(new).transpose(0, 2, 1), np.asarray(leaf))
        else:
            assert new is leaf, path
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(params)):
        assert not b.is_deleted()
        np.testing.assert_array_equal(a, np.asarray(b))


@pytest.mark.parametrize("shape,own,asked,want", [
    ((4, 1, 64), (1, 0, 2), (0, 1, 2), ()),        # a dimension of 1
    ((4, 3, 64), (1, 0, 2), (0, 1, 2), ()),        # asked for row-major
    ((4, 32, 64), (0, 1, 2), (0, 1, 2), ()),       # as it lies
    ((4, 32, 64), (0, 1, 2), (0, 2, 1), (0, 2, 1)),
    ((4, 1, 32, 64), (0, 1, 2, 3), (0, 1, 3, 2), (0, 1, 3, 2)),
    ((4, 32, 64), (0, 1, 2), None, ()),     # the program does not read it
], ids=["one-wide", "row-major", "as-it-lies", "a-stack", "a-period-s-stack",
        "unread"])
def test_what_counts_as_asked_for(shape, own, asked, want):
    """Orders are compared with the dimensions of 1 left out, a leaf
    asked for row-major stays (a transposition would store what the
    device already keeps), and so does a leaf the program prunes (GPT-MoE
    has one: its executable names no layout for it)."""
    sharding = jnp.zeros(()).sharding

    class Executable:
        input_formats = (({"w": Format(
            asked and Layout(major_to_minor=asked), sharding)},), {})

    leaf = jax.ShapeDtypeStruct(
        shape, jnp.float32,
        sharding=Format(Layout(major_to_minor=own), sharding))
    orders = chosen_orders({"w": leaf}, Executable)
    assert (orders or {"w": ()})["w"] == want


# ---- a stack stored as its layers ----------------------------------------------

def _matmuls(x, layers):
    for w in layers:
        x = jnp.tanh(x @ w)
    return x


def _unrolled(params, x):
    return _matmuls(x, (params["w"][i] for i in range(3)))


def _unrolled_in_a_jit(params, x):
    return jax.jit(_unrolled)(params, x)


def _counted_back(params, x):
    """A negative index traces to a ``dynamic_slice`` of a computed
    start."""
    return _matmuls(x, (params["w"][-1 - i] for i in range(3)))


def _scanned(params, x):
    return jax.lax.scan(
        lambda x, w: (jnp.tanh(x @ w), None), x, params["w"])[0]


def _mixed(params, x):
    """A layer by a static index and one under a traced index (a period
    loop's counter, ``layer_of``)."""
    at = jnp.argmax(x[0, :3])
    return (x @ params["w"][0]) @ jax.lax.dynamic_index_in_dim(
        params["w"], at, 0, keepdims=False)


def _under_remat(params, x):
    return jax.checkpoint(_unrolled)(params, x)


def _two_layers_at_once(params, x):
    return _matmuls(x, params["w"][0:2])


def _handed_on(params, x):
    return _unrolled(params, x), params["w"]


TOY = {"w": jnp.arange(3 * 8 * 8, dtype=jnp.float32).reshape(3, 8, 8) / 200,
       "unasked": jnp.ones((3, 8, 8))}


def toy_orders(step, asked=("['w']",)):
    program = compile_decode_for_layouts(
        jax.jit(step), TOY, (jax.ShapeDtypeStruct((2, 8), jnp.float32),))
    return chosen_orders(TOY, *stand_in(program, asked))


@pytest.mark.parametrize("step,layered", [
    (_unrolled, True), (_unrolled_in_a_jit, True), (_counted_back, False),
    (_scanned, False), (_mixed, False), (_under_remat, False),
    (_two_layers_at_once, False), (_handed_on, False),
], ids=lambda x: getattr(x, "__name__", None))
def test_a_stack_read_only_one_static_layer_at_a_time_is_layered(
        step, layered):
    """The program says so, not the family: every equation that reads
    the leaf is a ``slice`` of extent 1 along axis 0 at a static index,
    through plain ``jit`` equations. A scan over the stack, a traced
    or computed index anywhere, a ``remat`` around the readers, two
    layers in one slice or the stack handed on whole keep it a stack,
    moved as it was."""
    orders = toy_orders(step)
    assert orders == {"w": (0, 2, 1), "unasked": ()}
    assert isinstance(orders["w"], ByLayer) == layered
    assert ByLayer((0, 2, 1)).of_a_layer == (1, 0)
    assert ByLayer((1, 0, 3, 2)).of_a_layer == (0, 2, 1)


def test_a_stack_that_is_not_moved_anyway_stays_whole():
    """(a): the rule adds no byte. What the program reads as it lies is
    handed on by identity however it is indexed."""
    def step(params, x):
        return _scanned({"w": params["unasked"]}, _unrolled(params, x))

    assert toy_orders(step, asked=()) is None
    orders = toy_orders(step, asked=("['unasked']",))
    assert orders == {"w": (), "unasked": (0, 2, 1)}
    assert not isinstance(orders["unasked"], ByLayer)


def test_a_leaf_across_several_devices_stays_whole():
    """A mesh engine's stack is moved shard by shard as it was at PR 48
    and not cut into layers."""
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    sharded = {**TOY, "w": jax.device_put(
        TOY["w"], NamedSharding(mesh, P(None, None, "tp")))}
    program = compile_decode_for_layouts(
        jax.jit(_unrolled), sharded,
        (jax.ShapeDtypeStruct((2, 8), jnp.float32),))
    orders = chosen_orders(sharded, *stand_in(program, ("['w']",)))
    assert orders["w"] == (0, 2, 1) and not isinstance(orders["w"], ByLayer)


def test_the_layers_come_back_bit_for_bit_and_the_counters_say_so():
    """``place_params`` stores ``shape[0]`` arrays, each the layer in the
    asked order; ``in_model_order`` hands the forward something whose
    ``[index]`` is the layer as the model laid it, and that still reads
    as the stack where a program wants that."""
    orders = toy_orders(_unrolled)
    before = np.asarray(TOY["w"])
    placed, moved = place_params(TOY, orders)
    assert placed["unasked"] is TOY["unasked"]
    assert isinstance(placed["w"], tuple) and len(placed["w"]) == 3
    for index, layer in enumerate(placed["w"]):
        np.testing.assert_array_equal(np.asarray(layer), before[index].T)
    assert moved == {
        "params_relaid_leaves": 1, "params_relaid_bytes": before.nbytes,
        "params_layered_leaves": 1, "params_layered_bytes": before.nbytes}
    back = in_model_order(placed, orders)
    assert back["unasked"] is TOY["unasked"]
    assert back["w"].shape == before.shape and back["w"].dtype == before.dtype
    for index in range(-3, 3):
        np.testing.assert_array_equal(np.asarray(back["w"][index]),
                                      before[index])
    np.testing.assert_array_equal(np.asarray(back["w"][1:]), before[1:])
    np.testing.assert_array_equal(np.asarray(jnp.asarray(back["w"])), before)
    np.testing.assert_array_equal(np.asarray(TOY["w"]), before)
    x = jnp.ones((2, 8))
    for step in (_unrolled, _two_layers_at_once):
        np.testing.assert_array_equal(
            np.asarray(jax.jit(lambda tree, x: step(
                in_model_order(tree, orders), x))(placed, x)),
            np.asarray(jax.jit(step)(TOY, x)))


def test_the_kept_answer_says_which_leaves_go_by_layer(
        monkeypatch, tmp_path):
    """``store_orders`` -> ``load_orders``: an order comes back as the
    type it went in as; a file that cannot be read, or one of the form
    kept before PR 60 (no ``by_layer``), counts as none."""
    from scaletorch_tpu import env

    monkeypatch.setattr(env, "compile_cache_dir", lambda: str(tmp_path))
    orders = {"w": ByLayer((0, 2, 1)), "unasked": (0, 2, 1),
              "norm": ()}
    tree = {"w": 0, "unasked": 0, "norm": 0}
    store_orders("a-key", orders)
    found, kept = load_orders("a-key", tree)
    assert found and kept == orders
    assert type(kept["w"]) is ByLayer and type(kept["unasked"]) is tuple
    store_orders("nothing-moves", None)
    assert load_orders("nothing-moves", tree) == (True, None)
    assert load_orders("no-such-key", tree) == (False, None)
    for text in ("not json", '{"moved": {"[\'w\']": [0, 2, 1]}}',
                 '{"moved": {}, "by_layer": 3}'):
        (tmp_path / "param_orders-a-key.json").write_text(text)
        assert load_orders("a-key", tree) == (False, None)


MIMO_MOVED = ("['q_proj']", "['k_proj']", "['v_proj']")


@pytest.fixture(scope="module")
def tiny_mimo():
    cfg = tiny_mimo_config()
    return cfg, jax.jit(mimo.init_params, static_argnums=1)(
        jax.random.PRNGKey(3), cfg), None


def test_an_unrolled_family_serves_from_its_layers(monkeypatch, tiny_mimo):
    """A tiny ``mimo_v2_flash`` engine whose compiler (the stand-in)
    asks for the attention projections contraction-minor, as the v5e's
    does: ``q_proj`` and both kinds' ``k_proj`` / ``v_proj`` are tuples
    of layers in ``engine.params``, the one-row prefill program and the
    decode program read them, and the tokens are those of the
    teacher-forced paged harness on the caller's own arrays, which are
    as they were afterwards."""
    cfg, params, _ = tiny_mimo
    before = jax.tree.map(np.asarray, params)
    real = engine_module.compile_decode_for_layouts
    monkeypatch.setattr(
        engine_module, "compile_decode_for_layouts",
        lambda *a, **kw: stand_in(real(*a, **kw), MIMO_MOVED))
    monkeypatch.setattr(engine_module, "load_orders",
                        lambda key, params: (False, None))
    monkeypatch.setattr(engine_module, "store_orders", lambda *a: None)
    engine = make_engine(tiny_mimo)
    layers, placed = params["layers"], engine.params["layers"]
    layered = [(layers[part][name], placed[part][name])
               for part, name in (("block", "q_proj"), ("full", "k_proj"),
                                  ("full", "v_proj"), ("window", "k_proj"),
                                  ("window", "v_proj"))]
    for own, stored in layered:
        assert isinstance(stored, tuple) and len(stored) == own.shape[0]
        assert stored[0].shape == own.shape[:0:-1]
    assert placed["block"]["o_proj"] is layers["block"]["o_proj"]
    snap = engine.metrics.snapshot()
    nbytes = sum(own.nbytes for own, _ in layered)
    assert (snap["params_layered_leaves"], snap["params_layered_bytes"],
            snap["params_relaid_leaves"], snap["params_relaid_bytes"]
            ) == (5, nbytes, 5, nbytes)
    ids = [engine.submit(p, max_new_tokens=n) for p, n in PROMPTS]
    results = engine.run()
    assert engine.decode_compile_count == 1
    assert engine.prefill_compile_count == len(engine.prefill_shapes) == 1
    for (prompt, n), rid in zip(PROMPTS[:2], ids):
        tokens = results[rid].tokens
        assert len(tokens) == n
        assert tokens == teacher_forced_greedy(tiny_mimo, prompt, tokens)
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(params)):
        assert not b.is_deleted()
        np.testing.assert_array_equal(a, np.asarray(b))


# ---- an engine built through it -----------------------------------------------

@pytest.mark.parametrize("placement", ["as-they-come", "two-leaves-moved"])
def test_the_tokens_are_those_of_the_steps_on_the_caller_s_arrays(
        request, model, placement):
    """Request for request: the bare jitted steps and the teacher-forced
    paged harness, both on the arrays the caller handed the engine."""
    if placement == "two-leaves-moved":
        request.getfixturevalue("moving")
    engine = make_engine(model)
    _, params, _ = model
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    for (path, own), leaf in zip(flat, jax.tree.leaves(engine.params)):
        asked = (placement == "two-leaves-moved"
                 and jax.tree_util.keystr(path).endswith(MOVED))
        assert (leaf is not own) == asked, path
    if isinstance(engine.cache, HybridCache):
        assert engine.metrics.snapshot()["recurrent_state_bytes"] > 0
    ids = [engine.submit(p, max_new_tokens=n) for p, n in PROMPTS]
    results = engine.run()
    for i, ((prompt, n), rid) in enumerate(zip(PROMPTS, ids)):
        tokens = results[rid].tokens
        assert len(tokens) == n
        assert tokens == bare_steps_greedy(model, prompt, n)
        if i < 2:   # the harness's prefill compiles anew for every length
            assert tokens == teacher_forced_greedy(model, prompt, tokens)


def test_one_decode_program_and_one_prefill_program_a_shape(model, moving):
    """The steps built to read the placed tree compile once, through the
    warm-up and through admissions into freed slots."""
    engine = make_engine(model)
    engine.warm_prefill_shapes()
    assert engine.prefill_compile_count == len(engine.prefill_shapes)
    assert engine.decode_compile_count == 0
    for _ in range(2):
        for prompt, n in PROMPTS:
            engine.submit(prompt, max_new_tokens=n)
        engine.run()
    assert engine.decode_compile_count == 1
    assert engine.prefill_compile_count == len(engine.prefill_shapes)
    assert engine.metrics.requests_completed == 2 * len(PROMPTS)


def test_the_caller_s_arrays_outlive_the_engine_s_placement(dense, moving):
    cfg, params, _ = dense
    before = jax.tree.map(np.asarray, params)
    engine = make_engine(dense)
    engine.submit([1, 2, 3], max_new_tokens=3)
    engine.run()
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(params)):
        assert not b.is_deleted()
        np.testing.assert_array_equal(a, np.asarray(b))
    # and a second engine over the same arrays reads what the first did
    assert (make_engine(dense).metrics.params_relaid_leaves
            == engine.metrics.params_relaid_leaves == 2)


@pytest.mark.parametrize("placement,leaves", [("as-they-come", 0),
                                              ("two-leaves-moved", 2)])
def test_the_snapshot_says_what_was_moved(request, dense, placement, leaves):
    if leaves:
        request.getfixturevalue("moving")
    _, params, _ = dense
    snap = make_engine(dense).metrics.snapshot()
    assert snap["params_relaid_leaves"] == leaves
    # the llama forward scans its layers: no stack is cut into them
    assert snap["params_layered_leaves"] == snap["params_layered_bytes"] == 0
    assert snap["params_relaid_bytes"] == (leaves and sum(
        params["layers"][name].nbytes for name in ("q_proj", "o_proj")))


@pytest.mark.parametrize("placement", ["as-they-come", "two-leaves-moved"])
def test_the_answer_is_kept_for_the_next_process(
        monkeypatch, tmp_path, dense, placement):
    """Beside the compile cache: the second engine over the same program
    asks no compiler and places the same leaves; a ``forward_fn`` engine
    keeps nothing (its code has no name here), and with no compile
    cache nothing is kept either."""
    cfg, params, _ = dense
    model = cfg, params, None
    asked = []
    real = engine_module.compile_decode_for_layouts

    def compile_for_layouts(*a, **kw):
        asked.append(1)
        program = real(*a, **kw)
        return (stand_in(program, MOVED) if placement == "two-leaves-moved"
                else program)

    monkeypatch.setattr(engine_module, "compile_decode_for_layouts",
                        compile_for_layouts)
    with compile_cache_at(None):
        make_engine(model)
        make_engine(model)
        assert len(asked) == 2 and not list(tmp_path.iterdir())
    with compile_cache_at(str(tmp_path)):
        first = make_engine(model)
        kept = list(tmp_path.glob("param_orders-*.json"))
        assert len(asked) == 3 and len(kept) == 1
        second = make_engine(model)
        assert len(asked) == 3
        assert (second.metrics.params_relaid_leaves
                == first.metrics.params_relaid_leaves
                == (2 if placement == "two-leaves-moved" else 0))
        for a, b in zip(jax.tree.leaves(first.params),
                        jax.tree.leaves(second.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        make_engine(model, max_slots=2)       # another program: asked
        assert len(asked) == 4
        kept[0].write_text("not json")        # unreadable: asked again
        make_engine(model)
        assert len(asked) == 5
        make_engine(dense)                    # a forward_fn: never kept
        make_engine(dense)
        assert len(asked) == 7
        assert len(list(tmp_path.glob("param_orders-*.json"))) == 2
        rid = second.submit([1, 2, 3], max_new_tokens=5)
        assert second.run()[rid].tokens == bare_steps_greedy(
            dense, [1, 2, 3], 5)


@pytest.mark.parametrize("placement", ["as-they-come", "two-leaves-moved"])
def test_an_engine_on_a_two_device_mesh_builds_and_steps(
        request, dense, placement):
    """The leaf's ``NamedSharding`` rides inside the ``Format`` the
    compiler is asked with, and a column-sharded ``q_proj`` is stored
    transposed shard by shard: its columns, now rows, stay split over
    ``tp``."""
    from scaletorch_tpu.parallel.tensor_parallel import llama_param_specs

    if placement == "two-leaves-moved":
        request.getfixturevalue("moving")
    cfg, params, fwd = dense
    want = bare_steps_greedy(dense, [1, 2, 3], 6)
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    sharded = jax.tree.map(
        lambda leaf, spec: jax.device_put(leaf, NamedSharding(mesh, spec)),
        params, llama_param_specs(cfg, tp_axis="tp"),
        is_leaf=lambda x: isinstance(x, P))
    engine = make_engine((cfg, sharded, fwd), mesh=mesh, tp_axis="tp")
    q, own = engine.params["layers"]["q_proj"], sharded["layers"]["q_proj"]
    layers, rows, columns = own.shape
    if placement == "two-leaves-moved":
        assert q.shape == (layers, columns, rows)
        assert q.sharding.shard_shape(q.shape) == (
            layers, columns // 2, rows)
    else:
        assert q is own
    rid = engine.submit([1, 2, 3], max_new_tokens=6)
    assert engine.run()[rid].tokens == want
    assert engine.decode_compile_count == 1
