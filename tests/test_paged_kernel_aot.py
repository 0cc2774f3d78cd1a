"""The paged kernels, the two paged step programs that call them, and
(last section) the training flash kernels, compiled for the v5e at real
widths with no chip attached: Mosaic refuses here what it would refuse
there (a slice off the tiling, too much VMEM or SMEM), and the compiled
step shows what XLA made of the pool (a copy per layer, a re-laid
stack: PR 27's expert stack, PR 28's page pool), neither of which
interpret mode can see. One file, one fixture, nothing at import time:
only the worker that runs this file loads the TPU compiler, and a
second file that did would skip wherever two workers may not both hold
libtpu. Quick tier, ~1 s a kernel case, 2-20 s a step program.
"""

import hashlib
import json
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmarks.lib.trace import short_name
from tests.ops.test_flash_pallas import flash_call_blocks, pallas_calls
from scaletorch_tpu.ops.pallas.flash import (
    MAX_CAUSAL_STEPS,
    causal_block_plan,
    flash_blocks,
    pallas_flash_attention,
)
from scaletorch_tpu.ops.pallas.paged_attention import (
    pallas_paged_decode_attention,
    pallas_paged_write,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as env:
        env.setenv("TPU_SKIP_MDS_QUERY", "1")
        env.setenv("TPU_WORKER_HOSTNAMES", "localhost")
        env.setenv("TPU_LOG_DIR", "disabled")
        # the target is a topology, so the platform test cannot see it:
        # the step programs pick their pair as they would on the chip
        env.setenv("SCALETORCH_TPU_FORCE_PALLAS", "1")
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


def _compiled_text(one_chip, b, hq, hkv, d, page, max_pages, dtype):
    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pool = arg((b * max_pages + 1, hkv, page, d), dtype)
    return jax.jit(pallas_paged_decode_attention).lower(
        arg((b, hq, d), dtype), pool, pool,
        arg((b, max_pages), jnp.int32), arg((b,), jnp.int32),
    ).compile().as_text()


def _mosaic_calls(text):
    return [line for line in text.splitlines()
            if "custom-call(" in line and "tpu_custom_call" in line]


def _named(calls, name):
    """The calls that ARE ``name`` (its result), not those that read it."""
    return [c for c in calls if re.match(rf"\s*(ROOT )?%{name}\S* = ", c)]


def test_serving_shape_is_one_call_the_benchmark_can_find(one_chip):
    """qwen3-1.7b-serve: 16 slots, 16/8 heads x 128, page 16, 96 pages a
    slot. `benchmarks/metrics/serve_paged_attn_roofline.json` tells the
    kernel by its single 4-D bf16 result; a second Mosaic call, a tuple
    result or another name would make that metric count wrongly."""
    calls = _mosaic_calls(_compiled_text(
        one_chip, 16, 16, 8, 128, 16, 96, jnp.bfloat16))
    assert len(calls) == 1, calls
    assert re.search(r"%paged_decode\S* = bf16\[16,8,2,128\]", calls[0]), calls


@pytest.mark.parametrize("hq,hkv,page,max_pages,dtype", [
    (8, 8, 16, 96, jnp.bfloat16),     # MHA: one query row a KV head
    (32, 8, 16, 13, jnp.bfloat16),    # n_rep 4, a short last block
    (64, 8, 16, 13, jnp.bfloat16),    # n_rep 8
    (2, 1, 16, 96, jnp.bfloat16),     # one KV head of a tp shard
    (20, 1, 16, 216, jnp.bfloat16),   # Jamba2: 20 query rows, ONE KV head
    (16, 8, 8, 96, jnp.bfloat16),     # a page of half a bf16 tile
    (16, 8, 32, 48, jnp.bfloat16),
    (16, 8, 8, 13, jnp.float32),      # fp32 pools
], ids=["mha", "nrep4-ragged", "nrep8", "hkv1", "jamba-20-on-1", "page8",
        "page32", "fp32"])
def test_kernel_compiles_for_the_layouts_the_models_use(
        one_chip, hq, hkv, page, max_pages, dtype):
    calls = _mosaic_calls(_compiled_text(
        one_chip, 4, hq, hkv, 128, page, max_pages, dtype))
    assert len(calls) == 1, calls


@pytest.mark.parametrize("hq,hkv", [(16, 2), (4, 2)],
                         ids=["qwen3-next-group8", "group2"])
def test_kernel_compiles_for_a_256_wide_head(one_chip, hq, hkv):
    """qwen3-next-80b-a3b-serve: 16 query heads over 2 K/V heads of 256,
    16 slots x 96 pages of 16. Two lane tiles a head, 8 query rows a
    K/V head: still one call with the one 4-D bf16 result that
    ``serve_paged_attn_roofline`` tells the kernel by."""
    calls = _mosaic_calls(_compiled_text(
        one_chip, 16, hq, hkv, 256, 16, 96, jnp.bfloat16))
    assert len(calls) == 1, calls
    assert re.search(
        rf"%paged_decode\S* = bf16\[16,{hkv},{hq // hkv},256\]", calls[0]), calls


@pytest.mark.parametrize("layers,slots,hkv,rows,page,max_pages,dtype", [
    (28, 16, 8, 1, 16, 96, jnp.bfloat16),      # qwen3-1.7b-serve, decode
    (28, 16, 8, 1024, 16, 96, jnp.bfloat16),   # ... and its prefill
    (8, 16, 16, 1, 16, 96, jnp.bfloat16),      # olmoe-1b-7b-serve (MHA)
    (8, 16, 16, 1024, 16, 96, jnp.bfloat16),
    (2, 3, 8, 1000, 16, 96, jnp.bfloat16),     # a partly filled last page
    (2, 4, 1, 50, 16, 13, jnp.bfloat16),       # one KV head of a tp shard
    (2, 4, 8, 100, 8, 13, jnp.bfloat16),       # a page of half a bf16 tile
    (2, 4, 8, 1, 32, 13, jnp.bfloat16),
    (2, 4, 8, 20, 8, 13, jnp.float32),         # fp32 pools
], ids=["qwen-decode", "qwen-prefill", "olmoe-decode", "olmoe-prefill",
        "ragged-rows", "hkv1", "page8", "page32", "fp32"])
def test_page_write_compiles_and_aliases_the_pool(
        one_chip, layers, slots, hkv, rows, page, max_pages, dtype):
    """``paged_write``: one Mosaic call whose only result is the donated
    pool itself, with nothing of the pool's size beside it."""
    _page_write_aliases_the_pool(
        one_chip, layers, slots, hkv, rows, page, max_pages, dtype, 128)


@pytest.mark.parametrize("rows", [1, 512], ids=["decode", "prefill"])
def test_page_write_compiles_for_a_256_wide_head(one_chip, rows):
    """qwen3-next-80b-a3b-serve: 3 full-attention layers, 2 K/V heads of
    256."""
    _page_write_aliases_the_pool(
        one_chip, 3, 16, 2, rows, 16, 96, jnp.bfloat16, 256)


def _page_write_aliases_the_pool(one_chip, layers, slots, hkv, rows, page,
                                 max_pages, dtype, d):
    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pool = arg((layers, slots * max_pages + 1, hkv, page, d), dtype)
    compiled = jax.jit(
        lambda pool, *a: pallas_paged_write(pool, *a[:-1], layer=a[-1]),
        donate_argnums=0,
    ).lower(
        pool, arg((slots, hkv, rows, d), dtype),
        arg((slots, rows), jnp.int32), arg((slots, max_pages), jnp.int32),
        arg((slots,), jnp.bool_), arg((), jnp.int32),
    ).compile()
    calls = _mosaic_calls(compiled.as_text())
    assert len(calls) == 1 and _named(calls, "paged_write"), calls
    memory = compiled.memory_analysis()
    pool_bytes = math.prod(pool.shape) * jnp.dtype(dtype).itemsize
    assert memory.alias_size_in_bytes >= pool_bytes
    assert memory.temp_size_in_bytes < pool_bytes // 10


# ---------------------------------------------------------------------------
# the whole step programs
# ---------------------------------------------------------------------------
# {key: the orders of dimensions the decode program chose for the
# parameters}: one compile with free layouts a configuration, whatever
# asks for its programs
_CHOSEN = {}


def _serving_steps(one_chip, cfg, init, *, slots, max_seq, prefill_len,
                   page_size, prefill_rows=None, key=None):
    """(decode, prefill) compiled from the engine's own step builders,
    donated, on abstract arguments, AS AN ENGINE RUNS THEM: the
    parameters stored in the orders of dimensions the compiler chooses
    for the decode program (``decode.compile_decode_for_layouts`` +
    ``chosen_orders``, the rule ``InferenceEngine`` places its weights
    by; kept under ``key``), both steps built to read them so; and the
    pool's shape. With ``prefill_rows`` the prefill step alone, at
    ``[prefill_rows, prefill_len]``: a row is a slot only through its
    page table and its key, so the step takes any number of them.
    Where a row names its slot (``decode.rows_name_slots``) the prefill
    program is the engine's one: ONE row, with its slot id."""
    from jax.experimental.layout import Format

    from scaletorch_tpu.inference.decode import (
        chosen_orders,
        compile_decode_for_layouts,
        counts_routing,
        make_paged_decode_step,
        make_paged_prefill_step,
        place_params,
        rows_name_slots,
    )
    from scaletorch_tpu.inference.kv_cache import init_paged_kv_cache
    from scaletorch_tpu.inference.routing_counters import ROUTING_COUNTERS
    from scaletorch_tpu.inference.sampling import SamplingParams

    def arg(shape, dt, where=one_chip):
        return jax.ShapeDtypeStruct(shape, dt, sharding=where)

    def on_chip(tree):
        return jax.tree.map(lambda x: arg(x.shape, x.dtype), tree)

    params = on_chip(jax.eval_shape(
        lambda: init(jax.random.PRNGKey(0), cfg)))
    max_pages = -(-max_seq // page_size)
    pool = on_chip(jax.eval_shape(lambda: init_paged_kv_cache(
        cfg, slots * max_pages + 1, page_size, dtype=cfg.dtype,
        slots=slots)))
    counted = counts_routing(cfg)
    build = dict(page_size=page_size, seq_limit=max_seq, donate_cache=True,
                 routing_counts=counted)
    sampling = SamplingParams(temperature=0.0)

    def operands(rows, *lead, slot_ids=False):
        ints = arg((rows,), jnp.int32)
        return (*lead, ints, ints, arg((rows,), jnp.bool_),
                arg((rows, max_pages), jnp.int32), pool,
                arg((rows, 2), jnp.uint32)) + (ints,) * slot_ids + (
            (arg((len(ROUTING_COUNTERS),), jnp.uint32),) if counted else ())

    if key is None or key not in _CHOSEN:
        # as arrays on the chip lie: the device's own default layouts
        # (not row-major for every shape), read off a program that
        # hands its parameters on
        lying = jax.tree.map(
            lambda x, own: arg(x.shape, x.dtype, Format(own.layout, one_chip)),
            params,
            jax.jit(lambda tree: tree).lower(params).compile(
            ).input_formats[0][0])
        _CHOSEN[key] = chosen_orders(lying, *compile_decode_for_layouts(
            make_paged_decode_step(cfg, sampling, **build), lying,
            operands(slots), donate_cache=True))
    orders = _CHOSEN[key]
    build["param_orders"] = orders
    placed = on_chip(jax.eval_shape(
        lambda tree: place_params(tree, orders)[0], params))
    by_id = rows_name_slots(cfg)
    rows = prefill_rows or (1 if by_id else slots)
    prefill = make_paged_prefill_step(cfg, sampling, **build).lower(
        placed, *operands(rows, arg((rows, prefill_len), jnp.int32),
                          slot_ids=by_id)).compile()
    if prefill_rows is not None:
        return None, prefill, pool.k.shape
    decode = make_paged_decode_step(cfg, sampling, **build).lower(
        placed, *operands(slots)).compile()
    return decode, prefill, pool.k.shape


_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%\S+ = (?P<type>\(.*?\)|\S+) (?P<op>[\w-]+)\(")
# what may carry the pool: the program's and the loop's parameters, the
# tuples they travel in, the loop itself
_PLUMBING = {"parameter", "tuple", "get-tuple-element", "while"}


def _pool_shaped(text, pool_shape):
    """{op: count} of the instructions, fused ones included, whose result
    is the pool or one layer of it, Mosaic calls and plumbing apart."""
    dims = ",".join(map(str, pool_shape[1:]))
    shapes = [f"[{pool_shape[0]},{dims}]", f"[1,{dims}]", f"[{dims}]"]
    found = {}
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m or not any(s in m["type"] for s in shapes):
            continue
        if m["op"] in _PLUMBING or "tpu_custom_call" in line:
            continue
        found[m["op"]] = found.get(m["op"], 0) + 1
    return found


def _serving_model(name):
    """(the configuration's file, the model config the program builds
    for it, its initialiser)."""
    from benchmarks.lib.program import serving_model

    with open(os.path.join(REPO, "benchmarks", "configs",
                           name + ".json")) as f:
        config = json.load(f)
    return (config,) + tuple(serving_model(config, config["serve"]["dtype"]))


# {(configuration, prefill shape): its programs}: compiled once a run of
# this file, whichever test asks first
_PROGRAMS = {}


def _programs_of(one_chip, name, prefill_shape=None):
    """The configuration's two step programs at its serve shapes; with
    ``prefill_shape`` its prefill program at that ``(rows, length)``
    alone."""
    if (name, prefill_shape) not in _PROGRAMS:
        config, cfg, init = _serving_model(name)
        serve = config["serve"]
        rows, length = prefill_shape or (None, serve["prefill_len"])
        _PROGRAMS[name, prefill_shape] = _serving_steps(
            one_chip, cfg, init, slots=serve["max_slots"],
            max_seq=serve["max_seq"], prefill_len=length,
            page_size=serve["page_size"], prefill_rows=rows, key=name)
    return _PROGRAMS[name, prefill_shape]


@pytest.fixture(scope="module")
def serving_cfgs():
    """{configuration name: the model config the program builds for it}
    of the four whose step programs ``serving_programs`` compiles."""
    return {name: _serving_model(name)[1]
            for name in ("qwen3-1.7b-serve", "olmoe-1b-7b-serve",
                         "olmo-hybrid-7b-serve", "qwen3-next-80b-a3b-serve")}


@pytest.fixture(scope="module", params=["qwen3-1.7b-serve",
                                        "olmoe-1b-7b-serve",
                                        "olmo-hybrid-7b-serve",
                                        "qwen3-next-80b-a3b-serve"])
def serving_programs(request, one_chip):
    return _programs_of(one_chip, request.param)


def test_no_step_program_moves_the_pool(serving_programs):
    """The guard that would have caught, with no chip, 22 GB of pool
    copies a decode step (PR 28) and the expert stack's copy per layer
    (PR 27): in the compiled step nothing but parameters, tuple
    plumbing, the layer loop and Mosaic calls has a result of the pool's
    shape or of one layer of it."""
    decode, prefill, pool_shape = serving_programs
    for name, program in (("decode", decode), ("prefill", prefill)):
        assert _pool_shaped(program.as_text(), pool_shape) == {}, name
        writes = _named(_mosaic_calls(program.as_text()), "paged_write")
        assert len(writes) == 2, (name, writes)    # K and V, in the loop


_COPY = re.compile(
    r"= bf16\[(?P<dims>[\d,]+)\](?P<layout>\S*) copy\(%(?P<operand>[^\s,)]+)")
_RESULT = re.compile(r"^\s*(?:ROOT )?%(?P<name>\S+) = \w+\[[\d,]*\](?P<layout>\S*) ")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%(?P<name>\S+) \(.*\) -> .* \{$")
_ARRAY_OF = re.compile(r"\w+\[(?P<dims>[\d,]*)\](?P<layout>\{[^}]*\})?")
# what moves an array and computes nothing
_MOVES = {"parameter", "slice", "dynamic-slice", "bitcast", "copy",
          "transpose", "reshape", "tuple", "get-tuple-element"}
_WEIGHT_SIZED = 2 ** 20


def _arrays(hlo_type):
    """(dtype[dims] text, dims, layout) of each array of an
    instruction's result type, a tuple's elements in turn."""
    return [(m[0], [int(d) for d in m["dims"].split(",") if d],
             m["layout"] or "") for m in _ARRAY_OF.finditer(hlo_type)]


def _weight_copies(text, params):
    """The instructions of a compiled program that copy a weight from
    HBM to HBM, each a line's first 120 characters.

    A ``copy`` that RE-LAYS a weight: the result a bf16 array of 1 Mi
    elements or more with a weight's shape (a leaf of ``params`` whole,
    or its trailing dimensions: a layer of a stack, a period's slice; in
    any order, a copy being how a matrix is turned contraction-minor;
    dimensions of 1 apart), in another layout than its operand has. A
    copy that keeps the layout and changes the memory space (``S(1)``:
    XLA fetching a stack into fast memory ahead of its use) re-lays
    nothing.

    A SLICE of a weight materialised (PR 60: MiMo-V2-Flash's decode
    program copied 0.81 GB of layers out of its re-laid stacks every
    step, ``fusion.519``): a ``slice`` / ``dynamic-slice`` that stands
    on its own in the program, or a loop fusion all of whose weight-sized
    instructions move and compute nothing, with such a result, or such
    an element of a tuple result, NOT in fast memory: landing in
    ``S(1)`` the slice is the weight's one reading, in HBM it is a copy
    that the matmul reads once more. A slice INSIDE a fusion that
    computes (the matmul that reads ``o_proj[index]``) is no
    instruction of the program's own."""
    def key(dims):
        return tuple(sorted(d for d in dims if d != 1))

    def laid(layout):
        return re.sub(r"S\(\d+\)", "", layout)

    def weight_sized(arrays):
        return [(array, dims, layout) for array, dims, layout in arrays
                if math.prod(dims) >= _WEIGHT_SIZED]

    weights = {key(leaf.shape[i:]) for leaf in jax.tree.leaves(params)
               for i in range(leaf.ndim)}
    lines = text.splitlines()
    layouts = {m["name"]: laid(m["layout"])
               for m in map(_RESULT.match, lines) if m}
    # {computation: whether its weight-sized instructions only move},
    # and the computations that are fusions' bodies
    moves, inside, bodies = {}, None, set()
    for line in lines:
        header, m = _COMPUTATION.match(line), _INSTRUCTION.match(line)
        if header:
            inside = header["name"]
            moves[inside] = True
        elif m and weight_sized(_arrays(m["type"])):
            moves[inside] = moves.get(inside, True) and m["op"] in _MOVES
        bodies.update(re.findall(r"\bfusion\(.*calls=%([^\s,]+)", line))
    found, inside = [], None
    for line in lines:
        header = _COMPUTATION.match(line)
        if header:
            inside = header["name"]
        m = _COPY.search(line)
        if m is not None:
            dims = [int(d) for d in m["dims"].split(",")]
            if (math.prod(dims) >= _WEIGHT_SIZED and key(dims) in weights
                    and laid(m["layout"]) != layouts.get(m["operand"])):
                found.append(line.strip()[:120])
            continue
        m = _INSTRUCTION.match(line)
        if m is None or inside in bodies:
            continue
        if m["op"] == "fusion":
            body = re.search(r"calls=%([^\s,]+)", line)
            if "kind=kLoop" not in line or not moves.get(
                    body and body[1], True):
                continue
        elif m["op"] not in ("slice", "dynamic-slice"):
            continue
        if any(array.startswith("bf16[") and key(dims) in weights
               and "S(1)" not in layout
               for array, dims, layout in weight_sized(_arrays(m["type"]))):
            found.append(line.strip()[:120])
    return found


_TILED = "{1,2,0:T(8,128)(2,1)}"
_FAST = "{1,2,0:T(8,128)(2,1)S(1)}"
# MiMo-V2-Flash's decode program at the parent of PR 60: seven static
# slices of the re-laid ``q_proj`` stack in one loop fusion, six results
# in HBM and one in fast memory (two and one here)
_MIMO_Q_LAYERS = f"""
%fused_computation.845 (param_0.2778: bf16[7,4096,12288]) -> (bf16[1,4096,12288], bf16[1,4096,12288], bf16[1,4096,12288]) {{
  %param_0.2778 = bf16[7,4096,12288]{_TILED} parameter(0)
  %slice.622 = bf16[1,4096,12288]{_TILED} slice(%param_0.2778), slice={{[2:3], [0:4096], [0:12288]}}
  %slice.623 = bf16[1,4096,12288]{_FAST} slice(%param_0.2778), slice={{[1:2], [0:4096], [0:12288]}}
  %slice.624 = bf16[1,4096,12288]{_TILED} slice(%param_0.2778), slice={{[0:1], [0:4096], [0:12288]}}
  ROOT %tuple.105 = (bf16[1,4096,12288]{_TILED}, bf16[1,4096,12288]{_FAST}, bf16[1,4096,12288]{_TILED}) tuple(%slice.622, %slice.623, %slice.624)
}}
ENTRY %main.143 (p: bf16[7,4096,12288]) -> bf16[32,19072] {{
  %fusion.519 = (bf16[1,4096,12288]{_TILED}, bf16[1,4096,12288]{_FAST}, bf16[1,4096,12288]{_TILED}) fusion(%bitcast.6), kind=kLoop, calls=%fused_computation.845, metadata={{op_name="jit(decode)/slice" stack_frame_id=53}}
}}"""
# a layer of ``o_proj`` sliced inside the fusion of the matmul that
# reads it, and a residual sum with a weight's shape: nothing is copied
_NO_COPIES = f"""
%fused_computation.7 (param_0.1: bf16[28,2048,2048], param_1.1: bf16[16,2048]) -> bf16[16,2048] {{
  %param_0.1 = bf16[28,2048,2048]{_TILED} parameter(0)
  %slice.583 = bf16[1,2048,2048]{_TILED} slice(%param_0.1), slice={{[3:4], [0:2048], [0:2048]}}
  ROOT %convolution.1 = bf16[16,2048]{{1,0:T(8,128)(2,1)}} convolution(%param_1.1, %slice.583), dim_labels=bf_io->bf
}}
%fused_computation.8 (param_0.2: bf16[1,2048,4096], param_1.2: bf16[1,2048,4096]) -> bf16[1,2048,4096] {{
  ROOT %add.1 = bf16[1,2048,4096]{_TILED} add(%param_0.2, %param_1.2)
}}
ENTRY %main.9 (p: bf16[28,2048,2048]) -> bf16[16,2048] {{
  %fusion.7 = bf16[16,2048]{{1,0:T(8,128)(2,1)}} fusion(%p.1, %x.1), kind=kOutput, calls=%fused_computation.7
  %fusion.8 = bf16[1,2048,4096]{_TILED} fusion(%x.2, %x.3), kind=kLoop, calls=%fused_computation.8
}}"""


@pytest.mark.parametrize("line,found", [
    # the parent's decode programs (ISSUE 48: the ledger's costliest
    # copies by name), and a copy that is no weight's
    ("  %copy.372 = bf16[16,2048,4096]{1,2,0:T(8,128)(2,1)} copy(%p.1)", 1),
    ("  %copy.61 = bf16[1,2048,2048]{1,2,0:T(8,128)(2,1)S(1)} copy(%f.2)", 1),
    ("  %copy.9 = bf16[4096,2048]{0,1:T(8,128)(2,1)S(1)} copy(%b.3)", 1),
    ("  %copy.3 = bf16[16,1024,128]{1,2,0:T(8,128)(2,1)S(1)} copy(%f.2)", 0),
    ("  %copy.4 = bf16[1,2048,128]{1,2,0:T(8,128)(2,1)} copy(%f.2)", 0),
    # jamba's x_proj stack, fetched into fast memory as it lies (the
    # parent does it too): a move, not a re-laying
    ("  %copy.95 = bf16[16,2048,4096]{2,1,0:T(8,128)(2,1)S(1)} copy(%p.1)", 0),
    # slices of a weight materialised (ISSUE 60): MiMo's seven layers of
    # ``q_proj`` a step; the one of them that lands in fast memory, alone;
    # Qwen3-1.7B's layer of ``o_proj`` under the loop's counter, the
    # weight's one reading; a slice on its own into HBM; what computes
    (_MIMO_Q_LAYERS, 1),
    (f"  %fusion.9 = bf16[1,4096,12288]{_FAST} fusion(%bitcast.6), "
     "kind=kLoop, calls=%fused_computation.9", 0),
    (f"  %constant_dynamic-slice_fusion.11 = bf16[1,2048,2048]{_FAST} "
     "fusion(%get-tuple-element.766, %get-tuple-element.728), kind=kLoop, "
     "calls=%fused_computation.69.clone.clone.clone", 0),
    (f"  %dynamic-slice.4 = bf16[1,2048,4096]{_TILED} dynamic-slice(%p.1, "
     "%i.1, %c.0, %c.0), dynamic_slice_sizes={1,2048,4096}", 1),
    (f"  %slice.5 = bf16[1,2048,128]{_TILED} slice(%p.2), "
     "slice={[3:4], [0:2048], [0:128]}", 0),
    (_NO_COPIES, 0),
], ids=["a-stack", "a-layer", "a-layer-transposed", "no-weight", "small",
        "as-it-lies", "mimo-q-proj-layers", "a-layer-into-fast-memory",
        "qwen3-o-proj-into-fast-memory", "a-slice-on-its-own",
        "a-small-slice", "inside-a-matmul-and-a-sum"])
def test_the_guard_finds_the_copies_the_parent_made(line, found):
    weights = {"q_proj": jax.ShapeDtypeStruct((16, 2048, 4096), jnp.bfloat16),
               "o_proj": jax.ShapeDtypeStruct((28, 2048, 2048), jnp.bfloat16),
               "norm": jax.ShapeDtypeStruct((28, 2048, 128), jnp.bfloat16),
               "mimo": jax.ShapeDtypeStruct((7, 4096, 12288), jnp.bfloat16)}
    text = "\n".join([
        "  %p.1 = bf16[16,2048,4096]{2,1,0:T(8,128)(2,1)} parameter(0)",
        "  %f.2 = bf16[1,2048,2048]{2,1,0:T(8,128)(2,1)S(1)} fusion(%p.1)",
        "  %b.3 = bf16[4096,2048]{1,0:T(8,128)(2,1)} bitcast(%f.2)", line])
    assert len(_weight_copies(text, weights)) == found


# what placing the weights cannot take away, each in the parent too.
# jamba's ``x_proj`` stack lies ``{2,3,1,0}`` by the DEVICE's default (192
# columns would pad to 256 the other way round), the decode program
# compiled with free layouts asks for exactly that, and compiled against
# it the program still turns the stack into fast memory once a step (51
# MB, ~0.06 ms of 9.3). Its prefill program reads ``out_proj``
# contraction-minor where the decode program, which the rule asks, reads
# it as it lies (0.68 GB, ~1.7 ms of a 1.2 s call). The hybrid's gates'
# projections ``[4,3,3840,30]`` are stored ``[4,3,30,3840]`` as asked and
# tiled again for the matmul (2.7 MB each).
#
# What the search for materialised SLICES (PR 60) finds besides, none of
# it a stack the rule may cut into layers (``decode.chosen_orders``: a
# leaf the decode program re-lays AND reads only at static indices).
# A layer picked under a loop's counter (``dynamic-slice``: the index is
# traced, so the layer is no parameter of its own) that is too large for
# fast memory and lands in HBM: Trinity-Mini's period loop, three
# ``q_proj`` / ``k_proj`` / ``v_proj`` layers an iteration
# (``constant_dynamic-slice_fusion.46`` - ``.48``; 0.156 s of a 7.95 s
# window: ledger, PR 59), and the unrolled last period's reading of the
# same stacks (``fusion.1064`` - ``.1066``: static, but the loop reads
# the stacks too, and the rule says ONLY); in PREFILL programs, which
# the rule does not ask, jamba's ``[1,1,2560,2560]`` (13 MB), qwen3-next's
# attention projections (38 MB a full layer) and openPangu's ``q_b_proj``
# ``[1,1536,24576]`` / ``kv_b_proj`` (75 + 34 MB a layer, and the
# ``kv_b_proj`` stack re-laid whole, 201 MB, in a 1.2 s call).
# Kimi-Linear indexes its unrolled layers with
# ``lax.dynamic_index_in_dim`` at a Python int (``afmoe._layer_of``),
# which traces to a ``dynamic_slice`` and not to a ``slice``: its latent
# layers' ``q_proj`` ``[1,2304,6144]`` (28 MB) and a ``kv_b_proj`` layer
# (8 MB) are copied out of their re-laid stacks in both programs, ~0.05
# ms of an 11.1 ms step (PERF.md section 7: the next of this kind)
_STILL_RE_LAID = {
    ("jamba2-3b-serve", "decode"): ["bf16[2,13,5120,192]"],
    ("jamba2-3b-serve", "prefill"): ["bf16[1,1,2560,2560]",
                                     "bf16[2,13,5120,2560]"],
    ("olmo-hybrid-7b-serve", "decode"): ["bf16[4,3,30,3840]"] * 2,
    ("olmo-hybrid-7b-serve", "prefill"): ["bf16[4,3,30,3840]"] * 2,
    ("qwen3-next-80b-a3b-serve", "prefill"): [
        "bf16[1,1,2048,512]", "bf16[1,1,2048,512]", "bf16[1,1,2048,8192]"],
    ("trinity-mini-serve", "decode"): [
        "bf16[1,2048,512]", "bf16[1,2048,4096]", "bf16[1,2048,512]"] * 2,
    ("trinity-mini-serve", "prefill"): [
        "bf16[1,2048,512]", "bf16[1,2048,512]", "bf16[1,2048,4096]"] * 2,
    ("kimi-linear-48b-a3b-serve", "decode"): [
        "bf16[1,2304,6144]", "bf16[512,32,256]"],
    ("kimi-linear-48b-a3b-serve", "prefill"): [
        "bf16[2,32,512,256]", "bf16[1,2304,6144]"],
    ("openpangu-ultra-moe-718b-serve", "prefill"): [
        "bf16[1,1536,24576]", "bf16[512,128,256]", "bf16[6,128,512,256]",
        "bf16[512,128,256]", "bf16[1,1536,24576]"],
}


@pytest.mark.parametrize("name,every_shape", [
    pytest.param(name, every_shape, id=name) for name, every_shape in (
        ("qwen3-1.7b-serve", True), ("olmoe-1b-7b-serve", True),
        ("olmo-hybrid-7b-serve", True), ("qwen3-next-80b-a3b-serve", True),
        ("trinity-mini-serve", True), ("jamba2-3b-serve", True),
        # the two programs other tests of this file compile anyway
        ("openpangu-ultra-moe-718b-serve", False),
        ("kimi-linear-48b-a3b-serve", False))])
def test_no_step_program_copies_a_weight(one_chip, name, every_shape):
    """What an engine runs once its parameters are stored in the orders
    of dimensions the decode program reads them in
    (``decode.place_params``): no step re-lays a weight. Compiled against the default layouts the decode programs
    copied, EVERY token, ``q_proj``'s whole 16-layer stack in
    Trinity-Mini (``copy.372 bf16[16,2048,4096]``, 0.8 ms of a 9.2 ms
    step, the costliest operation of the cell's trace) and a layer of
    ``q_proj`` / ``k_proj`` / ``v_proj`` in Qwen3-1.7B (0.5 ms of 7.6),
    a ``bf16[1,2048,2048]`` a layer in OLMoE, ``bf16[1,1,3840,3840]`` in
    the hybrid, ``bf16[1,1,2048,8192]`` in qwen3-next,
    ``bf16[1,1,2560,2560]`` in jamba (PERF.md, PR 48). Every prefill
    shape the engine lists is compiled against the SAME placed
    weights and has none either. What is left, in the parent and
    here, is listed by name (``_STILL_RE_LAID``); MiMo-V2-Flash's two
    programs are in ``tests/test_mimo_step_programs_aot.py``."""
    from scaletorch_tpu.inference.decode import prefill_shapes
    from scaletorch_tpu.inference.kv_cache import carries_state, window_of

    config, cfg, init = _serving_model(name)
    decode, prefill, _ = _programs_of(one_chip, name)
    programs = {"decode": decode, "prefill": prefill}
    if every_shape and not (carries_state(cfg)
                            or window_of(cfg) is not None):
        serve = config["serve"]
        for shape in prefill_shapes(
                serve["max_slots"], serve["prefill_len"])[:-1]:
            programs[f"prefill {shape}"] = _programs_of(
                one_chip, name, shape)[1]
    params = jax.eval_shape(lambda: init(jax.random.PRNGKey(0), cfg))
    for label, program in programs.items():
        left = [re.search(r"bf16\[[\d,]+\]", line)[0]
                for line in _weight_copies(program.as_text(), params)]
        assert left == _STILL_RE_LAID.get((name, label), []), (name, label)


def test_the_rule_moves_the_attention_projections_and_no_mlp_weight(
        one_chip):
    """Qwen3-1.7B: the decode program reads ``q_proj`` / ``k_proj`` /
    ``v_proj`` ``[28, 2048, out]`` contraction-minor (a layer's slice
    then lands in fast memory as the matmul reads it) and the three MLP
    stacks, the embedding and the head as they come."""
    name = "qwen3-1.7b-serve"
    _programs_of(one_chip, name)
    orders = _CHOSEN[name]
    for leaf in ("q_proj", "k_proj", "v_proj"):
        assert orders["layers"][leaf] == (0, 2, 1), leaf
    moved = [order for order in jax.tree.leaves(
        orders, is_leaf=lambda x: isinstance(x, tuple)) if order]
    assert len(moved) == 3      # no MLP stack, not the embedding, no norm
    for leaf in ("gate_proj", "up_proj", "down_proj", "o_proj"):
        assert orders["layers"][leaf] == (), leaf


def test_decode_program_reserves_no_second_pool(serving_programs):
    decode, _prefill, pool_shape = serving_programs
    one_pool = 2 * math.prod(pool_shape)
    # a tenth of a pool, or the 16 MB of ordinary scratch a step has
    # (Qwen3-Next's pool of three layers x two heads is 76 MB in all)
    assert decode.memory_analysis().temp_size_in_bytes < max(
        one_pool // 10, 16 * 2**20)
    assert decode.memory_analysis().alias_size_in_bytes >= 2 * one_pool


def test_decode_kernel_is_still_the_one_4d_call(serving_programs):
    """`serve_paged_attn_roofline` finds the kernel by its 4-D bf16
    result: the write's result is the 5-D pool, an expert matmul's 2-D."""
    decode, prefill, _ = serving_programs
    four_d = re.compile(r"= bf16\[\d+,\d+,\d+,\d+\]\S* custom-call")
    calls = [c for c in _mosaic_calls(decode.as_text()) if four_d.search(c)]
    assert len(calls) == 1 and _named(calls, "paged_decode"), calls
    assert not [c for c in _mosaic_calls(prefill.as_text())
                if four_d.search(c)]


# ``memory_analysis().temp_size_in_bytes`` of each prefill program at
# the parent of PR 36 (8574b90), whose head multiplied every row of the
# buffer, and the room the counter has over it (AOT, PR 36; it reads
# 2.594e9, 2.561e9, 3.094e9 and 1.70788e9 now). OLMoE's peak never held
# its 1.65 GB of logits (its scores do: f32[16,16,1024,1536] and their
# bf16 copy, 2.42 GB): the buffer assignment's heap peak is the parent's
# to 4 KB (9,622,502,448 -> 9,622,506,624 B), its allocations 126 KB
# smaller, and this counter reads 2.0 % MORE, so it is held to 2.5 %.
# The two delta-rule families' program is ONE row since PR 53 (a row
# names its slot): held to what it reads now, 131,150,336 and
# 106,652,160 B (AOT, PR 53), with 5 % of room; the full ``(16, 512)``
# program's scratch was 3.493e9 and 1.708e9.
_PREFILL_TEMP_WITH_EVERY_ROW_S_LOGITS = {
    "qwen3-1.7b-serve": (8_973_132_288, 1.0),
    "olmoe-1b-7b-serve": (2_511_168_512, 1.025),
    "olmo-hybrid-7b-serve": (131_150_336, 1.05),
    "qwen3-next-80b-a3b-serve": (106_652_160, 1.05),
}
_ARRAY = re.compile(r"\b(?:pred|[a-z]+\d+)\[([\d,]+)\]")


def _prefill_rows(cfg, slots):
    """The rows of a configuration's largest prefill program: every
    slot, or the one row of a family whose rows name their slots."""
    from scaletorch_tpu.inference.decode import rows_name_slots

    return 1 if rows_name_slots(cfg) else slots


def test_prefill_program_runs_the_head_on_the_sampled_from_rows_only(
        request, serving_programs, serving_cfgs):
    """The prefill step names one row a slot (``logit_rows``) and the
    forward takes it before the final norm and the head: no array of
    ``rows x prefill_len x vocab`` elements, of any type or layout, is
    left in the compiled program (Qwen3-1.7B's was 4.98 GB in bf16, five
    instructions of it), the logits it does hold are ``[rows, vocab]``
    (the decode step's ``[slots, vocab]``; one row where a row names
    its slot), and the program's scratch is smaller for it."""
    name = request.node.callspec.params["serving_programs"]
    _, prefill, _ = serving_programs
    with open(os.path.join(REPO, "benchmarks", "configs",
                           name + ".json")) as f:
        config = json.load(f)
    serve = config["serve"]
    slots, vocab = serve["max_slots"], config["vocab_size"]
    rows = _prefill_rows(serving_cfgs[name], slots)
    text = prefill.as_text()
    sizes = {dims: math.prod(map(int, dims.split(",")))
             for dims in set(_ARRAY.findall(text))}
    assert not [d for d, n in sizes.items()
                if n in (slots * serve["prefill_len"] * vocab,
                         rows * serve["prefill_len"] * vocab)], name
    assert f"f32[{rows},{vocab}]" in text       # last_logits
    temp = prefill.memory_analysis().temp_size_in_bytes
    before, room = _PREFILL_TEMP_WITH_EVERY_ROW_S_LOGITS[name]
    assert temp < before * room, (
        f"{name}: prefill scratch {temp:,} B, not under the {before:,} B "
        f"(x {room}) of the program that multiplied every row by the head")


def test_the_listed_prefill_shapes_compile_at_their_own_size(
        request, one_chip, serving_programs):
    """The largest shape of an engine's list is the fixture's program.
    Where the cache is addressed by page (Qwen3-1.7B, OLMoE) the list
    starts with one row of half the buffer: compiled for the v5e at
    published widths its buffer is the call's ``[1, 512]``, no operand
    has the full buffer's shape, no array of ``16 x 1024 x vocab``
    elements (or of ``512 x vocab``: one row is sampled from) exists,
    and its scratch is under a sixteenth of the full program's, which
    holds 16 x 1024 rows of every layer's activations and scores. The
    delta-rule families, whose rows name their slots, list ONE row of
    the whole length and nothing else: the buffer is ``[1, 512]``, no
    operand has sixteen rows of it, one row is sampled from."""
    from scaletorch_tpu.inference.decode import prefill_shapes
    from scaletorch_tpu.inference.kv_cache import carries_state

    name = request.node.callspec.params["serving_programs"]
    with open(os.path.join(REPO, "benchmarks", "configs",
                           name + ".json")) as f:
        config = json.load(f)
    serve, vocab = config["serve"], config["vocab_size"]
    slots, length = serve["max_slots"], serve["prefill_len"]
    *shorter, top = prefill_shapes(slots, length)
    assert top == (slots, length) and len(shorter) <= 6
    _, full, _ = serving_programs
    cfg = request.getfixturevalue("serving_cfgs")[name]
    if carries_state(cfg):
        assert _prefill_rows(cfg, slots) == 1
        text = full.as_text()
        assert f"s32[1,{length}]" in text, name
        assert f"s32[{slots},{length}]" not in text, name
        assert f"f32[1,{vocab}]" in text            # last_logits
        assert f"f32[{slots},{vocab}]" not in text
        return
    assert shorter == [(1, 512)]
    for rows, rung in shorter:
        _, program, _ = _programs_of(one_chip, name, (rows, rung))
        text = program.as_text()
        assert f"s32[{rows},{rung}]" in text, (name, rows, rung)
        assert f"s32[{slots},{length}]" not in text, (name, rows, rung)
        sizes = {math.prod(map(int, dims.split(",")))
                 for dims in set(_ARRAY.findall(text))}
        assert slots * length * vocab not in sizes
        assert rows * rung * vocab not in sizes
        assert f"f32[{rows},{vocab}]" in text       # last_logits
        temp = program.memory_analysis().temp_size_in_bytes
        full_temp = full.memory_analysis().temp_size_in_bytes
        assert temp < full_temp // 16, (name, rows, rung, temp, full_temp)


def _flash_forwards(text):
    """The flash forward's calls in a compiled program, as the trace
    reader names them."""
    return [n for n in _short_names(text)
            if n.startswith("flash_fwd") and "tpu_custom_call" in n]


@pytest.mark.parametrize("shape", [(1, 512), (16, 1024)],
                         ids=["one-row", "full"])
@pytest.mark.parametrize("name", ["qwen3-1.7b-serve", "olmoe-1b-7b-serve"])
def test_a_prefix_sharing_prefill_program_chooses_its_attention_on_the_device(
        one_chip, name, shape):
    """Where a prefix may lie in the pool the program holds both
    attentions and ONE ``conditional`` between them in the layer loop's
    body (the predicate is the call's ``starts``): the flash forward
    over the call's own rows, a TUPLE result ``(bf16 4-D, f32)`` (so
    ``serve_paged_attn_roofline``, which takes any Mosaic call with one
    4-D bf16 result for the decode kernel, does not count it), and the
    gather's score array over the whole cache, as the parent had it."""
    rows, length = shape
    _, prefill, _ = _programs_of(
        one_chip, name, None if shape == (16, 1024) else shape)
    text = prefill.as_text()
    assert len(re.findall(r" conditional\(", text)) == 1
    flash = _flash_forwards(text)
    assert len(flash) == 1 and flash[0].endswith(
        f"(bf16[{rows},16,{length},128], f32[{rows},16,1,{length}])"), flash
    scores = rows * 16 * length * 1536
    assert [dims for dims in set(_ARRAY.findall(text))
            if math.prod(map(int, dims.split(","))) == scores]


@pytest.mark.parametrize("name,heads,width", [
    ("olmo-hybrid-7b-serve", 30, 128),
    ("qwen3-next-80b-a3b-serve", 16, 256)])
def test_a_family_without_prefixes_holds_no_scores_over_the_cache(
        one_chip, name, heads, width):
    """``starts`` is 0 by construction there, so the choice is static:
    no ``conditional``, one flash forward in the text (the scanned
    period's one full-attention layer) over the program's ONE row, a
    TUPLE result as in every prefill program (so
    ``serve_paged_attn_roofline``, which takes a Mosaic call with one
    4-D bf16 result for the decode kernel, does not count it), and no
    array of ``heads x 512 x 1536`` elements a row of any type (the
    ``f32[16,30,512,1536]`` of PR 51's full-shape program was 1.5 GB a
    layer)."""
    _, prefill, _ = _programs_of(one_chip, name)
    text = prefill.as_text()
    assert " conditional(" not in text
    flash = _flash_forwards(text)
    assert len(flash) == 1 and flash[0].endswith(
        f"(bf16[1,{heads},512,{width}], f32[1,{heads},1,512])"), flash
    sizes = {dims: math.prod(map(int, dims.split(",")))
             for dims in set(_ARRAY.findall(text))}
    # (a row's element count is an expert stack's in qwen3-next: by shape)
    assert not [d for d, n in sizes.items()
                if n == 16 * heads * 512 * 1536 or d.endswith(",512,1536")]


@pytest.mark.parametrize("name,state,tail", [
    ("olmo-hybrid-7b-serve", "f32[12,16,30,96,192]", "bf16[12,16,3,11520]"),
    ("qwen3-next-80b-a3b-serve", "f32[9,16,32,128,128]",
     "bf16[9,16,3,8192]")])
def test_the_one_row_prefill_program_holds_no_copy_of_the_state(
        one_chip, name, state, tail):
    """A row names its slot (PR 53): the ``(1, 512)`` program of the two
    delta-rule families runs its recurrence on ``[layers, 1, ...]`` of
    zeros and writes the row's final state and tail at its slot id. In
    the program compiled for the v5e the whole state (and the whole
    tail) is returned, beside plumbing, by ONE operation each, the
    dynamic-update-slice (fused with its select, or alone) that writes
    the slot's ``[layers, 1, ...]`` window in place into the donated
    buffer (XLA turns the one-index scatter into it; every donated byte
    is aliased), no operation returns a layer of either, the scan's
    carry is the one-row state, and the program's whole scratch is
    under half of the state: nowhere is there room for a copy of it."""
    _, prefill, _ = _programs_of(one_chip, name)
    text = prefill.as_text()
    dims = {buf: [int(d) for d in buf[buf.index("[") + 1:-1].split(",")]
            for buf in (state, tail)}
    for buf in (state, tail):
        whole = [(op, line) for op, line in _top_level(text, buf)
                 if op not in _PLUMBING]
        assert [op for op, _ in whole] in (
            ["fusion"], ["dynamic-update-slice"]), whole
        layer = buf.replace(f"[{dims[buf][0]},", "[")
        assert not [x for x in _top_level(text, layer)
                    if x[0] not in _PLUMBING], f"a layer of {buf} is copied"
    assert re.search(r"ROOT %\S+ = " + re.escape(state)
                     + r"\S* dynamic-update-slice\(", text), (
        "the state's write is no dynamic-update-slice")
    one_row = state.replace(f",{dims[state][1]},", ",1,", 1)
    assert one_row in text                      # the scan's carry
    memory = prefill.memory_analysis()
    state_bytes = math.prod(dims[state]) * 4
    assert memory.temp_size_in_bytes < state_bytes // 2
    assert memory.alias_size_in_bytes >= state_bytes


def test_no_decode_program_holds_a_choice_or_a_flash_call(serving_programs):
    """A call of one row reads the pool through the decode kernel, as
    it did: the choice exists for S > 1 alone."""
    decode, _, _ = serving_programs
    text = decode.as_text()
    assert " conditional(" not in text and not _flash_forwards(text)


def _top_level(text, wanted):
    """Instructions outside fused computations (what is scheduled as an
    operation of its own: a fusion, a copy, a call) whose result type
    holds ``wanted``."""
    found, fused = [], False
    for line in text.splitlines():
        if line.startswith(("ENTRY", "%")) and line.rstrip().endswith("{"):
            fused = line.startswith("%fused_computation")
        m = _INSTRUCTION.match(line)
        if m and not fused and wanted in m["type"]:
            found.append((m["op"], line.strip()[:160]))
    return found


def test_the_recurrent_state_is_updated_in_place_and_no_weight_is_moved(
        one_chip):
    """Olmo-Hybrid's decode step at the cell's shapes. The state
    ``f32[12,16,30,96,192]`` is the layer loop's carry: beside plumbing,
    the only operations that return it are the select +
    dynamic-update-slice fusions that write one layer of it in place
    (one per linear layer of a period), and no operation returns a copy
    of one layer. No operation returns a whole weight stack or a
    period's slice of one: indexed ``[period][j]`` out of scanned
    operands, XLA copied every linear layer's weights out once more a
    step (6 GB written and read back), and split into heads the gate's
    projection was re-laid whole (0.53 GB), both found here before the
    first chip call (PERF.md, PR 32). What is left of scratch is a
    hundredth of the state."""
    decode, prefill, _ = _programs_of(one_chip, "olmo-hybrid-7b-serve")
    text = decode.as_text()
    state = [op for op, _ in _top_level(text, "f32[12,16,30,96,192]")
             if op not in _PLUMBING]
    assert state == ["fusion"] * 3, state
    assert not [x for x in _top_level(text, "f32[16,30,96,192]")
                if x[0] not in _PLUMBING], "a layer of the state is copied"
    # a layer's matrices are 22 to 85 MB; the gates' [3840, 30] columns
    # (0.2 MB a layer) may be fetched ahead as XLA likes
    for width in (2880, 5760, 11008, 3840):
        for stack in (f"bf16[4,3,3840,{width}]", f"bf16[3,3840,{width}]",
                      f"bf16[4,3,{width},3840]", f"bf16[3,{width},3840]"):
            moved = [x for x in _top_level(text, stack)
                     if x[0] not in _PLUMBING]
            assert not moved, moved[:3]
    state_bytes = 12 * 16 * 30 * 96 * 192 * 4
    memory = decode.memory_analysis()
    assert memory.temp_size_in_bytes < state_bytes // 10
    assert memory.alias_size_in_bytes >= state_bytes
    # the prefill call's scan inverts its triangular systems by matrix
    # products: the solver's custom call took a quarter of the call
    assert "InvertDiagBlocks" not in prefill.as_text()


def _reader_patterns(name):
    with open(os.path.join(REPO, "benchmarks", "metrics",
                           name + ".json")) as f:
        return [p for term in json.load(f)["reducer"]["terms"]
                for p in term["patterns"]]


def _short_names(text):
    """Every instruction of a compiled program as the trace reader names
    an ``XLA Ops`` event."""
    return [short_name(re.sub(r"^\s*(ROOT )?", "", line))
            for line in text.splitlines() if _INSTRUCTION.match(line)]


def test_qwen3_next_steps_are_what_the_new_readers_look_for(one_chip):
    """Qwen3-Next's step programs at the cell's shapes (a 512-wide
    router over 128 held experts, 16 slots). The decode step's grouped
    matmuls are 12 Mosaic calls a period (gate, up, down of 4 layers)
    over the WHOLE expert stack as 1,536 groups, told by their results
    ``bf16[256, 512]`` / ``bf16[256, 2048]`` (160 sorted rows padded to
    two row tiles), which is what
    ``serve_qwen3_next_expert_mlp_roofline`` matches and the prefill's
    81,920-row calls are not; no operation returns the expert stack or a
    layer of it (a copy of 0.8 GB a layer: PR 27). The state
    ``f32[9,16,32,128,128]`` is the loop's carry, written in place by
    one select + dynamic-update-slice fusion a linear layer, beside the
    fusion that returns the pair of ``[16,32,128]`` sums:
    ``serve_qwen3_next_gdn_state_update_roofline`` matches exactly those
    two a layer."""
    decode, prefill, pool_shape = _programs_of(
        one_chip, "qwen3-next-80b-a3b-serve")
    assert pool_shape == (3, 16 * 96 + 1, 2, 16, 256)
    text = decode.as_text()
    names = _short_names(text)

    gmm = [c for c in _mosaic_calls(text) if re.search(r"%gmm\S* = ", c)]
    assert len(gmm) == 12, gmm
    experts = _reader_patterns("serve_qwen3_next_expert_mlp_roofline")
    found = [n for n in names if any(re.search(p, n) for p in experts)]
    assert len(found) == 12 and all(n.startswith("gmm") for n in found), found
    assert sorted(n.rsplit(" | ", 1)[1] for n in found) == (
        ["bf16[256,2048]"] * 4 + ["bf16[256,512]"] * 8)
    assert not [n for n in _short_names(prefill.as_text())
                if any(re.search(p, n) for p in experts)]
    for stack in ("bf16[12,128,2048,512]", "bf16[128,2048,512]",
                  "bf16[1536,2048,512]", "bf16[12,128,512,2048]",
                  "bf16[128,512,2048]", "bf16[1536,512,2048]"):
        moved = [x for x in _top_level(text, stack)
                 if x[0] not in _PLUMBING | {"bitcast"}
                 and "tpu_custom_call" not in x[1]]
        assert not moved, moved[:3]

    state = [op for op, _ in _top_level(text, "f32[9,16,32,128,128]")
             if op not in _PLUMBING]
    assert state == ["fusion"] * 3, state
    assert not [x for x in _top_level(text, "f32[16,32,128,128]")
                if x[0] not in _PLUMBING], "a layer of the state is copied"
    update = _reader_patterns("serve_qwen3_next_gdn_state_update_roofline")
    found = [n for n in names if any(re.search(p, n) for p in update)]
    assert len(found) == 6, found            # two a linear layer of a period
    memory = decode.memory_analysis()
    state_bytes = 9 * 16 * 32 * 128 * 128 * 4
    assert memory.temp_size_in_bytes < state_bytes // 10
    assert memory.alias_size_in_bytes >= state_bytes
    # the one-row prefill program's scratch beside 11.3 GB of arguments
    assert prefill.memory_analysis().temp_size_in_bytes < 0.12e9
    assert "InvertDiagBlocks" not in prefill.as_text()


def test_decode_kernel_with_a_window_is_still_the_one_4d_call(one_chip):
    """trinity-mini-serve's window layers: 32 query heads over 4 K/V
    heads of 128, 8 slots, a table of 216 logical pages over a ring of
    129, the rings' buffer ``[12, 1 + 8 x 129, ...]`` read at a layer
    index. Told the window, the kernel is still one Mosaic call with the
    one 4-D bf16 result ``serve_trinity_paged_attn_roofline`` tells it
    by: the first page and the band are arithmetic on the
    scalar-prefetched position."""
    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    rings = arg((12, 1 + 8 * 129, 4, 16, 128), jnp.bfloat16)
    text = jax.jit(
        lambda q, k, v, tables, pos, layer: pallas_paged_decode_attention(
            q, k, v, tables, pos, layer=layer, window=2048)
    ).lower(
        arg((8, 32, 128), jnp.bfloat16), rings, rings,
        arg((8, 216), jnp.int32), arg((8,), jnp.int32), arg((), jnp.int32),
    ).compile().as_text()
    calls = _mosaic_calls(text)
    assert len(calls) == 1, calls
    assert re.search(r"%paged_decode\S* = bf16\[8,4,8,128\]", calls[0]), calls


def test_trinity_steps_are_what_the_new_readers_look_for(one_chip):
    """Trinity-Mini's two step programs at the cell's shapes (8 slots x
    3,072-row prompts, ``max_seq`` 3,456, 12 window + 4 full layers, a
    128-wide router over 32 held experts). **Prefill attention runs in
    key blocks**: no array of ``slots x heads x prefill_len x max_seq``
    (or ``x prefill_len``) elements exists, of any type (the gather
    path's ``f32[8,32,3072,3456]`` would be 10.9 GB); the attention is
    the flash forward, 8 calls in the text (a period unrolled + the
    scanned period's), told by the ``(bf16 4-D, f32)`` pair
    ``serve_trinity_prefill_attn_roofline`` matches. The decode step:
    8 ``paged_decode`` calls in the text with the one 4-D result (what
    ``serve_trinity_paged_attn_roofline`` matches, and nothing else
    does), 18 decode-shaped ``gmm`` calls ``bf16[128, 1024 | 2048]``
    (``serve_trinity_expert_mlp_roofline``; the prefill's run 196,608
    rows and are not matched). Neither program has an operation that
    returns the page pool, the rings, a layer of either, or the expert
    stack; both fit the chip: arguments + scratch under 11 GB."""
    decode, prefill, pool_shape = _programs_of(one_chip, "trinity-mini-serve")
    slots, heads, rows, max_seq = 8, 32, 3072, 3456
    assert pool_shape == (4, slots * 216 + 1, 4, 16, 128)
    ring_shape = (12, slots * 129 + 1, 4, 16, 128)
    texts = {"decode": decode.as_text(), "prefill": prefill.as_text()}
    for name, text in texts.items():
        assert _pool_shaped(text, pool_shape) == {}, name
        assert _pool_shaped(text, ring_shape) == {}, name
        writes = _named(_mosaic_calls(text), "paged_write")
        # K and V: three window layers and one full layer of the
        # unrolled period, and of the scanned one
        assert len(writes) == 2 * (3 + 1) * 2, (name, len(writes))
        for stack in ("bf16[14,32,2048,1024]", "bf16[32,2048,1024]",
                      "bf16[448,2048,1024]", "bf16[14,32,1024,2048]",
                      "bf16[32,1024,2048]", "bf16[448,1024,2048]"):
            moved = [x for x in _top_level(text, stack)
                     if x[0] not in _PLUMBING | {"bitcast"}
                     and "tpu_custom_call" not in x[1]]
            assert not moved, (name, moved[:3])

    sizes = {dims: math.prod(map(int, dims.split(",")))
             for dims in set(_ARRAY.findall(texts["prefill"]))}
    scores = {slots * heads * rows * max_seq, slots * heads * rows * rows}
    assert not [d for d, n in sizes.items() if n in scores]
    assert f"f32[{slots},50048]" in texts["prefill"]      # last_logits

    patterns = {name: _reader_patterns(name) for name in (
        "serve_trinity_paged_attn_roofline",
        "serve_trinity_expert_mlp_roofline",
        "serve_trinity_prefill_attn_roofline")}

    def found(program, reader):
        return [n for n in _short_names(texts[program])
                if any(re.search(p, n) for p in patterns[reader])]

    attn = found("decode", "serve_trinity_paged_attn_roofline")
    assert len(attn) == 8 and all(
        n.startswith("paged_decode") and n.endswith("bf16[8,4,8,128]")
        for n in attn), attn
    experts = found("decode", "serve_trinity_expert_mlp_roofline")
    assert len(experts) == 18 and all(n.startswith("gmm") for n in experts)
    assert sorted(n.rsplit(" | ", 1)[1] for n in experts) == (
        ["bf16[128,1024]"] * 12 + ["bf16[128,2048]"] * 6)
    flash = found("prefill", "serve_trinity_prefill_attn_roofline")
    assert len(flash) == 8 and all(
        n.startswith("flash_fwd")
        and n.endswith("(bf16[8,32,3072,128], f32[8,32,1,3072])")
        for n in flash), flash
    assert not found("prefill", "serve_trinity_paged_attn_roofline")
    assert not found("prefill", "serve_trinity_expert_mlp_roofline")
    assert not found("decode", "serve_trinity_prefill_attn_roofline")

    cache_bytes = 2 * 2 * (math.prod(pool_shape) + math.prod(ring_shape))
    for name, program, scratch in (("decode", decode, 0.5e9),
                                   ("prefill", prefill, 2.5e9)):
        memory = program.memory_analysis()
        assert memory.alias_size_in_bytes >= cache_bytes, name
        assert memory.temp_size_in_bytes < scratch, name
        assert (memory.argument_size_in_bytes
                + memory.temp_size_in_bytes) < 11e9, name


def test_jamba_steps_are_what_the_new_readers_look_for(one_chip):
    """Jamba2-3B's two step programs at the cell's shapes (8 slots x
    3,072-row prompts, 26 Mamba layers + 2 attention layers on ONE K/V
    head). **The scan never materialises a state axis over the prompt**:
    no array of ``slots x prefill_len x channels x N`` elements exists,
    of any type or layout (``exp(dt A)`` written the obvious way is
    ``f32[8,3072,5120,16]``, 8.05 GB a layer); the scan is the Mosaic
    kernel, 2 calls in the text (the two runs of Mamba layers in a
    period), named ``ssm_scan_fwd`` and returning ``(y [8, 3072, 5120],
    the state [8, 16, 40, 128])`` with NO copy around them. Prefill attention is the flash forward on 20 query
    heads over one K/V head; the decode step's is ``paged_decode`` with
    the ``[8, 1, 20, 128]`` tile: the lax fallback is not taken for a
    head of 128. The state ``f32[26,8,16,40,128]`` is the layer loops'
    carry, written in place by one select + dynamic-update-slice fusion
    a Mamba run, never copied. The readers of the cell's new metrics
    find the kernel and the decode update by these names, and both
    programs fit the chip."""
    decode, prefill, pool_shape = _programs_of(one_chip, "jamba2-3b-serve")
    slots, rows, channels, n = 8, 3072, 5120, 16
    assert pool_shape == (2, slots * 216 + 1, 1, 16, 128)
    texts = {"decode": decode.as_text(), "prefill": prefill.as_text()}
    sizes = {dims: math.prod(map(int, dims.split(",")))
             for dims in set(_ARRAY.findall(texts["prefill"]))}
    assert not [d for d, count in sizes.items()
                if count >= slots * rows * channels * n]
    calls = _mosaic_calls(texts["prefill"])
    scans = _named(calls, "ssm_scan_fwd")
    assert len(scans) == 2, calls
    for call in scans:
        assert re.search(
            r"= \(f32\[8,3072,5120\]\S*, f32\[8,16,40,128\]\S*\) "
            r"custom-call\(", call), call
    flash = _named(calls, "flash_fwd")
    assert len(flash) == 1 and re.search(
        r"= \(bf16\[8,20,3072,128\]\S*, f32\[8,20,1,3072\]", flash[0])
    assert len(_named(calls, "paged_write")) == 2
    assert not _named(calls, "paged_decode")
    # u, dt and y go in and out as the projections hold them: nothing of
    # their size is copied, transposed or re-laid around the call (as
    # [.., 40, 128] views each was: 4.6 ms a layer on the chip)
    for shape in ("f32[8,3072,5120]", "f32[3072,8,40,128]",
                  "f32[8,3072,40,128]"):
        assert not [x for x in _top_level(texts["prefill"], shape)
                    if x[0] in ("copy", "transpose", "reshape")], shape
    calls = _mosaic_calls(texts["decode"])
    attn = _named(calls, "paged_decode")
    assert len(attn) == 1 and re.search(
        r"= bf16\[8,1,20,128\]", attn[0]), calls
    assert len(_named(calls, "paged_write")) == 2
    assert not _named(calls, "ssm_scan_fwd")
    for name, text in texts.items():
        state = [op for op, _ in _top_level(text, "f32[26,8,16,40,128]")
                 if op not in _PLUMBING]
        assert state == ["fusion"] * 2, (name, state)

    with open(os.path.join(REPO, "benchmarks", "metrics",
                           "serve_jamba_ssm_scan_share.json")) as f:
        share = json.load(f)["reducer"]

    def scan_ops(program):
        """What the share's reader would count of a program's
        operations: fusions and custom calls are what the trace's ``XLA
        Ops`` line holds of them."""
        return [name for name in _short_names(texts[program])
                if re.search(r" \| (fusion|custom-call) \| ", name)
                and any(re.search(p, name) for p in share["patterns"])
                and not any(re.search(p, name) for p in share["exclude"])]

    decode_ops = scan_ops("decode")
    # a Mamba run of the decode step: the fusion that reads the state
    # for y, and the in-place write
    assert sum(name.endswith("| kLoop | f32[8,40,128]")
               for name in decode_ops) == 2, decode_ops
    assert sum(name.endswith("f32[26,8,16,40,128]")
               for name in decode_ops) == 2, decode_ops
    assert not [name for name in decode_ops if "bf16" in name], decode_ops
    kernel = [name for name in _short_names(texts["prefill"])
              if any(re.search(p, name) for p in _reader_patterns(
                  "serve_jamba_ssm_scan_roofline"))]
    assert len(kernel) == 2 and all(
        name.startswith("ssm_scan_fwd") for name in kernel), kernel
    assert set(kernel) <= set(scan_ops("prefill"))
    assert not [name for name in _short_names(texts["decode"])
                if any(re.search(p, name) for p in _reader_patterns(
                    "serve_jamba_ssm_scan_roofline"))]

    cache_bytes = (2 * 2 * math.prod(pool_shape) + 26 * 8 * 16 * 5120 * 4
                   + 26 * 8 * 3 * 5120 * 2)
    for name, program, scratch in (("decode", decode, 16 * 2**20),
                                   ("prefill", prefill, 4e9)):
        memory = program.memory_analysis()
        assert memory.alias_size_in_bytes >= cache_bytes, name
        assert memory.temp_size_in_bytes < scratch, name
        assert (memory.argument_size_in_bytes
                + memory.temp_size_in_bytes) < 11e9, name


def test_pangu_steps_are_what_the_new_readers_look_for(one_chip):
    """openPangu-Ultra-MoE's two step programs at the cell's shapes (8
    slots x 3,072-row prompts, ``max_seq`` 3,456, 6 latent-attention
    layers of 128 heads, a 256-wide router over 8 held experts). **The
    decode step holds no expanded key or value per cached token and no
    gather of the pool**: its attention is ``latent_decode``, 2 calls in
    the text (the unrolled dense layer's + the scanned sparse layers'),
    the one 3-D Mosaic result ``bf16[8,128,512]``, and no array has a
    cached token's 128 heads x 128 (or 192, or 256) numbers for every
    position of a slot. The cached row is 640 wide (512 + 64 padded to
    whole tiles), written by one ``paged_write`` a layer. **The prefill
    call attends in key blocks, four groups of 32 heads one after the
    other, keys 192 and values 128 wide** (the flash
    forward's ``(bf16[8,32,3072,128], f32)``, the head count
    ``costs/pangu_ultra_moe.prefill_head_groups`` charges: no ``slots x
    heads x prefill_len x max_seq`` scores, which would be 43 GB, and no
    array of all 128 heads' expanded queries, 1.21 GB) **and
    bounds its sorted rows**: no array of ``slots x prefill_len x 8``
    rows x 7,680 (3.02 GB a copy; its blocks are ``[16384, 7680]``).
    Both fit the chip."""
    decode, prefill, pool_shape = _programs_of(
        one_chip, "openpangu-ultra-moe-718b-serve")
    slots, heads, rows, max_seq = 8, 128, 3072, 3456
    assert pool_shape == (6, slots * 216 + 1, 1, 16, 640)
    texts = {"decode": decode.as_text(), "prefill": prefill.as_text()}
    for name, text in texts.items():
        assert _pool_shaped(text, pool_shape) == {}, name
        writes = _named(_mosaic_calls(text), "paged_write")
        assert len(writes) == 2, (name, len(writes))    # ONE row a token
        for stack in ("bf16[5,8,7680,2048]", "bf16[8,7680,2048]",
                      "bf16[40,7680,2048]", "bf16[5,8,2048,7680]",
                      "bf16[8,2048,7680]", "bf16[40,2048,7680]"):
            moved = [x for x in _top_level(text, stack)
                     if x[0] not in _PLUMBING | {"bitcast"}
                     and "tpu_custom_call" not in x[1]]
            assert not moved, (name, moved[:3])

    def sizes(text):
        return {dims: math.prod(map(int, dims.split(",")))
                for dims in set(_ARRAY.findall(text))}

    # decode: nothing per cached token and head, nothing of a slot's
    # whole gathered context
    per_token_and_head = {slots * max_seq * heads * w
                          for w in (128, 192, 256, 320, 512, 576, 640)}
    gathered = {slots * max_seq * 640, slots * 216 * 16 * 640}
    found = sizes(texts["decode"])
    assert not [d for d, n in found.items()
                if n in per_token_and_head | gathered]
    assert f"f32[{slots},19200]" in texts["decode"]
    # prefill: no scores over the whole buffer, no unbounded sorted rows
    found = sizes(texts["prefill"])
    scores = {slots * heads * rows * max_seq, slots * heads * rows * rows}
    every_head = {slots * heads * rows * w for w in (192, 256)}
    assert not [d for d, n in found.items() if n in scores | every_head]
    unbounded = slots * rows * 8 * 7680
    assert not [d for d, n in found.items() if n >= unbounded]
    assert "bf16[16384,7680]" in texts["prefill"]       # a block's rows
    assert f"f32[{slots},19200]" in texts["prefill"]    # last_logits

    patterns = {name: _reader_patterns(name) for name in (
        "serve_pangu_latent_attn_mxu_roofline",
        "serve_pangu_latent_attn_hbm_roofline",
        "serve_pangu_expert_mlp_roofline",
        "serve_pangu_prefill_attn_roofline")}

    def matched(program, reader):
        return [n for n in _short_names(texts[program])
                if any(re.search(p, n) for p in patterns[reader])]

    for reader in ("serve_pangu_latent_attn_mxu_roofline",
                   "serve_pangu_latent_attn_hbm_roofline"):
        attn = matched("decode", reader)
        assert len(attn) == 2 and all(
            n.startswith("latent_decode")
            and n.endswith("bf16[8,128,512]") for n in attn), attn
        assert not matched("prefill", reader)
    experts = matched("decode", "serve_pangu_expert_mlp_roofline")
    assert len(experts) == 3 and all(n.startswith("gmm") for n in experts)
    assert sorted(n.rsplit(" | ", 1)[1] for n in experts) == (
        ["bf16[128,2048]"] * 2 + ["bf16[128,7680]"])
    assert not matched("prefill", "serve_pangu_expert_mlp_roofline")
    from benchmarks.costs import pangu_ultra_moe as costs

    config = _serving_model("openpangu-ultra-moe-718b-serve")[0]
    group = heads // costs.prefill_head_groups(config)
    flash = matched("prefill", "serve_pangu_prefill_attn_roofline")
    assert group == 32 and len(flash) == 2 and all(
        n.startswith("flash_fwd") and n.endswith(
            f"(bf16[8,{group},3072,128], f32[8,{group},1,3072])")
        for n in flash), flash
    assert not matched("decode", "serve_pangu_prefill_attn_roofline")
    assert not _named(_mosaic_calls(texts["decode"]), "paged_decode")

    cache_bytes = 2 * math.prod(pool_shape)
    chip = 15.75 * 2 ** 30
    for name, program, scratch in (("decode", decode, 0.1e9),
                                   ("prefill", prefill, 4.2e9)):
        memory = program.memory_analysis()
        assert memory.alias_size_in_bytes >= cache_bytes, name
        assert memory.temp_size_in_bytes < scratch, name
        assert (memory.argument_size_in_bytes
                + memory.temp_size_in_bytes) < 0.75 * chip, name


def test_kimi_steps_are_what_the_new_readers_look_for(one_chip):
    """Kimi-Linear's two step programs at the cell's shapes (32 slots,
    8,192-row prompts, ``max_seq`` 9,728; 6 KDA + 2 latent layers, a
    256-wide router over 64 held experts). **One cache, two kinds of
    memory**: the latent pool ``[2, 32 x 608 + 1, 1, 16, 640]`` is
    written by ONE ``paged_write`` a latent layer and returned by
    nothing else, and the state ``f32[6,32,32,128,128]`` is written in
    place (no copy returns it or a layer of it). **The decode step**
    reads the pool through ``latent_decode`` at 32 heads
    (``bf16[32,32,512]``, what ``serve_kimi_latent_attn_hbm_roofline``
    matches) and its experts through 21 ``gmm`` calls told by
    ``bf16[256,1024]`` / ``bf16[256,2304]`` (32 x 8 sorted rows:
    ``serve_kimi_expert_mlp_roofline``). **The prefill program is one
    row that names its slot**: ``s32[1,8192]`` tokens, its latent
    attention the flash forward over the row's 32 expanded heads (keys
    192, values 128 wide), no scores ``[heads, 8192, 8192]`` and none of
    the full shape's ``32 x 8192`` rows. Both fit the chip."""
    decode, prefill, pool_shape = _programs_of(
        one_chip, "kimi-linear-48b-a3b-serve")
    slots, rows = 32, 8192
    assert pool_shape == (2, slots * 608 + 1, 1, 16, 640)
    texts = {"decode": decode.as_text(), "prefill": prefill.as_text()}
    for name, text in texts.items():
        assert _pool_shaped(text, pool_shape) == {}, name
        writes = _named(_mosaic_calls(text), "paged_write")
        assert len(writes) == 2, (name, len(writes))   # one a latent layer
        for state in ("f32[6,32,32,128,128]", "f32[32,32,128,128]",
                      "f32[1,32,32,128,128]"):
            copies = [x for x in _top_level(text, state)
                      if x[0] in ("copy", "copy-start", "transpose")]
            assert not copies, (name, copies[:3])
        for stack in ("bf16[7,64,2304,1024]", "bf16[64,2304,1024]",
                      "bf16[448,2304,1024]", "bf16[7,64,1024,2304]",
                      "bf16[64,1024,2304]", "bf16[448,1024,2304]"):
            moved = [x for x in _top_level(text, stack)
                     if x[0] not in _PLUMBING | {"bitcast"}
                     and "tpu_custom_call" not in x[1]]
            assert not moved, (name, moved[:3])

    def sizes(text):
        return {dims: math.prod(map(int, dims.split(",")))
                for dims in set(_ARRAY.findall(text))}

    assert "s32[1,8192]" in texts["prefill"]
    found = sizes(texts["prefill"])
    scores = {32 * rows * rows, 32 * rows * 9728}
    full_shape = {slots * rows * 2304, slots * rows * 4096}
    assert not [d for d, n in found.items() if n in scores | full_shape]
    assert f"f32[{slots},40960]" in texts["decode"]

    patterns = {name: _reader_patterns(name) for name in (
        "serve_kimi_latent_attn_hbm_roofline",
        "serve_kimi_expert_mlp_roofline",
        "serve_kimi_kda_state_update_roofline")}

    def matched(program, reader):
        return [n for n in _short_names(texts[program])
                if any(re.search(p, n) for p in patterns[reader])]

    attn = matched("decode", "serve_kimi_latent_attn_hbm_roofline")
    assert len(attn) == 2 and all(
        n.startswith("latent_decode") and n.endswith("bf16[32,32,512]")
        for n in attn), attn
    assert not matched("prefill", "serve_kimi_latent_attn_hbm_roofline")
    experts = matched("decode", "serve_kimi_expert_mlp_roofline")
    assert experts and all(n.startswith("gmm") for n in experts)
    assert {n.rsplit(" | ", 1)[1] for n in experts} == {
        "bf16[256,1024]", "bf16[256,2304]"}
    assert not matched("prefill", "serve_kimi_expert_mlp_roofline")
    updates = matched("decode", "serve_kimi_kda_state_update_roofline")
    assert any("select_dynamic-update-slice_fusion" in n for n in updates)
    assert any("(f32[32,32,128], f32[32,32,128])" in n for n in updates)
    flash = _named(_mosaic_calls(texts["prefill"]), "flash_fwd")
    assert flash and all("bf16[1,32,8192,128]" in c for c in flash), flash
    assert not _named(_mosaic_calls(texts["decode"]), "paged_decode")

    cache_bytes = 2 * math.prod(pool_shape) + 6 * 32 * (
        32 * 128 * 128 * 4 + 3 * 12288 * 2)
    chip = 15.75 * 2 ** 30
    for name, program, scratch in (("decode", decode, 0.1e9),
                                   ("prefill", prefill, 2.4e9)):
        memory = program.memory_analysis()
        assert memory.alias_size_in_bytes >= cache_bytes, name
        assert memory.temp_size_in_bytes < scratch, name
        assert (memory.argument_size_in_bytes
                + memory.temp_size_in_bytes) < 0.75 * chip, name


@pytest.mark.parametrize("hkv,layers,pages,window", [
    (4, 2, 32 * 608 + 1, None), (8, 5, 32 * 9 + 1, 128)],
    ids=["full", "window-sink"])
def test_decode_kernel_compiles_at_two_widths_and_with_a_sink(
        one_chip, hkv, layers, pages, window):
    """mimo-v2-flash-serve's two calls alone: 64 query heads (padded to
    the stored key's 256) over 4 or 8 K/V heads, a K buffer 256 wide
    beside a V buffer of 128, the window layers' under a band of 128 and
    a float32 sink a head. One Mosaic call each whose one 4-D result has
    the VALUE's width."""
    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    sink = None if window is None else arg((64,), jnp.float32)
    text = jax.jit(
        lambda q, k, v, tables, pos, layer, sink: (
            pallas_paged_decode_attention(
                q, k, v, tables, pos, layer=layer, window=window,
                scale=192 ** -0.5, sink=sink))
    ).lower(
        arg((32, 64, 256), jnp.bfloat16),
        arg((layers, pages, hkv, 16, 256), jnp.bfloat16),
        arg((layers, pages, hkv, 16, 128), jnp.bfloat16),
        arg((32, 608), jnp.int32), arg((32,), jnp.int32),
        arg((), jnp.int32), sink,
    ).compile().as_text()
    calls = _mosaic_calls(text)
    assert len(calls) == 1, calls
    assert re.search(
        rf"%paged_decode\S* = bf16\[32,{hkv},{64 // hkv},128\]", calls[0]), calls


def _traced_digest(fn, *shapes):
    """sha256 of a function's traced program (a kernel's jaxpr, its grid
    and its operands; no source location is in it)."""
    return hashlib.sha256(str(jax.make_jaxpr(fn)(*(
        jax.ShapeDtypeStruct(shape, dt) for shape, dt in shapes
    ))).encode()).hexdigest()


def _decode_digest(slots, hq, hkv, d, max_pages, layers, window=None,
                   ring=None):
    pool = ((layers, 1 + slots * (ring or max_pages), hkv, 16, d),
            jnp.bfloat16)
    return _traced_digest(
        lambda q, k, v, t, p: pallas_paged_decode_attention(
            q, k, v, t, p, layer=jnp.int32(1), window=window),
        ((slots, hq, d), jnp.bfloat16), pool, pool,
        ((slots, max_pages), jnp.int32), ((slots,), jnp.int32))


def _flash_digest(b, hq, hkv, s, dk, dv, window=None):
    from scaletorch_tpu.ops.pallas.flash import flash_forward_with_lse

    return _traced_digest(
        lambda q, k, v: flash_forward_with_lse(
            q, k, v, causal=True, window=window),
        ((b, hq, s, dk), jnp.bfloat16), ((b, hkv, s, dk), jnp.bfloat16),
        ((b, hkv, s, dv), jnp.bfloat16))


def _flash_training_digest():
    def grads(q, k, v):
        return jax.grad(lambda q, k, v: pallas_flash_attention(
            q, k, v).astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    return _traced_digest(
        grads, ((1, 16, 8192, 128), jnp.bfloat16),
        ((1, 8, 8192, 128), jnp.bfloat16), ((1, 8, 8192, 128), jnp.bfloat16))


@pytest.mark.parametrize("digest,want", [
    (lambda: _decode_digest(16, 16, 8, 128, 96, 28),
     "046d94fb0dfc6142a1326c878c3d69afcb1d6c9b2e5d69e3d105efd4ed69a36f"),
    (lambda: _decode_digest(16, 16, 2, 256, 96, 12),
     "ba7e362e411dca4be7a27145e8976705f59d86e7a3afe02e486c93bfdb28e155"),
    (lambda: _decode_digest(8, 32, 4, 128, 216, 4),
     "0d72caf1a79f0f5a610f4aedaf3bbbe6bceef7b405b1c78502a285a691c36a73"),
    (lambda: _decode_digest(8, 32, 4, 128, 216, 12, window=2048, ring=129),
     "50dd7211c900f3421087126cd90a1fc511882ac33c99322b3b8d0f959ee9b184"),
    (_flash_training_digest,
     "e74c658a1a00225f889938675350843f602ddc170bccd408ee464ae149fab54a"),
    (lambda: _flash_digest(8, 32, 4, 3072, 128, 128, window=2048),
     "b38cb64712e0d11977e7af01c6820523ad3869c25db1c63ad9502649371e2dde"),
    (lambda: _flash_digest(8, 128, 128, 3072, 192, 128),
     "3bbf465cd93715673f249ffd8859cbdde974915a56df64eda4929b7f8f31d6c8"),
    (lambda: _flash_digest(1, 16, 8, 512, 128, 128),
     "550b2429ad94ca8d6e5b811ea603580c20c7971f73b724c36b9cef44820e8485"),
    # PR 62, computed at its parent `e199970`: the 8,192-token rows, the
    # only serving forwards long enough for the rule to have moved them
    (lambda: _flash_digest(1, 64, 4, 8192, 192, 128),
     "d11a365e000e0411a630ea6fb241202bc770304a3188e43f4dbaaf075cac487b"),
    (lambda: _flash_digest(1, 32, 32, 8192, 192, 128),
     "18733b9d3f97db2dcd0a5ef3f4ca3dbde4213b588d0ec7b57a01758a10b624ce"),
    (lambda: _flash_digest(1, 16, 8, 8192, 128, 128),
     "0d17ee40655e127933224a6a397f422069d362211fd77b9f5b2cc9a2e3aca671"),
], ids=["decode-longgen", "decode-qwen3-next", "decode-trinity-full",
        "decode-trinity-window", "flash-training-fwd-bwd",
        "flash-trinity-window", "flash-openpangu-192-128",
        "flash-longgen-row", "flash-mimo-full-row", "flash-kimi-linear-row",
        "flash-training-fwd"])
def test_callers_without_a_sink_at_one_width_trace_to_what_they_did(
        digest, want):
    """PR 59 gave the decode kernel and the flash forward a value width
    of their own and an optional sink. A caller with neither must get
    the program it had: each traced program below (the decode kernel at
    three cells' shapes and under Trinity-Mini's window, the flash
    forward and backward at the training cell's shape, the flash forward
    under Trinity-Mini's window, at openPangu's 192 / 128 heads and at
    the one-row call of the dense cells) hashes to what it hashed to at
    the parent (`7c85100`, computed there with the same functions). A PR
    that means to change one of these kernels measures the cells that
    run it and writes the new digests here. PR 62 did for the training
    cell's forward and backward: ``flash_dq`` runs in 1,024 x 512 blocks
    and ``flash_dkv`` in 1,024 x 1,024 there (``flash_blocks``), the
    forward and every serving caller in the blocks they had."""
    assert digest() == want


def test_the_latent_kernel_compiles_for_a_longer_table(one_chip):
    """``latent_decode`` alone at 128 heads over a 640-wide row with a
    table of 8,192 pages (131,072 positions, the published context): one
    Mosaic call, the pool an operand left in HBM."""
    from scaletorch_tpu.ops.pallas.paged_attention import (
        pallas_latent_decode_attention,
    )

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    text = jax.jit(lambda q, pool, tables, pos: (
        pallas_latent_decode_attention(
            q, pool, tables, pos, layer=jnp.int32(1), value_width=512,
            scale=192 ** -0.5))).lower(
        arg((2, 128, 640), jnp.bfloat16),
        arg((2, 16385, 1, 16, 640), jnp.bfloat16),
        arg((2, 8192), jnp.int32), arg((2,), jnp.int32)).compile().as_text()
    calls = _named(_mosaic_calls(text), "latent_decode")
    assert len(calls) == 1, calls


def _latent_kernel_digest(slots, heads, max_pages, layers):
    """sha256 of ``latent_decode``'s traced program (the kernel's jaxpr,
    its grid and its operands; no source location is in it) at a cell's
    shape."""
    from scaletorch_tpu.ops.pallas.paged_attention import (
        pallas_latent_decode_attention,
    )

    arg = jax.ShapeDtypeStruct
    traced = jax.make_jaxpr(lambda q, pool, tables, pos: (
        pallas_latent_decode_attention(
            q, pool, tables, pos, layer=jnp.int32(1), value_width=512,
            scale=192 ** -0.5)))(
        arg((slots, heads, 640), jnp.bfloat16),
        arg((layers, slots * max_pages + 1, 1, 16, 640), jnp.bfloat16),
        arg((slots, max_pages), jnp.int32), arg((slots,), jnp.int32))
    return hashlib.sha256(str(traced).encode()).hexdigest()


@pytest.mark.parametrize("name,slots,heads,max_pages,layers,digest", [
    ("openpangu-ultra-moe-718b-serve", 8, 128, 216, 6,
     "957cbaedb8afdabafb1864902737c5cf485dd29ad0ae8e31c19e45b59fc7e713"),
    ("kimi-linear-48b-a3b-serve", 32, 32, 608, 2,
     "862da136927d7a76120782d05ab0594387b5e12f8ed4ed9e61655b175d78634b"),
], ids=["openpangu", "kimi-linear"])
def test_the_latent_cells_decode_with_the_kernel_they_were_measured_with(
        one_chip, name, slots, heads, max_pages, layers, digest):
    """PR 55 changed the dense decode kernel (its walk goes on into the
    next slot) and left ``latent_decode``, the same shape of loop beside
    it, alone: both latent cells' decode programs hold no
    ``paged_decode`` call, and the latent kernel traces to the program
    it traced to at the parent (`f9961ab`), at each cell's shape. A PR
    that means to change the latent kernel measures both cells and
    writes the new digests here."""
    decode, _, pool_shape = _programs_of(one_chip, name)
    assert pool_shape == (layers, slots * max_pages + 1, 1, 16, 640)
    calls = _mosaic_calls(decode.as_text())
    assert _named(calls, "latent_decode") and not _named(calls, "paged_decode")
    assert _latent_kernel_digest(slots, heads, max_pages, layers) == digest


def test_the_flash_forward_takes_a_value_width_of_its_own(one_chip):
    """Keys 192 and values 128 wide (latent attention's expanded heads):
    one ``flash_fwd`` whose output and accumulator are the value's
    width; a call with one width lowers to what it lowered to."""
    from scaletorch_tpu.ops.pallas.flash import flash_forward_with_lse

    def arg(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def lowered(dk, dv):
        return jax.jit(lambda q, k, v: flash_forward_with_lse(
            q, k, v, causal=True)).lower(
            arg((1, 4, 1024, dk)), arg((1, 4, 1024, dk)),
            arg((1, 4, 1024, dv)))

    text = lowered(192, 128).compile().as_text()
    names = [n for n in _short_names(text) if n.startswith("flash_fwd")]
    assert len(names) == 1 and names[0].endswith(
        "(bf16[1,4,1024,128], f32[1,4,1,1024])"), names
    names = [n for n in _short_names(lowered(128, 128).compile().as_text())
             if n.startswith("flash_fwd")]
    assert len(names) == 1 and names[0].endswith(
        "(bf16[1,4,1024,128], f32[1,4,1,1024])"), names


@pytest.mark.parametrize("block_t", [128, 256])
def test_ssm_scan_kernel_compiles_at_the_cell_s_shape(one_chip, block_t):
    """One Mamba layer's prefill call: 8 x 3,072 rows of 5,120 channels,
    16 states held as ``[16, 40, 128]``. One Mosaic call; its scalars (B and C,
    ``[block_t, 16]`` float32 a grid step) fit SMEM at 128 and 256 rows
    a step (512 did not on the v5e: PERF.md, PR 47)."""
    from scaletorch_tpu.ops.pallas.ssm_scan import ssm_scan_fwd

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    text = jax.jit(
        lambda *xs: ssm_scan_fwd(*xs, block_t=block_t)).lower(
        arg(8, 3072, 5120), arg(8, 3072, 5120), arg(16, 40, 128),
        arg(8, 3072, 16), arg(8, 3072, 16), arg(8, 16, 40, 128),
    ).compile().as_text()
    calls = _mosaic_calls(text)
    assert len(calls) == 1 and _named(calls, "ssm_scan_fwd"), calls


def test_narrow_heads_take_the_lax_pair_in_the_same_loop(one_chip):
    """head_dim 64: no kernel serves it, so the carried loop scatters
    and gathers. What a compile found (PERF.md, PR 28): no layer is
    sliced out of the pool or stacked back (the parent did both, eight
    layer-sized operations a layer), the scatter updates the carried
    pool in place, and what is left are four whole-pool copies at the
    program's edge, because the device keeps a 64-wide pool pages-minor
    and the scatter wants rows: the pool pair once more in the scatter's
    padded layout, 2.0 times its bytes of temp (the parent: 1.4)."""
    from scaletorch_tpu.models.llama import LlamaConfig, init_params

    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=1024, intermediate_size=2816,
        num_hidden_layers=16, num_attention_heads=16, num_key_value_heads=8,
        head_dim=64, max_position_embeddings=4096, dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16)
    decode, prefill, pool_shape = _serving_steps(
        one_chip, cfg, init_params, slots=16, max_seq=1536,
        prefill_len=1024, page_size=16)
    pool_pair = 2 * 2 * math.prod(pool_shape)
    for program in (decode, prefill):
        text = program.as_text()
        # nothing Mosaic touches the pool; a call of cold prompts
        # attends in key blocks, which the flash forward does at 64
        assert not [c for c in _mosaic_calls(text) if "flash_fwd" not in c]
        assert bool(_mosaic_calls(text)) == (program is prefill)
        found = _pool_shaped(text, pool_shape)
        assert set(found) <= {"copy", "fusion", "scatter", "bitcast"}, found
        assert found.get("copy", 0) <= 4, found
        layer = ",".join(map(str, pool_shape[1:]))
        assert not re.search(
            rf"= bf16\[(1,)?{layer}\]\S* (?!parameter)", text)
    assert decode.memory_analysis().temp_size_in_bytes < 2.1 * pool_pair


# ---------------------------------------------------------------------------
# the flash kernels (training): what the compiled text shows is what the
# benchmark's readers will find in a trace. They tell the three kernels
# by what each RETURNS, so a fourth kind of Mosaic call, a fused backward
# or a result of another shape would leave `train_attn_roofline` with
# nothing, or the wrong thing, to read.
# ---------------------------------------------------------------------------
def _flash_args(one_chip, hq, hkv, s, d):
    """``q, k, v`` at batch 1, bf16, on the one chip."""
    return [jax.ShapeDtypeStruct((1, heads, s, d), jnp.bfloat16,
                                 sharding=one_chip)
            for heads in (hq, hkv, hkv)]


def _flash_calls(one_chip, fn, hq, hkv, s, d):
    """The Mosaic calls of ``fn(q, k, v)`` at batch 1, bf16, as the trace
    reader names them: ``instr | opcode | target | result``."""
    text = jax.jit(fn).lower(
        *_flash_args(one_chip, hq, hkv, s, d)).compile().as_text()
    return [short_name(re.sub(r"^\s*(ROOT )?", "", line))
            for line in _mosaic_calls(text)]


def _flash_grad(**kw):
    return jax.grad(
        lambda q, k, v: jnp.sum(pallas_flash_attention(
            q, k, v, **kw).astype(jnp.float32)),
        argnums=(0, 1, 2))


def _flash_kernel(name):
    """``jvp_flash_fwd_.1 | custom-call | ...`` -> ``flash_fwd``: the
    instruction carries the kernel's name inside its autodiff scope."""
    found = re.search(r"flash_(fwd|dq|dkv)", name.split(" | ")[0])
    return found.group(0) if found else name


def _flash_kinds(calls):
    return sorted({_flash_kernel(name) for name in calls})


# what ``flash_blocks`` says at qwen3-0.6b-train's shape, written out: a
# later edit that sends the training call back to 512 x 512 fails here
TRAINING_BLOCKS = {"flash_fwd": (512, 512), "flash_dq": (1024, 512),
                   "flash_dkv": (1024, 1024)}


def test_training_shape_is_three_kernels_the_roofline_can_find(one_chip):
    """qwen3-0.6b-train at seq 8192: 1 x 16 / 8 heads x 8192 x 128, bf16,
    causal. Forward and gradient compile; the program holds the three
    kernels and no other, and the patterns of
    ``benchmarks/metrics/train_attn_roofline.json`` charge one call as
    a forward and two as one backward."""
    calls = _flash_calls(one_chip, _flash_grad(), 16, 8, 8192, 128)
    assert _flash_kinds(calls) == ["flash_dkv", "flash_dq", "flash_fwd"]
    assert len(calls) == 3, calls

    with open(os.path.join(
            REPO, "benchmarks", "metrics", "train_attn_roofline.json")) as f:
        terms = json.load(f)["reducer"]["terms"]
    found = {term["charge"]: [
        name for name in calls
        if any(re.search(p, name) for p in term["patterns"])]
        for term in terms}
    assert [_flash_kernel(n) for n in found["forward"]] == ["flash_fwd"], found
    assert _flash_kinds(found["backward"]) == ["flash_dkv", "flash_dq"], found
    assert len(found["backward"]) == 2 == terms[1]["events_per_call"], found
    assert found["forward"][0].endswith(
        "(bf16[1,16,8192,128], f32[1,16,1,8192])"), found
    # and the other reader of these calls counts exactly the same three
    with open(os.path.join(
            REPO, "benchmarks", "metrics",
            "train_attn_kernel_share.json")) as f:
        share = json.load(f)["reducer"]["patterns"]
    assert [n for n in calls if any(re.search(p, n) for p in share)] == calls

    # the blocks are the rule's (``flash_blocks``: PERF.md, PR 62), a pair
    # a kernel, and no plan at them holds a dead grid step
    traced = jax.jit(_flash_grad()).trace(
        *_flash_args(one_chip, 16, 8, 8192, 128))
    blocks = flash_call_blocks(traced.jaxpr.jaxpr)
    assert blocks == {"flash_" + kind: flash_blocks(kind, 8192, 8192)
                      for kind in ("fwd", "dq", "dkv")}
    assert blocks == TRAINING_BLOCKS, "the rule's answer at the cell's shape"
    grids = {call.params["name"]: call.params["grid_mapping"].grid
             for call in pallas_calls(traced.jaxpr.jaxpr)}
    for name, (bq, bkv) in blocks.items():
        plan = causal_block_plan(8192, 8192, bq, bkv)
        # a query block sees the key blocks up to its own last row
        assert plan.live == sum(
            -(-(i + 1) * bq // bkv) for i in range(8192 // bq))
        assert plan.dead == 0
        assert len(plan.by_query[0]) == len(plan.by_key[0]) == plan.live
        heads, rep = ((8, (2,)) if name == "flash_dkv" else (16, ()))
        assert grids[name] == (1, heads, plan.live, *rep), grids


@pytest.mark.parametrize("blocks", [None, (1024, 2048)],
                         ids=["the-rule", "1024x2048"])
def test_flash_dkv_carries_the_vmem_limit_its_blocks_compute(
        one_chip, blocks):
    """``flash_dkv`` at the cell's shape: the lowered call carries what
    ``_vmem_limit`` computes from its blocks and the backward compiles
    to its two Mosaic calls. In the rule's blocks that is None, Mosaic's
    16 MiB default (1,024 x 1,024 needs 9). At 1,024 x 2,048 the call
    needs 17 MiB (AOT, PR 62, bisected) and does not compile without
    the limit; the sum asks for 24."""
    from scaletorch_tpu.ops.pallas.flash import flash_block_backward

    q, k, v = _flash_args(one_chip, 16, 8, 8192, 128)
    lse = jax.ShapeDtypeStruct((1, 16, 8192), jnp.float32, sharding=one_chip)
    kw = dict(block_q=blocks[0], block_kv=blocks[1]) if blocks else {}
    traced = jax.jit(lambda q, k, v, out, lse, g: flash_block_backward(
        q, k, v, out, lse, g, causal=True, **kw)).trace(q, k, v, q, lse, q)
    bq, bkv = flash_call_blocks(traced.jaxpr.jaxpr)["flash_dkv"]
    assert (bq, bkv) == (blocks or flash_blocks("dkv", 8192, 8192))
    (limit,) = [call.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes
                for call in pallas_calls(traced.jaxpr.jaxpr)
                if call.params["name"] == "flash_dkv"]
    assert limit == (24 * 2 ** 20 if blocks else None)
    calls = _mosaic_calls(traced.lower().compile().as_text())
    assert len(calls) == 2 and _named(calls, "flash_dkv"), calls


def test_flash_forward_alone_is_one_call(one_chip):
    calls = _flash_calls(
        one_chip, lambda q, k, v: pallas_flash_attention(q, k, v),
        16, 8, 8192, 128)
    assert _flash_kinds(calls) == ["flash_fwd"] and len(calls) == 1, calls


@pytest.mark.parametrize("d", [128, 64, 256])
def test_flash_forward_holds_its_statistics_a_register_wide(one_chip, d):
    """The running maximum and sum are ``(bq, 128)`` float32 in VMEM,
    every lane of a row the row's value, whatever the head's width: a
    ``(bq, 1)`` scratch uses one lane of 128 in every register it
    touches and cost the forward 1.4 of its 4.0 ms on the chip (PERF.md,
    PR 38). A later edit that narrows them fails here, on a CPU."""
    traced = jax.jit(lambda q, k, v: pallas_flash_attention(q, k, v)).trace(
        *_flash_args(one_chip, 16, 8, 8192, d))
    (call,) = pallas_calls(traced.jaxpr.jaxpr)
    assert call.params["name"] == "flash_fwd"
    bq, _ = flash_blocks("fwd", 8192, 8192)
    scratch = call.params["grid_mapping"].scratch_avals
    assert [(str(ref.memory_space), ref.shape, ref.dtype)
            for ref in scratch] == [
        ("vmem", (bq, d), jnp.float32),     # the output's accumulator
        ("vmem", (bq, 128), jnp.float32),   # running max
        ("vmem", (bq, 128), jnp.float32),   # running sum
    ]
    assert len(_mosaic_calls(traced.lower().compile().as_text())) == 1


@pytest.mark.parametrize("hq,hkv,s,d,kw", [
    (16, 8, 8192, 128, dict(causal=False)),  # ring's off-diagonal hops
    (16, 8, 2048, 128, {}),     # its diagonal hop at cp 4
    (8, 1, 4096, 128, {}),      # MQA: eight query heads a key block
    (16, 16, 4096, 64, {}),     # MHA at head_dim 64
    (4, 2, 1536, 128, {}),      # three blocks a side
    (32, 8, 32768, 128, {}),    # 64 x 64 blocks, 2,080 live
    (4, 2, 131072, 128, {}),    # Ulysses at cp 4: the whole 128k sequence
    # the longest walk the tables take (flash.MAX_CAUSAL_STEPS), in SMEM
    (8, 1, 361 * 128, 128, dict(block_q=128, block_kv=128)),
], ids=["rect", "seq2k", "mqa", "mha-d64", "seq1536", "seq32k-nrep4",
        "seq128k", "longest-walk"])
def test_flash_compiles_for_its_other_callers_shapes(
        one_chip, hq, hkv, s, d, kw):
    calls = _flash_calls(one_chip, _flash_grad(**kw), hq, hkv, s, d)
    assert _flash_kinds(calls) == ["flash_dkv", "flash_dq", "flash_fwd"]
    assert len(calls) == 3, calls
    if kw.get("block_q"):
        assert causal_block_plan(s, s, 128, 128).live == 65341
        assert 65341 <= MAX_CAUSAL_STEPS < 362 * 363 // 2


# ---- the Mamba-2 decode state update (PR 61) -------------------------------------

def test_a_mamba2_decode_layer_advances_its_state_in_one_pass(one_chip):
    """One Mamba-2 layer of granite-4.0-h-small-serve's decode step at
    the cell's shapes (64 slots, a ``[128, 8192]`` float32 state a slot,
    nine layers in the donated buffer), compiled for the v5e: the state
    is advanced by ONE Mosaic call (``ops/pallas/ssd_update.py``) that
    the whole buffer goes into and comes out of (aliased: no copy of
    it), ``y`` comes out of the same call, and no other instruction of
    the program, fused ones included, has a result of the buffer's or of
    one layer's shape: no ``[slots, 128, 8192]`` temporary beside it.
    Written in XLA the update compiles to two fusions that each read the
    state (PERF.md, PR 61)."""
    from scaletorch_tpu.models import granite_moe_hybrid as granite

    config, cfg, init = _serving_model("granite-4.0-h-small-serve")
    slots = config["serve"]["max_slots"]
    state_shape, tail_shape = cfg.recurrent_state_shapes(slots)
    assert state_shape == (9, 64, 128, 8192)
    assert granite.update_kernel_serves(cfg)    # FORCE_PALLAS: the fixture

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = jax.eval_shape(lambda: init(jax.random.PRNGKey(0), cfg))
    layer = jax.tree.map(lambda a: arg(a.shape[1:], a.dtype),
                         params["layers"]["mamba"])

    def step(u, layer, states, tail, fresh, written):
        return granite.mamba2_decode(u, layer, cfg, states, 4, tail, fresh,
                                     written, row_mask=written[:, None])

    flags = arg((slots,), jnp.bool_)
    compiled = jax.jit(step, donate_argnums=2).lower(
        arg((slots, 1, cfg.hidden_size), cfg.dtype), layer,
        arg(state_shape, jnp.float32), arg(tail_shape[1:], cfg.dtype),
        flags, flags).compile()
    text = compiled.as_text()
    calls = _named(_mosaic_calls(text), "ssd_state_update")
    assert len(calls) == 1, calls
    # (``_pool_shaped`` asks of any ``[layers, ...]`` buffer)
    assert _pool_shaped(text, state_shape) == {}
    memory = compiled.memory_analysis()
    state_bytes = 9 * 64 * 128 * 8192 * 4
    assert memory.alias_size_in_bytes == state_bytes
    # the projections' activations and the kernel's small operands: far
    # under one layer's 268 MB of state
    assert memory.temp_size_in_bytes < 64 * 2 ** 20
