"""What the ``tests/aot/`` files share: a serving configuration's two paged
step programs compiled for the v5e with no chip attached, AS AN ENGINE RUNS
THEM (``_serving_steps``), kept for whichever test of the file asks first
(``_programs_of``), and what reads a compiled program's text (its Mosaic
calls, what has the pool's shape, what copies a weight, the names the
benchmark's trace readers give its instructions). Not collected, and
nothing at import time touches the TPU compiler: the fixture that
describes the chip is ``conftest.one_chip``.

A configuration's programs are 12 to 135 s of compiling, so each is
compiled in ONE file (ROADMAP D1: ``--dist loadfile`` gives a file to one
worker; as one file these were that worker's whole run, 558 s): a test
file names its configurations in ``CONFIGURATIONS`` and no name stands in
two files.
"""

import json
import math
import os
import re

import jax
import jax.numpy as jnp

from benchmarks.lib.trace import short_name

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _mosaic_calls(text):
    return [line for line in text.splitlines()
            if "custom-call(" in line and "tpu_custom_call" in line]


def _named(calls, name):
    """The calls that ARE ``name`` (its result), not those that read it."""
    return [c for c in calls if re.match(rf"\s*(ROOT )?%{name}\S* = ", c)]


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
_ARRAY = re.compile(r"\b(?:pred|[a-z]+\d+)\[([\d,]+)\]")


def _prefill_rows(cfg, slots):
    """The rows of a configuration's largest prefill program: every
    slot, or the one row of a family whose rows name their slots."""
    from scaletorch_tpu.inference.decode import rows_name_slots

    return 1 if rows_name_slots(cfg) else slots


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


def _flash_forwards(text):
    """The flash forward's calls in a compiled program, as the trace
    reader names them."""
    return [n for n in _short_names(text)
            if n.startswith("flash_fwd") and "tpu_custom_call" in n]
