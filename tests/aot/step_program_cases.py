"""The cases every serving configuration's two step programs are held
to, compiled for the v5e at its cell's shapes: the pool is moved by
nothing, no weight is copied, no second pool is reserved, the decode
kernel is the one call the benchmark's readers tell it by, the prefill
programs run the head on the sampled-from rows alone and at each listed
shape's own size. Not collected here: a test file imports the cases and
names the configurations it compiles in ``CONFIGURATIONS``
(``conftest.pytest_generate_tests``), so that no configuration is
compiled in two files.
"""

import math
import re

import jax

from tests.aot.programs import (
    _ARRAY,
    _STILL_RE_LAID,
    _flash_forwards,
    _mosaic_calls,
    _named,
    _pool_shaped,
    _prefill_rows,
    _programs_of,
    _serving_model,
    _weight_copies,
)


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


# their file's other tests compile these two programs anyway, and no
# other prefill shape
_THE_TWO_PROGRAMS_ALONE = {"openpangu-ultra-moe-718b-serve",
                           "kimi-linear-48b-a3b-serve"}


def test_no_step_program_copies_a_weight(one_chip, configuration):
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
    programs are in ``test_mimo_steps.py``."""
    from scaletorch_tpu.inference.decode import prefill_shapes
    from scaletorch_tpu.inference.kv_cache import carries_state, window_of

    name = configuration
    config, cfg, init = _serving_model(name)
    decode, prefill, _ = _programs_of(one_chip, name)
    programs = {"decode": decode, "prefill": prefill}
    if name not in _THE_TWO_PROGRAMS_ALONE and not (
            carries_state(cfg) or window_of(cfg) is not None):
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


def test_prefill_program_runs_the_head_on_the_sampled_from_rows_only(
        configuration, serving_programs):
    """The prefill step names one row a slot (``logit_rows``) and the
    forward takes it before the final norm and the head: no array of
    ``rows x prefill_len x vocab`` elements, of any type or layout, is
    left in the compiled program (Qwen3-1.7B's was 4.98 GB in bf16, five
    instructions of it), the logits it does hold are ``[rows, vocab]``
    (the decode step's ``[slots, vocab]``; one row where a row names
    its slot), and the program's scratch is smaller for it."""
    name = configuration
    _, prefill, _ = serving_programs
    config, cfg, _ = _serving_model(name)
    serve = config["serve"]
    slots, vocab = serve["max_slots"], config["vocab_size"]
    rows = _prefill_rows(cfg, slots)
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
        one_chip, configuration, serving_programs):
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

    name = configuration
    config, cfg, _ = _serving_model(name)
    serve, vocab = config["serve"], config["vocab_size"]
    slots, length = serve["max_slots"], serve["prefill_len"]
    *shorter, top = prefill_shapes(slots, length)
    assert top == (slots, length) and len(shorter) <= 6
    _, full, _ = serving_programs
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


def test_no_decode_program_holds_a_choice_or_a_flash_call(serving_programs):
    """A call of one row reads the pool through the decode kernel, as
    it did: the choice exists for S > 1 alone."""
    decode, _, _ = serving_programs
    text = decode.as_text()
    assert " conditional(" not in text and not _flash_forwards(text)
