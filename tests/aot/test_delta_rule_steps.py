"""olmo-hybrid-7b-serve's and qwen3-next-80b-a3b-serve's paged step
programs, the two delta-rule families (a recurrent state a slot beside
the pool, a prefill row that names its slot), compiled for the v5e at
the cells' shapes: the cases of every configuration
(``step_program_cases.py``) and what is these two's own.
"""

import math
import re

import pytest

from tests.aot.programs import (
    _ARRAY,
    _PLUMBING,
    _flash_forwards,
    _mosaic_calls,
    _programs_of,
    _reader_patterns,
    _short_names,
    _top_level,
)
from tests.aot.step_program_cases import (  # noqa: F401  (collected here)
    test_decode_kernel_is_still_the_one_4d_call,
    test_decode_program_reserves_no_second_pool,
    test_no_decode_program_holds_a_choice_or_a_flash_call,
    test_no_step_program_copies_a_weight,
    test_no_step_program_moves_the_pool,
    test_prefill_program_runs_the_head_on_the_sampled_from_rows_only,
    test_the_listed_prefill_shapes_compile_at_their_own_size,
)

CONFIGURATIONS = ["olmo-hybrid-7b-serve", "qwen3-next-80b-a3b-serve"]


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
