"""qwen3-1.7b-serve's and olmoe-1b-7b-serve's paged step programs, the
two whose cache is addressed by page alone (a prefix may lie in the
pool), compiled for the v5e at the cells' shapes: the cases of every
configuration (``step_program_cases.py``) and what is these two's own.
"""

import math
import re

import jax
import pytest

from tests.aot.programs import _ARRAY, _CHOSEN, _flash_forwards, _programs_of
from tests.aot.step_program_cases import (  # noqa: F401  (collected here)
    test_decode_kernel_is_still_the_one_4d_call,
    test_decode_program_reserves_no_second_pool,
    test_no_decode_program_holds_a_choice_or_a_flash_call,
    test_no_step_program_copies_a_weight,
    test_no_step_program_moves_the_pool,
    test_prefill_program_runs_the_head_on_the_sampled_from_rows_only,
    test_the_listed_prefill_shapes_compile_at_their_own_size,
)

CONFIGURATIONS = ["qwen3-1.7b-serve", "olmoe-1b-7b-serve"]


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
