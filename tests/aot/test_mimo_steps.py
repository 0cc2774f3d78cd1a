"""mimo-v2-flash-serve's two paged step programs, compiled for the v5e
with no chip attached, AS AN ENGINE RUNS THEM (PERF.md, PR 60): the five
attention-projection stacks the decode program re-lays are stored as
their layers, each a program parameter, and neither program copies a
weight-sized slice of a stack from HBM to HBM (the parent's decode
program copied 0.81 GB of them every step, ``fusion.519`` - ``.523``).

In a file of its own, over this folder's fixture and helpers
(``conftest.one_chip``, ``programs.py``): ``--dist loadfile`` gives a
file to one worker, and these two programs are ~55 s of compiling.
Nothing at import time touches the TPU compiler.
"""

import re

import jax
import pytest

from scaletorch_tpu.inference.decode import ByLayer
from tests.aot.programs import (
    _CHOSEN,
    _programs_of,
    _serving_model,
    _weight_copies,
)

NAME = "mimo-v2-flash-serve"
# the leaf, its layers, a layer as stored: [out, in], contraction-minor
LAYERED = {
    "['layers']['block']['q_proj']": (7, "bf16[12288,4096]"),
    "['layers']['full']['k_proj']": (2, "bf16[768,4096]"),
    "['layers']['full']['v_proj']": (2, "bf16[512,4096]"),
    "['layers']['window']['k_proj']": (5, "bf16[1536,4096]"),
    "['layers']['window']['v_proj']": (5, "bf16[1024,4096]"),
}


@pytest.fixture(scope="module")
def programs(one_chip):
    decode, prefill, _ = _programs_of(one_chip, NAME)
    return {"decode": decode.as_text(), "prefill": prefill.as_text()}


def test_the_rule_layers_the_five_attention_projections_and_no_other_leaf(
        programs):
    """``chosen_orders`` on the decode program's own trace: the five
    stacks it asks for contraction-minor are the five it reads one
    static layer at a time (``models/mimo_v2_flash._at``); ``o_proj``,
    the dense MLP and the expert stacks are read as they lie and stay
    whole."""
    moved = {jax.tree_util.keystr(at): order for at, order in
             jax.tree_util.tree_flatten_with_path(
                 _CHOSEN[NAME], is_leaf=lambda x: isinstance(x, tuple))[0]
             if order}
    assert moved == {leaf: (0, 2, 1) for leaf in LAYERED}
    assert all(isinstance(order, ByLayer) for order in moved.values())


@pytest.mark.parametrize("label", ["decode", "prefill"])
def test_every_layer_is_a_parameter_of_its_own(programs, label):
    entry = next(line for line in programs[label].splitlines()
                 if line.startswith("ENTRY "))
    for leaf, (layers, stored) in LAYERED.items():
        name = "params" + re.sub(r"\W", "_", leaf)
        found = re.findall(rf"{name}_(\d)_\.\d+: (bf16\[[\d,]+\])", entry)
        assert found == [(str(i), stored) for i in range(layers)], leaf


@pytest.mark.parametrize("label", ["decode", "prefill"])
def test_no_step_program_copies_a_weight(programs, label):
    """No bf16 result of a weight's shape and 1 Mi elements or more
    produced by a ``copy``, a loop fusion that only moves or a slice
    into HBM."""
    _, cfg, init = _serving_model(NAME)
    params = jax.eval_shape(lambda: init(jax.random.PRNGKey(0), cfg))
    assert _weight_copies(programs[label], params) == []
