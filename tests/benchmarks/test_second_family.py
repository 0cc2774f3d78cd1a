"""A second model family in the toy tree, as files only: a ``llama``
configuration (no q/k norm, a head of its own, MHA) whose plain
reference is a file the configuration names, one train and one serve
cell on it, run through ``run.py --root --rehearse``. The reference a
configuration names is the one used: pointed at the other family's it
reads ``correct: false``, and one that breaks the contract is refused
with the missing name."""

import os

import pytest

from tests.benchmarks.helpers import CONTRACT_KEYS, run_cell
from tests.benchmarks.toy import (REPO, TOY_LLAMA_REFERENCE, TOY_MODEL,
                                  make_toy_root)

SEED = str(2**31 + 26)
QWEN3_REFERENCE = TOY_MODEL["reference"]


def _run(root, cell, trace="0"):
    return run_cell(["--root", root, "--workload", cell, "--seed", SEED,
                     "--seconds", "1", "--trace", trace, "--rehearse"])


@pytest.fixture(scope="module")
def own_reference(tmp_path_factory):
    """Both llama cells traced, each family under its own reference,
    float32 serving held to a float32 tolerance."""
    root = make_toy_root(str(tmp_path_factory.mktemp("two-families")),
                         second_family=True, extra_metric=True,
                         serve_rtol_of_max=1e-4)
    return {"root": root,
            "toy-llama-train": _run(root, "toy-llama-train", "1"),
            "toy-llama-serve": _run(root, "toy-llama-serve", "1")}


@pytest.fixture(scope="module")
def swapped_references(tmp_path_factory):
    """The same tree with each family pointed at the other's reference."""
    root = make_toy_root(
        str(tmp_path_factory.mktemp("swapped")), second_family=True,
        references={"qwen3": TOY_LLAMA_REFERENCE, "llama": QWEN3_REFERENCE})
    return {cell: _run(root, cell)
            for cell in ("toy-train", "toy-serve", "toy-llama-serve")}


@pytest.mark.parametrize("cell", ["toy-llama-train", "toy-llama-serve"])
def test_second_family_is_correct_under_its_own_reference(own_reference,
                                                          cell):
    rc, line, out = own_reference[cell]
    assert rc == 3, out
    assert CONTRACT_KEYS <= set(line), out
    assert line["correct"] is True, out
    assert line["attempted"] > 0 and line["failed"] == 0


def test_second_family_checks_the_gains_its_reference_names(own_reference):
    """``GAIN_KEYS`` comes from the reference file: this family has no
    q/k norm to compare."""
    _, line, out = own_reference["toy-llama-train"]
    assert set(line["check"]["gain_grad_rel_l2"]) == {
        "input_layernorm", "post_attention_layernorm", "norm"}, out
    assert line["check"]["gain_grad_rel_err"] < 5e-2


def test_second_family_serves_float32_to_float32_tolerance(own_reference):
    _, line, out = own_reference["toy-llama-serve"]
    assert line["check"]["rtol_of_max"] == 1e-4
    assert line["check"]["err_of_max"] < 1e-5, out


def test_the_toy_tree_names_no_file_of_the_harness_in_code(own_reference):
    """The second family's reference imports nothing of the benchmark or
    of the system; the tree reaches the harness's files by the paths in
    its data files only."""
    source = open(os.path.join(own_reference["root"],
                               TOY_LLAMA_REFERENCE)).read()
    assert "benchmarks" not in source.split('"""', 2)[2]
    assert "scaletorch_tpu" not in source


@pytest.mark.parametrize("cell,metric", [
    ("toy-llama-train", "toy_step_loss"),
    ("toy-llama-train", "toy_steps_counted"),
    ("toy-llama-serve", "toy_engine_decode_steps"),
])
def test_counter_readers_find_the_programs_own_counters(own_reference, cell,
                                                        metric):
    """``step.<name>``: a scalar of the last step's metrics;
    ``engine.<name>``: the engine's snapshot over the window."""
    _, line, out = own_reference[cell]
    assert line["metrics"][metric]["value"] > 0, out


@pytest.mark.parametrize("cell", ["toy-train", "toy-serve"])
def test_the_other_familys_reference_reads_not_correct(swapped_references,
                                                       cell):
    """The qwen3 cells against the llama reference: the q/k norm is the
    difference, far outside both tolerances. So the key is what is
    used."""
    rc, line, out = swapped_references[cell]
    assert rc == 3, out
    assert line["correct"] is False, out
    assert line["check"]["ok"] is False
    if cell == "toy-train":
        assert line["check"]["gain_grad_rel_err"] > 0.5
    else:
        assert line["check"]["err_of_max"] > 0.1


def test_a_reference_that_cannot_take_the_tree_gives_no_result(
        swapped_references):
    """The llama cell against ``reference/qwen3.py``, which reads the
    q/k norm gains this family's parameters do not have: the run stops
    there, with no result line (a crash is not a verdict)."""
    rc, line, out = swapped_references["toy-llama-serve"]
    assert rc not in (0, 3), out
    assert line == {}
    assert "q_norm" in out


# a contract name -> the line of the toy reference that defines it
DEFINITIONS = {"make_loss_fn": "def make_loss_fn(",
               "make_logits_fn": "def make_logits_fn(",
               "GAIN_KEYS": "GAIN_KEYS = ("}


@pytest.mark.parametrize("name", sorted(DEFINITIONS))
def test_a_reference_without_a_contract_name_is_refused(tmp_path, name):
    """Exit code 2 with the missing name and no result line, whichever
    runner asks."""
    root = make_toy_root(str(tmp_path / "t"), second_family=True)
    path = os.path.join(root, TOY_LLAMA_REFERENCE)
    source = open(path).read()
    assert DEFINITIONS[name] in source
    with open(path, "w") as f:
        f.write(source.replace(DEFINITIONS[name],
                               DEFINITIONS[name].replace(name, "_" + name)))
    cell = "toy-llama-train" if name == "GAIN_KEYS" else "toy-llama-serve"
    rc, line, out = _run(root, cell)
    assert rc == 2, out
    assert f"lacks {name}" in out
    assert line == {}


@pytest.mark.parametrize("reference,message", [
    (None, "names no 'reference'"),
    ("benchmarks/reference/not_there.py", "not_there.py"),
])
def test_a_configuration_without_its_reference_is_refused(tmp_path,
                                                          reference, message):
    """No key or no such file: exit code 2, no fallback to another
    family's reference."""
    root = make_toy_root(str(tmp_path / "t"), second_family=True,
                         references={"llama": reference})
    rc, line, out = _run(root, "toy-llama-train")
    assert rc == 2, out
    assert message in out
    assert line == {}


def test_the_real_reference_is_found_from_a_toy_root():
    """A path in a data file is looked up in the ``--root`` tree, then in
    the checkout: the toy tree names ``reference/qwen3.py`` and holds no
    copy of it."""
    from benchmarks.lib.spec import Spec

    assert Spec().find(QWEN3_REFERENCE) == os.path.join(REPO,
                                                        QWEN3_REFERENCE)
