"""``run.py`` end to end on the CPU at toy size: the training cells,
the result line's contract, the reference check, cells as files."""

import json
import os

import pytest

from tests.benchmarks.helpers import CONTRACT_KEYS, run_cell
from tests.benchmarks.toy import make_toy_root

SEED = str(2**31 + 77)
TOY_GAIN_GRAD_RTOL = 5e-2


@pytest.fixture(scope="module")
def one_chip(tmp_path_factory):
    """A toy cell, configuration, traffic mix and one extra per-layer
    metric, all added as files; one traced and one untraced run."""
    root = make_toy_root(str(tmp_path_factory.mktemp("toy1")),
                         extra_metric=True)
    common = ["--root", root, "--workload", "toy-train", "--seed", SEED,
              "--seconds", "1", "--rehearse"]
    return {"root": root,
            "plain": run_cell(common + ["--trace", "0"]),
            "traced": run_cell(common + ["--trace", "1"])}


@pytest.fixture(scope="module")
def four_chips(tmp_path_factory):
    root = make_toy_root(str(tmp_path_factory.mktemp("toy4")), cp=4)
    return run_cell(["--root", root, "--workload", "toy-train", "--seed",
                     SEED, "--seconds", "1", "--trace", "0", "--rehearse"])


def test_a_rehearsal_exits_3_and_names_its_device(one_chip):
    rc, line, out = one_chip["plain"]
    assert rc == 3, out
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == 1
    assert "memory_peak_bytes" in line["device"]


def test_last_line_has_the_contracts_keys(one_chip):
    _, line, out = one_chip["plain"]
    assert CONTRACT_KEYS <= set(line), out
    assert line["correct"] is True, out
    assert line["attempted"] > 0 and line["failed"] == 0
    for metric in line["metrics"].values():
        assert set(metric) == {"value", "unit"}
        assert isinstance(metric["value"], float)


def test_untraced_run_reports_only_end_to_end_metrics(one_chip):
    _, line, _ = one_chip["plain"]
    assert set(line["metrics"]) == {"train_tokens_per_s_per_chip",
                                    "setup_s"}
    assert "breakdown" not in line


def test_traced_run_reports_only_per_layer_metrics(one_chip):
    rc, line, out = one_chip["traced"]
    assert rc == 3, out
    index = json.load(open(os.path.join(one_chip["root"],
                                        "BENCHMARK.json")))
    per_layer = {m["name"] for m in index["per_layer"]}
    assert set(line["metrics"]) <= per_layer
    assert "setup_s" not in line["metrics"]
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert "window_s" in line["device"]


def test_a_metric_added_as_files_is_read(one_chip):
    _, line, out = one_chip["traced"]
    assert line["metrics"]["toy_steps_counted"]["value"] == \
        line["attempted"], out
    assert line["metrics"]["toy_steps_counted"]["unit"] == "steps"


def test_system_agrees_with_the_reference_on_one_device(one_chip):
    _, line, out = one_chip["plain"]
    check = line["check"]
    assert check["ok"], out
    # same mathematics, bf16 against float32 at toy size
    assert check["loss_rel_err"] < 5e-4
    assert check["grad_norm_rel_err"] < 8e-3
    # every norm gain's gradient, read back from Adam's first moment
    assert set(check["gain_grad_rel_l2"]) == {
        "input_layernorm", "post_attention_layernorm", "q_norm", "k_norm",
        "norm"}
    assert check["gain_grad_rel_err"] < TOY_GAIN_GRAD_RTOL


def test_cp4_zigzag_ring_agrees_with_the_reference(four_chips):
    rc, line, out = four_chips
    assert rc == 3, out
    assert line["device"]["count"] == 4
    assert line["correct"] is True, out
    assert line["check"]["loss_rel_err"] < 5e-4
    assert line["check"]["grad_norm_rel_err"] < 8e-3
    assert line["check"]["gain_grad_rel_err"] < TOY_GAIN_GRAD_RTOL


def test_measuring_without_a_tpu_is_refused(one_chip):
    rc, line, out = run_cell(
        ["--root", one_chip["root"], "--workload", "toy-train", "--seed",
         "1", "--seconds", "1", "--trace", "0"])
    assert rc not in (0, 3), out
    assert line == {}


def test_fewer_chips_than_the_cell_asks_for_is_refused(tmp_path):
    root = make_toy_root(str(tmp_path / "t"), cp=4)
    rc, line, out = run_cell(
        ["--root", root, "--workload", "toy-train", "--seed", "1",
         "--seconds", "1", "--trace", "0", "--rehearse"],
        xla_flags="--xla_force_host_platform_device_count=2")
    assert rc == 2, out
    assert "needs 4 chips" in out
    assert line == {}


def test_a_tree_with_only_the_benchmarks_files_is_refused(tmp_path):
    """BENCHMARK.json and the files under ``paths`` alone: no program
    to measure, so no result."""
    import shutil

    from tests.benchmarks.toy import REPO
    bare = tmp_path / "bare"
    shutil.copytree(os.path.join(REPO, "benchmarks"), bare / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), bare)
    rc, line, out = run_cell(
        ["--workload", "train-0.6b-seq8k", "--seed", "1", "--seconds", "1",
         "--trace", "0", "--rehearse"], cwd=str(bare),
        script=str(bare / "benchmarks" / "run.py"))
    assert rc not in (0, 3), out
    assert line == {}
