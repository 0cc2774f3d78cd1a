"""``run.py`` end to end on the CPU at toy size: the serving cells
(gateway, engine worker, paged engine, a client process over HTTP)."""

import re

import pytest

from benchmarks.lib.spec import Spec
from tests.benchmarks.helpers import CONTRACT_KEYS, run_cell
from tests.benchmarks.toy import make_toy_root

SEED = str(2**31 + 78)
# the toy cell stands for every serving cell: it reports each gap
# percentile that some serving cell of BENCHMARK.json is held to
GAP_PERCENTILES = sorted(
    (m["name"] for m in Spec().index["end_to_end"]
     if re.fullmatch(r"serve_itl_p\d+_ms", m["name"])),
    key=lambda name: float("0." + name[len("serve_itl_p"):-len("_ms")]))


def _run(tmp_path_factory, kind, trace):
    root = make_toy_root(str(tmp_path_factory.mktemp(f"toy-{kind}")),
                         serve_kind=kind)
    return run_cell(["--root", root, "--workload", "toy-serve", "--seed",
                     SEED, "--seconds", "2", "--trace", trace,
                     "--rehearse"])


@pytest.fixture(scope="module")
def closed_loop(tmp_path_factory):
    return _run(tmp_path_factory, "closed_loop", "0")


@pytest.fixture(scope="module")
def open_loop(tmp_path_factory):
    return _run(tmp_path_factory, "open_loop_stratified", "0")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    return _run(tmp_path_factory, "open_loop_stratified", "1")


@pytest.mark.parametrize("which", ["closed_loop", "open_loop"])
def test_serve_cell_prints_the_contracts_line(which, request):
    rc, line, out = request.getfixturevalue(which)
    assert rc == 3, out
    assert CONTRACT_KEYS <= set(line), out
    assert line["correct"] is True, out
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"


def test_closed_loop_reports_the_gap_tail(closed_loop, traced):
    _, line, out = closed_loop
    assert {"serve_itl_p95_ms", "serve_itl_p99_ms"} <= set(GAP_PERCENTILES)
    assert set(line["metrics"]) == set(GAP_PERCENTILES) | {"setup_s"}, out
    values = [line["metrics"][name]["value"] for name in GAP_PERCENTILES]
    assert values == sorted(values) and values[0] > 0
    # the runtime's bring-up is a key of the line, held by nothing
    assert line["runtime_bringup_s"] > 0
    assert "runtime_bringup_s" not in line["metrics"]
    # delivered tokens/s has a reader (a counter) and is in no cell yet
    _, line, out = traced
    assert line["metrics"]["serve_tokens_per_s"]["value"] > 0, out


@pytest.mark.parametrize("which", ["closed_loop", "open_loop", "traced"])
def test_long_gap_share_is_printed_with_and_without_a_trace(which, request):
    """A ``client_value`` reader needs no trace: an untraced run prints
    it under a key of its own (its ``metrics`` are the end-to-end ones
    and no other), a traced run among its per-layer metrics."""
    _, line, out = request.getfixturevalue(which)
    where = "metrics" if which == "traced" else "per_layer_untraced"
    found = line[where]["serve_itl_long_gap_share_pct"]
    assert found["unit"] == "%" and 0.0 <= found["value"] < 100.0, out
    if which != "traced":
        # the client view's readers and no other: the chat cell's two
        # edges (PR 46) beside the share every serving cell prints
        assert {"serve_itl_long_gap_share_pct",
                "serve_itl_long_gap_share_pct.chat",
                "serve_itl_over_10x_median_share_pct.chat"} <= set(
                    line["per_layer_untraced"])
        assert all(name.startswith("serve_itl_")
                   for name in line["per_layer_untraced"])
    # what was compared, beside its limit, comes last in the line
    assert list(line)[-2:] == ["check", "problems"]


def test_open_loop_times_from_the_due_instant(open_loop, traced):
    _, line, out = open_loop
    assert {"serve_itl_p95_ms", "serve_itl_p99_ms", "setup_s"} <= set(
        line["metrics"]), out
    # TTFT, the generator's lateness and the engine's queue have readers
    # over the records of the whole window and are in no cell yet: the
    # toy tree lists them, as a later PR will
    _, line, out = traced
    assert line["metrics"]["serve_ttft_p50_ms"]["value"] > 0, out
    assert line["metrics"]["serve_ttft_p90_ms"]["value"] >= \
        line["metrics"]["serve_ttft_p50_ms"]["value"]
    assert 0 <= line["metrics"]["serve_loadgen_late_p95_ms"]["value"] < 500
    # engine_queue_wait_s of the gateway's access records
    assert line["metrics"]["serve_queue_wait_p95_ms"]["value"] >= 0


@pytest.mark.parametrize("which", ["closed_loop", "open_loop"])
def test_paged_prefill_then_decode_agrees_with_the_reference(which, request):
    """Logits of the engine's own paged prefill step and 8 decode steps
    against the reference's full forward pass, float32 at toy size."""
    _, line, out = request.getfixturevalue(which)
    check = line["check"]
    assert check["ok"], out
    assert check["err_of_max"] < 1e-4
    assert min(check["prompt_lens"]) == 8 and max(check["prompt_lens"]) == 48


def test_traced_serve_run_reports_per_layer_metrics_only(traced):
    rc, line, out = traced
    assert rc == 3, out
    assert "setup_s" not in line["metrics"]
    assert "serve_itl_p95_ms" not in line["metrics"]
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
