"""The per-layer metrics that read the program's own clocks (PR 24):
four reader files over the gateway's access records, found for their
cells, worked by hand on made-up records, and printed by a traced toy
run of the real gateway and engine."""

import pytest

from benchmarks.lib import reducers
from benchmarks.lib.spec import Spec
from tests.benchmarks.helpers import chat_held_metric, run_cell
from tests.benchmarks.toy import make_toy_root

SPEC = Spec()
CHAT, LONGGEN = "serve-1.7b-chat", "serve-1.7b-longgen"
CHAT_HELD = chat_held_metric(SPEC)["name"]

# metric -> (access-record field, the cells that report it)
PROGRAM_METRICS = {
    "serve_req_host_ms_per_token": ("host_s_per_token", {CHAT, LONGGEN}),
    "serve_req_device_wait_ms_per_token": (
        "device_wait_s_per_token", {CHAT, LONGGEN}),
    "serve_req_stall_ms_per_token": ("stall_s_per_token", {CHAT, LONGGEN}),
    "serve_prefill_wall_p50_ms": ("prefill_s", {CHAT}),
}


def name_in(cell, name):
    """The chat cell has had a gap percentile of its own since PR 41
    (``CHAT_HELD``) and a per-layer metric names one end-to-end metric:
    there the quantity has a twin of its own, ``<name>.chat``, with the
    same reader."""
    return f"{name}.chat" if cell == CHAT else name


def reader(name, cell=CHAT):
    return next(m for m in SPEC.per_layer(cell)
                if m["name"] == name_in(cell, name))


@pytest.mark.parametrize("name", sorted(PROGRAM_METRICS))
def test_the_spec_finds_the_metric_for_its_cells_only(name):
    field, cells = PROGRAM_METRICS[name]
    for cell in (CHAT, LONGGEN, "train-0.6b-seq8k"):
        found = [m for m in SPEC.per_layer(cell)
                 if m["name"] == name_in(cell, name)]
        assert len(found) == (cell in cells), (name, cell)
        for m in found:
            assert m["moves"] == (CHAT_HELD if cell == CHAT
                                  else "serve_itl_p99_ms")
    metric = reader(name, LONGGEN if LONGGEN in cells else CHAT)
    assert metric["reducer"] == reader(name)["reducer"]
    assert metric["reducer"] == {
        "kind": "record_percentile", "records": "access", "field": field,
        "percentile": 50, "scale": 1000.0}
    assert (metric["unit"], metric["better"], metric["source"],
            metric["moves"]) == (
                "ms", "lower", "program_counter",
                "serve_itl_p99_ms" if LONGGEN in cells
                else CHAT_HELD)
    # an inside view of a layer the benchmark already names
    outside = {m["layer"] for m in SPEC.index["per_layer"]
               if m["name"].removesuffix(".chat") not in PROGRAM_METRICS}
    assert metric["layer"] in outside


@pytest.mark.parametrize("name", sorted(PROGRAM_METRICS))
def test_the_median_skips_records_without_the_field(name):
    """Five requests, seconds in the record, milliseconds out; a
    one-token request's per-token fields are null, a rejected request
    has none of them."""
    field, _ = PROGRAM_METRICS[name]
    records = [{field: v, "outcome": "ok"}
               for v in (0.004, 0.100, 0.006, 0.005, 0.007)]
    records += [{field: None, "outcome": "ok"}, {"outcome": "rejected"}]
    ctx = {"records": {"access": records, "loadgen": [{field: 9.0}]}}
    assert reducers.read_metric(ctx, reader(name)) == pytest.approx(6.0)


@pytest.mark.parametrize("name", sorted(PROGRAM_METRICS))
def test_nothing_to_read_leaves_the_metric_out(name):
    """Access records of a program without the phase clocks (the parent
    commit), and a run with no records at all."""
    field, _ = PROGRAM_METRICS[name]
    old = [{"outcome": "ok", "queue_wait_s": 0.001, "tokens": 12}]
    for records in ({"access": old}, {"access": []}, {}):
        assert reducers.read_metric({"records": records},
                                    reader(name)) is None


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    root = make_toy_root(str(tmp_path_factory.mktemp("toy-clocks")),
                         serve_kind="closed_loop")
    return run_cell(["--root", root, "--workload", "toy-serve", "--seed",
                     str(2**31 + 24), "--seconds", "2", "--trace", "1",
                     "--rehearse"])


def test_a_traced_run_prints_all_four_from_the_programs_clocks(traced):
    """The real gateway and engine at toy size on the CPU: the access
    records carry the clocks and the four readers find them. (The values
    are CPU times: what is checked is that they are there and add up.)"""
    rc, line, out = traced
    assert rc == 3 and line["correct"] is True, out
    got = {name: line["metrics"][name]["value"] for name in PROGRAM_METRICS}
    assert all(line["metrics"][name]["unit"] == "ms"
               for name in PROGRAM_METRICS)
    assert got["serve_req_device_wait_ms_per_token"] > 0, out
    assert got["serve_req_host_ms_per_token"] > 0
    assert got["serve_req_stall_ms_per_token"] >= 0
    assert got["serve_prefill_wall_p50_ms"] > 0
