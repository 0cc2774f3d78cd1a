"""The per-layer metrics of the delivery leg (engine readback -> socket
write) and the whole-engine stall's counter: each reader file loads,
names a reader kind that exists and reads the number it should from a
synthetic run; each is in the committed ``BENCHMARK.json`` with the
cells and the end-to-end metric it moves; and the gateway's span keeps
out of the patterns ``breakdown.idle_gaps`` attributes device idle time
by."""

import json
import os
import re

import pytest

from benchmarks.lib import reducers
from benchmarks.lib.spec import Spec
from tests.benchmarks.helpers import chat_held_metric
from tests.benchmarks.toy import REPO

LONGGEN = ["serve-1.7b-longgen", "serve-olmoe-longgen",
           "serve-olmo-hybrid-longgen", "serve-qwen3-next-longgen"]
SERVE = ["serve-1.7b-chat"] + LONGGEN
# metric -> (layer, access-record field)
DELIVERY = {
    "serve_emit_gap_p95_ms": ("engine worker", "emit_gap_p95_s"),
    "serve_deliver_held_ms_per_token": (
        "engine worker", "deliver_held_s_per_token"),
    "serve_write_gap_p95_ms": ("gateway", "write_gap_p95_s"),
    "serve_deliver_loop_ms_per_token": (
        "gateway", "deliver_loop_s_per_token"),
    "serve_deliver_lag_p95_ms": ("gateway", "deliver_lag_p95_s"),
}
SLOW_TICKS = "serve_engine_slow_ticks"


def index_entry(name):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        index = json.load(f)
    (entry,) = [m for m in index["per_layer"] if m["name"] == name]
    return index, entry


def reader(name):
    """The metric as the harness merges it for a cell that lists it."""
    (metric,) = [m for m in Spec(REPO).per_layer(LONGGEN[0])
                 if m["name"] == name]
    return metric


@pytest.mark.parametrize("name", sorted(DELIVERY))
def test_a_delivery_metric_reads_the_median_of_its_field(name):
    layer, field = DELIVERY[name]
    metric = reader(name)
    assert metric["reducer"]["kind"] in reducers.KINDS
    assert metric["reducer"]["kind"] == "record_percentile"
    assert metric["what"]
    # five requests of the window, one of a single token (null), and a
    # record of a program without the stamps (no such key)
    access = [{field: v, "measured": True}
              for v in (0.0090, 0.0084, None, 0.0102, 0.0087, 0.0089)]
    access.append({"tokens": 5, "measured": True})
    ctx = {"records": {"access": access}, "counters": {}}
    # nearest rank: the third of the five sorted values, in ms
    assert reducers.read_metric(ctx, metric) == pytest.approx(8.9)
    # the parent's records carry no such field: nothing to read, no raise
    ctx = {"records": {"access": [{"tokens": 5}, {"tokens": 9}]}}
    assert reducers.read_metric(ctx, metric) is None
    assert reducers.read_metric({"records": {}}, metric) is None


@pytest.mark.parametrize("name", sorted(DELIVERY))
def test_a_delivery_metric_is_listed_with_the_longgen_cells(name):
    index, entry = index_entry(name)
    # on the list, wherever: a later cell is appended after them
    assert set(LONGGEN) <= set(entry.pop("workloads"))
    assert entry == {
        "name": name, "unit": "ms", "better": "lower",
        "source": "program_counter", "layer": DELIVERY[name][0],
        "moves": "serve_itl_p95_ms"}
    # every cell it lists reports the end-to-end metric it moves
    (moved,) = [m for m in index["end_to_end"]
                if m["name"] == entry["moves"]]
    assert set(index_entry(name)[1]["workloads"]) <= set(
        moved["workloads"])


def test_the_new_entries_are_all_there_once():
    """Where in the list they stand is nobody's business: a later PR
    appends, a ``benchmark`` PR may sort."""
    index, _ = index_entry(SLOW_TICKS)
    names = [m["name"] for m in index["per_layer"]]
    assert set(DELIVERY) | {SLOW_TICKS} <= set(names)
    assert len(names) == len(set(names))
    layers = {m["layer"] for m in index["per_layer"]}
    assert "gateway" in layers and "engine worker" in layers


def test_the_stall_counter_is_named_in_every_serving_cell():
    index, entry = index_entry(SLOW_TICKS)
    assert set(LONGGEN) <= set(entry.pop("workloads"))
    assert entry == {
        "name": SLOW_TICKS, "unit": "ticks", "better": "lower",
        "source": "program_counter", "layer": "engine worker",
        "moves": "serve_itl_p99_ms"}
    (moved,) = [m for m in index["end_to_end"]
                if m["name"] == "serve_itl_p99_ms"]
    assert set(LONGGEN) <= set(moved["workloads"])
    # the chat cell has had a percentile of its own since PR 41: the
    # same counter under a name of its own, which moves the metric
    # that cell is held to
    _, twin = index_entry(SLOW_TICKS + ".chat")
    assert twin == dict(entry, name=SLOW_TICKS + ".chat",
                        moves=chat_held_metric(Spec(REPO))["name"],
                        workloads=SERVE[:1])
    (chat_reader,) = [m for m in Spec(REPO).per_layer(SERVE[0])
                      if m["name"] == twin["name"]]
    assert chat_reader["reducer"] == reader(SLOW_TICKS)["reducer"]
    metric = reader(SLOW_TICKS)
    assert metric["reducer"] == {"kind": "counter",
                                 "key": "engine.slow_ticks"}
    ctx = {"counters": {"engine.slow_ticks": 0, "engine.decode_steps": 5000}}
    assert reducers.read_metric(ctx, metric) == 0
    ctx["counters"]["engine.slow_ticks"] = 2
    assert reducers.read_metric(ctx, metric) == 2
    assert reducers.read_metric({"counters": {}}, metric) is None


def test_the_engine_counts_what_the_reader_names():
    """``engine.<name>`` is every number of the engine's snapshot."""
    from scaletorch_tpu.inference.engine import EngineMetrics

    assert EngineMetrics().snapshot()["slow_ticks"] == 0


@pytest.mark.parametrize("cell", SERVE)
def test_the_gateways_span_is_no_host_span_of_the_idle_gaps(cell):
    """``breakdown.idle_gaps`` gives a device gap to the innermost span
    that matches the cell's ``host_spans``: the delivery runs on the
    loop's thread beside the engine's, and must not take the gaps from
    ``engine.tick*``."""
    with open(os.path.join(REPO, "benchmarks", "workloads",
                           f"{cell}.json")) as f:
        patterns = json.load(f)["host_spans"]
    assert patterns
    assert not any(re.search(p, "gateway.deliver") for p in patterns)
    assert any(re.search(p, "engine.tick.decode_wait") for p in patterns)


def test_a_traced_toy_run_prints_the_metrics_from_the_gateways_records(
        tmp_path):
    """Gateway -> access records -> readers -> the result line, on the
    CPU at toy size (closed loop, 4 streams of 12-24 tokens)."""
    from tests.benchmarks.helpers import run_cell
    from tests.benchmarks.toy import make_toy_root

    root = make_toy_root(str(tmp_path / "toy"), serve_kind="closed_loop")
    rc, line, out = run_cell(
        ["--root", root, "--workload", "toy-serve", "--seed",
         str(2**31 + 37), "--seconds", "2", "--trace", "1", "--rehearse"])
    assert rc == 3, out
    got = {name: line["metrics"][name]["value"] for name in DELIVERY}
    assert all(v > 0 for v in got.values()), got
    assert all(line["metrics"][n]["unit"] == "ms" for n in DELIVERY)
    assert line["metrics"][SLOW_TICKS] == {"value": 0, "unit": "ticks"}
