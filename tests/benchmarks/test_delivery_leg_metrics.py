"""The per-layer metrics of a delivery's four legs (the gaps a request's
95th percentile stands on: ``write_shoulder_*`` of the access record)
and of the interpreter's collections (``host_gc_*`` of the engine's
snapshot): each reader file loads, names a reader kind the harness has
and a number the program really reports, reads it from a synthetic run,
and stands once in the committed ``BENCHMARK.json`` with cells that all
report the metric it moves; a traced toy run prints all ten."""

import json
import os

import pytest

from benchmarks.lib import reducers
from benchmarks.lib.spec import Spec
from tests.benchmarks.toy import REPO

# metric -> (layer, access-record field, scale, unit)
LEGS = {
    "serve_write_shoulder_gap_ms": (
        "gateway", "write_shoulder_gap_s", 1000.0, "ms"),
    "serve_write_shoulder_emit_ms": (
        "engine worker", "write_shoulder_emit_s", 1000.0, "ms"),
    "serve_write_shoulder_held_ms": (
        "engine worker", "write_shoulder_held_s", 1000.0, "ms"),
    "serve_write_shoulder_wake_ms": (
        "gateway", "write_shoulder_wake_s", 1000.0, "ms"),
    "serve_write_shoulder_pauses_ms": (
        "gateway", "write_shoulder_pauses_s", 1000.0, "ms"),
    "serve_write_shoulder_writes_ms": (
        "gateway", "write_shoulder_writes_s", 1000.0, "ms"),
    "serve_write_shoulder_place_moved_pct": (
        "gateway", "write_shoulder_place_moved_share", 100.0, "%"),
}
# metric -> (snapshot key, scale, unit)
COLLECTIONS = {
    "serve_host_gc_pause_ms": ("host_gc_pause_s", 1000.0, "ms"),
    "serve_host_gc_full_collections": (
        "host_gc_full_collections", 1.0, "collections"),
    "serve_host_gc_full_pause_ms": ("host_gc_full_pause_s", 1000.0, "ms"),
}
CELL = "serve-1.7b-longgen"


def index():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def reader(name):
    """The metric as the harness merges it for a cell that lists it."""
    (metric,) = [m for m in Spec(REPO).per_layer(CELL) if m["name"] == name]
    return metric


def cells_that_report(moved):
    (metric,) = [m for m in index()["end_to_end"] if m["name"] == moved]
    return metric["workloads"]


@pytest.mark.parametrize("name", sorted(LEGS))
def test_a_leg_metric_reads_the_median_of_a_field_the_gateway_returns(name):
    from scaletorch_tpu.serving.gateway import _delivery_fields, _Pending
    from scaletorch_tpu.serving.protocol import GenerateRequest

    _, field, scale, _ = LEGS[name]
    metric = reader(name)
    assert metric["what"]
    assert metric["reducer"] == {
        "kind": "record_percentile", "records": "access", "field": field,
        "percentile": 50, "scale": scale}
    assert metric["reducer"]["kind"] in reducers.KINDS
    # the field is one ``_delivery_fields`` returns, null or not
    pending = _Pending(GenerateRequest(prompt=[1], max_new_tokens=4),
                       deadline=None)
    assert field in _delivery_fields(pending)
    # five requests of the window, one of a single token (null), and a
    # record of a program without the stamps: a signed leg's median may
    # be negative
    access = [{field: v, "measured": True}
              for v in (0.0009, -0.0004, None, 0.0002, 0.0003, 0.0011)]
    access.append({"tokens": 5, "measured": True})
    ctx = {"records": {"access": access}, "counters": {}}
    assert reducers.read_metric(ctx, metric) == pytest.approx(0.0003 * scale)
    ctx = {"records": {"access": [{"tokens": 5}, {"tokens": 9}]}}
    assert reducers.read_metric(ctx, metric) is None
    assert reducers.read_metric({"records": {}}, metric) is None


@pytest.mark.parametrize("name", sorted(COLLECTIONS))
def test_a_collection_metric_reads_a_key_the_snapshot_has(name):
    from scaletorch_tpu.inference.engine import EngineMetrics

    key, scale, _ = COLLECTIONS[name]
    metric = reader(name)
    assert metric["what"]
    want = {"kind": "counter", "key": f"engine.{key}"}
    if scale != 1.0:
        want["scale"] = scale
    assert metric["reducer"] == want
    # ``engine.<name>`` is every number of the engine's snapshot
    assert isinstance(EngineMetrics().snapshot()[key], (int, float))
    ctx = {"counters": {f"engine.{key}": 0.25, "engine.decode_steps": 5000}}
    assert reducers.read_metric(ctx, metric) == pytest.approx(0.25 * scale)
    ctx["counters"][f"engine.{key}"] = 0
    assert reducers.read_metric(ctx, metric) == 0
    # the parent's snapshot has no such key: nothing to read, no raise
    assert reducers.read_metric({"counters": {}}, metric) is None


@pytest.mark.parametrize("name", sorted({**LEGS, **COLLECTIONS}))
def test_a_new_metric_is_listed_once_with_cells_that_report_what_it_moves(
        name):
    entries = [m for m in index()["per_layer"] if m["name"] == name]
    assert len(entries) == 1
    (entry,) = entries
    if name in LEGS:
        layer, _, _, unit = LEGS[name]
        moves = "serve_itl_p95_ms"
    else:
        layer, unit = "engine worker", COLLECTIONS[name][2]
        moves = "serve_itl_p99_ms"
    cells = entry.pop("workloads")
    assert entry == {"name": name, "unit": unit, "better": "lower",
                     "source": "program_counter", "layer": layer,
                     "moves": moves}
    # all eight cells held to the metric it moves (a later cell is
    # appended after them), and none that is not
    assert set(cells) <= set(cells_that_report(moves))
    assert len(cells) >= 8 and len(cells) == len(set(cells))
    assert os.path.isfile(os.path.join(
        REPO, "benchmarks", "metrics", f"{name}.json"))


def test_the_five_parts_of_a_record_sum_to_its_gap():
    """What the readers' medians come from: per access record the emit
    gap and the four legs' changes are the write gap (an identity of
    the stamps; the medians over requests need not sum)."""
    from scaletorch_tpu.serving.gateway import _delivery_fields, _Pending
    from scaletorch_tpu.serving.protocol import GenerateRequest

    pending = _Pending(GenerateRequest(prompt=[1], max_new_tokens=64),
                       deadline=None)
    t = 7000.0
    for k in range(40):
        t += 0.0069 + 0.0003 * (k % 5 == 0) + (0.013 if k == 17 else 0.0)
        held = 0.0011 + 0.0002 * (k % 3)
        wake = 0.0002 + 0.0001 * (k % 4 == 1)
        slept = 0.00036 * (4 + k % 2)
        writes = 0.00011 * (12 - (k > 20))
        pending.emitted_ts.append(t)
        pending.posted_ts.append(t + held)
        pending.drained_ts.append(t + held + wake)
        pending.slept_ss.append(slept)
        pending.places.append(12 - (k > 20))
        pending.written_ts.append(t + held + wake + slept + writes)
    got = _delivery_fields(pending)
    parts = sum(got[LEGS[name][1]] for name in LEGS
                if name not in ("serve_write_shoulder_gap_ms",
                                "serve_write_shoulder_place_moved_pct"))
    assert parts == pytest.approx(got["write_shoulder_gap_s"], abs=1e-9)
    assert 0.0069 < got["write_shoulder_emit_s"] < 0.0075
    assert got["write_shoulder_gap_s"] > got["write_shoulder_emit_s"]


def test_a_traced_toy_run_prints_the_ten_metrics(tmp_path):
    """Gateway and engine -> access records and snapshot -> readers ->
    the result line, on the CPU at toy size (closed loop, 4 streams of
    12-24 tokens)."""
    from tests.benchmarks.helpers import run_cell
    from tests.benchmarks.toy import make_toy_root

    root = make_toy_root(str(tmp_path / "toy"), serve_kind="closed_loop")
    rc, line, out = run_cell(
        ["--root", root, "--workload", "toy-serve", "--seed",
         str(2**31 + 56), "--seconds", "2", "--trace", "1", "--rehearse"])
    assert rc == 3, out
    metrics = line["metrics"]
    assert set(LEGS) | set(COLLECTIONS) <= set(metrics), out
    for name, (_, _, _, unit) in LEGS.items():
        assert metrics[name]["unit"] == unit
    for name, (_, _, unit) in COLLECTIONS.items():
        assert metrics[name]["unit"] == unit
        assert metrics[name]["value"] >= 0
    assert metrics["serve_write_shoulder_gap_ms"]["value"] > 0
    assert metrics["serve_write_shoulder_emit_ms"]["value"] > 0
    assert 0 <= metrics["serve_write_shoulder_place_moved_pct"]["value"] <= 100
    # a toy window allocates enough for young collections to run in it
    assert metrics["serve_host_gc_pause_ms"]["value"] > 0
    assert metrics["serve_host_gc_full_pause_ms"]["value"] \
        <= metrics["serve_host_gc_pause_ms"]["value"]
