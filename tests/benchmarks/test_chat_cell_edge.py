"""The chat cell and the edge its percentile stands on (PR 41).

A gap between two tokens of a stream is one tick or, where the engine
admitted a request in between, one prefill call plus a tick. A nearest-
rank percentile of the gaps is one real gap: the 99th reads the long
population while its share is over 1 % and the short one under it, and
a cell whose share is near 1 % reads the one or the other by the seed
(ledger, PR 40: 10 ms or 518 ms, spread 190 %). So the committed cell
runs at four fifths of the knee its traffic file names, which puts the
share at 2.5 x the tail or more, and the share itself is a per-layer
metric of every serving cell.
"""

import json
import math
import os
import re

import numpy as np
import pytest

from benchmarks.lib import reducers, serve_cell, trace
from benchmarks.lib.spec import Spec
from tests.benchmarks.toy import REPO

SPEC = Spec()
CELL = "serve-1.7b-chat"
SHARE_METRIC = "serve_itl_long_gap_share_pct"
SERVING_CELLS = [w["name"] for w in SPEC.index["workloads"]
                 if w["name"].startswith("serve-")]
# the tail of the percentile a cell is held to may be at most this
# share of its long gaps' share (benchmarks/README.md)
CLEAR_BY = 2.5
TICK_S, STALL_S = 0.007, 0.500


def held_metric():
    """The end-to-end gap percentile the chat cell is held to."""
    held = [m for m in SPEC.end_to_end(CELL)
            if re.fullmatch(r"serve_itl_p\d+_ms", m["name"])]
    assert len(held) == 1, [m["name"] for m in held]
    return held[0]


def tail_pct(name: str) -> float:
    """serve_itl_p99_ms -> 1.0, serve_itl_p995_ms -> 0.5."""
    digits = re.fullmatch(r"serve_itl_p(\d+)_ms", name).group(1)
    return 100.0 - float(f"{digits[:2]}.{digits[2:]}")


# -- (i) the committed cell ---------------------------------------------------

def test_the_chat_cell_names_its_traffic_in_all_three_places():
    cell = SPEC.workload(CELL)
    with open(os.path.join(REPO, "benchmarks", "workloads",
                           f"{CELL}.json")) as f:
        assert json.load(f)["traffic"] == cell["traffic"]
    assert os.path.isfile(os.path.join(
        REPO, "benchmarks", "traffic", f"{cell['traffic']}.json"))
    assert not os.path.exists(os.path.join(
        REPO, "benchmarks", "traffic", "chat-open-loop-r0.8.json"))


def test_the_rate_is_the_one_in_the_files_name():
    name = SPEC.workload(CELL)["traffic"]
    mix = SPEC.traffic(name)
    assert name == f"chat-open-loop-r{mix['rate_per_s']}"
    assert mix["kind"] == "open_loop_stratified"
    assert f"{mix['rate_per_s']}/s" in SPEC._entry("workloads", CELL)["why"]


def test_the_rate_is_four_fifths_of_the_knee_the_note_names():
    mix = SPEC.traffic(SPEC.workload(CELL)["traffic"])
    knee = float(mix["knee_per_s"])
    assert re.search(rf"knee[^.;]*\b{re.escape(str(knee))}/s", mix["note"])
    assert "PR 41" in mix["note"]
    # rounded down to 0.1/s
    assert mix["rate_per_s"] == math.floor(0.8 * knee * 10 + 1e-9) / 10


def test_lengths_and_lead_in_are_the_old_files():
    """The rate moved; the population did not (ISSUE 23's lengths)."""
    mix = SPEC.traffic(SPEC.workload(CELL)["traffic"])
    assert mix["lead_in_s"] == 5.0
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 192,
                                    "sigma": 0.8, "min": 16, "max": 1024}
    assert mix["max_new_tokens"] == {"dist": "lognormal", "median": 96,
                                     "sigma": 0.6, "min": 8, "max": 384}
    assert "ShareGPT" in mix["lengths_source"]


def test_exactly_one_gap_percentile_holds_the_chat_cell():
    assert held_metric()["bound"] == 0.01
    assert tail_pct("serve_itl_p99_ms") == pytest.approx(1.0)
    assert tail_pct("serve_itl_p995_ms") == pytest.approx(0.5)


def share_reader(cell):
    """A per-layer metric names ONE end-to-end metric, so the share has
    a name for the cells held to the 99th percentile and a twin for the
    chat cell, held to the 99.5th: the same reader."""
    name = SHARE_METRIC + (".chat" if cell == CELL else "")
    (entry,) = [m for m in SPEC.per_layer(cell)
                if m["name"].startswith(SHARE_METRIC)]
    assert entry["name"] == name
    return entry


@pytest.mark.parametrize("cell", SERVING_CELLS)
def test_every_serving_cell_reports_its_long_gap_share(cell):
    entry = share_reader(cell)
    held = [m["name"] for m in SPEC.end_to_end(cell)
            if re.fullmatch(r"serve_itl_p99\d?_ms", m["name"])]
    assert [entry["moves"]] == held
    assert entry["unit"] == "%"
    assert entry["reducer"]["kind"] in reducers.UNTRACED_KINDS


def test_the_training_cell_does_not():
    assert SHARE_METRIC not in {
        m["name"].removesuffix(".chat")
        for m in SPEC.per_layer("train-0.6b-seq8k")}


# -- (ii) two populations under a nearest-rank percentile --------------------

def streams(share_pct: float, shuffle: int, n_streams: int = 5,
            per_stream: int = 800):
    """Client records of ``n_streams`` streams whose gaps are dealt, by
    the shuffle, from ONE multiset: ticks of 7 ms (the slowest 10 ms)
    and ``share_pct`` % stalls of 500 ms + a tick. The window cuts
    the streams' ends off, as a run's does, so the share inside it moves
    a little with the shuffle: what a seed does to a cell."""
    n = n_streams * per_stream
    stalls = int(round(n * share_pct / 100.0))
    gaps = np.concatenate([
        np.linspace(TICK_S, TICK_S + 0.003, n - stalls),
        np.full(stalls, STALL_S + TICK_S)])
    gaps = np.random.default_rng([41, shuffle]).permutation(gaps)
    records = []
    for i in range(n_streams):
        times = np.cumsum(gaps[i * per_stream:(i + 1) * per_stream])
        records.append({
            "token_times": times.tolist(), "token_counts": [1] * len(times),
            "measured": True, "due_t": 0.0, "send_t": 0.0,
            "end_t": float(times[-1])})
    return records


def held_reading(share_pct: float, shuffle: int, metric: str):
    records = streams(share_pct, shuffle)
    shortest = min(r["end_t"] for r in records)
    out = serve_cell.client_metrics(records, 0.1 * shortest, 0.9 * shortest,
                                    60.0)
    assert 2000 < out["n_gaps"] < 4000
    return out[metric], out


@pytest.mark.parametrize("share_pct", [2.5, 4.0])
@pytest.mark.parametrize("shuffle", range(20))
def test_clear_of_the_edge_the_held_percentile_reads_a_stall(share_pct,
                                                              shuffle):
    metric = held_metric()["name"]
    # the rule, as a number: the share is CLEAR_BY x the tail or more
    assert share_pct >= CLEAR_BY * tail_pct(metric)
    value, out = held_reading(share_pct, shuffle, metric)
    assert value == pytest.approx(1e3 * (STALL_S + TICK_S))
    assert trace.samples_beyond(
        out["n_gaps"], 100.0 - tail_pct(metric)) >= 10
    assert out["itl_over_3x_median_share_pct"] > tail_pct(metric)


@pytest.mark.parametrize("share_pct", [0.8, 1.0, 1.2])
def test_on_the_edge_the_99th_percentile_reads_both(share_pct):
    """The edge PR 40 was lost on: at a share of 1.0 +- 0.2 % the same
    multiset of gaps reads a tick under one shuffle and a stall under
    another, and no reading lies between the two."""
    values = [held_reading(share_pct, s, "serve_itl_p99_ms")[0]
              for s in range(20)]
    ticks = [v for v in values if v < 1e3 * (TICK_S + 0.004)]
    stalls = [v for v in values if v >= 1e3 * STALL_S]
    assert len(ticks) + len(stalls) == 20
    if share_pct == 1.0:
        assert ticks and stalls
    elif share_pct < 1.0:
        assert len(ticks) > len(stalls)
    else:
        assert len(stalls) > len(ticks)


def test_the_three_shares_together_read_both_populations():
    values = [held_reading(share, s, "serve_itl_p99_ms")[0]
              for share in (0.8, 1.0, 1.2) for s in range(20)]
    spread = (max(values) - min(values)) / float(np.median(values))
    assert min(values) < 11.0 and max(values) > 500.0
    assert spread > 0.9  # of the median: what a 1 % bound was asked to hold


def test_the_share_printed_is_the_share_of_gaps_over_three_medians():
    out = held_reading(4.0, 0, "serve_itl_p99_ms")[1]
    assert 3.0 < out["itl_over_3x_median_share_pct"] < 5.0
    assert out["serve_itl_p50_ms"] < 1e3 * (TICK_S + 0.003)


# -- (iii) the reader kind ----------------------------------------------------

def test_client_value_reads_the_client_view_and_nothing_else():
    params = {"key": "itl_over_3x_median_share_pct"}
    ctx = {"client": {"itl_over_3x_median_share_pct": 3.25},
           "counters": {"itl_over_3x_median_share_pct": 99.0}}
    assert reducers.client_value(ctx, params) == 3.25
    assert reducers.client_value(ctx, dict(params, scale=0.01)) == \
        pytest.approx(0.0325)
    # nothing to read: nothing returned (never 0)
    assert reducers.client_value({"client": {}}, params) is None
    assert reducers.client_value({}, params) is None
    assert reducers.KINDS["client_value"] is reducers.client_value


def test_the_committed_reader_is_a_client_value():
    for cell in (CELL, "serve-1.7b-longgen"):
        reader = share_reader(cell)
        assert reader["reducer"] == {"kind": "client_value",
                                     "key": "itl_over_3x_median_share_pct"}
        assert reducers.read_metric(
            {"client": {"itl_over_3x_median_share_pct": 2.75}},
            reader) == 2.75


CHAT_TWINS = sorted(
    m["name"] for m in SPEC.index["per_layer"] if m["name"].endswith(".chat")
    and any(o["name"] == m["name"][:-len(".chat")]
            for o in SPEC.index["per_layer"]))


@pytest.mark.parametrize("twin", CHAT_TWINS)
def test_a_chat_twin_is_its_original_but_for_the_metric_it_moves(twin):
    (mine,) = [m for m in SPEC.per_layer(CELL) if m["name"] == twin]
    original = twin[:-len(".chat")]
    (theirs,) = [m for m in SPEC.per_layer("serve-1.7b-longgen")
                 + SPEC.per_layer("serve-qwen3-next-longgen")
                 if m["name"] == original][:1]
    assert mine["reducer"] == theirs["reducer"]
    for key in ("unit", "better", "source", "layer"):
        assert mine[key] == theirs[key]
    assert (mine["moves"], theirs["moves"]) == ("serve_itl_p995_ms",
                                                "serve_itl_p99_ms")
    assert mine["workloads"] == [CELL] and CELL not in theirs["workloads"]


def test_the_chat_cell_has_nine_twins():
    assert len(CHAT_TWINS) == 9
