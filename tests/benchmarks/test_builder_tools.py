"""The builders' tools under ``benchmarks/tools/`` (never run by the
driver): what ``read_sets.py`` calls a spread, what ``run_many.py``
calls a sustained rung, and the row a held percentile is placed by."""

import statistics

import pytest

from benchmarks.tools import read_sets, run_many


def test_both_spreads_of_a_set_of_six():
    values = [29.28, 29.61, 29.66, 30.07, 30.09, 30.00]
    s = read_sets.spreads(values)
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert s["n"] == 6 and s["median"] == pytest.approx(29.83)
    # the contract's: the distance between the quartiles over the median
    assert s["iqr_pct"] == pytest.approx(100 * (q3 - q1) / 29.83)
    # the driver's: the range once the run farthest from the median
    # (29.28) is left out
    assert s["trimmed_range"] == pytest.approx(30.09 - 29.61)
    assert s["trimmed_range_pct"] == pytest.approx(
        100 * (30.09 - 29.61) / 29.83)


def test_one_far_off_run_does_not_widen_the_drivers_spread():
    near = [16.40, 16.43, 16.48, 16.51, 16.52]
    assert read_sets.spreads(near + [23.65])["trimmed_range"] == \
        pytest.approx(0.12)
    assert read_sets.spreads(near + [23.65])["max"] == 23.65


def record(correct=True, growth=0.1, ttft_p90=520.0):
    return {"line": {"correct": correct},
            "client_view": {"backlog_growth_s": growth,
                            "serve_ttft_p90_ms": ttft_p90}}


@pytest.mark.parametrize("made, want", [
    (record(), True),
    (record(correct=False), False),
    (record(growth=0.565), False),      # the backlog grows
    (record(growth=None), False),       # too few requests to tell
    (record(ttft_p90=4091.0), False),   # three full-shape calls and over
    ({"rc": 2}, False),                 # no result line at all
])
def test_a_rung_is_sustained_by_pr_41s_rule(made, want):
    assert run_many.sustained(made) is want


def test_a_runs_numbers_are_its_metrics_and_its_bring_up():
    line = {"metrics": {"setup_s": {"value": 16.4, "unit": "s"}},
            "runtime_bringup_s": 12.1}
    assert read_sets.numbers_of({"line": line}) == {
        "setup_s": 16.4, "runtime_bringup_s": 12.1}
    assert read_sets.numbers_of({}) == {}


def test_the_gap_row_says_which_edge_a_percentile_fails():
    view = dict.fromkeys(run_many.GAP_KEYS, 1.0)
    view.update(n_gaps=6341, itl_over_3x_median_share_pct=1.23,
                itl_over_10x_median_share_pct=0.30)
    row = read_sets.gap_row({"seed": 7, "root": "/x/.checkouts/b",
                             "line": {"correct": True, "failed": 0,
                                      "attempted": 63},
                             "client_view": view})
    assert (row["set"], row["failed"]) == ("b", "0/63")
    # the 99th wants 2.5 % over three medians; the 99.5th has 1.25 %
    # nearly and 0.2 % over ten medians against it
    assert row["clear_p99"] == "-FS" and row["clear_p99.5"] == "--S"
    assert set(run_many.GAP_KEYS) <= set(row)
