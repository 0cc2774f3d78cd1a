"""The reduction from trace events to numbers, on hand-made events with
hand-worked answers and on the small trace recorded on the v5e."""

import pytest

from benchmarks.lib import reducers, trace
from benchmarks.lib.spec import Spec

SPEC = Spec()
DEV = "/device:TPU:0"
DEV1 = "/device:TPU:1"
HOST = "/host:CPU"


def ev(plane, line, name, start, dur):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": start, "dur_ns": dur}


def op(name, start, dur, plane=DEV):
    return ev(plane, trace.OPS_LINE, name, start, dur)


# window 0..1000 on one device:
#   matmul      [  0, 300)
#   all-reduce  [250, 450)   overlaps the matmul for 50, alone for 150
#   kernel      [500, 700)
#   all-reduce  [650, 800)   overlaps the kernel for 50, alone for 100
#   idle        [450, 500) and [800, 1000) = 250
HAND = [
    op("fusion.1 | fusion | kOutput | bf16[8,8]", 0, 300),
    op("all-reduce.1 | all-reduce | - | f32[8]", 250, 200),
    op("closed_call.2 | custom-call | tpu_custom_call | bf16[8]", 500, 200),
    op("all-reduce.2 | all-reduce | - | f32[8]", 650, 150),
    ev(DEV, trace.MODULES_LINE, "jit_step(1)", 0, 450),
    ev(DEV, trace.MODULES_LINE, "jit_step(1)", 500, 300),
    ev(DEV, trace.MODULES_LINE, "jit_step(1)", 1000, 0),
    ev(HOST, "python3", "train_step", 440, 40),
    ev(HOST, "python3", "loss_readback", 790, 300),
]
WINDOW = (0, 1000)


def ctx(events=HAND, window=WINDOW, **more):
    return dict({"events": events, "window": window, "records": {},
                 "counters": {}, "config": {}, "traffic": {},
                 "workload": {"chips": 1}, "peaks": {}}, **more)


# -- interval arithmetic ------------------------------------------------------

def test_union_merges_overlaps_and_nesting():
    assert trace.union([(5, 9), (0, 3), (2, 4), (6, 7)]) == [(0, 4), (5, 9)]
    assert trace.total(trace.union([(0, 10), (2, 3), (10, 12)])) == 12


def test_subtract_leaves_the_uncovered_parts():
    assert trace.subtract([(0, 10), (20, 30)], [(2, 4), (8, 22)]) == \
        [(0, 2), (4, 8), (22, 30)]
    assert trace.subtract([(0, 10)], []) == [(0, 10)]
    assert trace.subtract([(0, 10)], [(0, 10)]) == []


def test_clip_cuts_events_to_the_window():
    cut = trace.clip([op("a", 90, 30), op("b", 200, 10)], (100, 150))
    assert [(e["start_ns"], e["dur_ns"]) for e in cut] == [(100, 20)]


@pytest.mark.parametrize("n,q,want_rank", [
    (100, 90, 90), (100, 95, 95), (10, 90, 9), (41, 90, 37), (1, 95, 1)])
def test_percentile_is_nearest_rank_on_real_samples(n, q, want_rank):
    values = list(range(1, n + 1))
    assert trace.percentile(values, q) == want_rank
    assert trace.samples_beyond(n, q) == n - want_rank


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        trace.percentile([], 50)


# -- reducers on the hand-made trace -------------------------------------------

def test_busy_union_and_idle_share():
    busy = trace.busy_by_device(HAND, WINDOW)[DEV]
    assert busy == [(0, 450), (500, 800)]
    assert reducers.idle_share(ctx(), {}) == pytest.approx(25.0)


def test_share_of_window_by_pattern():
    got = reducers.share_of_window(ctx(), {"patterns": ["tpu_custom_call"]})
    assert got == pytest.approx(20.0)


def test_exposed_collective_is_the_part_no_other_op_covers():
    got = reducers.exposed_share(
        ctx(), {"patterns": [r"\| all-reduce \|"]})
    assert got == pytest.approx(25.0)  # 150 + 100 of 1000


def test_exposed_share_averages_over_devices():
    second = [op("all-reduce.9 | all-reduce | - | f32[8]", 0, 100, DEV1),
              op("fusion.9 | fusion | kLoop | f32[8]", 0, 100, DEV1)]
    got = reducers.exposed_share(
        ctx(HAND + second), {"patterns": [r"\| all-reduce \|"]})
    assert got == pytest.approx(12.5)  # 25 % and 0 %


def test_start_to_start_interval_and_median_duration():
    params = {"line": trace.MODULES_LINE, "patterns": [r"^jit_step\("]}
    # starts 0, 500, 1000 -> intervals 500, 500 ns
    assert reducers.start_interval(ctx(window=(0, 2000)), params) == \
        pytest.approx(500 / 1e6)
    # inside 0..1000: durations 450 and 300 ns
    assert reducers.median_duration(ctx(), params) == pytest.approx(
        375 / 1e6)


def test_roofline_share_charges_each_call_what_it_must_do():
    peaks = {"bf16_flops_per_s": 1e12}
    config = SPEC.config("qwen3-0.6b-train")
    traffic = {"sequence_length": 8192}
    flops = 4 * (8192 * 8193 / 2) * 128 * 16
    params = {"cost_function": "flash_train_call_flops",
              "cost_args": ["config", "seq_local", "seq_total"],
              "peak": "bf16_flops_per_s",
              "terms": [{"patterns": ["tpu_custom_call"],
                         "charge": "forward"}]}
    got = reducers.roofline_share(
        ctx(config=config, traffic=traffic, peaks=peaks), params)
    assert got == pytest.approx(100 * flops / 200e-9 / 1e12)


def test_record_percentile_and_counter():
    rows = [{"queue_wait_s": w, "measured": True} for w in
            (0.1, 0.2, 0.3, 0.4)] + [{"queue_wait_s": 9, "measured": False}]
    c = ctx(records={"access": rows}, counters={"memory_peak_bytes": 2**31})
    assert reducers.record_percentile(c, {
        "records": "access", "field": "queue_wait_s", "percentile": 50,
        "scale": 1000, "where": {"measured": True}}) == pytest.approx(200)
    assert reducers.counter(c, {"key": "memory_peak_bytes",
                                "scale": 2**-30}) == pytest.approx(2.0)


def test_a_reader_with_nothing_to_read_returns_nothing():
    empty = ctx(events=[])
    assert reducers.idle_share(empty, {}) is None
    assert reducers.share_of_window(empty, {"patterns": ["x"]}) is None
    assert reducers.exposed_share(ctx(), {"patterns": ["nothing"]}) is None
    assert reducers.start_interval(ctx(), {
        "line": trace.MODULES_LINE, "patterns": ["^jit_other"]}) is None
    assert reducers.record_percentile(ctx(), {
        "records": "access", "field": "x", "percentile": 50}) is None
    assert reducers.counter(ctx(), {"key": "absent"}) is None


def test_idle_gaps_are_named_by_the_host_span_over_them():
    gaps = trace.idle_gaps_by_host_span(HAND, WINDOW, [".*"])
    # [450, 500) lies under train_step, [800, 1000) under loss_readback
    assert gaps == [["loss_readback", 200 / 1e9], ["train_step", 50 / 1e9]]


def test_top_operations_leave_out_control_flow_wrappers():
    events = HAND + [op("while.4 | while | - | (s32[])", 0, 1000)]
    names = [n for n, _ in trace.top_operations(events, WINDOW)]
    assert not any("while" in n for n in names)
    assert names[0].startswith("fusion.1")


def test_short_name_keeps_what_tells_operations_apart():
    full = ('%closed_call.12 = bf16[16,8,2,128]{3,2,1,0:T(2,128)(2,1)S(1)} '
            'custom-call(s32[16,96]{1,0:T(8,128)S(1)} %copy-done), '
            'custom_call_target="tpu_custom_call", frontend_attributes={}')
    assert trace.short_name(full) == \
        "closed_call.12 | custom-call | tpu_custom_call | bf16[16,8,2,128]"
    assert trace.short_name("jit_decode(123)") == "jit_decode(123)"
    assert trace.short_name(
        "%while.4 = (s32[]{:T(128)}, bf16[2]{0}) while((s32[]) %t), "
        "condition=%c") == "while.4 | while | - | (s32[], bf16[2])"


# -- the traces recorded on the v5e, read by the metric files' own patterns -----

def recorded(name):
    events = trace.load_recorded(SPEC.path("benchmarks", "testdata", name))
    return events, trace.device_window(events)


def metric_reader(cell, name):
    return next(m for m in SPEC.per_layer(cell) if m["name"] == name)


def cell_ctx(cell, events, window, **counters):
    workload = SPEC.workload(cell)
    return {"events": events, "window": window, "records": {},
            "counters": counters, "config": SPEC.config(workload["config"]),
            "traffic": SPEC.traffic(workload["traffic"]),
            "workload": workload, "peaks": SPEC.peaks("TPU v5 lite")}


@pytest.fixture(scope="module")
def train_trace():
    return recorded("train_8k_two_steps.json")


@pytest.fixture(scope="module")
def chat_trace():
    return recorded("chat_two_seconds.json")


def test_recorded_train_trace_holds_two_whole_steps(train_trace):
    events, window = train_trace
    steps = trace.select(events, line=trace.MODULES_LINE,
                         patterns=[r"^jit_step\("])
    whole = [e for e in steps if e["dur_ns"] > 700_000_000]
    assert len(whole) == 2
    assert whole[1]["start_ns"] - whole[0]["start_ns"] == pytest.approx(
        794.86e6, rel=1e-3)
    # inside one whole step: 28 layers x (forward, recomputed forward,
    # dq, dk/dv) Mosaic calls
    lo, hi = whole[0]["start_ns"], whole[0]["start_ns"] + whole[0]["dur_ns"]
    calls = [e for e in trace.select(
        events, line=trace.OPS_LINE,
        patterns=[r"\| custom-call \| tpu_custom_call \|"])
        if lo <= e["start_ns"] < hi]
    assert len(calls) == 4 * 28


def test_train_metric_files_read_the_recorded_trace(train_trace):
    events, window = train_trace
    ctx_ = cell_ctx("train-0.6b-seq8k", events, window)
    share = reducers.read_metric(
        ctx_, metric_reader("train-0.6b-seq8k", "train_attn_kernel_share"))
    roofline = reducers.read_metric(
        ctx_, metric_reader("train-0.6b-seq8k", "train_attn_roofline"))
    idle = reducers.read_metric(
        ctx_, metric_reader("train-0.6b-seq8k", "train_device_idle_share"))
    # the full trace read 55.2 %, 35.6 % and 0.003 % (PERF.md); this
    # cut starts and ends mid-step and lacks the operations under 50 us
    assert 45 < share < 60
    assert 30 < roofline < 40
    assert 0 <= idle < 5


def test_roofline_charges_forward_and_backward_calls_apart(train_trace):
    events, window = train_trace
    reader = metric_reader("train-0.6b-seq8k", "train_attn_roofline")
    forward, backward = reader["reducer"]["terms"]
    plane = trace.device_planes(events)[0]
    n_fwd = len(trace.select(events, plane=plane, line=trace.OPS_LINE,
                             patterns=forward["patterns"]))
    n_bwd = len(trace.select(events, plane=plane, line=trace.OPS_LINE,
                             patterns=backward["patterns"]))
    # two forward calls (one recomputed) for each dq + dk/dv pair; the
    # cut starts and ends mid-step, so the counts differ by part of a step
    assert n_fwd > 100 and abs(n_fwd - n_bwd) < 28
    assert backward["events_per_call"] == 2


def test_chat_metric_files_read_the_recorded_trace(chat_trace):
    events, window = chat_trace
    cell = "serve-1.7b-chat"
    ctx_ = cell_ctx(cell, events, window)
    decode = reducers.read_metric(
        ctx_, metric_reader(cell, "serve_decode_step_device_ms.chat"))
    tick = reducers.read_metric(
        ctx_, metric_reader(cell, "serve_tick_interval_p50_ms.chat"))
    prefill = reducers.read_metric(
        ctx_, metric_reader(cell, "serve_prefill_device_share.chat"))
    assert decode == pytest.approx(96.0, abs=1.0)
    assert decode < tick < decode + 10     # the host's part of a tick
    assert 25 < prefill < 70               # one or two 0.64-0.75 s calls in 2 s


def test_paged_kernel_roofline_on_the_recorded_trace(chat_trace):
    events, window = chat_trace
    cell = "serve-1.7b-longgen"
    reader = metric_reader(cell, "serve_paged_attn_roofline")
    got = reducers.read_metric(
        cell_ctx(cell, events, window, live_tokens_mean=4000.0), reader)
    # 4,000 live tokens: 16.4 MB of K/V per call, 20 us at 819 GB/s,
    # against calls of about 2 ms
    assert 0.3 < got < 3.0
    calls = trace.select(events, line=trace.OPS_LINE,
                         patterns=reader["reducer"]["terms"][0]["patterns"])
    assert len(calls) % 28 == 0 or len(calls) > 28


def test_idle_gaps_on_the_recorded_chat_trace_fall_under_the_tick(chat_trace):
    events, window = chat_trace
    gaps = trace.idle_gaps_by_host_span(
        events, window, SPEC.workload("serve-1.7b-chat")["host_spans"])
    assert gaps and gaps[0][0] == "engine.tick"
    assert sum(seconds for _, seconds in gaps) < 0.2 * (
        window[1] - window[0]) / 1e9
