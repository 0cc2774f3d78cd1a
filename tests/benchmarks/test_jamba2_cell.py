"""The jamba (AI21-Jamba2-3B) family in the benchmark. First the index:
the committed ``BENCHMARK.json`` holds the configuration's and the
cell's entries and ``Spec`` loads the files they name (membership, never
a place in a list). Then the configuration against the catalog's row,
the cost module against hand arithmetic, the readers against names
recorded on the v5e (PR 47's traced run), and a toy tree with the
published ``config.json`` key names, ``benchmarks/reference/jamba.py``
and every ``wrong=`` the reference offers through ``run.py --root
--rehearse`` to its result line."""

import json
import os

import pytest

from tests.benchmarks.helpers import CONTRACT_KEYS, run_cell
from tests.benchmarks.toy import REPO, TOY_MODEL, make_toy_root

SEED = str(2**31 + 47)
REFERENCE = "benchmarks/reference/jamba.py"
COSTS = "benchmarks/costs/jamba.py"
REAL_CELL = "serve-jamba2-3b-longprompt"
REAL_CONFIG = "jamba2-3b-serve"
TRAFFIC = "longprompt-closed8"
SOURCE = "https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json"
TOY_CELL = "toy-jamba-serve"
TOY_JAMBA = {
    "model_type": "jamba", "vocab_size": 128, "hidden_size": 64,
    "intermediate_size": 96, "num_hidden_layers": 8,
    "num_attention_heads": 4, "num_key_value_heads": 1,
    "attn_layer_period": 4, "attn_layer_offset": 1,
    "expert_layer_period": 2, "expert_layer_offset": 1,
    "num_experts": 1, "num_experts_per_tok": 1,
    "mamba_d_state": 8, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_dt_rank": 8, "mamba_conv_bias": True, "mamba_proj_bias": False,
    "use_mamba_kernels": True, "sliding_window": None,
    "max_position_embeddings": 4096, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": True, "reduced": [], "assumed": {},
}
# what the real cell lists, and every departure the reference offers.
# ``bf16_state`` is OFF the cell's list: on the chip it read 1.02 x the
# limit on its lowest seed of sixteen, so the cell cannot hold the
# float32 state; the toy cell below and tests/models/test_jamba.py do,
# and tests/test_paged_kernel_aot.py reads its type off the compiled
# step programs
CELL_WRONG = ["no_inner_norms", "rope_on_attention", "fp8_activations"]
WRONG = CELL_WRONG + ["bf16_state", "conv_bias_dropped", "dt_bias_dropped"]
NEW_READERS = [
    "serve_jamba_decode_step_hbm_roofline", "serve_jamba_ssm_scan_share",
    "serve_jamba_ssm_scan_roofline",
    "serve_device_idle_share.jamba2-longprompt"]
SHARED_METRICS = [
    "serve_itl_p95_ms", "serve_itl_p99_ms", "serve_prefill_device_share",
    "serve_tick_interval_p50_ms", "serve_decode_step_device_ms",
    "serve_req_host_ms_per_token", "serve_req_device_wait_ms_per_token",
    "serve_req_stall_ms_per_token", "serve_prefill_wall_p50_ms",
    "serve_itl_long_gap_share_pct",
    "serve_recurrent_state_owner_mismatches"]
# the catalog's row (model-configs guide, AI21-Jamba2-3B): its config
PUBLISHED = {
    "attn_layer_offset": 7, "attn_layer_period": 14,
    "expert_layer_offset": 1, "expert_layer_period": 2,
    "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 8192,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 16,
    "mamba_dt_rank": 160, "mamba_expand": 2, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "model_type": "jamba",
    "num_attention_heads": 20, "num_experts": 1, "num_experts_per_tok": 1,
    "num_hidden_layers": 28, "num_key_value_heads": 1,
    "num_logits_to_keep": 1, "rms_norm_eps": 1e-06, "sliding_window": None,
    "tie_word_embeddings": True, "use_mamba_kernels": True,
    "vocab_size": 65536}
# names as the v5e's trace printed them (PR 47, the cell's traced run
# and the compiled decode program), shortened by lib/trace.short_name
SCAN_KERNEL = ("ssm_scan_fwd.12 | custom-call | tpu_custom_call | "
               "(f32[8,3072,5120], f32[8,16,40,128])")
DECODE_READ = "fusion.444 | fusion | kLoop | f32[8,40,128]"
DECODE_WRITE = ("select_dynamic-update-slice_fusion.4 | fusion | kLoop | "
                "f32[26,8,16,40,128]")
NOT_THE_SCAN = [
    "fusion.323 | fusion | kOutput | bf16[8,3072,10240]",
    "fusion.324 | fusion | kLoop | (f32[8,3072,5120], bf16[8,3072,5120])",
    "fusion.443 | fusion | kOutput | (f32[8,40,128], f32[8,40,128])",
    "flash_fwd.11 | custom-call | tpu_custom_call | "
    "(bf16[8,20,3072,128], f32[8,20,1,3072])",
    "paged_decode.9 | custom-call | tpu_custom_call | bf16[8,1,20,128]",
    "while.75 | while | - | (s32[], bf16[8,3072,2560], "
    "f32[26,8,16,40,128], bf16[26,8,3,5120])"]


def _real(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


# ---- step 0: the index --------------------------------------------------------

def test_the_committed_benchmark_holds_the_two_entries_and_their_files():
    from benchmarks.lib.spec import Spec

    index = _real("BENCHMARK.json")
    entry = [c for c in index["configs"] if c["name"] == REAL_CONFIG]
    assert len(entry) == 1, [c["name"] for c in index["configs"]]
    entry = entry[0]
    assert entry["source"] == SOURCE and len(SOURCE) <= 200
    assert entry["file"] == f"benchmarks/configs/{REAL_CONFIG}.json"
    assert entry["reduced"] == []
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    cell = [w for w in index["workloads"] if w["name"] == REAL_CELL]
    assert len(cell) == 1, [w["name"] for w in index["workloads"]]
    assert cell[0] == dict(cell[0], config=REAL_CONFIG, traffic=TRAFFIC,
                           chips=1)
    assert set(cell[0]) == {"name", "config", "traffic", "chips", "why"}
    assert all(len(e["why"]) <= 200 for e in (entry, cell[0]))
    for path in (entry["file"], f"benchmarks/workloads/{REAL_CELL}.json",
                 f"benchmarks/traffic/{TRAFFIC}.json", REFERENCE, COSTS,
                 "benchmarks/costs/jamba.md"):
        assert os.path.isfile(os.path.join(REPO, path)), path
    spec = Spec()
    config = spec.config(REAL_CONFIG)
    loaded = spec.workload(REAL_CELL)
    assert config["name"] == REAL_CONFIG and config["source"] == SOURCE
    assert (loaded["config"], loaded["traffic"], loaded["chips"],
            loaded["kind"]) == (REAL_CONFIG, TRAFFIC, 1, "serve")
    assert set(NEW_READERS) <= {m["name"] for m in index["per_layer"]}
    # two cells on the long-prompt traffic, every cell on one chip
    assert sum(w["traffic"] == TRAFFIC for w in index["workloads"]) == 2
    assert sum(w["chips"] == 4 for w in index["workloads"]) <= max(
        1, len(index["workloads"]) // 4)
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024


def test_the_cell_reports_what_the_index_says():
    from benchmarks.lib import modules
    from benchmarks.lib.spec import Spec

    spec = Spec()
    config = spec.config(REAL_CONFIG)
    reference = modules.reference_of(spec, config)
    assert reference.__file__.endswith(REFERENCE)
    assert reference.GAIN_KEYS
    names = [m["name"] for m in spec.per_layer(REAL_CELL)]
    assert set(NEW_READERS) <= set(names)
    # NOT on the shared kernel metric: costs.paged_decode_kv_bytes
    # charges every layer the whole context, and this model's two calls
    # a step over 12 MB are latency, not bandwidth (costs/jamba.md)
    assert "serve_paged_attn_roofline" not in names
    assert {m["name"] for m in spec.end_to_end(REAL_CELL)} == {
        "serve_itl_p95_ms", "serve_itl_p99_ms", "setup_s"}
    index = _real("BENCHMARK.json")
    reported = {m["name"] for m in spec.end_to_end(REAL_CELL)}
    for metric in index["end_to_end"] + index["per_layer"]:
        if metric["name"] in SHARED_METRICS:
            assert REAL_CELL in metric["workloads"], metric["name"]
        if metric["name"] in NEW_READERS:
            assert metric["workloads"] == [REAL_CELL]
            assert set(metric) == {"name", "unit", "better", "source",
                                   "layer", "moves", "workloads"}
        if REAL_CELL in metric.get("workloads", []) and "moves" in metric:
            assert metric["moves"] in reported, metric["name"]
    by_name = {m["name"]: m for m in index["per_layer"]}
    assert by_name["serve_jamba_decode_step_hbm_roofline"]["moves"] == \
        "serve_itl_p95_ms"
    assert by_name["serve_jamba_ssm_scan_share"]["moves"] == \
        by_name["serve_jamba_ssm_scan_roofline"]["moves"] == \
        "serve_itl_p99_ms"
    # one new layer name, shared by the scan's two metrics
    assert by_name["serve_jamba_ssm_scan_share"]["layer"] == \
        by_name["serve_jamba_ssm_scan_roofline"]["layer"] == \
        "state-space layer"


@pytest.mark.parametrize("name", NEW_READERS)
def test_every_new_reader_is_a_kind_the_harness_has(name):
    from benchmarks.lib import modules, reducers
    from benchmarks.lib.spec import Spec

    spec = Spec()
    reader = [m for m in spec.per_layer(REAL_CELL) if m["name"] == name][0]
    reducer = reader["reducer"]
    assert reducer["kind"] in reducers.KINDS
    if reducer["kind"] == "roofline_share":
        assert reducer["cost_module"] == COSTS
        assert reader["name"].endswith("_roofline") and reader["unit"] == "%"
        modules.cost_function(spec, reducer["cost_function"], COSTS)
    # nothing to read (no trace, no counter: the parent's program): the
    # metric is left out, nothing raises
    empty = {"events": [], "window": None, "records": {}, "counters": {},
             "config": spec.config(REAL_CONFIG), "traffic": {},
             "workload": spec.workload(REAL_CELL), "peaks": {},
             "spec": spec}
    assert reducers.read_metric(empty, reader) is None


# ---- the readers against recorded names --------------------------------------

def _ctx(events, window):
    from benchmarks.lib.spec import Spec

    spec = Spec()
    return {"events": events, "window": window, "records": {},
            "counters": {"live_tokens_mean": 22000.0},
            "config": spec.config(REAL_CONFIG), "traffic": {},
            "workload": spec.workload(REAL_CELL),
            "peaks": {"hbm_bytes_per_s": 819e9}, "spec": spec}


def _reader(name):
    from benchmarks.lib.spec import Spec

    return [m for m in Spec().per_layer(REAL_CELL) if m["name"] == name][0]


def _op(name, start, dur, line="XLA Ops"):
    return {"plane": "/device:TPU:0", "line": line, "name": name,
            "start_ns": start, "dur_ns": dur}


def test_the_scan_share_counts_the_kernel_and_the_decode_update_alone():
    """A window of 1,000,000 ns: one prefill kernel call of 200,000, a
    decode step's read (30,000) and write (20,000) of the state, and
    600,000 of what is NOT the scan (projections, the convolution, the
    step projection's pair, both attention kernels, the layer loop that
    spans everything)."""
    from benchmarks.lib import reducers

    events = [_op(SCAN_KERNEL, 0, 200_000), _op(DECODE_READ, 300_000, 30_000),
              _op(DECODE_WRITE, 330_000, 20_000)]
    events += [_op(name, 400_000 + 100_000 * i, 100_000)
               for i, name in enumerate(NOT_THE_SCAN[:-1])]
    events.append(_op(NOT_THE_SCAN[-1], 0, 1_000_000))
    share = reducers.read_metric(_ctx(events, (0, 1_000_000)),
                                 _reader("serve_jamba_ssm_scan_share"))
    assert share == pytest.approx(25.0)


def test_the_scan_roofline_charges_each_kernel_call_its_bytes():
    """Two kernel calls of 5.9 ms each (the v5e took 5.2-5.9, PR 47):
    1.267 GB a call over 5.9 ms over 819 GB/s = 26 %; the decode update
    is not in it."""
    from benchmarks.lib import reducers

    events = [_op(SCAN_KERNEL, 0, 5_900_000),
              _op(SCAN_KERNEL.replace(".12", ".13"), 6_000_000, 5_900_000),
              _op(DECODE_WRITE, 12_000_000, 20_000)]
    share = reducers.read_metric(_ctx(events, (0, 13_000_000)),
                                 _reader("serve_jamba_ssm_scan_roofline"))
    assert share == pytest.approx(100 * 1_266_679_808 / 5.9e-3 / 819e9)
    assert 25 < share < 27


def test_the_step_roofline_reads_the_decode_program_on_the_modules_line():
    from benchmarks.lib import reducers

    events = [_op("jit_decode(7347424983595633825)", 0, 9_300_000,
                  line="XLA Modules"),
              _op("jit_prefill(4801700397201107661)", 10_000_000,
                  1_300_000_000, line="XLA Modules"),
              _op(DECODE_WRITE, 100, 20_000)]
    share = reducers.read_metric(
        _ctx(events, (0, 1_400_000_000)),
        _reader("serve_jamba_decode_step_hbm_roofline"))
    want = 6_058_674_944 + 22000 * 1024 + 26 * 2 * (2_621_440 + 245_760)
    assert share == pytest.approx(100 * want / 9.3e-3 / 819e9)
    assert 79 < share < 83


# ---- the configuration, the cell, the costs ------------------------------------

def test_the_real_configuration_keeps_every_published_key():
    """Every key of the catalog's row under the same name and value,
    nothing reduced, the whole model on the chip."""
    config = _real("benchmarks", "configs", f"{REAL_CONFIG}.json")
    assert {k: config.get(k, "-") for k in PUBLISHED} == PUBLISHED
    assert config["reduced"] == []
    assert config["reference"] == REFERENCE
    assert config["serve"] == {
        "dtype": "bfloat16", "max_slots": 8, "max_seq": 3456,
        "prefill_len": 3072, "page_size": 16}
    for key in ("reduced_how", "deployment", "memory_arithmetic", "assumed"):
        assert config[key], key
    assert "one chip holds the whole model" in config["deployment"]
    for key in ("equations_from", "head_dim", "layer_order", "block",
                "positions", "attention", "mamba", "state_layout",
                "precision", "weights", "sampling"):
        assert config["assumed"][key], key
    assert "3,029,337,472" in config["memory_arithmetic"]["weights_bytes"]
    assert "measured" in config["memory_arithmetic"]


def test_the_traffic_fits_the_serve_shapes():
    traffic = _real("benchmarks", "traffic", f"{TRAFFIC}.json")
    serve = _real("benchmarks", "configs", f"{REAL_CONFIG}.json")["serve"]
    assert traffic["clients"] == serve["max_slots"]
    assert traffic["prompt_tokens"]["max"] == serve["prefill_len"]
    assert serve["max_seq"] >= 3072 + 320 and serve["max_seq"] >= 3072 + 64


def test_the_real_cell_checks_what_the_issue_names():
    cell = _real("benchmarks", "workloads", f"{REAL_CELL}.json")
    other = _real("benchmarks", "workloads",
                  "serve-trinity-mini-longprompt.json")
    for key in ("expect", "trace_seconds", "host_spans", "launch", "chips",
                "traffic"):
        assert cell[key] == other[key], key
    assert cell["wrong_variants"] == CELL_WRONG
    from benchmarks.reference import jamba

    assert set(WRONG) == set(jamba.WRONG)
    check = cell["check"]
    assert {k: check[k] for k in ("prompts", "decode_positions",
                                  "q_block")} == {
        "prompts": 8, "decode_positions": 64, "q_block": 64}
    assert check["prompts"] % check["seq_batch"] == 0
    assert str(check["rtol_of_max"]) in cell["check_why"]
    for variant in CELL_WRONG:
        assert variant in cell["check_why"], variant
    # the float32 state is not this cell's to hold, and it says so
    assert "bf16_state" not in cell["wrong_variants"]
    assert "bf16_state" in cell["check_why"]
    assert "CPU tests" in cell["check_why"]
    assert (3072 + 64) % check["q_block"] == 0


def test_the_program_builds_the_model_from_the_file():
    from benchmarks.lib.program import serving_model
    from scaletorch_tpu.models import jamba

    config = _real("benchmarks", "configs", f"{REAL_CONFIG}.json")
    cfg, init = serving_model(config, "bfloat16")
    assert isinstance(cfg, jamba.JambaConfig) and init is jamba.init_params
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size,
            cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.actual_head_dim, cfg.mamba_inner, cfg.mamba_d_state,
            cfg.mamba_dt_rank, cfg.mamba_d_conv, cfg.rms_norm_eps) == (
        2560, 8192, 65536, 20, 1, 128, 5120, 16, 160, 4, 1e-6)
    assert cfg.embed_init_std == 0.02 and cfg.rope_theta is None
    with pytest.raises(NotImplementedError, match="embed_init_std"):
        serving_model(dict(TOY_MODEL, embed_init_std=1.0), "float32")


def test_the_attention_scores_scale_is_the_initialiser_s_and_stated():
    """``W_q`` and ``W_k`` at twice their fan-in bound is a constant of
    the program's initialiser (``jamba.QK_INIT_SCALE``), stated under
    ``assumed.weights`` with the sweep that found it in ``check_data``;
    the file hands the program no scale of its own."""
    from benchmarks.lib.program import launch_arguments
    from scaletorch_tpu.models import jamba

    config = _real("benchmarks", "configs", f"{REAL_CONFIG}.json")
    assert "attn_score_init_gain" not in config
    assert "embed_init_std" not in config
    weights = config["assumed"]["weights"]
    assert "2 x their fan-in bound" in weights
    assert "QK_INIT_SCALE" in weights and jamba.QK_INIT_SCALE == 2.0
    assert "no other scale" not in weights
    assert set(config["check_data"]) == {
        "qk_init_scale", "readings_by_gain", "what_it_cannot_replace"}
    for reading in ("0.0181", "0.119", "0.517", "0.080"):
        assert reading in config["check_data"]["readings_by_gain"]
    assert not hasattr(launch_arguments(config, dtype="bfloat16"),
                       "attn_score_init_gain")


def test_cost_functions_against_hand_counts():
    from benchmarks.lib import modules
    from benchmarks.lib.spec import Spec

    spec = Spec()
    config = spec.config(REAL_CONFIG)

    def cost(name):
        return modules.cost_function(spec, name, COSTS)

    assert cost("scan_dims")(config) == {
        "channels": 5120, "state": 16, "dt_rank": 160, "conv_kernel": 4,
        "conv_bias": True, "attention_layers": 2, "mamba_layers": 26,
        "slots": 8, "prefill_len": 3072}
    mixer = (2560 * 10240 + 4 * 5120 + 5120 + 5120 * 192 + 192
             + 160 * 5120 + 5120 + 16 * 5120 + 5120 + 5120 * 2560)
    assert cost("mamba_mixer_params")(config) == mixer == 41_241_792
    assert cost("attention_mixer_params")(config) == 13_762_560
    assert cost("mlp_params")(config) == 62_914_560 + 5_120
    total = 26 * (mixer + 62_919_680) + 2 * (13_762_560 + 62_919_680) \
        + 65536 * 2560 + 2560
    assert cost("num_params")(config) == total == 3_029_337_472
    assert cost("weight_bytes")(config) == 2 * total        # 6.06 GB
    assert cost("kv_bytes_per_token")(config) == 2 * 2 * 128 * 2 == 1024
    assert cost("state_bytes")(config) == 8 * 16 * 5120 * 4 == 2_621_440
    assert cost("conv_tail_bytes")(config) == 8 * 3 * 5120 * 2 == 245_760
    live = 8 * 2800.0
    step = cost("decode_step_bytes")(config, live)
    assert step == pytest.approx(
        2 * total + 1024 * live + 26 * 2 * (2_621_440 + 245_760))
    assert 6.2e9 < step < 6.3e9
    # the cache is under 3 % of a step's bytes: the architecture's point
    assert (step - 2 * total) / step < 0.03
    rows = 8 * 3072
    call = cost("scan_call_bytes")(config)
    assert call == rows * (5120 * 10 + 2 * 16 * 4) + 2 * 2_621_440
    assert 1.26e9 < call < 1.27e9
    ops = cost("scan_call_vector_ops")(config)
    assert ops["exp"] == rows * 5120 * 16 == 2_013_265_920
    assert ops["f32_ops"] == 6 * ops["exp"] + rows * 5120
    # written the obvious way, exp(dt A) alone would be 8.05 GB a layer
    assert ops["exp"] * 4 == pytest.approx(8.05e9, rel=1e-3)


# ---- the toy cell through run.py ---------------------------------------------

def make_jamba_root(root, reference=REFERENCE):
    """The toy tree plus ``toy-jamba-serve``: two periods of (mamba,
    attention, mamba, mamba) with one K/V head, a cell, and its name on
    the ``workloads`` lists the real cell is on."""
    make_toy_root(root, extra_metric=True)
    bench = os.path.join(root, "benchmarks")
    with open(os.path.join(bench, "configs", f"{TOY_CELL}.json"), "w") as f:
        json.dump(dict(
            TOY_JAMBA, name=TOY_CELL, reference=reference,
            source="made up for the tests",
            serve={"max_slots": 4, "max_seq": 128, "prefill_len": 64,
                   "page_size": 16, "dtype": "float32"}), f)
    with open(os.path.join(bench, "workloads", f"{TOY_CELL}.json"),
              "w") as f:
        json.dump({"name": TOY_CELL, "kind": "serve", "config": TOY_CELL,
                   "traffic": "toy-requests", "chips": 1,
                   "trace_seconds": 0.5,
                   "expect": {"decode_compile_count": 1},
                   "wrong_variants": WRONG,
                   "check": {"prompts": 4, "decode_positions": 8,
                             "q_block": 8, "seq_batch": 2,
                             "rtol_of_max": 1e-4}}, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        index = json.load(f)
    real = _real("BENCHMARK.json")
    index["configs"].append({
        "name": TOY_CELL, "source": "made up for the tests",
        "file": f"benchmarks/configs/{TOY_CELL}.json", "reduced": [],
        "why": "toy"})
    index["workloads"].append({
        "name": TOY_CELL, "config": TOY_CELL, "traffic": "toy-requests",
        "chips": 1, "why": "toy"})
    on = {m["name"] for s in ("end_to_end", "per_layer") for m in real[s]
          if REAL_CELL in m.get("workloads", [])}
    for section in ("end_to_end", "per_layer"):
        for metric in index[section]:
            if metric["name"] in on:
                metric["workloads"] = sorted(
                    set(metric["workloads"]) | {TOY_CELL})
            elif metric["name"] == "toy_engine_decode_steps":
                metric["workloads"].append(TOY_CELL)
    with open(path, "w") as f:
        json.dump(index, f)
    return root


def _run(root, trace="0"):
    return run_cell(["--root", root, "--workload", TOY_CELL,
                     "--seed", SEED, "--seconds", "1", "--trace", trace,
                     "--rehearse"])


@pytest.fixture(scope="module")
def own_reference(tmp_path_factory):
    return _run(make_jamba_root(
        str(tmp_path_factory.mktemp("jamba"))), "1")


@pytest.fixture(scope="module")
def qwen3_reference(tmp_path_factory):
    return _run(make_jamba_root(
        str(tmp_path_factory.mktemp("swapped")),
        reference=TOY_MODEL["reference"]))


def test_jamba_cell_walks_to_its_result_line(own_reference):
    rc, line, out = own_reference
    assert rc == 3, out
    assert CONTRACT_KEYS <= set(line), out
    assert line["correct"] is True, out
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["check"]["err_of_max"] < 2e-5, out


@pytest.mark.parametrize("variant", WRONG)
def test_jamba_cell_rejects_each_wrong_variant(own_reference, variant):
    _, line, out = own_reference
    verdict = line["check"]["wrong_variants"][variant]
    assert verdict["ok"] is False, out
    assert verdict["err_of_max"] > 10 * line["check"]["rtol_of_max"]


def test_jamba_cell_reports_the_state_s_counter(own_reference):
    """``engine.recurrent_state_owner_mismatches`` reaches its
    ``counter`` reader with no edit to the harness, and reads 0."""
    _, line, out = own_reference
    metrics = line["metrics"]
    assert metrics["serve_recurrent_state_owner_mismatches"]["value"] == 0, \
        out
    assert metrics["toy_engine_decode_steps"]["value"] > 0
    # a window model's counter has nothing to read here
    assert "serve_window_ring_wraps" not in metrics


def test_jamba_cell_under_the_qwen3_reference_is_not_correct(
        qwen3_reference):
    rc, line, out = qwen3_reference
    assert line.get("correct") is not True, out
    if not line:
        assert rc not in (0, 3), out
