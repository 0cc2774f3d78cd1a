"""The kimi_linear (Kimi-Linear-48B-A3B) family in the benchmark. First
the index: the committed ``BENCHMARK.json`` holds the configuration's
and the cell's entries and ``Spec`` loads the files they name
(membership, never a place in a list and never a count). Then the
configuration against the catalog's row, the cost module against hand
arithmetic, the readers against the names the compiled step programs
print, and a toy tree with the published ``config.json`` key names,
``benchmarks/reference/kimi_linear.py`` and every ``wrong=`` the
reference offers through ``run.py --root --rehearse`` to its result
line."""

import json
import os
import re

import pytest

from tests.benchmarks.helpers import CONTRACT_KEYS, run_cell
from tests.benchmarks.toy import REPO, TOY_MODEL, make_toy_root

SEED = str(2**31 + 54)
REFERENCE = "benchmarks/reference/kimi_linear.py"
COSTS = "benchmarks/costs/kimi_linear.py"
REAL_CELL = "serve-kimi-linear-longctx"
REAL_CONFIG = "kimi-linear-48b-a3b-serve"
TRAFFIC = "longctx-closed32"
SOURCE = ("https://huggingface.co/moonshotai/"
          "Kimi-Linear-48B-A3B-Instruct/blob/main/config.json")
TOY_CELL = "toy-kimi-serve"
TOY_KIMI = {
    "model_type": "kimi_linear", "vocab_size": 128, "hidden_size": 64,
    "intermediate_size": 96, "num_hidden_layers": 5,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
    "linear_attn_config": {
        "kda_layers": [1, 2, 4], "full_attn_layers": [3, 5],
        "head_dim": 16, "num_heads": 2, "short_conv_kernel_size": 4},
    "q_lora_rank": None, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "rope_theta": 10000,
    "rope_scaling": None, "mla_use_nope": True,
    "first_k_dense_replace": 1, "num_experts": 4,
    "num_routed_experts": 16, "first_expert_id": 4,
    "num_experts_per_token": 3, "moe_intermediate_size": 32,
    "num_shared_experts": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "moe_layer_freq": 1,
    "num_expert_group": 1, "topk_group": 1, "use_grouped_topk": True,
    "routed_scaling_factor": 2.446, "num_nextn_predict_layers": 0,
    "hidden_act": "silu", "model_max_length": 4096,
    "rms_norm_eps": 1e-05, "tie_word_embeddings": False, "reduced": [],
    "assumed": {},
}
REDUCED = ["num_hidden_layers", "linear_attn_config", "num_experts",
           "vocab_size"]
NEW_READERS = [
    "serve_kimi_decode_step_hbm_roofline",
    "serve_kimi_kda_state_update_roofline",
    "serve_kimi_kda_recurrence_share",
    "serve_kimi_latent_attn_hbm_roofline", "serve_kimi_expert_mlp_roofline",
    "serve_kimi_kda_scan_share", "serve_device_idle_share.kimi-longctx",
    "serve_moe_assignments_held.kimi",
    "serve_moe_assignments_elsewhere.kimi",
    "serve_prefill_positions_run.kimi", "serve_latent_keys_attended.kimi"]
SHARED_METRICS = [
    "serve_itl_p95_ms", "serve_itl_p99_ms", "serve_prefill_device_share",
    "serve_tick_interval_p50_ms", "serve_decode_step_device_ms",
    "serve_req_host_ms_per_token", "serve_req_device_wait_ms_per_token",
    "serve_req_stall_ms_per_token", "serve_prefill_wall_p50_ms",
    "serve_itl_long_gap_share_pct", "serve_engine_slow_ticks",
    "serve_moe_dropped_assignments", "serve_moe_expert_mlp_share",
    "serve_recurrent_state_owner_mismatches",
    # the delivery leg: the first cell with 32 streams a tick
    "serve_emit_gap_p95_ms", "serve_deliver_held_ms_per_token",
    "serve_deliver_loop_ms_per_token", "serve_write_gap_p95_ms",
    "serve_deliver_lag_p95_ms"]
# the catalog's row (model-configs guide, Kimi-Linear-48B-A3B-Instruct)
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840}
# names as the compiled step programs print them (AOT for the v5e and the
# chip's traces, PR 54; lib/trace.short_name's form), the readers' own
# and their neighbours
LATENT_KERNEL = ("latent_decode.3 | custom-call | tpu_custom_call | "
                 "bf16[32,32,512]")
FLASH = ("flash_fwd.3 | custom-call | tpu_custom_call | "
         "(bf16[1,32,8192,128], f32[1,32,1,8192])")
GMM_DECODE = ["gmm.12 | custom-call | tpu_custom_call | bf16[256,1024]",
              "gmm.14 | custom-call | tpu_custom_call | bf16[256,2304]"]
GMM_PREFILL = ["gmm.10 | custom-call | tpu_custom_call | bf16[65536,1024]",
               "gmm.11 | custom-call | tpu_custom_call | bf16[65536,2304]"]
PAGE_WRITE = ("paged_write.3 | custom-call | tpu_custom_call | "
              "bf16[2,19457,1,16,640]")
STATE_WRITE = ("select_dynamic-update-slice_fusion.4 | fusion | kLoop | "
               "f32[6,32,32,128,128]")
STATE_SUMS = ("multiply_reduce_fusion.7 | fusion | kLoop | "
              "(f32[32,32,128], f32[32,32,128])")
PREFILL_STATE_WRITE = ("scatter_fusion.2 | fusion | kLoop | "
                       "f32[6,32,32,128,128]")
SCAN_CHUNK = "fusion.2633 | fusion | kOutput | f32[16,1,32,64,256]"
SCAN_PAIRWISE = ("multiply_reduce_fusion.32 | fusion | kLoop | "
                 "f32[16,32,4,16,16]")
SCAN_STATE = "bitcast_add_fusion.28 | fusion | kOutput | f32[1,32,128,128]"
SCAN_LOOP = ("while.469 | while | - | (s32[], f32[1,32,128,128], "
             "bf16[128,1,32,64,128])")
HIDDEN = "fusion.77 | fusion | kOutput | bf16[1,8192,2304]"


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
    assert entry["reduced"] == REDUCED
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    cell = [w for w in index["workloads"] if w["name"] == REAL_CELL]
    assert len(cell) == 1, [w["name"] for w in index["workloads"]]
    assert cell[0] == dict(cell[0], config=REAL_CONFIG, traffic=TRAFFIC,
                           chips=1)
    assert set(cell[0]) == {"name", "config", "traffic", "chips", "why"}
    assert all(len(e["why"]) <= 200 for e in (entry, cell[0]))
    # the routed experts set the step, and the cell's why says so
    assert "experts" in cell[0]["why"] and "set the step" in cell[0]["why"]
    for path in (entry["file"], f"benchmarks/workloads/{REAL_CELL}.json",
                 f"benchmarks/traffic/{TRAFFIC}.json", REFERENCE, COSTS,
                 "benchmarks/costs/kimi_linear.md"):
        assert os.path.isfile(os.path.join(REPO, path)), path
    spec = Spec()
    config = spec.config(REAL_CONFIG)
    loaded = spec.workload(REAL_CELL)
    assert config["name"] == REAL_CONFIG and config["source"] == SOURCE
    assert (loaded["config"], loaded["traffic"], loaded["chips"],
            loaded["kind"]) == (REAL_CONFIG, TRAFFIC, 1, "serve")
    assert set(NEW_READERS) <= {m["name"] for m in index["per_layer"]}
    # one more cell on one chip: no more than a quarter ask for four
    assert sum(w["chips"] == 4 for w in index["workloads"]) <= max(
        1, len(index["workloads"]) // 4)
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024
    # the traffic file is this cell's alone
    assert [w["name"] for w in index["workloads"]
            if w["traffic"] == TRAFFIC] == [REAL_CELL]


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
    # charges K and V of expanded heads
    assert "serve_paged_attn_roofline" not in names
    assert {m["name"] for m in spec.end_to_end(REAL_CELL)} == {
        "serve_itl_p95_ms", "serve_itl_p99_ms", "setup_s"}
    index = _real("BENCHMARK.json")
    reported = {m["name"] for m in spec.end_to_end(REAL_CELL)}
    for metric in index["end_to_end"] + index["per_layer"]:
        if metric["name"] in SHARED_METRICS:
            # on the list, wherever: the next cell is appended after it
            assert REAL_CELL in metric["workloads"], metric["name"]
        if metric["name"] in NEW_READERS:
            assert REAL_CELL in metric["workloads"]
            assert set(metric) == {"name", "unit", "better", "source",
                                   "layer", "moves", "workloads"}
        if REAL_CELL in metric.get("workloads", []) and "moves" in metric:
            assert metric["moves"] in reported, metric["name"]
    layers = {m["layer"] for m in index["per_layer"]
              if m["name"] not in NEW_READERS}
    assert {m["layer"] for m in index["per_layer"]
            if m["name"] in NEW_READERS} <= layers
    # the twins read what the originals read (lists a test pins)
    by_name = {m["name"]: m for m in spec.per_layer(REAL_CELL)}
    for twin, original in (
            ("serve_moe_assignments_held.kimi",
             "serve_moe_assignments_held"),
            ("serve_moe_assignments_elsewhere.kimi",
             "serve_moe_assignments_elsewhere"),
            ("serve_prefill_positions_run.kimi",
             "serve_prefill_positions_run"),
            ("serve_latent_keys_attended.kimi",
             "serve_latent_keys_attended")):
        assert by_name[twin]["reducer"] == _real(
            "benchmarks", "metrics", f"{original}.json")["reducer"]
        assert original not in by_name


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
        # a cost function is handed the configuration and the live
        # tokens: never a number of slots to multiply a call's rows by
        assert set(reducer["cost_args"]) <= {"config", "live_tokens"}
    # nothing to read (no trace, no counter: the parent's program): the
    # metric is left out, nothing raises
    empty = {"events": [], "window": None, "records": {}, "counters": {},
             "config": spec.config(REAL_CONFIG), "traffic": {},
             "workload": spec.workload(REAL_CELL), "peaks": {},
             "spec": spec}
    assert reducers.read_metric(empty, reader) is None


def _patterns(name):
    reducer = _real("benchmarks", "metrics", f"{name}.json")["reducer"]
    if "terms" not in reducer:
        return reducer["patterns"], reducer.get("exclude", [])
    return [p for term in reducer["terms"] for p in term["patterns"]], []


@pytest.mark.parametrize("reader,finds,leaves", [
    ("serve_kimi_latent_attn_hbm_roofline", [LATENT_KERNEL],
     [FLASH, PAGE_WRITE] + GMM_DECODE + GMM_PREFILL),
    ("serve_kimi_expert_mlp_roofline", GMM_DECODE,
     [LATENT_KERNEL, FLASH, PAGE_WRITE] + GMM_PREFILL),
    ("serve_kimi_decode_step_hbm_roofline",
     ["jit_decode(1234567890)"], ["jit_prefill(123)", LATENT_KERNEL]),
    ("serve_kimi_kda_state_update_roofline", [STATE_WRITE, STATE_SUMS],
     [PREFILL_STATE_WRITE, SCAN_CHUNK, PAGE_WRITE, HIDDEN] + GMM_DECODE),
    ("serve_kimi_kda_recurrence_share",
     [STATE_WRITE, STATE_SUMS, SCAN_CHUNK, SCAN_PAIRWISE, SCAN_STATE],
     [PAGE_WRITE, HIDDEN, LATENT_KERNEL, FLASH, SCAN_LOOP] + GMM_DECODE),
    ("serve_kimi_kda_scan_share", [SCAN_CHUNK, SCAN_PAIRWISE, SCAN_STATE],
     [STATE_WRITE, STATE_SUMS, PAGE_WRITE, HIDDEN, LATENT_KERNEL, FLASH,
      SCAN_LOOP] + GMM_PREFILL),
])
def test_the_readers_patterns_find_their_kernels_and_no_other(reader, finds,
                                                             leaves):
    patterns, exclude = _patterns(reader)

    def found(name):
        return any(re.search(p, name) for p in patterns) and not any(
            re.search(p, name) for p in exclude)

    for name in finds:
        assert found(name), (reader, name)
    for name in leaves:
        assert not found(name), (reader, name)


def test_the_real_configuration_keeps_the_published_keys():
    """Every key of the catalog's row under the same name; the depth
    with its layer lists, the experts held and the vocabulary the cuts,
    each at or over the guide's floor, the published numbers beside
    them, and no width among them."""
    config = _real("benchmarks", "configs", f"{REAL_CONFIG}.json")
    differs = {k for k, v in PUBLISHED.items() if config.get(k, "-") != v}
    assert differs == set(config["reduced"]) == set(REDUCED)
    assert config["published"] == {k: PUBLISHED[k] for k in REDUCED}
    lists, whole = config["linear_attn_config"], PUBLISHED[
        "linear_attn_config"]
    # two whole periods of the published pattern: its first eight layers
    assert lists["kda_layers"] == [1, 2, 3, 5, 6, 7]
    assert lists["full_attn_layers"] == [4, 8]
    for key in ("head_dim", "num_heads", "short_conv_kernel_size"):
        assert lists[key] == whole[key]
    assert config["num_hidden_layers"] == 8
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4
    assert (config["num_experts"], config["num_routed_experts"],
            config["first_expert_id"]) == (64, 256, 0)
    assert config["num_experts"] >= 8
    assert config["vocab_size"] == 163840 // 4
    assert not [k for k in config["reduced"] if k.endswith(
        ("_dim", "_rank", "_size")) and k != "vocab_size"]
    assert config["reference"] == REFERENCE
    assert config["serve"]["dtype"] == "bfloat16"
    assert config["serve"]["max_slots"] == 32
    assert config["serve"]["page_size"] == 16
    for key in ("reduced_how", "deployment", "memory_arithmetic", "assumed",
                "cost_inputs", "check_data"):
        assert config[key], key
    assert "four pipeline stages" in config["deployment"]
    for key in ("source_of_these", "norm", "block", "kda", "kda_initialisers",
                "latent_attention", "cache", "router", "experts",
                "dense_layers", "weights", "sampling"):
        assert config["assumed"][key], key
    assert "modeling_kimi.py" in config["assumed"]["source_of_these"]
    assert "2510.26692" in config["assumed"]["source_of_these"]
    assert "measured" in config["memory_arithmetic"]


def test_the_traffic_is_the_issue_s():
    traffic = _real("benchmarks", "traffic", f"{TRAFFIC}.json")
    serve = _real("benchmarks", "configs", f"{REAL_CONFIG}.json")["serve"]
    assert {k: traffic[k] for k in (
        "kind", "clients", "requests_per_client", "max_new_tokens")} == {
        "kind": "closed_loop", "clients": 32, "requests_per_client": 8,
        "max_new_tokens": {"dist": "uniform", "min": 768, "max": 1280}}
    prompts = traffic["prompt_tokens"]
    assert (prompts["dist"], prompts["sigma"]) == ("lognormal", 0.2)
    # ISSUE 54's numbers, or its stated fallback with everything halved
    assert (prompts["median"], prompts["min"], prompts["max"],
            serve["prefill_len"], serve["max_seq"]) in (
        (6144, 4096, 8192, 8192, 9728), (3072, 2048, 4096, 4096, 5376))
    assert traffic["clients"] == serve["max_slots"]
    assert prompts["max"] == serve["prefill_len"]
    assert serve["max_seq"] >= prompts["max"] + 1280
    # 32 admissions per ~1,024 tokens of 32 streams: 3.1 % of the gaps
    assert 0.025 < 32 / (32 * 1024) * 32 < 0.05
    for key in ("lengths_source", "note"):
        assert len(traffic[key]) > 100, key


def test_the_real_cell_checks_what_the_issue_names():
    cell = _real("benchmarks", "workloads", f"{REAL_CELL}.json")
    other = _real("benchmarks", "workloads", "serve-1.7b-longgen.json")
    for key in ("expect", "trace_seconds", "host_spans", "launch", "chips"):
        assert cell[key] == other[key]
    from benchmarks.reference import kimi_linear
    from benchmarks.reference.check import SERVE_LOGITS_RTOL_OF_MAX

    assert set(cell["wrong_variants"]) <= set(kimi_linear.WRONG)
    assert len(cell["wrong_variants"]) <= 5
    assert "fp8_activations" in cell["wrong_variants"]
    check = cell["check"]
    assert (check["prompts"], check["decode_positions"]) == (8, 64)
    assert check["rtol_of_max"] <= SERVE_LOGITS_RTOL_OF_MAX
    assert str(check["rtol_of_max"]) in cell["check_why"]
    for variant in cell["wrong_variants"] + ["scalar_gate",
                                             "rope_on_latent_key"]:
        assert variant in cell["check_why"], variant
    serve = _real("benchmarks", "configs", f"{REAL_CONFIG}.json")["serve"]
    assert (serve["prefill_len"] + 64) % check["q_block"] == 0
    assert 64 % check["expert_chunk"] == 0


def test_the_program_builds_the_share_from_the_file():
    """``benchmarks/lib/program.py`` hands the file's keys to the
    program's own dispatch: a 256-wide router over 64 held experts, one
    dense layer then seven sparse ones, six KDA layers to two latent
    ones, every published width."""
    from benchmarks.lib.program import serving_model
    from scaletorch_tpu.inference.kv_cache import (
        carries_state,
        kv_cache_bytes,
        latent_of,
        latent_row_width,
    )
    from scaletorch_tpu.models import kimi_linear

    config = _real("benchmarks", "configs", f"{REAL_CONFIG}.json")
    cfg, init = serving_model(config, "bfloat16")
    assert isinstance(cfg, kimi_linear.KimiLinearConfig)
    assert init is kimi_linear.init_params
    assert cfg.layer_kinds == ("kda", "kda", "kda", "full") * 2
    assert cfg.sparse_layer_ids() == (1, 2, 3, 4, 5, 6, 7)
    assert (cfg.num_experts, cfg.router_width, cfg.first_expert_id,
            cfg.num_experts_per_tok) == (64, 256, 0, 8)
    assert not cfg.holds_every_expert
    assert (cfg.hidden_size, cfg.intermediate_size,
            cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size,
            cfg.vocab_size, cfg.num_attention_heads, cfg.kda_num_heads,
            cfg.kda_head_dim, cfg.short_conv_kernel_size,
            cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.rms_norm_eps, cfg.head_dim) == (
        2304, 9216, 1024, 1024, 40960, 32, 32, 128, 4, 512, 128, 64, 128,
        1e-5, None)
    assert (cfg.score_func, cfg.norm_topk_prob, cfg.route_scale,
            cfg.shared_expert_gated) == ("sigmoid", True, 2.446, False)
    for name in ("embed_init_std", "routed_expert_init_scale",
                 "query_init_scale"):
        # a launch argument the file may set, with its readings
        if name in config:
            assert getattr(cfg, name) == config[name]
            assert config["check_data"][name], name
    assert latent_of(cfg) and carries_state(cfg)
    assert latent_row_width(cfg) == 640
    # ISSUE 54's arithmetic: 3.77 B parameters = 7.54 GB
    assert 3.76e9 < cfg.num_params() < 3.78e9
    serve = config["serve"]
    pages = serve["max_slots"] * -(-serve["max_seq"] // 16) + 1
    assert kv_cache_bytes(cfg, pages, 16) == 2 * pages * 16 * 1280
    state, conv = cfg.recurrent_state_shapes(32)
    assert state == (6, 32, 32, 128, 128) and conv == (6, 32, 3, 12288)
    tiny, _ = serving_model(dict(TOY_KIMI), "float32")
    assert (tiny.embed_init_std, tiny.routed_expert_init_scale,
            tiny.query_init_scale) == (0.02, 1.0, 1.0)


def test_cost_functions_against_hand_counts():
    from benchmarks.lib import modules
    from benchmarks.lib.spec import Spec

    spec = Spec()
    config = spec.config(REAL_CONFIG)

    def cost(name):
        return modules.cost_function(spec, name, COSTS)

    dims = cost("kimi_dims")(config)
    assert (dims["kda_layers"], dims["mla_layers"], dims["dense_layers"],
            dims["sparse_layers"], dims["held"], dims["routed"],
            dims["row"], dims["stored_row"], dims["slots"]) == (
        6, 2, 1, 7, 64, 256, 576, 640, 32)
    kda = (4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32
           + 3 * 4096 * 4 + 32 + 4096 + 128)
    assert cost("kda_mixer_params")(config) == kda == 39_514_272
    latent = (2304 * 32 * 192 + 2304 * 576 + 512 + 512 * 32 * 256
              + 32 * 128 * 2304)
    assert cost("latent_mixer_params")(config) == latent == 29_114_880
    sparse = 2304 * 256 + 256 + 3 * 1024 * 2304
    assert cost("sparse_mlp_dense_params")(config) == sparse
    dense = 2 * (6 * kda + 2 * latent + 8 * 2 * 2304 + 3 * 2304 * 9216
                 + 7 * sparse + 2304 + 2304 * 40960)
    assert cost("dense_weight_bytes")(config) == dense
    assert 1.0e9 < dense < 1.1e9
    assert cost("expert_matrix_bytes")(config) == 2304 * 1024 * 2
    even = 64 * (1 - (1 - 8 / 256) ** 32)
    assert 40.8 < even < 40.9                # "~41 of 64 a layer"
    assert cost("experts_touched")(config) == pytest.approx(even)
    call = even * 2304 * 1024 * 2
    assert cost("expert_decode_call_bytes")(config) == pytest.approx(call)
    # 65 % of the deployment's expert bytes a step; 16 slots give 46 %
    assert 0.63 < even / 64 < 0.65
    assert 0.39 < 1 - (1 - 8 / 256) ** 16 < 0.41
    state = 32 * 32 * 128 * 128 * 4 * 2
    assert cost("kda_state_update_bytes")(config) == state == 134_217_728
    tail = 32 * 3 * 12288 * 2 * 2
    assert cost("conv_tail_call_bytes")(config) == tail
    live = 32 * 6700.0
    assert cost("latent_bytes_per_token")(config) == 2 * 1280
    assert cost("latent_attn_call_bytes")(config, live) == 1280 * live
    step = cost("decode_step_bytes")(config, live)
    assert step == pytest.approx(
        dense + 7 * 3 * call + 2 * 1280 * live + 6 * (state + tail))
    # ISSUE 54: ~6.4 GB a step, 7.8 ms at the HBM peak; the state and the
    # latent rows about a fifth of it, the routed experts two thirds
    assert 6.2e9 < step < 6.6e9
    assert 7.5 < step / 819e9 * 1e3 < 8.1
    assert 0.18 < (6 * state + 2 * 1280 * live) / step < 0.23
    assert 0.60 < 7 * 3 * call / step < 0.67
    # the scan is charged the call's own rows: one row of prefill_len
    scan = cost("kda_scan_call_flops")
    assert scan(config) == scan(config, config["serve"]["prefill_len"])
    assert scan(config, 64) * 128 == scan(config, 8192)
    assert scan(config, 65) == scan(config, 128)      # whole chunks
    per_chunk_head = (5 * 64 * 16 * 128 + 2 * 64 * 48 * 128
                      + 64 ** 3 // 3 + 2 * 64 * 64 * 256
                      + 3 * 2 * 64 * 128 * 128 + 2 * 64 * 64 * 128)
    assert scan(config, 64) == 32 * per_chunk_head


# ---- the toy cell through run.py ---------------------------------------------

def make_kimi_root(root, reference=REFERENCE):
    """The toy tree plus ``toy-kimi-serve``: a configuration with KDA
    and latent layers, a leading dense layer and a share of its
    experts, a cell, and its name on the ``workloads`` lists the real
    cell is on."""
    from benchmarks.reference import kimi_linear

    make_toy_root(root, extra_metric=True)
    bench = os.path.join(root, "benchmarks")
    with open(os.path.join(bench, "configs", f"{TOY_CELL}.json"), "w") as f:
        json.dump(dict(
            TOY_KIMI, name=TOY_CELL, reference=reference,
            source="made up for the tests",
            serve={"max_slots": 4, "max_seq": 128, "prefill_len": 64,
                   "page_size": 16, "dtype": "float32"}), f)
    with open(os.path.join(bench, "workloads", f"{TOY_CELL}.json"),
              "w") as f:
        json.dump({"name": TOY_CELL, "kind": "serve", "config": TOY_CELL,
                   "traffic": "toy-requests", "chips": 1,
                   "trace_seconds": 0.5,
                   "expect": {"decode_compile_count": 1},
                   "wrong_variants": list(kimi_linear.WRONG),
                   "check": {"prompts": 4, "decode_positions": 8,
                             "q_block": 8, "expert_chunk": 2,
                             "rtol_of_max": 1e-3}}, f)
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
    return _run(make_kimi_root(
        str(tmp_path_factory.mktemp("kimi"))), "1")


@pytest.fixture(scope="module")
def qwen3_reference(tmp_path_factory):
    return _run(make_kimi_root(
        str(tmp_path_factory.mktemp("swapped")),
        reference=TOY_MODEL["reference"]))


def test_kimi_cell_walks_to_its_result_line(own_reference):
    rc, line, out = own_reference
    assert rc == 3, out
    assert CONTRACT_KEYS <= set(line), out
    assert line["correct"] is True, out
    assert line["attempted"] > 0 and line["failed"] == 0
    # float32 at toy size: the chunked scan's, the absorbed form's and
    # the grouped matmul's reassociation, under a third of the limit the
    # toy cell states
    assert line["check"]["err_of_max"] < 3e-4, out


@pytest.mark.parametrize("variant", [
    "scalar_gate", "decay_after_update", "no_qk_l2norm",
    "swish_output_gate", "rope_on_latent_key", "no_latent_norm",
    "softmax_router", "no_route_scale", "bf16_state", "fp8_activations",
    "fp8_layers"])
def test_kimi_cell_rejects_each_wrong_variant(own_reference, variant):
    from benchmarks.reference import kimi_linear

    _, line, out = own_reference
    assert variant in kimi_linear.WRONG
    verdict = line["check"]["wrong_variants"][variant]
    assert verdict["ok"] is False, out
    err = verdict["err_of_max"]
    assert err != err or err > 10 * line["check"]["rtol_of_max"]


def test_kimi_cell_reports_the_counters_of_all_three_mechanisms(
        own_reference):
    """``engine.latent_keys_attended``, ``engine.moe_assignments_*`` and
    ``engine.recurrent_state_owner_mismatches`` reach ``counter``
    readers with no edit to the harness: latent rows were walked,
    choices fell on the held experts and on the absent ones, none was
    dropped, no slot's state was read by a stranger."""
    _, line, out = own_reference
    metrics = line["metrics"]
    assert metrics["serve_latent_keys_attended.kimi"]["value"] > 0, out
    held = metrics["serve_moe_assignments_held.kimi"]["value"]
    elsewhere = metrics["serve_moe_assignments_elsewhere.kimi"]["value"]
    assert held > 0 and elsewhere > held, out
    assert metrics["serve_moe_dropped_assignments"]["value"] == 0, out
    assert metrics["serve_recurrent_state_owner_mismatches"]["value"] == 0
    assert metrics["serve_prefill_positions_run.kimi"]["value"] > 0, out
    assert metrics["toy_engine_decode_steps"]["value"] > 0
    # a window's counters have nothing to read here
    assert "serve_window_ring_wraps" not in metrics


def test_kimi_cell_under_the_qwen3_reference_is_not_correct(qwen3_reference):
    rc, line, out = qwen3_reference
    assert line.get("correct") is not True, out
    if not line:
        assert rc not in (0, 3), out
