"""The mimo_v2_flash (MiMo-V2-Flash) family in the benchmark. First the
index: the committed ``BENCHMARK.json`` holds the configuration's and
the cell's entries and ``Spec`` loads the files they name (membership,
never a place in a list and never a count). Then the configuration
against the catalog's row, the cost module against hand arithmetic, the
readers against the names the compiled step programs print, and a toy
tree with the published ``config.json`` key names,
``benchmarks/reference/mimo_v2_flash.py`` and every ``wrong=`` the
reference offers through ``run.py --root --rehearse`` to its result
line."""

import json
import os
import re

import pytest

from tests.benchmarks.helpers import CONTRACT_KEYS, run_cell
from tests.benchmarks.toy import REPO, TOY_MODEL, make_toy_root

SEED = str(2**31 + 59)
REFERENCE = "benchmarks/reference/mimo_v2_flash.py"
COSTS = "benchmarks/costs/mimo_v2_flash.py"
REAL_CELL = "serve-mimo-v2-flash-longctx"
REAL_CONFIG = "mimo-v2-flash-serve"
TRAFFIC = "agentctx-closed32"
SOURCE = ("https://huggingface.co/XiaomiMiMo/MiMo-V2-Flash/blob/main/"
          "config.json")
TOY_CELL = "toy-mimo-serve"
TOY_MIMO = {
    "model_type": "mimo_v2_flash", "vocab_size": 128, "hidden_size": 64,
    "intermediate_size": 96, "num_hidden_layers": 7,
    "num_attention_heads": 8, "num_key_value_heads": 2,
    "swa_num_key_value_heads": 4, "head_dim": 24, "v_head_dim": 16,
    "swa_num_attention_heads": 8, "swa_head_dim": 24, "swa_v_head_dim": 16,
    "rope_theta": 5000000, "swa_rope_theta": 10000,
    "partial_rotary_factor": 0.334, "layernorm_epsilon": 1e-05,
    "sliding_window": 20, "sliding_window_size": 20,
    "attention_chunk_size": 20, "attention_value_scale": 0.707,
    "attention_bias": False, "hybrid_layer_pattern": [0, 1, 1, 1, 1, 0, 1],
    "moe_layer_freq": [0, 1, 1, 1, 1, 1, 1],
    "add_swa_attention_sink_bias": True,
    "add_full_attention_sink_bias": False, "n_routed_experts": 4,
    "num_routed_experts": 16, "first_expert_id": 4,
    "n_shared_experts": None, "num_experts_per_tok": 3,
    "moe_intermediate_size": 32, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "n_group": 1, "topk_group": 1,
    "topk_method": "noaux_tc", "routed_scaling_factor": None,
    "hidden_act": "silu", "max_position_embeddings": 4096,
    "tie_word_embeddings": False, "sink_init_mean": 2.5, "reduced": [],
    "assumed": {},
}
REDUCED = ["num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
           "n_routed_experts", "vocab_size"]
NEW_READERS = [
    "serve_mimo_decode_step_hbm_roofline",
    "serve_mimo_full_attn_hbm_roofline",
    "serve_mimo_window_attn_hbm_roofline",
    "serve_mimo_expert_mlp_roofline", "serve_mimo_window_attn_share",
    "serve_mimo_prefill_attn_roofline",
    "serve_device_idle_share.mimo-longctx",
    "serve_window_ring_wraps.mimo",
    "serve_window_slot_reuse_mismatches.mimo",
    "serve_moe_assignments_held.mimo",
    "serve_moe_assignments_elsewhere.mimo",
    "serve_prefill_positions_run.mimo", "serve_full_keys_attended.mimo",
    "serve_window_keys_attended.mimo", "serve_window_pages_trashed.mimo"]
SHARED_METRICS = [
    "serve_itl_p95_ms", "serve_itl_p99_ms", "serve_prefill_device_share",
    "serve_tick_interval_p50_ms", "serve_decode_step_device_ms",
    "serve_req_host_ms_per_token", "serve_req_device_wait_ms_per_token",
    "serve_req_stall_ms_per_token", "serve_prefill_wall_p50_ms",
    "serve_itl_long_gap_share_pct", "serve_engine_slow_ticks",
    "serve_moe_dropped_assignments", "serve_moe_expert_mlp_share",
    "serve_emit_gap_p95_ms", "serve_deliver_held_ms_per_token",
    "serve_deliver_loop_ms_per_token", "serve_write_gap_p95_ms",
    "serve_deliver_lag_p95_ms", "serve_write_shoulder_gap_ms",
    "serve_write_shoulder_emit_ms", "serve_write_shoulder_held_ms",
    "serve_write_shoulder_wake_ms", "serve_write_shoulder_pauses_ms",
    "serve_write_shoulder_writes_ms", "serve_write_shoulder_place_moved_pct",
    "serve_host_gc_pause_ms", "serve_host_gc_full_collections",
    "serve_host_gc_full_pause_ms"]
_PATTERN = [0, 1, 1, 1, 1, 0] + [1, 1, 1, 1, 1, 0] * 7
# the catalog's row (model-configs guide, MiMo-V2-Flash)
PUBLISHED = {
    "attention_value_scale": 0.707, "hidden_act": "silu",
    "hidden_size": 4096, "intermediate_size": 16384,
    "max_position_embeddings": 262144, "model_type": "mimo_v2_flash",
    "num_attention_heads": 64, "head_dim": 192, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "layernorm_epsilon": 1e-05,
    "rope_theta": 5000000, "tie_word_embeddings": False,
    "vocab_size": 152576, "partial_rotary_factor": 0.334,
    "sliding_window": 128, "swa_rope_theta": 10000, "attention_bias": False,
    "v_head_dim": 128, "hybrid_layer_pattern": _PATTERN,
    "add_swa_attention_sink_bias": True,
    "add_full_attention_sink_bias": False, "sliding_window_size": 128,
    "attention_chunk_size": 128, "moe_layer_freq": [0] + [1] * 47,
    "moe_intermediate_size": 2048, "n_routed_experts": 256,
    "n_shared_experts": None, "num_experts_per_tok": 8,
    "norm_topk_prob": True, "scoring_func": "sigmoid", "n_group": 1,
    "topk_group": 1, "topk_method": "noaux_tc",
    "routed_scaling_factor": None, "swa_num_attention_heads": 64,
    "swa_num_key_value_heads": 8, "swa_head_dim": 192, "swa_v_head_dim": 128}
# names as the compiled step programs print them (AOT for the v5e and the
# chip's traces, PR 59; lib/trace.short_name's form), the readers' own
# and their neighbours
FULL_KERNEL = ("paged_decode.3 | custom-call | tpu_custom_call | "
               "bf16[32,4,16,128]")
WINDOW_KERNEL = ("paged_decode.5 | custom-call | tpu_custom_call | "
                 "bf16[32,8,8,128]")
FLASH = ("flash_fwd.3 | custom-call | tpu_custom_call | "
         "(bf16[1,64,8192,128], f32[1,64,1,8192])")
GMM_DECODE = ["gmm.12 | custom-call | tpu_custom_call | bf16[256,2048]",
              "gmm.14 | custom-call | tpu_custom_call | bf16[256,4096]"]
GMM_PREFILL = ["gmm.10 | custom-call | tpu_custom_call | bf16[65536,2048]",
               "gmm.11 | custom-call | tpu_custom_call | bf16[65536,4096]"]
PAGE_WRITES = [
    "paged_write.3 | custom-call | tpu_custom_call | bf16[2,19457,4,16,256]",
    "paged_write.4 | custom-call | tpu_custom_call | bf16[5,289,8,16,128]"]
HIDDEN = "fusion.77 | fusion | kOutput | bf16[1,8192,4096]"


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
    assert "rings" in cell[0]["why"] and "sink" in entry["why"]
    for path in (entry["file"], f"benchmarks/workloads/{REAL_CELL}.json",
                 f"benchmarks/traffic/{TRAFFIC}.json", REFERENCE, COSTS,
                 "benchmarks/costs/mimo_v2_flash.md"):
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
    # the traffic file is this cell's, and Kimi-Linear's stays its own
    assert REAL_CELL in [w["name"] for w in index["workloads"]
                         if w["traffic"] == TRAFFIC]
    assert REAL_CELL not in [w["name"] for w in index["workloads"]
                             if w["traffic"] == "longctx-closed32"]


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
    # NOT on the shared kernel metrics: costs.paged_decode_kv_bytes
    # charges one head count and one width to every layer
    assert "serve_paged_attn_roofline" not in names
    assert "serve_trinity_paged_attn_roofline" not in names
    # no recurrent state: nothing for that counter to read
    assert "serve_recurrent_state_owner_mismatches" not in names
    assert {m["name"] for m in spec.end_to_end(REAL_CELL)} == {
        "serve_itl_p95_ms", "serve_itl_p99_ms", "setup_s"}
    index = _real("BENCHMARK.json")
    reported = {m["name"] for m in spec.end_to_end(REAL_CELL)}
    for metric in index["end_to_end"] + index["per_layer"]:
        if metric["name"] in SHARED_METRICS:
            # on the list, wherever: the next cell is appended after it
            assert REAL_CELL in metric["workloads"], metric["name"]
        if metric["name"] in NEW_READERS:
            assert metric["workloads"] == [REAL_CELL]
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
    for twin in NEW_READERS:
        if not twin.endswith(".mimo"):
            continue
        original = twin[:-len(".mimo")]
        if os.path.isfile(os.path.join(REPO, "benchmarks", "metrics",
                                       f"{original}.json")):
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
    ("serve_mimo_full_attn_hbm_roofline", [FULL_KERNEL],
     [WINDOW_KERNEL, FLASH, HIDDEN] + PAGE_WRITES + GMM_DECODE),
    ("serve_mimo_window_attn_hbm_roofline", [WINDOW_KERNEL],
     [FULL_KERNEL, FLASH, HIDDEN] + PAGE_WRITES + GMM_DECODE),
    ("serve_mimo_window_attn_share", [WINDOW_KERNEL],
     [FULL_KERNEL, FLASH, HIDDEN] + PAGE_WRITES + GMM_DECODE),
    ("serve_mimo_expert_mlp_roofline", GMM_DECODE,
     [FULL_KERNEL, WINDOW_KERNEL, FLASH] + PAGE_WRITES + GMM_PREFILL),
    ("serve_mimo_prefill_attn_roofline", [FLASH],
     [FULL_KERNEL, WINDOW_KERNEL, HIDDEN] + PAGE_WRITES + GMM_PREFILL),
    ("serve_mimo_decode_step_hbm_roofline",
     ["jit_decode(1234567890)"], ["jit_prefill(123)", FULL_KERNEL]),
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
    """Every key of the catalog's row under the same name; the five cut
    keys differ and are listed, in the file and in the index alike, with
    what was published; no width, head count, window, top k or rotary
    share changes; the floors of the model-configs guide hold."""
    config = _real("benchmarks", "configs", f"{REAL_CONFIG}.json")
    differs = sorted(k for k, v in PUBLISHED.items() if config.get(k) != v)
    assert differs == sorted(REDUCED) == sorted(config["reduced"])
    assert config["published"] == {k: PUBLISHED[k] for k in REDUCED}
    assert set(PUBLISHED) <= set(config)
    # the cut: the published lists' first seven entries, 16 of 256
    # experts, an eighth of the vocabulary
    assert config["num_hidden_layers"] == 7
    assert config["hybrid_layer_pattern"] == _PATTERN[:7]
    assert config["moe_layer_freq"] == [0, 1, 1, 1, 1, 1, 1]
    after_dense = config["hybrid_layer_pattern"][1:]
    assert (after_dense.count(1), after_dense.count(0)) == (5, 1)
    assert config["n_routed_experts"] == 16 >= 8
    assert (config["num_routed_experts"], config["first_expert_id"]) == (
        256, 0)
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    for name in ("published", "reduced_how", "deployment", "assumed",
                 "memory_arithmetic", "cost_inputs", "check_data"):
        assert config[name], name
    assert config["serve"] == {
        "dtype": "bfloat16", "max_slots": 32, "max_seq": 9728,
        "prefill_len": 8192, "page_size": 16}
    assumed = config["assumed"]
    assert "modeling_mimo_v2_flash.py" in assumed["source_of_these"]
    for key in ("attention_chunk_size", "sliding_window", "hidden_act",
                "topk_method", "swa_num_attention_heads"):
        assert key in assumed["carried_unused"], key
    assert "sink" in assumed["weights"]


def test_the_traffic_is_the_issue_s():
    traffic = _real("benchmarks", "traffic", f"{TRAFFIC}.json")
    assert (traffic["kind"], traffic["clients"],
            traffic["requests_per_client"], traffic["lead_in_s"]) == (
        "closed_loop", 32, 8, 20.0)
    assert traffic["prompt_tokens"] == {
        "dist": "lognormal", "median": 6144, "sigma": 0.3, "min": 3072,
        "max": 8192}
    assert traffic["max_new_tokens"] == {
        "dist": "uniform", "min": 512, "max": 1536}
    config = _real("benchmarks", "configs", f"{REAL_CONFIG}.json")
    serve = config["serve"]
    assert traffic["clients"] == serve["max_slots"]
    assert traffic["prompt_tokens"]["max"] <= serve["prefill_len"]
    assert traffic["prompt_tokens"]["max"] + traffic["max_new_tokens"][
        "max"] <= serve["max_seq"]
    assert serve["max_seq"] % serve["page_size"] == 0


def test_the_real_cell_checks_what_the_issue_names():
    from benchmarks.reference import mimo_v2_flash

    cell = _real("benchmarks", "workloads", f"{REAL_CELL}.json")
    listed = cell["wrong_variants"]
    assert len(listed) <= 5 and set(listed) <= set(mimo_v2_flash.WRONG)
    assert "no_sink" in listed
    assert {"fp8_activations", "fp8_layers"} & set(listed)
    assert cell["expect"] == {"decode_compile_count": 1}
    assert set(cell["check"]) == {"prompts", "decode_positions", "q_block",
                                  "expert_chunk", "rtol_of_max"}
    # the cell's own limit is tighter than the harness's
    from benchmarks.reference import check

    assert cell["check"]["rtol_of_max"] < check.SERVE_LOGITS_RTOL_OF_MAX
    assert (8192 + cell["check"]["decode_positions"]) % cell["check"][
        "q_block"] == 0
    assert 16 % cell["check"]["expert_chunk"] == 0
    for word in ("no_sink", "fp8", "SOUND", "CONTROL"):
        assert word in cell["check_why"], word


def test_the_program_builds_the_share_from_the_file():
    """The file's keys reach the program's arguments under their
    published names and the program's own dispatch builds the family's
    class from them: the two lists, the two kinds' K/V heads and rope
    bases, the share, the draw."""
    from benchmarks.lib import program
    from scaletorch_tpu.models import mimo_v2_flash as mimo

    config = _real("benchmarks", "configs", f"{REAL_CONFIG}.json")
    passed = program.model_arguments(config)
    for key in ("hybrid_layer_pattern", "moe_layer_freq",
                "swa_num_key_value_heads", "swa_rope_theta", "v_head_dim",
                "attention_value_scale", "add_swa_attention_sink_bias",
                "layernorm_epsilon", "partial_rotary_factor",
                "sliding_window_size", "n_routed_experts",
                "num_routed_experts", "swa_head_dim", "swa_v_head_dim"):
        assert passed[key] == config[key], key
    cfg, init = program.serving_model(config, "bfloat16")
    assert type(cfg) is mimo.MimoV2FlashConfig and init is mimo.init_params
    assert cfg.hybrid_layer_pattern == (0, 1, 1, 1, 1, 0, 1)
    assert cfg.kv_head_shapes == ((4, 256, 128), (8, 256, 128))
    assert (cfg.sliding_window, cfg.rotary_dim, cfg.rms_norm_eps) == (
        128, 64, 1e-5)
    assert (cfg.rope_theta, cfg.swa_rope_theta) == (5e6, 1e4)
    assert (cfg.num_experts, cfg.router_width, cfg.num_experts_per_tok) == (
        16, 256, 8)
    assert cfg.route_scale == 1.0
    for name in ("embed_init_std", "routed_expert_init_scale",
                 "query_init_scale", "sink_init_mean"):
        if name in config:
            assert getattr(cfg, name) == config[name], name
    assert 3.42e9 < cfg.num_params() < 3.44e9


def test_cost_functions_against_hand_counts():
    from benchmarks.lib import modules
    from benchmarks.lib.spec import Spec

    spec = Spec()
    config = spec.config(REAL_CONFIG)

    def cost(name):
        return modules.cost_function(spec, name, COSTS)

    costs = modules.load(spec, COSTS, "the test")
    assert costs.MEASURED_FLOOR is None or 0 < costs.MEASURED_FLOOR <= 16
    h, heads, dk, dv = 4096, 64, 192, 128
    full_mixer = h * heads * dk + h * 4 * (dk + dv) + heads * dv * h
    window_mixer = h * heads * dk + h * 8 * (dk + dv) + heads * dv * h + 64
    assert (costs.mixer_params(config, 0), costs.mixer_params(config, 1)) == (
        full_mixer, window_mixer) == (89_128_960, 94_371_904)
    dense = (2 * full_mixer + 5 * window_mixer + 7 * 2 * h
             + 3 * h * 16384 + 6 * (h * 256 + 256) + h + h * 19072) * 2
    assert costs.dense_weight_bytes(config) == dense
    assert costs.expert_matrix_bytes(config) == h * 2048 * 2 == 16_777_216
    touched = costs.experts_touched(config)
    if costs.MEASURED_FLOOR is None:
        assert touched == pytest.approx(16 * (1 - (248 / 256) ** 32))
    assert cost("expert_decode_call_bytes")(config) == pytest.approx(
        touched * 16_777_216)
    # as stored: a key of 192 in a row of 256 beside a value of 128
    assert costs.kv_bytes_per_token(config, 0) == 4 * (256 + 128) * 2 == 3072
    assert costs.kv_bytes_per_token(config, 1) == 8 * (256 + 128) * 2 == 6144
    live = 32 * 6600.0
    assert cost("full_attn_call_bytes")(config, live) == 3072 * live
    assert cost("window_attn_call_bytes")(config) == 6144 * 32 * 128
    step = cost("decode_step_bytes")(config, live)
    assert step == pytest.approx(
        dense + 6 * 3 * touched * 16_777_216 + 2 * 3072 * live
        + 5 * 6144 * 32 * 128)
    # ISSUE 59: ~6.4 GB under uniform routing; 5.4 as the engine counts
    assert 5.0e9 < step < 7e9
    uniform = dense + 6 * 3 * 16 * (1 - (248 / 256) ** 32) * 16_777_216 \
        + 2 * 3072 * live + 5 * 6144 * 32 * 128
    assert 6.3e9 < uniform < 6.5e9
    # a window layer is charged no more than the live tokens
    short = cost("decode_step_bytes")(config, 1000.0)
    assert short == pytest.approx(
        dense + 6 * 3 * touched * 16_777_216 + (2 * 3072 + 5 * 6144) * 1000)
    # the prefill call's own rows: ONE row of 8,192, never max_slots
    rows, window = 8192, 128
    full_pairs = rows * (rows + 1) // 2
    window_pairs = window * (window + 1) // 2 + (rows - window) * window
    assert (costs.visible_pairs(rows), costs.visible_pairs(rows, window)) == (
        full_pairs, window_pairs)
    flops = cost("prefill_attn_call_flops")(config)
    assert flops == pytest.approx(
        64 * (2 * full_pairs + 5 * window_pairs) / 7 * 2 * (192 + 128))
    more_slots = dict(config, serve=dict(config["serve"], max_slots=64))
    assert cost("prefill_attn_call_flops")(more_slots) == flops


# ---- the toy cell through run.py --rehearse ------------------------------------

def make_mimo_root(root, reference=REFERENCE):
    """The toy tree with one more configuration, the family's at the
    tiny preset's sizes under the published key names with a share of
    the experts, a cell, and its name on the ``workloads`` lists the
    real cell is on."""
    from benchmarks.reference import mimo_v2_flash

    make_toy_root(root, extra_metric=True)
    bench = os.path.join(root, "benchmarks")
    with open(os.path.join(bench, "configs", f"{TOY_CELL}.json"), "w") as f:
        json.dump(dict(
            TOY_MIMO, name=TOY_CELL, reference=reference,
            source="made up for the tests",
            serve={"max_slots": 4, "max_seq": 128, "prefill_len": 64,
                   "page_size": 8, "dtype": "float32"}), f)
    with open(os.path.join(bench, "workloads", f"{TOY_CELL}.json"),
              "w") as f:
        json.dump({"name": TOY_CELL, "kind": "serve", "config": TOY_CELL,
                   "traffic": "toy-requests", "chips": 1,
                   "trace_seconds": 0.5,
                   "expect": {"decode_compile_count": 1},
                   "wrong_variants": list(mimo_v2_flash.WRONG),
                   "check": {"prompts": 4, "decode_positions": 8,
                             "q_block": 8, "expert_chunk": 2,
                             # float32 at toy size reads under 1e-5; the
                             # weakest departure, a selection bias of
                             # 0.005 in the weights, 3.6e-3
                             "rtol_of_max": 2e-4}}, f)
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
    have = {m["name"] for s in ("end_to_end", "per_layer") for m in index[s]}
    for section in ("end_to_end", "per_layer"):
        for metric in real[section]:     # this PR's readers, new to the toy
            if metric["name"] in on and metric["name"] not in have:
                index[section].append(dict(metric, workloads=[]))
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
    return _run(make_mimo_root(
        str(tmp_path_factory.mktemp("mimo"))), "1")


@pytest.fixture(scope="module")
def qwen3_reference(tmp_path_factory):
    return _run(make_mimo_root(
        str(tmp_path_factory.mktemp("swapped")),
        reference=TOY_MODEL["reference"]))


def test_mimo_cell_walks_to_its_result_line(own_reference):
    rc, line, out = own_reference
    assert rc == 3, out
    assert CONTRACT_KEYS <= set(line), out
    assert line["correct"] is True, out
    assert line["attempted"] > 0 and line["failed"] == 0
    # float32 at toy size: the key blocks' and the grouped matmul's
    # reassociation, far under the limit the toy cell states
    assert line["check"]["err_of_max"] < 2e-5, out


@pytest.mark.parametrize("variant", [
    "no_sink", "sink_on_full_layers", "window_off_by_one", "no_value_scale",
    "one_rope_theta", "rope_whole_head", "kv_heads_swapped",
    "bias_in_weights", "softmax_router", "fp8_activations", "fp8_layers"])
def test_mimo_cell_rejects_each_wrong_variant(own_reference, variant):
    from benchmarks.reference import mimo_v2_flash

    _, line, out = own_reference
    assert variant in mimo_v2_flash.WRONG
    verdict = line["check"]["wrong_variants"][variant]
    assert verdict["ok"] is False, out
    err = verdict["err_of_max"]
    assert err != err or err > 10 * line["check"]["rtol_of_max"]


def test_mimo_cell_reports_the_counters_of_both_mechanisms(own_reference):
    """``engine.window_*``, ``engine.full_keys_attended`` and
    ``engine.moe_assignments_*`` reach ``counter`` readers with no edit
    to the harness: rings wrapped, no slot's ring was read by a
    stranger, choices fell on the held experts and on the absent ones,
    none was dropped, every prefill call ran one row."""
    _, line, out = own_reference
    metrics = line["metrics"]
    assert metrics["serve_window_ring_wraps.mimo"]["value"] > 0, out
    assert metrics["serve_window_slot_reuse_mismatches.mimo"]["value"] == 0
    full = metrics["serve_full_keys_attended.mimo"]["value"]
    window = metrics["serve_window_keys_attended.mimo"]["value"]
    assert 0 < window < full, out
    assert metrics["serve_window_pages_trashed.mimo"]["value"] >= 0, out
    held = metrics["serve_moe_assignments_held.mimo"]["value"]
    elsewhere = metrics["serve_moe_assignments_elsewhere.mimo"]["value"]
    assert held > 0 and elsewhere > held, out
    assert metrics["serve_moe_dropped_assignments"]["value"] == 0, out
    assert metrics["serve_prefill_positions_run.mimo"]["value"] > 0, out
    assert metrics["toy_engine_decode_steps"]["value"] > 0
    # a recurrent state's counter has nothing to read here
    assert "serve_recurrent_state_owner_mismatches" not in metrics


def test_mimo_cell_under_the_qwen3_reference_is_not_correct(qwen3_reference):
    rc, line, out = qwen3_reference
    assert line.get("correct") is not True, out
    if not line:
        assert rc not in (0, 3), out
