"""The pangu_ultra_moe (openPangu-Ultra-MoE-718B) family in the
benchmark. First the index: the committed ``BENCHMARK.json`` holds the
configuration's and the cell's entries and ``Spec`` loads the files they
name (membership, never a place in a list). Then the configuration
against the catalog's row, the cost module against hand arithmetic, the
readers against the names the compiled step programs print, and a toy
tree with the published ``config.json`` key names,
``benchmarks/reference/pangu_ultra_moe.py`` and every ``wrong=`` the
reference offers through ``run.py --root --rehearse`` to its result
line."""

import json
import os
import re

import pytest

from tests.benchmarks.helpers import CONTRACT_KEYS, run_cell
from tests.benchmarks.toy import REPO, TOY_MODEL, make_toy_root

SEED = str(2**31 + 51)
REFERENCE = "benchmarks/reference/pangu_ultra_moe.py"
COSTS = "benchmarks/costs/pangu_ultra_moe.py"
REAL_CELL = "serve-openpangu-ultra-longprompt"
REAL_CONFIG = "openpangu-ultra-moe-718b-serve"
TRAFFIC = "longprompt-closed8"
SOURCE = ("https://huggingface.co/FreedomIntelligence/"
          "openPangu-Ultra-MoE-718B/blob/main/config.json")
TOY_CELL = "toy-pangu-serve"
TOY_PANGU = {
    "model_type": "pangu_ultra_moe", "vocab_size": 128, "hidden_size": 64,
    "intermediate_size": 96, "num_hidden_layers": 4,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "rope_theta": 10000,
    "first_k_dense_replace": 1, "n_routed_experts": 4,
    "num_routed_experts": 16, "first_expert_id": 4,
    "num_experts_per_tok": 3, "moe_intermediate_size": 32,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "sandwich_norm": True,
    "num_nextn_predict_layers": 1, "attention_bias": False,
    "hidden_act": "silu", "max_position_embeddings": 4096,
    "rms_norm_eps": 1e-05, "tie_word_embeddings": False, "reduced": [],
    "assumed": {},
}
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
           "vocab_size"]
NEW_READERS = [
    "serve_pangu_decode_step_hbm_roofline",
    "serve_pangu_latent_attn_mxu_roofline",
    "serve_pangu_latent_attn_hbm_roofline",
    "serve_pangu_prefill_attn_roofline", "serve_pangu_expert_mlp_roofline",
    "serve_device_idle_share.pangu-longprompt",
    "serve_moe_assignments_held.pangu",
    "serve_moe_assignments_elsewhere.pangu",
    "serve_prefill_positions_run.pangu", "serve_latent_keys_attended"]
SHARED_METRICS = [
    "serve_itl_p95_ms", "serve_itl_p99_ms", "serve_prefill_device_share",
    "serve_tick_interval_p50_ms", "serve_decode_step_device_ms",
    "serve_req_host_ms_per_token", "serve_req_device_wait_ms_per_token",
    "serve_req_stall_ms_per_token", "serve_prefill_wall_p50_ms",
    "serve_itl_long_gap_share_pct", "serve_engine_slow_ticks",
    "serve_moe_dropped_assignments", "serve_moe_expert_mlp_share"]
# the catalog's row (model-configs guide, openPangu-Ultra-MoE-718B)
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 3,
    "hidden_act": "silu", "hidden_size": 7680, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 131072,
    "model_type": "pangu_ultra_moe", "moe_intermediate_size": 2048,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 128,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_theta": 25600000, "routed_scaling_factor": 2.5,
    "sandwich_norm": True, "tie_word_embeddings": False, "v_head_dim": 128,
    "vocab_size": 153600}
# names as the compiled step programs print them (AOT for the v5e, PR
# 51; lib/trace.short_name's form), the readers' own and their
# neighbours
LATENT_KERNEL = ("latent_decode.13 | custom-call | tpu_custom_call | "
                 "bf16[8,128,512]")
FLASH = ("flash_fwd.13 | custom-call | tpu_custom_call | "
         "(bf16[8,32,3072,128], f32[8,32,1,3072])")
GMM_DECODE = ["gmm.12 | custom-call | tpu_custom_call | bf16[128,2048]",
              "gmm.13 | custom-call | tpu_custom_call | bf16[128,7680]"]
GMM_PREFILL = ["gmm.10 | custom-call | tpu_custom_call | bf16[16384,2048]",
               "gmm.11 | custom-call | tpu_custom_call | bf16[16384,7680]"]
PAGE_WRITE = ("paged_write.13 | custom-call | tpu_custom_call | "
              "bf16[6,1729,1,16,640]")


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
    for path in (entry["file"], f"benchmarks/workloads/{REAL_CELL}.json",
                 f"benchmarks/traffic/{TRAFFIC}.json", REFERENCE, COSTS,
                 "benchmarks/costs/pangu_ultra_moe.md"):
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
            ("serve_moe_assignments_held.pangu",
             "serve_moe_assignments_held"),
            ("serve_moe_assignments_elsewhere.pangu",
             "serve_moe_assignments_elsewhere"),
            ("serve_prefill_positions_run.pangu",
             "serve_prefill_positions_run")):
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
    # nothing to read (no trace, no counter: the parent's program): the
    # metric is left out, nothing raises
    empty = {"events": [], "window": None, "records": {}, "counters": {},
             "config": spec.config(REAL_CONFIG), "traffic": {},
             "workload": spec.workload(REAL_CELL), "peaks": {},
             "spec": spec}
    assert reducers.read_metric(empty, reader) is None


def _patterns(name):
    reducer = _real("benchmarks", "metrics", f"{name}.json")["reducer"]
    return [p for term in reducer["terms"] for p in term["patterns"]]


@pytest.mark.parametrize("reader,finds,leaves", [
    ("serve_pangu_latent_attn_mxu_roofline", [LATENT_KERNEL],
     [FLASH, PAGE_WRITE] + GMM_DECODE + GMM_PREFILL),
    ("serve_pangu_latent_attn_hbm_roofline", [LATENT_KERNEL],
     [FLASH, PAGE_WRITE] + GMM_DECODE + GMM_PREFILL),
    ("serve_pangu_prefill_attn_roofline", [FLASH],
     [LATENT_KERNEL, PAGE_WRITE] + GMM_DECODE + GMM_PREFILL),
    ("serve_pangu_expert_mlp_roofline", GMM_DECODE,
     [LATENT_KERNEL, FLASH, PAGE_WRITE] + GMM_PREFILL),
    ("serve_pangu_decode_step_hbm_roofline",
     ["jit_decode(1234567890)"], ["jit_prefill(123)", LATENT_KERNEL]),
])
def test_the_readers_patterns_find_their_kernels_and_no_other(reader, finds,
                                                             leaves):
    patterns = _patterns(reader)
    for name in finds:
        assert any(re.search(p, name) for p in patterns), (reader, name)
    for name in leaves:
        assert not any(re.search(p, name) for p in patterns), (reader, name)


def test_the_real_configuration_keeps_the_published_keys():
    """Every key of the catalog's row under the same name; the depth
    with its leading dense layers, the experts held and the vocabulary
    the cuts, each at or over the guide's floor, the published numbers
    beside them, and no width among them."""
    config = _real("benchmarks", "configs", f"{REAL_CONFIG}.json")
    differs = {k for k, v in PUBLISHED.items() if config.get(k, "-") != v}
    assert differs == set(config["reduced"]) == set(REDUCED)
    assert config["published"] == {k: PUBLISHED[k] for k in REDUCED}
    assert (config["num_hidden_layers"],
            config["first_k_dense_replace"]) == (6, 1)
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4
    assert (config["n_routed_experts"], config["num_routed_experts"],
            config["first_expert_id"]) == (8, 256, 0)
    assert config["n_routed_experts"] >= 8
    assert config["vocab_size"] == 153600 // 8
    assert not [k for k in config["reduced"] if k.endswith(
        ("_dim", "_rank", "_size")) and k != "vocab_size"]
    assert config["reference"] == REFERENCE
    assert config["serve"] == {
        "dtype": "bfloat16", "max_slots": 8, "max_seq": 3456,
        "prefill_len": 3072, "page_size": 16}
    for key in ("reduced_how", "deployment", "memory_arithmetic", "assumed"):
        assert config[key], key
    assert "8 pipeline stages of 32 chips" in config["deployment"]
    assert "22 %" in config["deployment"]
    for key in ("norm", "block", "embedding", "latent_attention", "rotary",
                "cache", "router", "experts", "dense_layers",
                "num_nextn_predict_layers", "weights", "sampling"):
        assert config["assumed"][key], key
    assert "measured" in config["memory_arithmetic"]


def test_the_traffic_is_the_issue_s_letter_for_letter():
    traffic = _real("benchmarks", "traffic", f"{TRAFFIC}.json")
    assert {k: traffic[k] for k in (
        "kind", "clients", "requests_per_client", "lead_in_s",
        "prompt_tokens", "max_new_tokens")} == {
        "kind": "closed_loop", "clients": 8, "requests_per_client": 32,
        "lead_in_s": 3.0,
        "prompt_tokens": {"dist": "lognormal", "median": 2560,
                          "sigma": 0.2, "min": 2048, "max": 3072},
        "max_new_tokens": {"dist": "uniform", "min": 192, "max": 320}}
    serve = _real("benchmarks", "configs", f"{REAL_CONFIG}.json")["serve"]
    assert traffic["clients"] == serve["max_slots"]
    assert traffic["prompt_tokens"]["max"] == serve["prefill_len"]
    assert serve["max_seq"] >= 3072 + 320 and serve["max_seq"] >= 3072 + 64
    # every prompt is over half the buffer: every admission takes the
    # full prefill shape (ROADMAP S2's waste, as it is)
    assert traffic["prompt_tokens"]["min"] > serve["prefill_len"] // 2


def test_the_real_cell_checks_what_the_issue_names():
    cell = _real("benchmarks", "workloads", f"{REAL_CELL}.json")
    other = _real("benchmarks", "workloads", "serve-1.7b-longgen.json")
    for key in ("expect", "trace_seconds", "host_spans", "launch", "chips"):
        assert cell[key] == other[key]
    from benchmarks.reference import pangu_ultra_moe
    from benchmarks.reference.check import SERVE_LOGITS_RTOL_OF_MAX

    assert set(cell["wrong_variants"]) <= set(pangu_ultra_moe.WRONG)
    assert {"no_latent_norm", "rope_key_dropped", "pre_norm_only",
            "fp8_activations"} <= set(cell["wrong_variants"])
    check = cell["check"]
    assert {k: check[k] for k in ("prompts", "decode_positions", "q_block",
                                  "expert_chunk")} == {
        "prompts": 8, "decode_positions": 64, "q_block": 64,
        "expert_chunk": 4}
    assert check["rtol_of_max"] <= SERVE_LOGITS_RTOL_OF_MAX
    assert str(check["rtol_of_max"]) in cell["check_why"]
    for variant in cell["wrong_variants"]:
        assert variant in cell["check_why"], variant
    assert (3072 + 64) % check["q_block"] == 0
    assert 8 % check["expert_chunk"] == 0


def test_the_program_builds_the_share_from_the_file():
    """``benchmarks/lib/program.py`` hands the file's keys to the
    program's own dispatch: a 256-wide router over 8 held experts, one
    dense layer then five sparse ones, every published width."""
    from benchmarks.lib.program import serving_model
    from scaletorch_tpu.inference.kv_cache import (
        kv_cache_bytes,
        latent_of,
        latent_row_width,
    )
    from scaletorch_tpu.models import pangu_ultra_moe

    config = _real("benchmarks", "configs", f"{REAL_CONFIG}.json")
    cfg, init = serving_model(config, "bfloat16")
    assert isinstance(cfg, pangu_ultra_moe.PanguUltraMoEConfig)
    assert init is pangu_ultra_moe.init_params
    assert cfg.sparse_layer_ids() == (1, 2, 3, 4, 5)
    assert (cfg.num_experts, cfg.router_width, cfg.first_expert_id,
            cfg.num_experts_per_tok) == (8, 256, 0, 8)
    assert not cfg.holds_every_expert
    assert (cfg.hidden_size, cfg.intermediate_size,
            cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size,
            cfg.vocab_size, cfg.num_attention_heads, cfg.q_lora_rank,
            cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.rope_theta, cfg.rms_norm_eps) == (
        7680, 18432, 2048, 2048, 19200, 128, 1536, 512, 128, 64, 128,
        25.6e6, 1e-5)
    assert (cfg.score_func, cfg.norm_topk_prob, cfg.route_scale,
            cfg.shared_expert_gated, cfg.embed_init_std,
            cfg.routed_expert_init_scale, cfg.query_init_scale) == (
        "sigmoid", True, 2.5, False, config["embed_init_std"],
        config["routed_expert_init_scale"], config["query_init_scale"])
    # the draw the check and the window's steadiness rest on: a held
    # routed expert a sixteenth of the shared one (a router's near-tie
    # under bfloat16's rounding), scores sharp enough for attention to
    # be a token's own (std 2), an embedding that carries a token's
    # router input
    assert (config["embed_init_std"], config["routed_expert_init_scale"],
            config["query_init_scale"]) == (4.0, 0.0625, 6.0)
    assert set(config["check_data"]) == {
        "embed_init_std", "routed_expert_init_scale", "query_init_scale",
        "readings_by_scale", "what_it_cannot_replace"}
    assert latent_of(cfg) and latent_row_width(cfg) == 640
    # the configuration file's arithmetic: 4.03 B parameters, and the
    # pool 0.212 GB
    assert 4.03e9 < cfg.num_params() < 4.04e9
    assert kv_cache_bytes(cfg, 8 * 216 + 1, 16) == 6 * 1729 * 16 * 1280
    tiny, _ = serving_model(dict(TOY_PANGU), "float32")
    assert (tiny.embed_init_std, tiny.routed_expert_init_scale,
            tiny.query_init_scale) == (0.02, 1.0, 1.0)


def test_cost_functions_against_hand_counts():
    from benchmarks.lib import modules
    from benchmarks.lib.spec import Spec

    spec = Spec()
    config = spec.config(REAL_CONFIG)

    def cost(name):
        return modules.cost_function(spec, name, COSTS)

    dims = cost("latent_dims")(config)
    assert (dims["row"], dims["row_stored"], dims["dense"], dims["sparse"],
            dims["held"], dims["routed"]) == (576, 640, 1, 5, 8, 256)
    attention = (7680 * 1536 + 1536 + 1536 * 128 * 192 + 7680 * 576 + 512
                 + 512 * 128 * 256 + 128 * 128 * 7680 + 4 * 7680)
    assert cost("attention_params")(config) == attention == 196_608_000
    sparse = 7680 * 256 + 3 * 7680 * 2048
    assert cost("sparse_mlp_dense_params")(config) == sparse
    dense = 2 * (6 * attention + 3 * 7680 * 18432 + 5 * sparse
                 + 7680 + 7680 * 19200)
    assert cost("dense_weight_bytes")(config) == dense
    assert 3.99e9 < dense < 4.0e9           # "3.995 GB of every token's"
    # the latent projections are 47 % of a step's 5.0 GB
    assert 0.46 < 2 * 6 * attention / 5.01e9 < 0.48
    assert cost("expert_matrix_bytes")(config) == 7680 * 2048 * 2
    costs = modules.load(spec, COSTS, "the test")
    even = 8 * (1 - (1 - 8 / 256) ** 8)
    assert 1.79 < even < 1.80
    touched = cost("experts_touched")(config)
    if costs.MEASURED_FLOOR is None:
        assert touched == pytest.approx(even)
    else:
        assert touched == costs.MEASURED_FLOOR < 0.9 * even + 0.05
    call = touched * 7680 * 2048 * 2
    assert cost("expert_decode_call_bytes")(config) == pytest.approx(call)
    live = 8 * 2750.0
    assert cost("latent_row_bytes")(config) == 1280
    assert cost("latent_attn_call_bytes")(config, live) == 1280 * live
    # ISSUE 51: 128 x 2 x (576 + 512) = 278,528 FLOP a cached token
    assert cost("latent_attn_call_flops")(config, live) == 278_528 * live
    # 217.6 FLOP a byte as stored, against the v5e's ridge of 240
    assert 278_528 / 1280 == pytest.approx(217.6)
    step = cost("decode_step_bytes")
    assert step(config, live) == pytest.approx(
        dense + 5 * 3 * call + 6 * 1280 * live)
    # a group's expanded queries within 512 MiB: 1.21 GB in 4 groups
    assert cost("prefill_head_groups")(config) == 4
    pairs = 3072 * 3073 // 2
    flops = cost("prefill_attn_call_flops")(config)
    assert flops == 8 * 32 * pairs * 2 * (192 + 128)
    assert 3.09e12 < 4 * flops < 3.10e12    # 15.7 ms a layer at the peak


# ---- the toy cell through run.py ---------------------------------------------

def make_pangu_root(root, reference=REFERENCE):
    """The toy tree plus ``toy-pangu-serve``: a configuration with
    latent attention, a leading dense layer and a share of its experts,
    a cell, and its name on the ``workloads`` lists the real cell is
    on."""
    from benchmarks.reference import pangu_ultra_moe

    make_toy_root(root, extra_metric=True)
    bench = os.path.join(root, "benchmarks")
    with open(os.path.join(bench, "configs", f"{TOY_CELL}.json"), "w") as f:
        json.dump(dict(
            TOY_PANGU, name=TOY_CELL, reference=reference,
            source="made up for the tests",
            serve={"max_slots": 4, "max_seq": 128, "prefill_len": 64,
                   "page_size": 16, "dtype": "float32"}), f)
    with open(os.path.join(bench, "workloads", f"{TOY_CELL}.json"),
              "w") as f:
        json.dump({"name": TOY_CELL, "kind": "serve", "config": TOY_CELL,
                   "traffic": "toy-requests", "chips": 1,
                   "trace_seconds": 0.5,
                   "expect": {"decode_compile_count": 1},
                   "wrong_variants": list(pangu_ultra_moe.WRONG),
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
    return _run(make_pangu_root(
        str(tmp_path_factory.mktemp("pangu"))), "1")


@pytest.fixture(scope="module")
def qwen3_reference(tmp_path_factory):
    return _run(make_pangu_root(
        str(tmp_path_factory.mktemp("swapped")),
        reference=TOY_MODEL["reference"]))


def test_pangu_cell_walks_to_its_result_line(own_reference):
    rc, line, out = own_reference
    assert rc == 3, out
    assert CONTRACT_KEYS <= set(line), out
    assert line["correct"] is True, out
    assert line["attempted"] > 0 and line["failed"] == 0
    # float32 at toy size: the absorbed form's and the grouped matmul's
    # reassociation, three orders under the limit the toy cell states
    assert line["check"]["err_of_max"] < 3e-4, out


@pytest.mark.parametrize("variant", [
    "no_latent_norm", "rope_key_dropped", "scale_by_128",
    "rope_on_whole_head", "pre_norm_only", "softmax_router",
    "no_route_scale", "fp8_activations", "fp8_layers"])
def test_pangu_cell_rejects_each_wrong_variant(own_reference, variant):
    from benchmarks.reference import pangu_ultra_moe

    _, line, out = own_reference
    assert variant in pangu_ultra_moe.WRONG
    verdict = line["check"]["wrong_variants"][variant]
    assert verdict["ok"] is False, out
    assert verdict["err_of_max"] > 10 * line["check"]["rtol_of_max"]


def test_pangu_cell_reports_the_latent_counter_and_the_share_s(
        own_reference):
    """``engine.latent_keys_attended`` and ``engine.moe_assignments_*``
    reach ``counter`` readers with no edit to the harness: rows were
    walked, choices fell on the held experts and on the absent ones,
    none was dropped."""
    _, line, out = own_reference
    metrics = line["metrics"]
    assert metrics["serve_latent_keys_attended"]["value"] > 0, out
    held = metrics["serve_moe_assignments_held.pangu"]["value"]
    elsewhere = metrics["serve_moe_assignments_elsewhere.pangu"]["value"]
    assert held > 0 and elsewhere > held, out
    assert metrics["serve_moe_dropped_assignments"]["value"] == 0, out
    assert metrics["serve_prefill_positions_run.pangu"]["value"] > 0, out
    assert metrics["toy_engine_decode_steps"]["value"] > 0
    # a window's or a state's counters have nothing to read here
    assert "serve_window_ring_wraps" not in metrics
    assert "serve_recurrent_state_owner_mismatches" not in metrics


def test_pangu_cell_under_the_qwen3_reference_is_not_correct(
        qwen3_reference):
    rc, line, out = qwen3_reference
    assert line.get("correct") is not True, out
    if not line:
        assert rc not in (0, 3), out
