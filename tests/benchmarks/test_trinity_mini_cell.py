"""The afmoe (Trinity-Mini) family in the benchmark. First the index:
the committed ``BENCHMARK.json`` holds the configuration's and the
cell's entries and ``Spec`` loads the files they name (membership, never
a place in a list). Then the configuration against the catalog's row,
the traffic against the issue's numbers, the cost module against hand
arithmetic, the readers, and a toy tree with the published
``config.json`` key names, a SHARE of the experts,
``benchmarks/reference/trinity.py`` and the real cell's
``wrong_variants`` through ``run.py --root --rehearse`` to its result
line."""

import json
import os

import pytest

from tests.benchmarks.helpers import CONTRACT_KEYS, run_cell
from tests.benchmarks.toy import REPO, TOY_MODEL, make_toy_root

SEED = str(2**31 + 42)
REFERENCE = "benchmarks/reference/trinity.py"
COSTS = "benchmarks/costs/trinity.py"
REAL_CELL = "serve-trinity-mini-longprompt"
REAL_CONFIG = "trinity-mini-serve"
TRAFFIC = "longprompt-closed8"
SOURCE = "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json"
REDUCED = ["num_hidden_layers", "layer_types", "num_experts", "vocab_size"]
TOY_CELL = "toy-trinity-serve"
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
TOY_TRINITY = {
    "model_type": "afmoe", "vocab_size": 128, "hidden_size": 64,
    "intermediate_size": 96, "num_hidden_layers": 8,
    "layer_types": PERIOD * 2, "global_attn_every_n_layers": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "rope_theta": 10000, "rope_scaling": None, "sliding_window": 24,
    "sliding_window_size": 24,
    "num_dense_layers": 2, "num_experts": 8, "num_routed_experts": 16,
    "first_expert_id": 4, "num_experts_per_tok": 3,
    "moe_intermediate_size": 32, "num_shared_experts": 1,
    "score_func": "sigmoid", "route_norm": True, "route_scale": 2.826,
    "n_group": 1, "topk_group": 1, "mup_enabled": True,
    "max_position_embeddings": 4096, "rms_norm_eps": 1e-05,
    "tie_word_embeddings": False, "reduced": [], "assumed": {},
}
# what the real cell lists (the departures the issue names for the
# chip), and every departure the reference offers
CELL_WRONG = ["window_ignored", "rope_on_full_layers", "no_output_gate",
              "bias_in_weights", "softmax_router", "fp8_activations"]
WRONG = CELL_WRONG + ["topk_not_renormalised", "no_route_scale",
                      "shared_expert_gated", "no_post_norms",
                      "no_embed_scale"]
NEW_READERS = [
    "serve_device_idle_share.trinity-longprompt",
    "serve_trinity_decode_step_hbm_roofline",
    "serve_trinity_paged_attn_roofline",
    "serve_trinity_expert_mlp_roofline",
    "serve_trinity_prefill_attn_roofline",
    "serve_window_ring_wraps", "serve_window_slot_reuse_mismatches",
    # twins of qwen3-next's two counters: its test pins their lists
    "serve_moe_assignments_held.trinity",
    "serve_moe_assignments_elsewhere.trinity"]
# (eight shared serving metrics do not list the cell yet: the delivery
# five, ``serve_engine_slow_ticks`` and qwen3-next's two counters, whose
# lists tests pinned until PR 46; ROADMAP B1 (n) puts it on them)
SHARED_METRICS = [
    "serve_itl_p95_ms", "serve_itl_p99_ms", "serve_tick_interval_p50_ms",
    "serve_decode_step_device_ms", "serve_req_host_ms_per_token",
    "serve_req_device_wait_ms_per_token", "serve_req_stall_ms_per_token",
    "serve_prefill_wall_p50_ms", "serve_prefill_device_share",
    "serve_itl_long_gap_share_pct", "serve_moe_dropped_assignments",
    "serve_moe_expert_mlp_share"]
# the catalog's row (model-configs guide, Trinity-Mini)
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144,
    "layer_types": PERIOD * 8, "load_balance_coeff": 0.001,
    "max_position_embeddings": 131072, "model_type": "afmoe",
    "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1,
    "num_attention_heads": 32, "num_dense_layers": 2,
    "num_expert_groups": 1, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 32, "num_key_value_heads": 4,
    "num_limited_groups": 1, "num_shared_experts": 1, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
    "route_scale": 2.826, "score_func": "sigmoid", "sliding_window": 2048,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192}


def _real(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


# ---- step 0: the index --------------------------------------------------------

def test_the_committed_benchmark_holds_the_two_entries_and_their_files():
    """What two refused PRs left out (ledger, PRs 31 and 34:
    ``config_not_added``): ``BENCHMARK.json`` has the ``configs`` entry
    and the ``workloads`` entry, the files they name are there, and
    ``Spec`` loads them."""
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
                 "benchmarks/costs/trinity.md"):
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
    # charges every layer the whole context
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


def test_the_real_configuration_keeps_the_published_keys():
    """Every key of the catalog's row under the same name; the depth
    with its list of layer kinds, the experts held and the vocabulary
    the cuts, each at or over the guide's floor, the published numbers
    beside them, and no width among them."""
    config = _real("benchmarks", "configs", f"{REAL_CONFIG}.json")
    differs = {k for k, v in PUBLISHED.items() if config.get(k, "-") != v}
    assert differs == set(config["reduced"]) == set(REDUCED)
    assert set(config["published"]) == differs
    for key in ("num_hidden_layers", "num_experts", "vocab_size"):
        assert config["published"][key] == PUBLISHED[key]
    assert config["num_hidden_layers"] == 16          # four whole periods
    assert config["layer_types"] == PUBLISHED["layer_types"][:16]
    assert config["num_hidden_layers"] - config["num_dense_layers"] >= 4
    assert (config["num_experts"], config["num_routed_experts"],
            config["first_expert_id"]) == (32, 128, 0)
    assert config["num_experts"] >= 8
    assert config["vocab_size"] == 200192 // 4 >= 200192 // 8
    assert not [k for k in config["reduced"] if k.endswith(
        ("_dim", "_rank", "_size")) and k != "vocab_size"]
    # the program's argument for the window beside the published key
    assert config["sliding_window_size"] == config["sliding_window"] == 2048
    assert config["reference"] == REFERENCE
    assert config["serve"] == {
        "dtype": "bfloat16", "max_slots": 8, "max_seq": 3456,
        "prefill_len": 3072, "page_size": 16}
    for key in ("reduced_how", "deployment", "memory_arithmetic", "assumed"):
        assert config[key], key
    assert "8 chips" in config["deployment"]
    assert "two pipeline stages" in config["deployment"]
    for key in ("norm", "block", "embedding", "gate_proj", "rotary",
                "window_edge", "router", "experts", "dense_layers",
                "weights"):
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
    assert "no public trace" in traffic["lengths_source"]
    serve = _real("benchmarks", "configs", f"{REAL_CONFIG}.json")["serve"]
    # clients = slots; the longest prompt fills the prefill buffer; the
    # longest request and the check's 64 positions fit the slot; every
    # prompt is at or past the window
    assert traffic["clients"] == serve["max_slots"]
    assert traffic["prompt_tokens"]["max"] == serve["prefill_len"]
    assert serve["max_seq"] >= 3072 + 320 and serve["max_seq"] >= 3072 + 64
    assert traffic["prompt_tokens"]["min"] >= 2048


def test_the_real_cell_checks_what_the_issue_names():
    cell = _real("benchmarks", "workloads", f"{REAL_CELL}.json")
    other = _real("benchmarks", "workloads", "serve-1.7b-longgen.json")
    for key in ("expect", "trace_seconds", "host_spans", "launch", "chips"):
        assert cell[key] == other[key]
    assert cell["wrong_variants"] == CELL_WRONG
    from benchmarks.reference import trinity
    from benchmarks.reference.check import SERVE_LOGITS_RTOL_OF_MAX

    assert set(WRONG) == set(trinity.WRONG)
    check = cell["check"]
    assert {k: check[k] for k in ("prompts", "decode_positions", "q_block",
                                  "expert_chunk")} == {
        "prompts": 8, "decode_positions": 64, "q_block": 64,
        "expert_chunk": 16}
    # the cell's own limit, never above the harness's, and its two
    # readings beside it
    assert check["rtol_of_max"] <= SERVE_LOGITS_RTOL_OF_MAX
    assert str(check["rtol_of_max"]) in cell["check_why"]
    for variant in CELL_WRONG:
        assert variant in cell["check_why"], variant
    # the check's prompts (3,072 + 64 positions) in whole query blocks
    assert (3072 + 64) % check["q_block"] == 0
    assert 32 % check["expert_chunk"] == 0


def test_the_stream_that_carries_the_token_is_the_file_s_not_the_program_s():
    """The embedding's scale is a stated property of this benchmark's
    random weights (``check_data``, with the sweep that found it),
    handed to the program as a launch argument; the family's own
    initialiser draws it at 0.02 like every family."""
    from benchmarks.lib.program import serving_model
    from scaletorch_tpu.models.presets import preset

    config = _real("benchmarks", "configs", f"{REAL_CONFIG}.json")
    assert config["embed_init_std"] == 1.0
    assert set(config["check_data"]) == {
        "embed_init_std", "readings_by_scale", "what_it_cannot_replace"}
    for reading in ("0.173", "0.207", "0.0114-0.0152", "0.0224-0.0257"):
        assert reading in config["check_data"]["readings_by_scale"]
    assert "bias_in_weights" in config["check_data"]["what_it_cannot_replace"]
    cfg, _ = serving_model(config, "bfloat16")
    assert cfg.embed_init_std == 1.0
    tiny, _ = serving_model(dict(preset("afmoe-tiny")), "float32")
    assert tiny.embed_init_std == 0.02
    with pytest.raises(NotImplementedError, match="embed_init_std"):
        serving_model(dict(TOY_MODEL, embed_init_std=1.0), "float32")


def test_the_program_builds_the_share_from_the_file():
    """``benchmarks/lib/program.py`` hands the file's keys to the
    program's own dispatch: a 128-wide router over 32 held experts, 12
    window and 4 full layers in the published order, 2 dense layers
    first, every published width."""
    from benchmarks.lib.program import serving_model
    from scaletorch_tpu.inference.kv_cache import window_of
    from scaletorch_tpu.models import afmoe

    config = _real("benchmarks", "configs", f"{REAL_CONFIG}.json")
    cfg, init = serving_model(config, "bfloat16")
    assert isinstance(cfg, afmoe.AfmoeConfig)
    assert init is afmoe.init_params
    assert (cfg.num_window_layers, cfg.num_kv_cache_layers,
            cfg.num_dense_layers) == (12, 4, 2)
    assert cfg.layer_kinds == tuple(PERIOD * 4)
    assert (cfg.num_experts, cfg.router_width, cfg.first_expert_id,
            cfg.num_experts_per_tok) == (32, 128, 0, 8)
    assert not cfg.holds_every_expert
    assert (cfg.hidden_size, cfg.intermediate_size,
            cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size,
            cfg.vocab_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.actual_head_dim, cfg.rope_theta,
            cfg.sliding_window, cfg.rms_norm_eps) == (
        2048, 6144, 1024, 1024, 50048, 32, 4, 128, 1e4, 2048, 1e-5)
    assert (cfg.score_func, cfg.route_norm, cfg.route_scale,
            cfg.shared_expert_gated, cfg.mup_enabled) == (
        "sigmoid", True, 2.826, False, True)
    assert window_of(cfg) == 2048
    # the configuration file's arithmetic: 3.63 B parameters
    block = 2048 * (3 * 4096 + 2 * 512) + 2 * 128 + 4 * 2048
    moe = 2048 * 128 + 128 + 33 * 3 * 2048 * 1024
    assert cfg.num_params() == (
        16 * block + 2 * 3 * 2048 * 6144 + 14 * moe
        + 2 * 50048 * 2048 + 2048)
    assert 3.62e9 < cfg.num_params() < 3.64e9


def test_cost_functions_against_hand_counts():
    from benchmarks.lib import modules
    from benchmarks.lib.spec import Spec

    spec = Spec()
    config = spec.config(REAL_CONFIG)

    def cost(name):
        return modules.cost_function(spec, name, COSTS)

    assert cost("layer_counts")(config) == {
        "window": 12, "full": 4, "dense": 2, "sparse": 14}
    attention = 2048 * (3 * 4096 + 2 * 512) + 2 * 128 + 4 * 2048
    sparse = 2048 * 128 + 128 + 3 * 2048 * 1024
    assert cost("attention_params")(config) == attention == 27_271_424
    assert cost("sparse_mlp_dense_params")(config) == sparse == 6_553_728
    dense = 2 * (16 * attention + 2 * 3 * 2048 * 6144 + 14 * sparse
                 + 2048 + 2048 * 50048)
    assert cost("dense_weight_bytes")(config) == dense
    assert 1.41e9 < dense < 1.42e9          # "1.4 GB of every token's"
    assert cost("expert_matrix_bytes")(config) == 2048 * 1024 * 2
    # even routing would touch 12.9 of the 32 held experts a layer; the
    # engine counted 11.55-11.88 on the chip, over 10 % under on one
    # seed, so the measured count rounded down is what is charged
    costs = modules.load(spec, COSTS, "the test")
    even = 32 * (1 - (1 - 8 / 128) ** 8)
    assert 12.9 < even < 13.0
    assert costs.MEASURED_FLOOR == 11.0 < 0.9 * even + 0.5
    touched = cost("experts_touched")(config)
    assert touched == 11.0
    call = touched * 2048 * 1024 * 2
    assert cost("expert_decode_call_bytes")(config) == pytest.approx(call)
    assert 1.9e9 < 14 * 3 * call < 2.0e9    # 2.27 GB under even routing
    costs.MEASURED_FLOOR = None
    try:
        assert cost("experts_touched")(config) == pytest.approx(even)
    finally:
        costs.MEASURED_FLOOR = 11.0
    assert cost("kv_bytes_per_token_and_layer")(config) == 2048
    # past the window in every slot: a window layer holds 8 x 2048 keys
    live = 8 * 2900.0
    assert cost("window_keys")(config, live) == 8 * 2048
    assert cost("window_keys")(config, 1000.0) == 1000.0
    kv = 2048 * (4 * live + 12 * 8 * 2048)
    assert cost("kv_step_bytes")(config, live) == pytest.approx(kv)
    assert 0.40e9 < 2048 * 12 * 8 * 2048 < 0.41e9       # "0.40 window"
    assert cost("paged_attn_call_bytes")(config, live) == pytest.approx(
        kv / 16)
    step = cost("decode_step_bytes")
    assert step(config, live) == pytest.approx(dense + 14 * 3 * call + kv)
    assert 3.9e9 < step(config, live) < 4.0e9     # 4.28 GB under even routing
    # without the window every layer would read every token
    assert 2048 * 16 * live - kv == pytest.approx(
        2048 * 12 * (live - 8 * 2048))
    pairs = cost("visible_pairs")
    assert pairs(3072) == 3072 * 3073 // 2 == 4_720_128
    assert pairs(3072, 2048) == 2048 * 2049 // 2 + 1024 * 2048 == 4_195_328
    assert pairs(1000, 2048) == pairs(1000)
    # brute force at a small size: row i sees min(i + 1, window) keys
    assert pairs(50, 8) == sum(min(i + 1, 8) for i in range(50))
    flops = cost("prefill_attn_call_flops")(config)
    assert flops == pytest.approx(
        8 * 32 * (4 * 4_720_128 + 12 * 4_195_328) / 16 * 4 * 128)
    # 0.57 TFLOP a layer: 2.9 ms at the bf16 peak
    assert 0.56e12 < flops < 0.58e12


# ---- the toy cell through run.py ---------------------------------------------

def make_trinity_root(root, reference=REFERENCE):
    """The toy tree plus ``toy-trinity-serve``: a configuration with
    window and full layers, leading dense layers and a share of its
    experts, a cell, and its name on the ``workloads`` lists the real
    cell is on. Prompts of 8-48 tokens and 12-24 new ones pass the
    24-token window and wrap the ring of 3 pages of 16."""
    make_toy_root(root, extra_metric=True)
    bench = os.path.join(root, "benchmarks")
    with open(os.path.join(bench, "configs", f"{TOY_CELL}.json"), "w") as f:
        json.dump(dict(
            TOY_TRINITY, name=TOY_CELL, reference=reference,
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
                             "q_block": 8, "expert_chunk": 4,
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
    return _run(make_trinity_root(
        str(tmp_path_factory.mktemp("trinity"))), "1")


@pytest.fixture(scope="module")
def qwen3_reference(tmp_path_factory):
    return _run(make_trinity_root(
        str(tmp_path_factory.mktemp("swapped")),
        reference=TOY_MODEL["reference"]))


def test_trinity_cell_walks_to_its_result_line(own_reference):
    rc, line, out = own_reference
    assert rc == 3, out
    assert CONTRACT_KEYS <= set(line), out
    assert line["correct"] is True, out
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["check"]["err_of_max"] < 3e-4, out
    # the check's own prompts pass the window (24) and the ring (48)
    assert max(line["check"]["prompt_lens"]) + 8 > 48 \
        or max(line["check"]["prompt_lens"]) > 24, out


@pytest.mark.parametrize("variant", WRONG)
def test_trinity_cell_rejects_each_wrong_variant(own_reference, variant):
    _, line, out = own_reference
    verdict = line["check"]["wrong_variants"][variant]
    assert verdict["ok"] is False, out
    assert verdict["err_of_max"] > 10 * line["check"]["rtol_of_max"]


def test_trinity_cell_reports_the_window_s_counters_and_the_share_s(
        own_reference):
    """``engine.window_*`` and ``engine.moe_assignments_*`` reach
    ``counter`` readers with no edit to the harness: rings wrapped, no
    slot-step ran on another request's ring, choices fell on the held
    experts and on the absent ones, none was dropped."""
    _, line, out = own_reference
    metrics = line["metrics"]
    # a number, whatever a one-second window on a loaded CPU saw of the
    # few requests that pass the ring's 48 tokens (the engine's tests
    # count the wraps themselves)
    assert metrics["serve_window_ring_wraps"]["value"] >= 0, out
    assert metrics["serve_window_slot_reuse_mismatches"]["value"] == 0, out
    held = metrics["serve_moe_assignments_held.trinity"]["value"]
    elsewhere = metrics["serve_moe_assignments_elsewhere.trinity"]["value"]
    assert held > 0 and elsewhere > 0, out
    assert metrics["serve_moe_dropped_assignments"]["value"] == 0, out
    assert metrics["toy_engine_decode_steps"]["value"] > 0
    # a state-carrying model's counter has nothing to read here
    assert "serve_recurrent_state_owner_mismatches" not in metrics


def test_trinity_cell_under_the_qwen3_reference_is_not_correct(
        qwen3_reference):
    rc, line, out = qwen3_reference
    assert line.get("correct") is not True, out
    if not line:
        assert rc not in (0, 3), out
