"""The Qwen3-Next family in the benchmark. First the index: the
committed ``BENCHMARK.json`` holds the configuration's and the cell's
entries and ``Spec`` loads the files they name (what two refused PRs
left out). Then the configuration against the catalog's row, the cost
module against hand arithmetic, the readers, and a toy tree with the
published ``config.json`` key names, a SHARE of the experts,
``benchmarks/reference/qwen3_next.py`` and the real cell's
``wrong_variants`` through ``run.py --root --rehearse`` to its result
line."""

import json
import os

import pytest

from tests.benchmarks.helpers import CONTRACT_KEYS, run_cell
from tests.benchmarks.toy import REPO, TOY_MODEL, make_toy_root

SEED = str(2**31 + 35)
REFERENCE = "benchmarks/reference/qwen3_next.py"
COSTS = "benchmarks/costs/qwen3_next.py"
REAL_CELL = "serve-qwen3-next-longgen"
REAL_CONFIG = "qwen3-next-80b-a3b-serve"
SOURCE = ("https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/"
          "main/config.json")
TOY_CELL = "toy-qwen3-next-serve"
TOY_NEXT = {
    "model_type": "qwen3_next", "vocab_size": 128, "hidden_size": 64,
    "intermediate_size": 96, "num_hidden_layers": 8,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "rope_theta": 10000, "partial_rotary_factor": 0.25,
    "full_attention_interval": 4, "decoder_sparse_step": 1,
    "mlp_only_layers": [],
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 8, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4,
    "num_experts": 8, "num_routed_experts": 16, "first_expert_id": 4,
    "num_experts_per_tok": 3, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 48, "norm_topk_prob": True,
    "max_position_embeddings": 4096, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "reduced": [], "assumed": {},
}
# what the real cell lists, and every departure the reference offers:
# bf16_router is off the cell's list because the comparison on the chip
# cannot tell it (the cell's check_why); the toy cell, in float32, can
CELL_WRONG = ["no_output_gate", "rope_on_whole_head", "plain_norm_gain",
              "no_shared_expert_gate", "topk_not_renormalised",
              "key_heads_not_repeated", "fp8_activations"]
WRONG = CELL_WRONG + ["bf16_router"]
NEW_READERS = [
    "serve_device_idle_share.qwen3-next-longgen",
    "serve_qwen3_next_decode_step_hbm_roofline",
    "serve_qwen3_next_expert_mlp_roofline",
    "serve_qwen3_next_gdn_state_update_roofline",
    "serve_moe_assignments_held", "serve_moe_assignments_elsewhere",
    "serve_qwen3_next_gdn_recurrence_share"]
SHARED_METRICS = [
    "serve_itl_p95_ms", "serve_itl_p99_ms", "serve_tick_interval_p50_ms",
    "serve_decode_step_device_ms", "serve_paged_attn_roofline",
    "serve_req_host_ms_per_token", "serve_req_device_wait_ms_per_token",
    "serve_req_stall_ms_per_token", "serve_prefill_wall_p50_ms",
    "serve_prefill_device_share", "serve_moe_dropped_assignments",
    "serve_moe_expert_mlp_share", "serve_recurrent_state_owner_mismatches"]
# the catalog's row (model-configs guide, Qwen3-Next-80B-A3B-Instruct)
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512,
    "num_experts_per_tok": 10, "num_hidden_layers": 48,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


def _real(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


# ---- step 0: the index --------------------------------------------------------

def test_the_committed_benchmark_holds_the_two_entries_and_their_files():
    """The first thing this family's PR wrote: ``BENCHMARK.json`` has
    the ``configs`` entry and the ``workloads`` entry, the files they
    name are there, and ``Spec`` loads both."""
    from benchmarks.lib.spec import Spec

    index = _real("BENCHMARK.json")
    entry = [c for c in index["configs"] if c["name"] == REAL_CONFIG]
    assert len(entry) == 1, [c["name"] for c in index["configs"]]
    entry = entry[0]
    assert entry["source"] == SOURCE and len(SOURCE) <= 200
    assert entry["file"] == f"benchmarks/configs/{REAL_CONFIG}.json"
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    cell = [w for w in index["workloads"] if w["name"] == REAL_CELL]
    assert len(cell) == 1, [w["name"] for w in index["workloads"]]
    assert cell[0] == dict(cell[0], config=REAL_CONFIG,
                           traffic="longgen-closed16", chips=1)
    assert set(cell[0]) == {"name", "config", "traffic", "chips", "why"}
    assert all(len(e["why"]) <= 200 for e in (entry, cell[0]))
    for path in (entry["file"], f"benchmarks/workloads/{REAL_CELL}.json",
                 "benchmarks/traffic/longgen-closed16.json", REFERENCE,
                 COSTS):
        assert os.path.isfile(os.path.join(REPO, path)), path
    spec = Spec()
    config = spec.config(REAL_CONFIG)
    loaded = spec.workload(REAL_CELL)
    assert config["name"] == REAL_CONFIG and config["source"] == SOURCE
    assert (loaded["config"], loaded["traffic"], loaded["chips"],
            loaded["kind"]) == (REAL_CONFIG, "longgen-closed16", 1, "serve")
    # (where on its list an entry sits is not asserted: a later PR
    # appends after it)
    assert set(NEW_READERS) <= {m["name"] for m in index["per_layer"]}


def test_the_cell_reports_what_the_index_says():
    from benchmarks.lib import modules
    from benchmarks.lib.spec import Spec

    spec = Spec()
    config = spec.config(REAL_CONFIG)
    reference = modules.reference_of(spec, config)
    assert reference.__file__.endswith(REFERENCE)
    assert reference.GAIN_KEYS
    spec.traffic("longgen-closed16")
    names = [m["name"] for m in spec.per_layer(REAL_CELL)]
    assert set(NEW_READERS) <= set(names)
    assert {m["name"] for m in spec.end_to_end(REAL_CELL)} == {
        "serve_itl_p95_ms", "serve_itl_p99_ms", "setup_s"}
    index = _real("BENCHMARK.json")
    for metric in index["end_to_end"] + index["per_layer"]:
        if metric["name"] in SHARED_METRICS:
            # on the list, wherever: the next cell is appended after it
            assert REAL_CELL in metric["workloads"], metric["name"]
        if metric["name"] in NEW_READERS:
            assert REAL_CELL in metric["workloads"]
            assert metric["moves"] == "serve_itl_p95_ms"
            assert set(metric) == {"name", "unit", "better", "source",
                                   "layer", "moves", "workloads"}
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
    """Every key of the catalog's row under the same name; the depth,
    the experts held and the vocabulary the three cuts, each at or over
    the guide's floor, the published numbers beside them."""
    config = _real("benchmarks", "configs", f"{REAL_CONFIG}.json")
    differs = {k for k, v in PUBLISHED.items() if config.get(k, "-") != v}
    assert differs == set(config["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert config["published"] == {k: PUBLISHED[k] for k in differs}
    assert config["num_hidden_layers"] == 12            # three periods
    assert config["num_hidden_layers"] % config["full_attention_interval"] \
        == 0
    assert (config["num_experts"], config["num_routed_experts"],
            config["first_expert_id"]) == (128, 512, 0)
    assert config["vocab_size"] == 151936 // 4 >= 151936 // 8
    assert config["reference"] == REFERENCE
    assert config["serve"] == _real(
        "benchmarks", "configs", "olmo-hybrid-7b-serve.json")["serve"]
    for key in ("reduced_how", "deployment", "memory_arithmetic", "assumed"):
        assert config[key], key
    assert "16 chips" in config["deployment"]
    assert "multi_token_prediction" in config["assumed"]


def test_the_real_cell_runs_the_longgen_traffic_unchanged():
    cell = _real("benchmarks", "workloads", f"{REAL_CELL}.json")
    other = _real("benchmarks", "workloads", "serve-1.7b-longgen.json")
    assert cell["traffic"] == other["traffic"] == "longgen-closed16"
    for key in ("expect", "trace_seconds", "host_spans", "launch", "chips"):
        assert cell[key] == other[key]
    # every departure the comparison can tell, the lower-precision
    # control among them; what it cannot tell is off the list and said
    # beside the limit
    assert cell["wrong_variants"] == CELL_WRONG
    assert "bf16_router" in cell["check_why"]
    assert "does NOT attest the float32 router" in cell["check_why"]
    from benchmarks.reference.check import SERVE_LOGITS_RTOL_OF_MAX

    # the cell's own limit, between its two readings on the chip (sound
    # 0.0200 at most, the nearest wrong variant 0.037 at least) and
    # tighter than the harness's
    assert cell["check"] == {"prompts": 8, "decode_positions": 64,
                             "q_block": 64, "expert_chunk": 16,
                             "rtol_of_max": 0.027}
    assert 1.3 * 0.0200 < cell["check"]["rtol_of_max"] < 0.037 / 1.3
    assert cell["check"]["rtol_of_max"] < SERVE_LOGITS_RTOL_OF_MAX


def test_the_stream_that_carries_the_token_is_the_file_s_not_the_program_s():
    """The embedding's scale is a stated property of this benchmark's
    random weights (``check_data``), handed to the program as a launch
    argument; the family's own initialiser draws it at 0.02 like every
    family, and no other family reads the argument."""
    import jax

    from benchmarks.lib.program import serving_model
    from scaletorch_tpu.models.presets import preset

    config = _real("benchmarks", "configs", f"{REAL_CONFIG}.json")
    assert config["embed_init_std"] == 1.0
    assert set(config["check_data"]) == {
        "embed_init_std", "readings_by_scale", "what_it_cannot_replace"}
    assert "0.298" in config["check_data"]["readings_by_scale"]
    cfg, _ = serving_model(config, "bfloat16")
    assert cfg.embed_init_std == 1.0

    tiny = dict(preset("qwen3-next-tiny"), model_type="qwen3_next")
    for asked, std in ((None, 0.02), (1.0, 1.0)):
        cfg, init = serving_model(
            dict(tiny, **({} if asked is None
                          else {"embed_init_std": asked})), "float32")
        assert cfg.embed_init_std == std
        embed = jax.jit(init, static_argnums=1)(
            jax.random.PRNGKey(0), cfg)["embed_tokens"]
        assert abs(float(embed.std()) / std - 1) < 0.05
    with pytest.raises(NotImplementedError, match="embed_init_std"):
        serving_model(dict(TOY_MODEL, embed_init_std=1.0), "float32")


def test_the_program_builds_the_share_from_the_file():
    """``benchmarks/lib/program.py`` hands the file's keys to the
    program's own dispatch: a 512-wide router over 128 held experts,
    the two layer kinds in order, every published width."""
    from benchmarks.lib.program import serving_model
    from scaletorch_tpu.models import qwen3_next

    config = _real("benchmarks", "configs", f"{REAL_CONFIG}.json")
    cfg, init = serving_model(config, "bfloat16")
    assert isinstance(cfg, qwen3_next.Qwen3NextConfig)
    assert init is qwen3_next.init_params
    assert (cfg.num_periods, cfg.num_linear_layers,
            cfg.num_kv_cache_layers) == (3, 9, 3)
    assert (cfg.num_experts, cfg.router_width, cfg.first_expert_id,
            cfg.num_experts_per_tok) == (128, 512, 0, 10)
    assert not cfg.holds_every_expert
    assert (cfg.hidden_size, cfg.moe_intermediate_size,
            cfg.shared_expert_intermediate_size, cfg.vocab_size,
            cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.actual_head_dim, cfg.rotary_dim, cfg.rope_theta) == (
        2048, 512, 512, 37984, 16, 2, 256, 64, 1e7)
    assert (cfg.linear_num_key_heads, cfg.linear_num_value_heads,
            cfg.linear_allow_neg_eigval) == (16, 32, False)
    assert cfg.recurrent_state_shapes(16) == (
        (9, 16, 32, 128, 128), (9, 16, 3, 8192))
    # the configuration file's arithmetic: 5.42 B parameters
    assert cfg.num_params() == (
        9 * 33_720_512 + 3 * 27_265_536
        + 12 * (4_198_400 + 128 * 3 * 2048 * 512)
        + 2 * 37984 * 2048 + 2048)
    assert 5.41e9 < cfg.num_params() < 5.43e9


def test_cost_functions_against_hand_counts():
    from benchmarks.lib import modules
    from benchmarks.lib.spec import Spec

    spec = Spec()
    config = spec.config(REAL_CONFIG)

    def cost(name):
        return modules.cost_function(spec, name, COSTS)

    assert cost("layer_counts")(config) == {"linear": 9, "full": 3}
    linear = (2048 * (2 * 2048 + 3 * 4096) + 2 * 2048 * 32 + 2 * 32
              + 8192 * 4 + 128 + 2048)
    full = 2048 * (3 * 4096 + 2 * 512) + 2 * 256 + 2048
    sparse = 2048 * 512 + (3 * 512 + 1) * 2048 + 2048
    assert cost("linear_mixer_params")(config) == linear == 33_720_512
    assert cost("full_mixer_params")(config) == full == 27_265_536
    assert cost("sparse_mlp_dense_params")(config) == sparse == 4_198_400
    dense = 2 * (9 * linear + 3 * full + 12 * sparse + 2048 + 2048 * 37984)
    assert cost("dense_weight_bytes")(config) == dense
    assert cost("expert_matrix_bytes")(config) == 2048 * 512 * 2
    touched = 128 * (1 - (1 - 10 / 512) ** 16)
    assert cost("experts_touched")(config) == pytest.approx(touched)
    assert 34.6 < touched < 34.7
    call = touched * 2048 * 512 * 2
    assert cost("expert_decode_call_bytes")(config) == pytest.approx(call)
    assert cost("kv_bytes_per_token")(config) == 2 * 3 * 2 * 256 * 2 == 6144
    update = 16 * 32 * 128 * 128 * 4 * 2
    assert cost("state_update_call_bytes")(config) == update
    tail = 16 * 3 * 8192 * 2 * 2
    step = cost("decode_step_bytes")
    assert step(config, 0.0) == pytest.approx(
        dense + 12 * 3 * call + 9 * (update + tail))
    assert step(config, 1000.0) - step(config, 0.0) == pytest.approx(
        1000 * 6144)
    # the issue's arithmetic: ~4.3 GB a step, 0.6 GB of it state
    assert 4.2e9 < step(config, 7200.0) < 4.4e9
    assert 0.60e9 < 9 * update < 0.61e9


# ---- the toy cell through run.py ---------------------------------------------

def make_next_root(root, reference=REFERENCE):
    """The toy tree plus ``toy-qwen3-next-serve``: a configuration that
    holds a share of its experts, a cell, and its name on the
    ``workloads`` lists the real cell is on."""
    make_toy_root(root, extra_metric=True)
    bench = os.path.join(root, "benchmarks")
    with open(os.path.join(bench, "configs", f"{TOY_CELL}.json"), "w") as f:
        json.dump(dict(
            TOY_NEXT, name=TOY_CELL, reference=reference,
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
    index = json.load(open(path))
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
    have = {m["name"] for s in ("end_to_end", "per_layer")
            for m in index[s]}
    for section in ("end_to_end", "per_layer"):
        for metric in index[section]:
            if metric["name"] in on:
                metric["workloads"] = sorted(
                    set(metric["workloads"]) | {TOY_CELL})
            elif metric["name"] == "toy_engine_decode_steps":
                metric["workloads"].append(TOY_CELL)
    # the two counters this family brings, as the real index has them
    for metric in real["per_layer"]:
        if metric["name"].startswith("serve_moe_assignments_") \
                and metric["name"] not in have:
            index["per_layer"].append(dict(metric, workloads=[TOY_CELL]))
            with open(os.path.join(
                    REPO, "benchmarks", "metrics",
                    metric["name"] + ".json")) as src, open(os.path.join(
                        bench, "metrics", metric["name"] + ".json"),
                        "w") as dst:
                dst.write(src.read())
    with open(path, "w") as f:
        json.dump(index, f)
    return root


def _run(root, trace="0"):
    return run_cell(["--root", root, "--workload", TOY_CELL,
                     "--seed", SEED, "--seconds", "1", "--trace", trace,
                     "--rehearse"])


@pytest.fixture(scope="module")
def own_reference(tmp_path_factory):
    return _run(make_next_root(str(tmp_path_factory.mktemp("next"))), "1")


@pytest.fixture(scope="module")
def qwen3_reference(tmp_path_factory):
    return _run(make_next_root(str(tmp_path_factory.mktemp("swapped")),
                               reference=TOY_MODEL["reference"]))


def test_next_cell_walks_to_its_result_line(own_reference):
    rc, line, out = own_reference
    assert rc == 3, out
    assert CONTRACT_KEYS <= set(line), out
    assert line["correct"] is True, out
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["check"]["err_of_max"] < 3e-4, out


@pytest.mark.parametrize("variant", WRONG)
def test_next_cell_rejects_each_wrong_variant(own_reference, variant):
    _, line, out = own_reference
    verdict = line["check"]["wrong_variants"][variant]
    assert verdict["ok"] is False, out
    assert verdict["err_of_max"] > 10 * line["check"]["rtol_of_max"]


def test_next_cell_reports_the_share_s_counters(own_reference):
    """``engine.moe_assignments_*`` and ``engine.recurrent_state_*``
    reach ``counter`` readers with no edit to the harness: choices fall
    on the held experts and on the absent ones, none is dropped, and no
    slot-step ran on another request's state."""
    _, line, out = own_reference
    metrics = line["metrics"]
    held = metrics["serve_moe_assignments_held"]["value"]
    elsewhere = metrics["serve_moe_assignments_elsewhere"]["value"]
    assert held > 0 and elsewhere > 0, out
    assert metrics["serve_moe_dropped_assignments"]["value"] == 0, out
    assert metrics["serve_recurrent_state_owner_mismatches"]["value"] == 0, \
        out
    assert metrics["toy_engine_decode_steps"]["value"] > 0


def test_next_cell_under_the_qwen3_reference_is_not_correct(
        qwen3_reference):
    rc, line, out = qwen3_reference
    assert line.get("correct") is not True, out
    if not line:
        assert rc not in (0, 3), out
