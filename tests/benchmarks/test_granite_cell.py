"""The granitemoehybrid (Granite 4.0-H) family in the benchmark. First the
index: the committed ``BENCHMARK.json`` holds the configuration's and
the cell's entries and ``Spec`` loads the files they name (membership,
never a place in a list and never a count). Then the configuration
against the catalog's row, the cost module against hand arithmetic, the
readers against the names the compiled step programs print, and a toy
tree with the published ``config.json`` key names and
``benchmarks/reference/granite_moe_hybrid.py`` through ``run.py --root
--rehearse`` to its result line."""

import json
import os
import re

import pytest

from tests.benchmarks.helpers import CONTRACT_KEYS, run_cell
from tests.benchmarks.toy import REPO, make_toy_root

SEED = str(2**31 + 61)
REFERENCE = "benchmarks/reference/granite_moe_hybrid.py"
COSTS = "benchmarks/costs/granite_moe_hybrid.py"
REAL_CELL = "serve-granite-4.0-h-small-draft64"
REAL_CONFIG = "granite-4.0-h-small-serve"
TRAFFIC = "draft-closed64"
SOURCE = ("https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/"
          "config.json")
TOY_CELL = "toy-granite-serve"
_PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
# the catalog's row (model-configs/architectures.jsonl), key for key
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.0078125,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 4096,
    "intermediate_size": 768, "layer_types": _PERIOD * 4,
    "logits_scaling": 16, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 128,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 10,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 72, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 1536, "tie_word_embeddings": True,
    "vocab_size": 100352}
REDUCED = ["num_hidden_layers", "layer_types", "num_local_experts",
           "vocab_size"]
TOY_GRANITE = {
    **{k: v for k, v in PUBLISHED.items()},
    "vocab_size": 128, "hidden_size": 32, "intermediate_size": 16,
    "num_hidden_layers": 5,
    "layer_types": ["mamba", "mamba", "attention", "mamba", "mamba"],
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "max_position_embeddings": 4096, "mamba_n_heads": 4, "mamba_d_head": 16,
    "mamba_d_state": 8, "mamba_chunk_size": 8, "num_local_experts": 4,
    "num_routed_experts": 8, "first_expert_id": 4, "num_experts_per_tok": 3,
    "shared_intermediate_size": 24, "attention_multiplier": 0.125,
    "reduced": [], "assumed": {},
}
NEW_READERS = [
    "serve_granite_decode_step_hbm_roofline",
    "serve_granite_ssd_state_update_hbm_roofline",
    "serve_granite_expert_mlp_roofline",
    "serve_granite_ssd_prefill_roofline",
    "serve_granite_mamba_mixer_share",
    "serve_device_idle_share.granite-draft64",
    "serve_ssd_state_slot_updates.granite",
    "serve_ssd_prefill_chunks.granite",
    "serve_moe_assignments_held.granite",
    "serve_moe_assignments_elsewhere.granite",
    "serve_prefill_positions_run.granite",
    "serve_full_keys_attended.granite"]
# the generic serving readers whose lists the cell joins
SHARED_METRICS = [
    "serve_itl_p95_ms", "serve_itl_p99_ms", "serve_tick_interval_p50_ms",
    "serve_decode_step_device_ms", "serve_prefill_device_share",
    "serve_moe_dropped_assignments", "serve_moe_expert_mlp_share",
    "serve_itl_long_gap_share_pct", "serve_deliver_lag_p95_ms",
    "serve_write_shoulder_gap_ms", "serve_host_gc_pause_ms",
    "serve_host_gc_full_pause_ms", "serve_engine_slow_ticks",
    "serve_recurrent_state_owner_mismatches"]
# names as the compiled step programs print them (AOT for the v5e and the
# chip's traces, PR 61; lib/trace.short_name's form), the readers' own
# and their neighbours
UPDATE = ("ssd_state_update.12 | custom-call | tpu_custom_call | "
          "(f32[64,1,8192], f32[9,64,128,8192])")
GMM_DECODE = ["gmm.12 | custom-call | tpu_custom_call | bf16[1024,768]",
              "gmm.14 | custom-call | tpu_custom_call | bf16[1024,4096]"]
GMM_PREFILL = ["gmm.10 | custom-call | tpu_custom_call | bf16[20480,768]",
               "gmm.11 | custom-call | tpu_custom_call | bf16[20480,4096]"]
PAGED = "paged_decode.3 | custom-call | tpu_custom_call | bf16[64,8,4,128]"
FLASH = ("flash_fwd.3 | custom-call | tpu_custom_call | "
         "(bf16[1,32,2048,128], f32[1,32,1,2048])")
HIDDEN = "fusion.77 | fusion | kOutput | bf16[1,2048,4096]"
SCAN = ("while.207 | while | - | (s32[], f32[1,128,8192], "
        "f32[8,1,256,128,64], f32[8,1,256,128,64], f32[8,1,256,128], "
        "/*index=5*/f32[8,1,256,128], f32[8,")
SORT_LOOP = ("while.209 | while | - | (s32[], s32[360], s32[360], f32[41], "
             "f32[360], /*index=5*/pred[360], s32[], s32[])")


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
    assert "64 slots" in cell[0]["why"] and "Mamba-2" in entry["why"]
    for path in (entry["file"], f"benchmarks/workloads/{REAL_CELL}.json",
                 f"benchmarks/traffic/{TRAFFIC}.json", REFERENCE, COSTS,
                 "benchmarks/costs/granite_moe_hybrid.md"):
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
    # NOT on another family's kernel metrics
    for other in ("serve_paged_attn_roofline", "serve_jamba_ssm_scan_share",
                  "serve_kimi_kda_state_update_roofline",
                  "serve_latent_keys_attended"):
        assert other not in names
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
        if not twin.endswith(".granite"):
            continue
        original = twin[:-len(".granite")]
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
        # tokens: never a call's own rows
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
    ("serve_granite_ssd_state_update_hbm_roofline", [UPDATE],
     [PAGED, FLASH, HIDDEN] + GMM_DECODE),
    ("serve_granite_expert_mlp_roofline", GMM_DECODE,
     [UPDATE, PAGED, FLASH] + GMM_PREFILL),
    ("serve_granite_mamba_mixer_share", [UPDATE, SCAN],
     [PAGED, FLASH, HIDDEN, SORT_LOOP] + GMM_DECODE + GMM_PREFILL),
    ("serve_granite_ssd_prefill_roofline", [SCAN],
     [UPDATE, PAGED, FLASH, HIDDEN, SORT_LOOP] + GMM_PREFILL),
    ("serve_granite_decode_step_hbm_roofline",
     ["jit_decode(1234567890)"], ["jit_prefill(123)", UPDATE]),
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
    """Every key of the catalog's row under the same name; the four cut
    keys differ and are listed, in the file and in the index alike, with
    what was published; no width, head count, state size, top k or
    multiplier changes; the floors of the model-configs guide hold."""
    config = _real("benchmarks", "configs", f"{REAL_CONFIG}.json")
    differs = sorted(k for k, v in PUBLISHED.items() if config.get(k) != v)
    assert differs == sorted(REDUCED) == sorted(config["reduced"])
    assert config["published"] == {k: PUBLISHED[k] for k in REDUCED}
    assert set(PUBLISHED) <= set(config)
    # the cut: one whole period, 36 of 72 experts, half the vocabulary
    assert config["num_hidden_layers"] == 10
    assert config["layer_types"] == _PERIOD
    assert config["num_local_experts"] == 36 >= 8
    assert (config["num_routed_experts"], config["first_expert_id"]) == (
        72, 0)
    assert config["vocab_size"] * 2 == PUBLISHED["vocab_size"]
    assert config["vocab_size"] % 128 == 0
    for name in ("published", "reduced_how", "deployment", "assumed",
                 "memory_arithmetic", "cost_inputs", "check_data"):
        assert config[name], name
    assert config["serve"] == {
        "dtype": "bfloat16", "max_slots": 64, "max_seq": 4608,
        "prefill_len": 2048, "page_size": 16}
    assumed = config["assumed"]
    assert "modeling_granitemoehybrid.py" in assumed["source_of_these"]
    assert "mamba2.py" in assumed["initialisers"]
    assert "8 chips" in config["deployment"]


def test_the_traffic_is_the_issue_s():
    traffic = _real("benchmarks", "traffic", f"{TRAFFIC}.json")
    assert (traffic["kind"], traffic["clients"],
            traffic["requests_per_client"], traffic["lead_in_s"]) == (
        "closed_loop", 64, 6, 30.0)
    assert traffic["prompt_tokens"] == {
        "dist": "lognormal", "median": 768, "sigma": 0.6, "min": 256,
        "max": 2048}
    assert traffic["max_new_tokens"] == {
        "dist": "uniform", "min": 1536, "max": 2560}
    serve = _real("benchmarks", "configs", f"{REAL_CONFIG}.json")["serve"]
    assert traffic["clients"] == serve["max_slots"]
    assert traffic["prompt_tokens"]["max"] <= serve["prefill_len"]
    assert traffic["prompt_tokens"]["max"] + traffic["max_new_tokens"][
        "max"] <= serve["max_seq"]
    assert serve["max_seq"] % serve["page_size"] == 0


def test_the_real_cell_checks_what_the_issue_names():
    from benchmarks.reference import check, granite_moe_hybrid

    cell = _real("benchmarks", "workloads", f"{REAL_CELL}.json")
    listed = cell["wrong_variants"]
    assert len(listed) <= 5 and set(listed) <= set(granite_moe_hybrid.WRONG)
    assert {"bf16_state", "fp8_activations"} <= set(listed)
    assert cell["expect"] == {"decode_compile_count": 1}
    assert set(cell["check"]) == {"prompts", "decode_positions", "q_block",
                                  "expert_chunk", "rtol_of_max"}
    # the cell's own limit is tighter than the harness's
    assert cell["check"]["rtol_of_max"] < check.SERVE_LOGITS_RTOL_OF_MAX
    assert (2048 + cell["check"]["decode_positions"]) % cell["check"][
        "q_block"] == 0
    assert 36 % cell["check"]["expert_chunk"] == 0
    for word in ("bf16_state", "fp8", "SOUND", "CONTROL"):
        assert word in cell["check_why"], word


def test_the_program_builds_the_share_from_the_file():
    """The file's keys reach the program's arguments under their
    published names and the program's own dispatch builds the family's
    class from them: the layer list, the Mamba-2 widths, the share, the
    four multipliers, the draw."""
    from benchmarks.lib import program
    from scaletorch_tpu.models import granite_moe_hybrid as granite

    config = _real("benchmarks", "configs", f"{REAL_CONFIG}.json")
    passed = program.model_arguments(config)
    for key in ("layer_types", "mamba_n_heads", "mamba_d_head",
                "mamba_d_state", "mamba_n_groups", "mamba_chunk_size",
                "num_local_experts", "num_routed_experts",
                "shared_intermediate_size", "embedding_multiplier",
                "attention_multiplier", "residual_multiplier",
                "logits_scaling", "position_embedding_type",
                "num_experts_per_tok", "intermediate_size"):
        assert passed[key] == config[key], key
    cfg, init = program.serving_model(config, "bfloat16")
    assert type(cfg) is granite.GraniteMoeHybridConfig
    assert init is granite.init_params
    assert cfg.layer_kinds == tuple(_PERIOD)
    assert cfg.recurrent_state_shapes(64) == (
        (9, 64, 128, 8192), (9, 64, 3, 8448))
    assert (cfg.num_experts, cfg.router_width, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size) == (36, 72, 10, 768)
    assert (cfg.embedding_multiplier, cfg.attention_multiplier,
            cfg.residual_multiplier, cfg.logits_scaling) == (
        12, 0.0078125, 0.22, 16)
    assert cfg.rope_theta is None and cfg.rms_norm_eps == 1e-5
    for name in config["check_data"]["launch_arguments"]:
        assert getattr(cfg, name) == config[name], name
    # ISSUE 61: 4.76 B parameters = 9.51 GB
    assert 4.75e9 < cfg.num_params() < 4.77e9


def test_cost_functions_against_hand_counts():
    from benchmarks.lib import modules
    from benchmarks.lib.spec import Spec

    spec = Spec()
    config = spec.config(REAL_CONFIG)

    def cost(name):
        return modules.cost_function(spec, name, COSTS)

    costs = modules.load(spec, COSTS, "the test")
    h = 4096
    mamba = (h * 16768 + 8448 * 5 + 3 * 128 + 8192 + 8192 * h)
    assert (costs.mamba_mixer_params(config),
            costs.attention_mixer_params(config)) == (
        mamba, 2 * h * (4096 + 1024)) == (102_286_976, 41_943_040)
    dense = (9 * mamba + 41_943_040 + 10 * (2 * h + h * 72 + 3 * 1536 * h)
             + h + h * 50176) * 2
    assert costs.dense_weight_bytes(config) == dense
    assert costs.expert_matrix_bytes(config) == h * 768 * 2 == 6_291_456
    touched = costs.experts_touched(config)
    assert touched == pytest.approx(36 * (1 - (62 / 72) ** 64))
    assert 35.99 < touched < 36
    assert cost("expert_decode_call_bytes")(config) == pytest.approx(
        touched * 6_291_456)
    state = 64 * 128 * 8192 * 4
    assert cost("ssd_state_update_bytes")(config) == 2 * state == 536_870_912
    assert costs.conv_tail_call_bytes(config) == 64 * 3 * 8448 * 2 * 2
    assert costs.kv_bytes_per_token(config) == 2 * 8 * 128 * 2 == 4096
    live = 64 * 1900.0
    step = cost("decode_step_bytes")(config, live)
    assert step == pytest.approx(
        dense + 10 * 3 * touched * 6_291_456 + 4096 * live
        + 9 * (2 * state + 64 * 3 * 8448 * 4))
    # ISSUE 61: ~14.9 GB, an 18.2 ms floor at 819 GB/s
    assert 14.8e9 < step < 15.0e9
    # the prefill call's own rows: ONE row of 2,048, never max_slots
    q, heads, p, n = 256, 128, 64, 128
    flops = costs.ssd_prefill_call_flops(config)
    assert flops == 8 * (2 * q * q * n + 2 * heads * q * q * p
                         + 4 * heads * p * n * q)
    nbytes = cost("ssd_prefill_call_bytes")(config)
    assert nbytes == 2048 * (2 * 8192 + 128 + 256) * 4 + 128 * 8192 * 4
    # at the v5e's peaks the bytes bind: the reader's peak is HBM's
    assert nbytes / 819e9 > flops / 197e12
    more_slots = dict(config, serve=dict(config["serve"], max_slots=128))
    assert cost("ssd_prefill_call_bytes")(more_slots) == nbytes


# ---- the toy cell through run.py --rehearse ------------------------------------

def make_granite_root(root):
    """The toy tree with one more configuration, the family's at the
    tiny preset's sizes under the published key names with a share of
    the experts, a cell, and its name on the ``workloads`` lists the
    real cell is on."""
    make_toy_root(root, extra_metric=True)
    bench = os.path.join(root, "benchmarks")
    with open(os.path.join(bench, "configs", f"{TOY_CELL}.json"), "w") as f:
        json.dump(dict(
            TOY_GRANITE, name=TOY_CELL, reference=REFERENCE,
            source="made up for the tests",
            serve={"max_slots": 4, "max_seq": 128, "prefill_len": 64,
                   "page_size": 8, "dtype": "float32"}), f)
    with open(os.path.join(bench, "workloads", f"{TOY_CELL}.json"),
              "w") as f:
        json.dump({"name": TOY_CELL, "kind": "serve", "config": TOY_CELL,
                   "traffic": "toy-requests", "chips": 1,
                   "trace_seconds": 0.5,
                   "expect": {"decode_compile_count": 1},
                   # three of the nine (tests/models holds them all): a
                   # variant is a compile of the reference in the child
                   "wrong_variants": ["norm_before_gate", "no_d_skip",
                                      "fp8_activations"],
                   "check": {"prompts": 4, "decode_positions": 8,
                             "q_block": 8, "expert_chunk": 2,
                             # float32 at toy size reads under 1e-6
                             "rtol_of_max": 2e-5}}, f)
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


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    return run_cell(["--root", make_granite_root(
        str(tmp_path_factory.mktemp("granite"))), "--workload", TOY_CELL,
        "--seed", SEED, "--seconds", "1", "--trace", "1", "--rehearse"])


def test_granite_cell_walks_to_its_result_line(rehearsed):
    rc, line, out = rehearsed
    assert rc == 3, out
    assert CONTRACT_KEYS <= set(line), out
    assert line["correct"] is True, out
    assert line["attempted"] > 0 and line["failed"] == 0
    # float32 at toy size: the chunked scan's and the grouped matmul's
    # reassociation, far under the limit the toy cell states
    assert line["check"]["err_of_max"] < 2e-6, out


@pytest.mark.parametrize("variant", ["norm_before_gate", "no_d_skip",
                                     "fp8_activations"])
def test_granite_cell_rejects_each_listed_wrong_variant(rehearsed, variant):
    _, line, out = rehearsed
    verdict = line["check"]["wrong_variants"][variant]
    assert verdict["ok"] is False, out
    err = verdict["err_of_max"]
    assert err != err or err > 10 * line["check"]["rtol_of_max"]


def test_granite_cell_reports_the_counters_of_both_mechanisms(rehearsed):
    """``engine.ssd_*``, ``engine.full_keys_attended`` and
    ``engine.moe_assignments_*`` reach ``counter`` readers with no edit
    to the harness: states advanced, chunks scanned, keys attended,
    choices on the held experts and on the absent ones, none dropped,
    every prefill call one row, no slot's state read by a stranger."""
    _, line, out = rehearsed
    metrics = line["metrics"]
    assert metrics["serve_ssd_state_slot_updates.granite"]["value"] > 0, out
    assert metrics["serve_ssd_state_slot_updates.granite"]["value"] % 4 == 0
    chunks = metrics["serve_ssd_prefill_chunks.granite"]["value"]
    run = metrics["serve_prefill_positions_run.granite"]["value"]
    # a row of 64 is 8 chunks of 8 in each of 4 layers (the window's
    # edges are read from another thread: within a call of each other)
    assert chunks > 0 and abs(chunks - run // 64 * 8 * 4) <= 8 * 4, out
    assert metrics["serve_full_keys_attended.granite"]["value"] > 0, out
    held = metrics["serve_moe_assignments_held.granite"]["value"]
    elsewhere = metrics["serve_moe_assignments_elsewhere.granite"]["value"]
    assert held > 0 and elsewhere > 0, out
    assert metrics["serve_moe_dropped_assignments"]["value"] == 0, out
    assert metrics["serve_recurrent_state_owner_mismatches"]["value"] == 0
    assert metrics["toy_engine_decode_steps"]["value"] > 0
    # no device plane on the CPU: the trace's readers report nothing
    assert "serve_granite_ssd_state_update_hbm_roofline" not in metrics
