"""The Olmo-Hybrid family in the benchmark, as files only. The committed
``BENCHMARK.json`` loads the real cell, its configuration, its
reference and every per-layer reader through ``Spec``; a toy tree with
the published ``config.json`` key names (``layer_types``, ``linear_*``,
``rope_parameters``), ``benchmarks/reference/olmo_hybrid.py`` and the
real cell's ``wrong_variants`` runs through ``run.py --root --rehearse``
to its result line; the cost module against hand counts."""

import json
import os

import pytest

from tests.benchmarks.helpers import CONTRACT_KEYS, run_cell
from tests.benchmarks.toy import REPO, TOY_MODEL, make_toy_root

SEED = str(2**31 + 32)
REFERENCE = "benchmarks/reference/olmo_hybrid.py"
COSTS = "benchmarks/costs/olmo_hybrid.py"
REAL_CELL = "serve-olmo-hybrid-longgen"
REAL_CONFIG = "olmo-hybrid-7b-serve"
TOY_CELL = "toy-olmo-hybrid-serve"
LINEAR, FULL = "linear_attention", "full_attention"
TOY_HYBRID = {
    "model_type": "olmo_hybrid", "vocab_size": 128, "hidden_size": 64,
    "intermediate_size": 96, "num_hidden_layers": 8,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "layer_types": [LINEAR, LINEAR, LINEAR, FULL] * 2,
    "linear_num_key_heads": 4, "linear_num_value_heads": 4,
    "linear_key_head_dim": 8, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None},
    "max_position_embeddings": 4096, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "reduced": [], "assumed": {},
}
WRONG = ["beta_unscaled", "no_decay", "no_short_conv",
         "rope_on_full_layers", "bf16_state"]
NEW_READERS = [
    "serve_device_idle_share.olmo-hybrid-longgen",
    "serve_hybrid_decode_step_hbm_roofline", "serve_gdn_recurrence_share",
    "serve_gdn_state_update_roofline",
    "serve_recurrent_state_owner_mismatches"]
SHARED_METRICS = [
    "serve_itl_p95_ms", "serve_itl_p99_ms", "serve_tick_interval_p50_ms",
    "serve_decode_step_device_ms", "serve_paged_attn_roofline",
    "serve_req_host_ms_per_token", "serve_req_device_wait_ms_per_token",
    "serve_req_stall_ms_per_token", "serve_prefill_wall_p50_ms",
    "serve_prefill_device_share"]


def _real(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


def make_hybrid_root(root, reference=REFERENCE):
    """The toy tree plus ``toy-olmo-hybrid-serve``: a configuration, a
    cell and its name on the ``workloads`` lists the real cell is on."""
    make_toy_root(root, extra_metric=True)
    bench = os.path.join(root, "benchmarks")
    with open(os.path.join(bench, "configs", f"{TOY_CELL}.json"), "w") as f:
        json.dump(dict(
            TOY_HYBRID, name=TOY_CELL, reference=reference,
            source="made up for the tests",
            serve={"max_slots": 4, "max_seq": 128, "prefill_len": 64,
                   "page_size": 16, "dtype": "float32"}), f)
    with open(os.path.join(bench, "workloads", f"{TOY_CELL}.json"),
              "w") as f:
        # float32 serving at toy size: the chunked scan against the
        # reference's row-after-row recurrence lands near 1e-4 of the
        # largest logit (the triangular solve's rounding), the wrong
        # variants three orders above
        json.dump({"name": TOY_CELL, "kind": "serve", "config": TOY_CELL,
                   "traffic": "toy-requests", "chips": 1,
                   "trace_seconds": 0.5,
                   "expect": {"decode_compile_count": 1},
                   "wrong_variants": WRONG,
                   "check": {"prompts": 4, "decode_positions": 8,
                             "q_block": 8, "rtol_of_max": 1e-3}}, f)
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
    return _run(make_hybrid_root(str(tmp_path_factory.mktemp("hybrid"))),
                "1")


@pytest.fixture(scope="module")
def qwen3_reference(tmp_path_factory):
    return _run(make_hybrid_root(str(tmp_path_factory.mktemp("swapped")),
                                 reference=TOY_MODEL["reference"]))


# ---- the committed benchmark ------------------------------------------------

def test_the_committed_benchmark_holds_the_cell_and_its_configuration():
    """What PR 31 lacked: ``BENCHMARK.json`` itself has the entries."""
    from benchmarks.lib import modules
    from benchmarks.lib.spec import Spec

    spec = Spec()
    cell = spec.workload(REAL_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"], cell["kind"]) \
        == (REAL_CONFIG, "longgen-closed16", 1, "serve")
    config = spec.config(REAL_CONFIG)
    reference = modules.reference_of(spec, config)
    assert reference.__file__.endswith(REFERENCE)
    assert reference.GAIN_KEYS
    spec.traffic(cell["traffic"])
    names = [m["name"] for m in spec.per_layer(REAL_CELL)]
    assert set(NEW_READERS) <= set(names)
    assert {m["name"] for m in spec.end_to_end(REAL_CELL)} == {
        "serve_itl_p95_ms", "serve_itl_p99_ms", "setup_s"}
    index = _real("BENCHMARK.json")
    for metric in index["end_to_end"] + index["per_layer"]:
        if metric["name"] in SHARED_METRICS:
            assert REAL_CELL in metric["workloads"], metric["name"]
        if metric["name"] in NEW_READERS:
            # the cell that brought the reader comes first; a later
            # cell's name is appended (PR 35's, to the owner counter)
            assert metric["workloads"][0] == REAL_CELL
            assert metric["moves"] == "serve_itl_p95_ms"


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
        modules.cost_function(spec, reducer["cost_function"], COSTS)
    # nothing to read (no trace, no counter): nothing reported, no raise
    empty = {"events": [], "window": None, "records": {}, "counters": {},
             "config": spec.config(REAL_CONFIG), "traffic": {},
             "workload": spec.workload(REAL_CELL), "peaks": {},
             "spec": spec}
    assert reducers.read_metric(empty, reader) is None


def test_the_real_configuration_keeps_the_published_keys():
    """Every key of the catalog's row for Olmo-Hybrid-7B under the same
    name; the depth and the list of layer kinds the two cuts, to four
    whole periods."""
    kinds = [LINEAR, LINEAR, LINEAR, FULL]
    published = {
        "model_type": "olmo_hybrid", "vocab_size": 100352,
        "hidden_size": 3840, "intermediate_size": 11008,
        "num_hidden_layers": 32, "num_attention_heads": 30,
        "num_key_value_heads": 30, "hidden_act": "silu",
        "max_position_embeddings": 65536, "attention_bias": False,
        "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
        "layer_types": kinds * 8, "linear_num_key_heads": 30,
        "linear_num_value_heads": 30, "linear_key_head_dim": 96,
        "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
        "linear_allow_neg_eigval": True,
        "rope_parameters": {"rope_theta": None}}
    config = _real("benchmarks", "configs", f"{REAL_CONFIG}.json")
    differs = {k for k, v in published.items() if config.get(k, "-") != v}
    assert differs == set(config["reduced"]) == {
        "num_hidden_layers", "layer_types"}
    assert config["num_hidden_layers"] == 16 >= 12
    assert config["layer_types"] == kinds * 4
    assert config["reference"] == REFERENCE
    assert config["serve"] == {
        "dtype": "bfloat16", "max_slots": 16, "max_seq": 1536,
        "prefill_len": 512, "page_size": 16}
    entry = [c for c in _real("BENCHMARK.json")["configs"]
             if c["name"] == REAL_CONFIG][0]
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/"
        "config.json")


def test_the_real_cell_runs_the_longgen_traffic_unchanged():
    cell = _real("benchmarks", "workloads", f"{REAL_CELL}.json")
    other = _real("benchmarks", "workloads", "serve-1.7b-longgen.json")
    assert cell["traffic"] == other["traffic"] == "longgen-closed16"
    for key in ("expect", "trace_seconds", "host_spans", "launch", "chips"):
        assert cell[key] == other[key]
    assert cell["wrong_variants"] == WRONG
    # the harness's own limit, not widened, with the reason beside it,
    # and what the comparison cannot see said there too: the bf16 state
    from benchmarks.reference.check import SERVE_LOGITS_RTOL_OF_MAX

    assert cell["check"] == {"prompts": 8, "decode_positions": 64,
                             "q_block": 64,
                             "rtol_of_max": SERVE_LOGITS_RTOL_OF_MAX}
    assert "not widened" in cell["check_why"]
    assert "bf16_state" in cell["check_why"]


def test_the_program_builds_the_published_model_from_the_file():
    """``benchmarks/lib/program.py`` hands the file's keys to the
    program's own dispatch: the two layer kinds in order, no rotary
    embedding, every width."""
    from benchmarks.lib.program import serving_model
    from scaletorch_tpu.models import olmo_hybrid

    config = _real("benchmarks", "configs", f"{REAL_CONFIG}.json")
    cfg, init = serving_model(config, "bfloat16")
    assert isinstance(cfg, olmo_hybrid.OlmoHybridConfig)
    assert init is olmo_hybrid.init_params
    assert cfg.layer_kinds == tuple(config["layer_types"])
    assert cfg.rope_theta is None
    assert (cfg.num_periods, cfg.num_linear_layers,
            cfg.num_kv_cache_layers) == (4, 12, 4)
    assert cfg.recurrent_state_shapes(16) == (
        (12, 16, 30, 96, 192), (12, 16, 3, 11520))
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size,
            cfg.num_attention_heads, cfg.actual_head_dim) == (
        3840, 11008, 100352, 30, 128)
    # 12 x 215,570,172 + 4 x 185,809,920 + 2 x 100352 x 3840 + 3840
    assert cfg.num_params() == 4_100_788_944


def test_cost_functions_against_hand_counts():
    from benchmarks.lib import modules
    from benchmarks.lib.spec import Spec

    spec = Spec()
    config = spec.config(REAL_CONFIG)

    def cost(name):
        return modules.cost_function(spec, name, COSTS)

    mlp = 3 * 3840 * 11008 + 2 * 3840
    linear = (3840 * (2 * 2880 + 3 * 5760) + 2 * 3840 * 30 + 2 * 30
              + 11520 * 4 + 192 + mlp)
    full = 4 * 3840 * 3840 + 2 * 3840 + mlp
    assert cost("linear_layer_params")(config) == linear
    assert cost("full_layer_params")(config) == full
    weights = 2 * (12 * linear + 4 * full + 3840 + 3840 * 100352)
    assert cost("weight_bytes")(config) == weights
    assert cost("kv_bytes_per_token")(config) == 2 * 4 * 30 * 128 * 2
    update = 16 * 30 * 96 * 192 * 4 * 2
    assert cost("state_update_call_bytes")(config) == update
    tail = 16 * 3 * 11520 * 2 * 2
    step = cost("decode_step_bytes")
    assert step(config, 0.0) == weights + 12 * (update + tail)
    assert step(config, 1000.0) - step(config, 0.0) == 1000 * 61440
    # the issue's arithmetic: 7.4 GB of weights, 0.85 GB of state
    assert 7.4e9 < weights < 7.5e9 and 0.84e9 < 12 * update < 0.86e9


# ---- the toy cell through run.py ---------------------------------------------

def test_hybrid_cell_walks_to_its_result_line(own_reference):
    rc, line, out = own_reference
    assert rc == 3, out
    assert CONTRACT_KEYS <= set(line), out
    assert line["correct"] is True, out
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["check"]["err_of_max"] < 3e-4, out


@pytest.mark.parametrize("variant", WRONG)
def test_hybrid_cell_rejects_each_wrong_variant(own_reference, variant):
    _, line, out = own_reference
    verdict = line["check"]["wrong_variants"][variant]
    assert verdict["ok"] is False, out
    assert verdict["err_of_max"] > 10 * line["check"]["rtol_of_max"]


def test_hybrid_cell_reports_the_state_counter(own_reference):
    """``engine.recurrent_state_*`` reach a ``counter`` reader with no
    edit to the harness; no slot-step ran on another request's state."""
    _, line, out = own_reference
    metrics = line["metrics"]
    assert metrics["serve_recurrent_state_owner_mismatches"]["value"] == 0, \
        out
    assert metrics["toy_engine_decode_steps"]["value"] > 0


def test_hybrid_cell_under_the_qwen3_reference_is_not_correct(
        qwen3_reference):
    rc, line, out = qwen3_reference
    assert line.get("correct") is not True, out
    if not line:
        assert rc not in (0, 3), out
