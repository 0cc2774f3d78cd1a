"""The seams through which a configuration, a cell's check sizes, a
metric's cost module and sharded parameters reach what they are for,
at the level of arguments: no reference run, no subprocess."""

import dataclasses
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import (costs, modules, program, reducers, serve_cell,
                            trace, train_cell)
from benchmarks.lib.spec import Refused, Spec
from tests.benchmarks.toy import REPO, TOY_MODEL

SPEC = Spec()
CONFIGS = ("qwen3-0.6b-train", "qwen3-1.7b-serve")
# what reached the trainer's arguments at the parent of PR 26
# (train_cell.MODEL_KEYS): model_type and eleven dense shape keys
THE_TWELVE = {
    "model_type", "vocab_size", "hidden_size", "intermediate_size",
    "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
    "head_dim", "rope_theta", "rms_norm_eps", "max_position_embeddings",
    "tie_word_embeddings"}
TOY_MOE = dict(TOY_MODEL, model_type="qwen3_moe", num_experts=8,
               num_experts_per_tok=2, moe_intermediate_size=32)


@pytest.mark.parametrize("name", CONFIGS)
def test_the_same_twelve_keys_reach_the_programs_arguments(name):
    """Membership, not equality: a key that a later configuration
    brings and the program declares goes through beside the twelve."""
    config = SPEC.config(name)
    passed = program.model_arguments(config)
    assert THE_TWELVE <= set(passed)
    assert all(passed[k] == config[k] for k in passed)


@pytest.mark.parametrize("name", CONFIGS)
def test_serving_model_config_is_the_parents_field_by_field(name):
    """The parent's harness built ``LlamaConfig(qk_norm=..., dtype=,
    param_dtype=, **shape keys)`` itself; the program's own dispatch
    must give every field the same value (the class is the program's
    business)."""
    from scaletorch_tpu.models import llama

    config = SPEC.config(name)
    shape_keys = THE_TWELVE - {"model_type"}
    parents = llama.LlamaConfig(
        qk_norm=config["model_type"] == "qwen3", dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16, **{k: config[k] for k in shape_keys})
    cfg, init = program.serving_model(config, "bfloat16")
    assert dataclasses.asdict(cfg) == dataclasses.asdict(parents)
    assert isinstance(cfg, llama.LlamaConfig)
    assert init.__module__ == type(cfg).__module__


def test_moe_keys_pass_through_to_the_arguments():
    args = program.launch_arguments(TOY_MOE)
    assert (args.model_type, args.num_experts, args.num_experts_per_tok,
            args.moe_intermediate_size) == ("qwen3_moe", 8, 2, 32)
    trainer_args = train_cell.trainer_arguments(
        dict(TOY_MOE, train={"dtype": "bfloat16"}),
        {"launch": {"expert_parallel_size": 1}},
        {"sequence_length": 64, "sequences_per_step": 1}, seed=7)
    assert (trainer_args.num_experts, trainer_args.sequence_length,
            trainer_args.dtype) == (8, 64, "bfloat16")


def test_moe_serving_model_comes_from_the_programs_dispatch():
    from scaletorch_tpu.models import qwen3_moe

    cfg, init = program.serving_model(TOY_MOE, "float32")
    assert type(cfg) is qwen3_moe.Qwen3MoEConfig
    assert (cfg.num_experts, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size) == (8, 2, 32)
    assert cfg.dtype == cfg.param_dtype == jnp.float32
    assert init is qwen3_moe.init_params


def test_keys_the_arguments_do_not_declare_stay_behind():
    passed = program.model_arguments(dict(
        TOY_MODEL, hidden_act="silu", serve={}, train={}, cost_inputs={}))
    assert "hidden_act" not in passed and "reference" not in passed
    assert not {"serve", "train", "cost_inputs", "reduced"} & set(passed)


# -- sharded parameters reach the reference ----------------------------------

def test_single_device_assembles_a_shard_and_leaves_a_replica_alone():
    devices = jax.devices()[:2]
    mesh = jax.sharding.Mesh(np.array(devices), ("x",))
    spec = jax.sharding.PartitionSpec
    whole = np.arange(8 * 6, dtype=np.float32).reshape(8, 6)
    tree = {
        "sharded": jax.device_put(whole, jax.sharding.NamedSharding(
            mesh, spec("x", None))),
        "replicated": jax.device_put(whole + 1, jax.sharding.NamedSharding(
            mesh, spec())),
    }
    assert tree["sharded"].addressable_shards[0].data.shape == (4, 6)
    got = train_cell._single_device(tree, devices[0])
    for leaf in got.values():
        assert leaf.shape == (8, 6) and leaf.devices() == {devices[0]}
    np.testing.assert_array_equal(np.asarray(got["sharded"]), whole)
    np.testing.assert_array_equal(np.asarray(got["replicated"]), whole + 1)
    own = next(s.data for s in tree["replicated"].addressable_shards
               if s.device == devices[0])
    assert got["replicated"].unsafe_buffer_pointer() == \
        own.unsafe_buffer_pointer()


# -- a cell's check sizes go to its reference as they are --------------------

class RecordingReference:
    GAIN_KEYS = ()

    def __init__(self):
        self.calls = []

    def make_loss_fn(self, config, **kwargs):
        self.calls.append(("loss", kwargs))
        return lambda params, tokens, targets, positions: jnp.float32(1.5)

    def make_logits_fn(self, config, **kwargs):
        self.calls.append(("logits", kwargs))
        return lambda params, tokens, rows: (tokens.shape, rows)


def test_train_check_sizes_reach_the_loss_factory():
    check = {"gradients": False, "loss_rtol": 1e-3, "grad_norm_rtol": 1e-2,
             "gain_grad_rtol": 1e-1, "q_block": 32, "loss_chunk": 16,
             "expert_chunk": 4}
    assert modules.check_sizes(check, train_cell.RUNNER_CHECK_KEYS) == {
        "q_block": 32, "loss_chunk": 16, "expert_chunk": 4}
    reference = RecordingReference()
    trainer = types.SimpleNamespace(params={"w": jnp.ones((2,))})
    batch = {"input_ids": np.zeros((1, 2, 8), np.int32),
             "target_ids": np.zeros((1, 2, 8), np.int32),
             "position_ids": np.arange(8, dtype=np.int32)[None]}
    got = train_cell.reference_first_step(trainer, reference, {}, check,
                                          batch, wrong="drop_block")
    assert got["loss"] == 1.5 and got["grad_norm"] is None
    assert reference.calls == [("loss", {
        "wrong": "drop_block", "with_gradients": False, "q_block": 32,
        "loss_chunk": 16, "expert_chunk": 4})]


def test_a_check_without_sizes_hands_the_reference_none():
    reference = RecordingReference()
    trainer = types.SimpleNamespace(params={})
    batch = {"input_ids": np.zeros((1, 1, 8), np.int32),
             "target_ids": np.zeros((1, 1, 8), np.int32),
             "position_ids": np.arange(8, dtype=np.int32)[None]}
    train_cell.reference_first_step(trainer, reference, {}, {}, batch)
    assert reference.calls == [("loss", {"wrong": None,
                                         "with_gradients": False})]


def test_serve_check_sizes_reach_the_logits_factory():
    """The runner keeps ``prompts``, ``decode_positions`` and
    ``rtol_of_max``; it reads ``q_block`` for the padding of its prompt
    buffer and hands it on with every other size."""
    reference = RecordingReference()
    check = {"prompts": 2, "decode_positions": 3, "rtol_of_max": 1e-3,
             "q_block": 8, "sample_rows": 5}
    tokens = np.ones((2, 13), np.int32)
    lens = np.array([4, 9])
    shape, rows = serve_cell.reference_logits(
        reference, {}, check, None, tokens, lens, 3, wrong="no_qk_norm")
    assert shape == (2, 16)                      # 13 padded up to 2 x 8
    np.testing.assert_array_equal(rows, [[3, 4, 5, 6], [8, 9, 10, 11]])
    assert reference.calls == [("logits", {
        "wrong": "no_qk_norm", "q_block": 8, "sample_rows": 5})]


@pytest.mark.parametrize("cell", ["train-0.6b-seq8k", "serve-1.7b-longgen",
                                  "serve-1.7b-chat"])
def test_every_cells_file_states_the_sizes_its_reference_gets(cell):
    """The runners' own defaults for ``q_block`` / ``loss_chunk`` went
    with PR 26: the three cells hand their reference what they state,
    which is what they were handed before."""
    check = SPEC.workload(cell)["check"]
    stated = {"train-0.6b-seq8k": {"q_block": 512, "loss_chunk": 1024},
              "serve-1.7b-longgen": {"q_block": 64},
              "serve-1.7b-chat": {"q_block": 64}}[cell]
    runner = train_cell if cell.startswith("train") else serve_cell
    assert modules.check_sizes(check, runner.RUNNER_CHECK_KEYS) == stated


# -- the reference is the file the configuration names -----------------------

@pytest.mark.parametrize("name", CONFIGS)
def test_the_configurations_reference_is_loaded_by_its_path(name):
    config = SPEC.config(name)
    module = modules.reference_of(SPEC, config)
    assert module.__file__ == os.path.join(REPO, config["reference"])
    assert all(hasattr(module, n) for n in modules.REFERENCE_CONTRACT)
    assert modules.reference_of(SPEC, config) is module      # once


def _tree_with_reference(tmp_path, source):
    root = tmp_path / "tree"
    (root / "benchmarks" / "reference").mkdir(parents=True)
    (root / "BENCHMARK.json").write_text(json.dumps({}))
    (root / "benchmarks" / "reference" / "mine.py").write_text(source)
    return Spec(str(root))


FULL = "GAIN_KEYS = ()\ndef make_loss_fn(c, **k): pass\n" \
       "def make_logits_fn(c, **k): pass\n"


@pytest.mark.parametrize("missing", modules.REFERENCE_CONTRACT)
def test_a_reference_without_a_contract_name_is_refused(tmp_path, missing):
    source = FULL.replace(missing, "other_" + missing)
    spec = _tree_with_reference(tmp_path, source)
    with pytest.raises(Refused, match=f"lacks {missing}"):
        modules.reference_of(spec, {
            "name": "mine", "reference": "benchmarks/reference/mine.py"})


def test_a_reference_in_the_root_tree_is_taken_before_the_checkouts(
        tmp_path):
    spec = _tree_with_reference(tmp_path, FULL + "MARK = 26\n")
    module = modules.reference_of(spec, {
        "name": "mine", "reference": "benchmarks/reference/mine.py"})
    assert module.MARK == 26


@pytest.mark.parametrize("config,message", [
    ({"name": "c"}, "names no 'reference'"),
    ({"name": "c", "reference": ""}, "names no 'reference'"),
    ({"name": "c", "reference": "benchmarks/reference/gone.py"},
     "gone.py"),
])
def test_no_reference_or_no_file_is_refused_without_a_fallback(config,
                                                               message):
    with pytest.raises(Refused, match=message):
        modules.reference_of(SPEC, config)


# -- a metric's reader names where its cost function lives -------------------

DEV = "/device:TPU:0"
KERNEL = [{"plane": DEV, "line": trace.OPS_LINE, "start_ns": 100,
           "dur_ns": 200,
           "name": "closed_call.2 | custom-call | tpu_custom_call | bf16[8]"}]


def _reduce_ctx(spec, **more):
    return dict({"events": KERNEL, "window": (0, 1000), "records": {},
                 "counters": {}, "config": {"width": 3}, "traffic": {},
                 "workload": {"chips": 1}, "peaks": {"p": 1e12},
                 "spec": spec}, **more)


def test_roofline_share_reads_a_cost_module_named_by_path(tmp_path):
    root = tmp_path / "tree"
    (root / "benchmarks" / "costs").mkdir(parents=True)
    (root / "BENCHMARK.json").write_text("{}")
    (root / "benchmarks" / "costs" / "expert.py").write_text(
        "def grouped_call_flops(config):\n"
        "    return {'forward': 1000.0 * config['width']}\n")
    params = {"cost_function": "grouped_call_flops",
              "cost_module": "benchmarks/costs/expert.py",
              "cost_args": ["config"], "peak": "p",
              "terms": [{"patterns": ["tpu_custom_call"],
                         "charge": "forward"}]}
    got = reducers.roofline_share(_reduce_ctx(Spec(str(root))), params)
    assert got == pytest.approx(100 * 3000.0 / 200e-9 / 1e12)
    # the same reader through its kind's name, as a metric file gives it
    assert reducers.read_metric(
        _reduce_ctx(Spec(str(root))),
        {"reducer": dict(params, kind="roofline_share")}) == got


def test_roofline_share_defaults_to_the_benchmarks_own_costs():
    assert modules.cost_function(None, "paged_decode_kv_bytes") is \
        costs.paged_decode_kv_bytes
    assert modules.cost_function(
        SPEC, "weight_bytes", modules.DEFAULT_COST_MODULE) is \
        costs.weight_bytes


@pytest.mark.parametrize("module,function,message", [
    (None, "no_such_cost", "no function 'no_such_cost'"),
    ("benchmarks/costs/nowhere.py", "f", "nowhere.py"),
])
def test_a_cost_that_is_not_there_is_refused(module, function, message):
    with pytest.raises(Refused, match=message):
        modules.cost_function(SPEC, function, module)


# -- the MFU line calls what the configuration names -------------------------

def _notes(config, rate=10_000.0):
    ctx = {"spec": SPEC, "config": config,
           "traffic": {"sequence_length": 8192}}
    result = {"values": {"train_tokens_per_s_per_chip": rate}}
    return train_cell.notes(ctx, result, SPEC.peaks("TPU v5 lite"))


def test_the_train_configurations_mfu_lines_are_the_parents_numbers():
    config = SPEC.config("qwen3-0.6b-train")
    lines = _notes(config)
    peak = SPEC.peaks("TPU v5 lite")["bf16_flops_per_s"]
    causal = 100 * 10_000.0 * costs.train_flops_per_token(config, 8192) / peak
    square = 100 * 10_000.0 * costs.train_flops_per_token_full_square(
        config, 8192) / peak
    assert len(lines) == 2
    assert lines[0].startswith(f"mfu.flops_per_token={causal:.2f}% ")
    assert lines[1].startswith(
        f"mfu.flops_per_token_repo_convention={square:.2f}% ")


@pytest.mark.parametrize("cost_inputs", [
    None, {}, {"peak": "bf16_flops_per_s"},
    {"flops_per_token": "prose only", "peak": "bf16_flops_per_s"},
    {"flops_per_token": {"function": "train_flops_per_token"}},
])
def test_a_configuration_that_names_no_function_prints_no_mfu_line(
        cost_inputs):
    config = dict(SPEC.config("qwen3-0.6b-train"))
    config.pop("cost_inputs")
    if cost_inputs is not None:
        config["cost_inputs"] = cost_inputs
    assert _notes(config) == []
