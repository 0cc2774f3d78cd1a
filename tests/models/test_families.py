"""The table of families (``models/families.py``): every ``model_type``
the presets name has a row, every row's module keeps the convention, a
config object finds its row by its exact class, and the step programs
get the cached forward they got from the ``isinstance`` chain the table
replaced."""

import dataclasses

import pytest

from scaletorch_tpu.config import ScaleTorchTPUArguments
from scaletorch_tpu.inference.decode import (
    counts_routing,
    resolve_forward_cached,
)
from scaletorch_tpu.models import (
    afmoe,
    gpt_moe,
    granite_moe_hybrid,
    jamba,
    kimi_linear,
    llama,
    mimo_v2_flash,
    olmo_hybrid,
    olmoe,
    pangu_ultra_moe,
    qwen3,
    qwen3_moe,
    qwen3_next,
)
from scaletorch_tpu.models.families import (
    FAMILIES,
    build_model_config,
    family_of,
)
from scaletorch_tpu.models.presets import MODEL_PRESETS, preset

# model_type: the module of its row, the cached forward the chain of
# PR 49 handed the step programs, and whether that forward counts what
# it routes
EXPECTED = {
    "llama": (llama, llama.forward_cached, False),
    "qwen3": (qwen3, llama.forward_cached, False),
    "qwen3_moe": (qwen3_moe, qwen3_moe.forward_cached, True),
    "olmoe": (olmoe, qwen3_moe.forward_cached, True),
    "olmo_hybrid": (olmo_hybrid, olmo_hybrid.forward_cached, False),
    "qwen3_next": (qwen3_next, qwen3_next.forward_cached, True),
    "afmoe": (afmoe, afmoe.forward_cached, True),
    "jamba": (jamba, jamba.forward_cached, False),
    "pangu_ultra_moe": (pangu_ultra_moe, pangu_ultra_moe.forward_cached,
                        True),
    "kimi_linear": (kimi_linear, kimi_linear.forward_cached, True),
    "mimo_v2_flash": (mimo_v2_flash, mimo_v2_flash.forward_cached, True),
    "granitemoehybrid": (granite_moe_hybrid,
                         granite_moe_hybrid.forward_cached, True),
    "gpt_moe": (gpt_moe, gpt_moe.forward_cached, False),
}
TRAINS = {"llama", "qwen3", "qwen3_moe", "olmoe", "gpt_moe"}
LOADS_HF = {"llama", "qwen3", "qwen3_moe", "olmoe"}


def built(name):
    return build_model_config(ScaleTorchTPUArguments(**preset(name)))


def test_the_rows_are_the_thirteen_families():
    assert set(FAMILIES) == set(EXPECTED)
    classes = [row.config_cls for row in FAMILIES.values()]
    assert len(set(classes)) == len(classes)


@pytest.mark.parametrize("name", sorted(MODEL_PRESETS))
def test_every_preset_s_model_type_has_a_row(name):
    row = FAMILIES[preset(name)["model_type"]]
    cfg = built(name)
    assert type(cfg) is row.config_cls
    assert family_of(cfg) is row


@pytest.mark.parametrize("model_type", sorted(EXPECTED))
def test_a_row_s_module_keeps_the_convention(model_type):
    row = FAMILIES[model_type]
    module, _, _ = EXPECTED[model_type]
    assert row.module is module
    for name in ("config_from_args", "init_params", "forward",
                 "forward_cached"):
        assert callable(getattr(module, name)), name
    assert getattr(module, row.config_cls.__name__) is row.config_cls
    assert dataclasses.is_dataclass(row.config_cls)
    assert (row.untrained is None) == (model_type in TRAINS)
    assert row.loads_hf == (model_type in LOADS_HF)
    assert hasattr(module, "config_from_hf") == row.loads_hf


@pytest.mark.parametrize("name", sorted(MODEL_PRESETS))
def test_a_preset_s_steps_trace_the_forward_they_traced(name):
    _, forward_cached, routing = EXPECTED[preset(name)["model_type"]]
    cfg = built(name)
    assert resolve_forward_cached(cfg) is forward_cached
    assert counts_routing(cfg) is routing


@pytest.mark.parametrize("model_type", ["llama", "gpt_moe"])
def test_a_family_without_a_preset_resolves_by_its_class(model_type):
    """No preset names ``llama`` or ``gpt_moe``: their config classes
    reach the step programs through the tests and the examples."""
    module, forward_cached, routing = EXPECTED[model_type]
    cfg = FAMILIES[model_type].config_cls()
    assert resolve_forward_cached(cfg) is forward_cached
    assert counts_routing(cfg) is routing
    assert family_of(cfg).module is module


def test_a_subclass_is_not_taken_for_its_base():
    """The lookup is by exact class: what the order of an ``isinstance``
    chain used to decide."""
    assert issubclass(qwen3_next.Qwen3NextConfig,
                      olmo_hybrid.OlmoHybridConfig)
    assert issubclass(olmoe.OlmoeConfig, qwen3_moe.Qwen3MoEConfig)
    assert family_of(built("qwen3-next-tiny")).module is qwen3_next
    assert family_of(built("olmo-hybrid-tiny")).module is olmo_hybrid
    assert family_of(built("olmoe-tiny")).module is olmoe


def test_a_config_class_with_no_row_is_refused():
    @dataclasses.dataclass(frozen=True)
    class Unlisted(llama.LlamaConfig):
        pass

    for ask in (family_of, resolve_forward_cached, counts_routing):
        with pytest.raises(TypeError, match="Unlisted"):
            ask(Unlisted())


@pytest.mark.parametrize("model_type, error, match", [
    ("gpt_moe", ValueError, "trains via its example"),
    ("lenet", ValueError, "trains via its example"),
    ("mingpt", ValueError, "trains via its example"),
    ("resnet", ValueError, "unknown model_type"),
])
def test_what_the_launch_arguments_cannot_build(model_type, error, match):
    with pytest.raises(error, match=match):
        build_model_config(ScaleTorchTPUArguments(model_type=model_type))


@pytest.mark.parametrize("model_type", sorted(set(EXPECTED) - LOADS_HF))
def test_one_refusal_of_hf_auto_fill_worded_from_the_row(model_type):
    with pytest.raises(NotImplementedError, match=f"{model_type} from "
                       "--model_name_or_path.*not written for this family"):
        build_model_config(ScaleTorchTPUArguments(
            model_type=model_type, model_name_or_path="/nowhere"))


@pytest.mark.parametrize("model_type", sorted(EXPECTED))
def test_embed_init_std_is_read_where_the_class_has_the_field(model_type):
    has = "embed_init_std" in FAMILIES[
        model_type].config_cls.__dataclass_fields__
    assert has == (model_type in ("qwen3_next", "afmoe", "jamba",
                                  "pangu_ultra_moe", "kimi_linear",
                                  "mimo_v2_flash"))
    if not has:
        with pytest.raises(NotImplementedError, match="embed_init_std"):
            build_model_config(ScaleTorchTPUArguments(
                model_type=model_type, embed_init_std=1.0))


@pytest.mark.parametrize("name", ["routed_expert_init_scale",
                                  "query_init_scale", "sink_init_mean",
                                  "ssm_decay_init_scale"])
@pytest.mark.parametrize("model_type", sorted(EXPECTED))
def test_the_draw_s_scales_are_read_where_the_class_has_the_field(
        model_type, name):
    """Four more properties of random weights a launch may set: the
    families whose initialisers read one have its field (the sinks'
    draw: the one family with sinks; the decay's: the one with
    Mamba-2's), every other refuses each by name."""
    has = name in FAMILIES[model_type].config_cls.__dataclass_fields__
    share = ("pangu_ultra_moe", "kimi_linear", "mimo_v2_flash")
    reads = {"sink_init_mean": ("mimo_v2_flash",),
             "ssm_decay_init_scale": ("granitemoehybrid",),
             "routed_expert_init_scale": share,
             "query_init_scale": share + ("granitemoehybrid",)}[name]
    assert has == (model_type in reads)
    if not has:
        with pytest.raises(NotImplementedError, match=name):
            build_model_config(ScaleTorchTPUArguments(
                model_type=model_type, **{name: 0.5}))


@pytest.mark.parametrize("model_type", sorted(set(EXPECTED) - TRAINS))
def test_the_trainer_raises_the_row_s_reason(model_type):
    from scaletorch_tpu.trainer.trainer import Trainer

    with pytest.raises(NotImplementedError) as refusal:
        Trainer(ScaleTorchTPUArguments(model_type=model_type))
    assert str(refusal.value) == (
        f"the trainer has no step for model_type {model_type!r}: "
        f"{FAMILIES[model_type].untrained}")
