"""Benchmark library + presets (scaletorch_tpu/benchmark.py).

The presets and the config builder of the in-process runner used by
bench.py and the tools. The runner itself (``benchmark_config``) is a
measurement path: it refuses to run off a TPU (tests/test_chip_path.py),
and chip_smoke.py is what drives the same Trainer on the chip.
"""

from __future__ import annotations

import pytest

from scaletorch_tpu.benchmark import make_bench_args
from scaletorch_tpu.models.presets import MODEL_PRESETS, preset


def test_presets_known_architectures():
    p = preset("qwen3-0.6b")
    assert p["hidden_size"] == 1024 and p["num_hidden_layers"] == 28
    moe = preset("qwen3-30b-a3b")
    assert moe["num_experts"] == 128 and moe["num_experts_per_tok"] == 8
    with pytest.raises(KeyError, match="unknown model preset"):
        preset("nope")
    # preset() hands out copies — mutating one must not poison the table
    p["hidden_size"] = 1
    assert preset("qwen3-0.6b")["hidden_size"] == 1024


def test_make_bench_args_shapes():
    cfg = make_bench_args("qwen3-0.6b", seq=4096, micro_bs=2, gc=True, tp=1)
    assert cfg.sequence_length == 4096
    assert cfg.micro_batch_size == 2
    assert cfg.gradient_checkpointing is True
    assert cfg.synthetic_data is True


@pytest.mark.parametrize("name", sorted(MODEL_PRESETS))
def test_all_presets_build_valid_configs(name):
    make_bench_args(name, seq=256)
