"""The training process runs none of the serving engine's code: building
the tiny ``Trainer`` and taking one step, then importing the benchmark's
training runner, leaves no ``scaletorch_tpu.inference`` module loaded.
So a change under ``scaletorch_tpu/inference/`` cannot move a number of
``train-0.6b-seq8k`` (ISSUE 44: PR 43 was refused on that cell's
``setup_s``, which moves 9-11 % on an unchanged tree). Its own process:
this suite's has imported the engine long before."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

SCRIPT = """
import json, sys
import numpy as np
from scaletorch_tpu.config import ScaleTorchTPUArguments
from scaletorch_tpu.trainer.trainer import Trainer

t = Trainer(ScaleTorchTPUArguments(
    model_type="llama", hidden_size=32, intermediate_size=64,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    vocab_size=64, sequence_length=16, max_position_embeddings=32,
    data_parallel_size=1, micro_batch_size=1,
    gradient_accumulation_steps=1, synthetic_data=True,
    total_train_steps=2, dtype="float32", donate_params=False,
    log_frequency=100))
try:
    loss = float(t.step()["loss"])
finally:
    t.close()
import benchmarks.lib.train_cell  # the benchmark's training runner
print(json.dumps({
    "loss_is_finite": bool(np.isfinite(loss)), "step": t.global_step,
    "inference": sorted(m for m in sys.modules
                        if m.startswith("scaletorch_tpu.inference")),
    "scaletorch": sum(m.startswith("scaletorch_tpu") for m in sys.modules),
}))
"""


def test_a_training_step_loads_no_module_of_the_serving_engine():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("XLA_FLAGS", None)          # one CPU device is enough
    out = subprocess.run([sys.executable, "-c", SCRIPT], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    seen = json.loads(out.stdout.strip().splitlines()[-1])
    assert seen["loss_is_finite"] and seen["step"] == 1
    assert seen["scaletorch"] > 10      # the trainer's own modules did load
    assert seen["inference"] == []
