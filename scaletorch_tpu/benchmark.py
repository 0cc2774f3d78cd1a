"""In-process benchmark runner shared by bench.py, tools/, and the sweep.

Role parity with the measurement core of reference
``scripts/benchmark_comprehensive.py:337-470`` (run_config + metric
parsing) and ``tools/bench_single.py``: build a Trainer from a config,
run warmup (compile) steps, time the steady window, report
tokens/s / tokens/s/chip / MFU / final loss / device memory.

Hermetic: synthetic data, random init — identical math/comms to real
training (the reference benchmarks with a real dataset but the step work
is the same; synthetic keeps the harness self-contained on any chip).

A measurement path: ``benchmark_config`` refuses to run without a TPU
(``utils.device.require_tpu``) — a rate or an MFU from a CPU run is the
speed of XLA's CPU backend, which nobody deploys — and every result
names the device it came from.
"""

from __future__ import annotations

import math
import time
from typing import Any, Dict, Optional


def benchmark_config(
    cfg, warmup: int = 3, steps: int = 10, progress=None
) -> Dict[str, Any]:
    """Run one timed benchmark for a ScaleTorchTPUArguments config.

    Returns {tokens_per_second, tokens_per_second_per_chip, mfu, loss,
    step_time_s, memory_gb, num_params, num_chips, platform, device_kind,
    attention_backend}. Raises ``NoTpuError`` off a TPU. ``progress`` is
    an optional callback taking a stage name ("trainer_built",
    "compiled", "timed").
    """
    import jax

    from scaletorch_tpu.trainer.trainer import Trainer
    from scaletorch_tpu.utils.device import device_memory_stats, require_tpu
    from scaletorch_tpu.utils.misc import get_mfu, get_num_params

    require_tpu("benchmark_config")
    progress = progress or (lambda stage: None)
    trainer = Trainer(cfg)
    progress("trainer_built")
    try:
        # Drive trainer.step (the public per-step API, not trainer.train)
        # so timing excludes the metrics/logging machinery and the final
        # loss is always captured.
        m = {}
        for _ in range(warmup):  # compile + stabilise
            m = trainer.step()
        jax.block_until_ready(trainer.params)
        progress("compiled")

        t0 = time.perf_counter()
        for _ in range(steps):
            m = trainer.step()
        # Completion barrier: the final loss data-depends on every
        # step's param update, so its host readback ends the window.
        final_loss = float(m["loss"])
        jax.block_until_ready(trainer.params)
        elapsed = time.perf_counter() - t0
        progress("timed")
        # The in-step non-finite guard freezes the params and carries on
        # (divergence_policy="skip"): a window whose last update was
        # skipped timed something, but not training.
        skipped = float(m.get("update_skipped", 0))
        if not math.isfinite(final_loss) or skipped:
            raise FloatingPointError(
                f"timed window ended on loss {final_loss} with "
                f"update_skipped={skipped}: the step produced non-finite "
                "values on this device"
            )

        tok_s = trainer.loader.tokens_per_step * steps / elapsed
        num_chips = len(jax.devices())
        n_params = get_num_params(trainer.params)
        is_moe = cfg.model_type == "qwen3_moe"
        # MoE MFU counts active params per token (reference README.md:123-128).
        mfu_params = trainer.model_cfg.num_active_params() if is_moe else n_params
        mfu = get_mfu(
            tok_s,
            mfu_params,
            trainer.model_cfg.num_hidden_layers,
            trainer.model_cfg.num_attention_heads,
            trainer.model_cfg.actual_head_dim,
            cfg.sequence_length,
            num_chips=num_chips,
        )
        mem = device_memory_stats()
        return {
            "tokens_per_second": round(tok_s, 1),
            "tokens_per_second_per_chip": round(tok_s / num_chips, 1),
            "mfu": round(mfu, 2),
            "loss": round(final_loss, 4),
            "step_time_s": round(elapsed / steps, 4),
            "memory_gb": round(mem["peak_bytes_in_use"] / 1e9, 2)
            if mem.get("peak_bytes_in_use")
            else None,
            "num_params": n_params,
            "num_chips": num_chips,
            "platform": jax.devices()[0].platform,
            "device_kind": jax.devices()[0].device_kind,
            "attention_backend": trainer.attention_backend,
        }
    finally:
        trainer.close()


def make_bench_args(
    model: str,
    *,
    seq: int,
    micro_bs: int = 1,
    grad_accum: int = 1,
    gc: bool = False,
    tp: int = 1,
    pp: int = 1,
    dp: int = 1,
    cp: int = 1,
    ep: int = 1,
    sp: bool = False,
    pp_engine: str = "afab",
    dtype: str = "bfloat16",
    remat_policy: str = "nothing_saveable",
    extra: Optional[Dict[str, Any]] = None,
):
    """Build ScaleTorchTPUArguments for a named preset + run shape
    (the kwargs mirror one row of the reference CONFIGS table,
    benchmark_comprehensive.py:55-174)."""
    from scaletorch_tpu.config import ScaleTorchTPUArguments
    from scaletorch_tpu.models.presets import preset

    kwargs = dict(
        preset(model),
        sequence_length=seq,
        micro_batch_size=micro_bs,
        gradient_accumulation_steps=grad_accum,
        gradient_checkpointing=gc,
        remat_policy=remat_policy,
        tensor_parallel_size=tp,
        pipeline_parallel_size=pp,
        data_parallel_size=dp,
        context_parallel_size=cp,
        expert_parallel_size=ep,
        sequence_parallel=sp,
        pp_engine=pp_engine,
        synthetic_data=True,
        dtype=dtype,
        max_grad_norm=1.0,
        log_frequency=10_000,  # silence per-step logging during timing
        total_train_steps=1_000_000,  # trainer.train(num_steps=...) drives
    )
    kwargs.update(extra or {})
    return ScaleTorchTPUArguments(**kwargs)
