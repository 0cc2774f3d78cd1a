"""Runner of the training cells: ``Trainer.step`` back to back.

The system under test is the program's own ``Trainer`` (the object
``train.py`` builds), driven through ``Trainer.step(batch)``, its public
per-step entry point. The benchmark owns the batches (from ``--seed``),
the clock, the reference check and the trace.
"""

from __future__ import annotations

import math
import time
from typing import Any, Dict, List

import numpy as np

from benchmarks.lib import modules, program
from benchmarks.lib import traffic as traffic_lib

# keys of a cell's ``check`` that are the runner's own; every other key
# is a size of the reference and goes to its factory as it is
RUNNER_CHECK_KEYS = ("gradients", "loss_rtol", "grad_norm_rtol",
                     "gain_grad_rtol")


def trainer_arguments(config: Dict[str, Any], workload: Dict[str, Any],
                      traffic: Dict[str, Any], seed: int):
    kwargs = dict(config.get("train", {}))
    kwargs.update(workload.get("launch", {}))
    dp = int(kwargs.get("data_parallel_size", 1))
    rows = int(traffic["sequences_per_step"])
    if rows % dp:
        raise ValueError(f"{rows} sequences per step over dp={dp}")
    kwargs.update(
        sequence_length=int(traffic["sequence_length"]),
        micro_batch_size=rows // dp,
        gradient_accumulation_steps=1,
        synthetic_data=True,
        seed=traffic_lib.fold_seed(seed),
        log_frequency=10_000_000,
        total_train_steps=10_000_000,
    )
    return program.launch_arguments(config, **kwargs)


def _single_device(tree, device):
    """Each leaf whole on ``device``. Where the state is replicated
    (over cp/dp) the device's own shard is the whole array and nothing
    moves; a leaf sharded over the mesh (experts over ep, anything over
    tp) is assembled from its shards and put there."""
    import jax

    def pick(leaf):
        for shard in leaf.addressable_shards:
            if shard.device == device and shard.data.shape == leaf.shape:
                return shard.data
        return jax.device_put(jax.device_get(leaf), device)

    return jax.tree.map(pick, tree)


def reference_first_step(trainer, reference, config, check: Dict[str, Any],
                         batch: Dict[str, np.ndarray],
                         wrong=None) -> Dict[str, Any]:
    """Loss (and, where the cell's check asks for gradients, the global
    gradient norm and the gradient of every norm gain) of the plain
    reference on the trainer's current parameters and the batch's rows,
    the loss averaged over rows as the system averages."""
    import jax
    import jax.numpy as jnp

    device = jax.local_devices()[0]
    params = _single_device(trainer.params, device)
    gradients = bool(check.get("gradients", False))
    fn = reference.make_loss_fn(config, wrong=wrong,
                                with_gradients=gradients,
                                **modules.check_sizes(
                                    check, RUNNER_CHECK_KEYS))
    positions = jnp.asarray(batch["position_ids"][0])
    rows = batch["input_ids"][0]
    if gradients and len(rows) != 1:
        raise ValueError("the gradient check takes one row per step")
    losses, out = [], {"grad_norm": None, "gain_grads": None}
    with jax.default_device(device):
        for r in range(len(rows)):
            got = fn(params, jnp.asarray(rows[r]),
                     jnp.asarray(batch["target_ids"][0][r]), positions)
            if gradients:
                losses.append(float(got[0]))
                out["grad_norm"] = float(got[1])
                out["gain_grads"] = dict(
                    jax.device_get(got[2]["layers"]),
                    norm=jax.device_get(got[2]["norm"]))
            else:
                losses.append(float(got))
    return dict(out, loss=float(np.mean(losses)))


def step_values(metrics: Dict[str, Any]) -> Dict[str, float]:
    """Host copies of one step's loss, gradient norm and skip flag (the
    readback waits for the step)."""
    return {"loss": float(metrics["loss"]),
            "grad_norm": float(metrics["grad_norm"]),
            "update_skipped": float(metrics.get("update_skipped", 0))}


def first_step_gain_gradients(trainer, args, grad_norm: float,
                              gain_keys) -> Dict[str, np.ndarray]:
    """The gradient the system's first step computed for every norm gain,
    read from where the step left it: after one update from zero
    moments, Adam's first moment is ``(1 - b1) x`` the gradient the
    optimizer was handed, and the step hands it the gradient scaled by
    ``min(1, max_grad_norm / grad_norm)`` (``parallel/spmd.py``
    ``clip_by_global_norm``). 0.07 M numbers instead of two."""
    import jax
    import optax

    mu = optax.tree_utils.tree_get(trainer.opt_state, "mu")
    clip = 1.0
    if args.max_grad_norm and args.max_grad_norm > 0:
        clip = min(1.0, args.max_grad_norm / max(grad_norm, 1e-12))
    unscale = 1.0 / ((1.0 - args.adam_beta1) * clip)
    picked = {k: mu["layers"][k] for k in gain_keys if k in mu["layers"]}
    picked["norm"] = mu["norm"]
    return {k: np.asarray(jax.device_get(v), np.float32) * unscale
            for k, v in picked.items()}


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    """One run of a train cell. ``ctx``: spec pieces, seed, seconds,
    trace flag, the stamp ``setup_s`` counts from, compile counter, log
    function."""
    import jax

    from benchmarks.reference import check as check_lib
    from scaletorch_tpu.trainer.trainer import Trainer

    config, workload, traffic = ctx["config"], ctx["workload"], ctx["traffic"]
    log = ctx["log"]
    check = workload.get("check", {})
    reference_module = modules.reference_of(ctx["spec"], config)
    batches = traffic_lib.train_batches(
        traffic, int(config["vocab_size"]), ctx["seed"])
    args = trainer_arguments(config, workload, traffic, ctx["seed"])
    trainer = Trainer(args)
    log(f"trainer built: mesh {dict(trainer.mm.mesh.shape)}, attention "
        f"{trainer.attention_backend}")
    try:
        problems: List[str] = []
        want = workload.get("expect", {}).get("attention_backend")
        if want and trainer.attention_backend != want:
            problems.append(f"attention backend is "
                            f"{trainer.attention_backend!r}, not {want!r}")

        t0 = time.monotonic()
        reference = reference_first_step(trainer, reference_module, config,
                                         check, batches[0])
        log(f"reference first step: loss {reference['loss']}, gradient "
            f"norm {reference['grad_norm']} ({time.monotonic() - t0:.1f}s)")
        # a cell measured at another size states its own tolerances in
        # its file; the defaults are the 8k cell's (reference/check.py)
        tolerances = {k: float(check[k]) for k in (
            "loss_rtol", "grad_norm_rtol", "gain_grad_rtol") if k in check}
        wrong = {}
        for variant in workload.get("wrong_variants", []):
            # how far a deliberately wrong computation lands from the
            # reference, and whether the tolerance rejects it
            off = reference_first_step(trainer, reference_module, config,
                                       check, batches[0], wrong=variant)
            wrong[variant] = check_lib.judge_train(off, reference,
                                                   **tolerances)
            log(f"wrong variant {variant}: {wrong[variant]}")
        first = step_values(trainer.step(batches[0]))
        if check.get("gradients"):
            first["gain_grads"] = first_step_gain_gradients(
                trainer, args, first["grad_norm"],
                reference_module.GAIN_KEYS)
        verdict = check_lib.judge_train(first, reference, **tolerances)
        if wrong:
            verdict["wrong_variants"] = wrong
        log(f"first step: {verdict}")
        if not verdict["ok"]:
            problems.append("first step disagrees with the reference")
        for i in range(int(workload.get("warmup_steps", 2))):
            step_values(trainer.step(batches[(i + 1) % len(batches)]))
        jax.block_until_ready(trainer.params)
        log("warm")

        tokens_per_step = (int(traffic["sequence_length"])
                           * int(traffic["sequences_per_step"]))
        chips = len(jax.devices())
        tracing = ctx["tracer"]
        seconds = (float(workload.get("trace_seconds", ctx["seconds"]))
                   if tracing.enabled else ctx["seconds"])
        compiles_before = ctx["compiles"].snapshot()["backend_compiles"]
        setup_s = time.monotonic() - ctx["setup_start"]
        tracing.start()
        window_start = time.monotonic()
        steps, seen, pending = 0, [], None
        while True:
            with tracing.step("train_step", steps):
                metrics = trainer.step(batches[steps % len(batches)])
            steps += 1
            if pending is not None:
                # the step before: its readback bounds the run-ahead to
                # one step and keeps the device fed
                with tracing.span("loss_readback"):
                    seen.append(step_values(pending))
            pending = metrics
            if time.monotonic() - window_start >= seconds:
                break
        with tracing.span("loss_readback"):
            seen.append(step_values(pending))
        jax.block_until_ready(trainer.params)
        window_s = time.monotonic() - window_start
        tracing.stop()
        # the program's own counters: every scalar of the last step's
        # metrics, one readback outside the timed window
        counters = {"steps": steps}
        for name, value in jax.device_get(pending).items():
            if np.ndim(value) == 0:
                counters[f"step.{name}"] = float(value)
        compiled = (ctx["compiles"].snapshot()["backend_compiles"]
                    - compiles_before)

        if compiled:
            problems.append(f"{compiled} programs compiled in the window")
        bad = [s for s in seen
               if not math.isfinite(s["loss"]) or s["update_skipped"]]
        if bad:
            problems.append(f"{len(bad)} steps non-finite or skipped")
        rate = steps * tokens_per_step / window_s / chips
        log(f"window: {steps} steps in {window_s:.3f}s, "
            f"{rate:.1f} tokens/s/chip, last loss {seen[-1]['loss']:.4f}")
        return {
            "problems": problems,
            "attempted": steps, "failed": len(bad),
            "setup_s": setup_s, "window_s": window_s,
            "values": {"train_tokens_per_s_per_chip": rate},
            "counters": counters,
            "records": {},
            "check": verdict,
        }
    finally:
        trainer.close()


def notes(ctx: Dict[str, Any], result: Dict[str, Any], peaks) -> List[str]:
    """Lines printed before the result: MFU by every count of operations
    per token that the configuration's ``cost_inputs`` names as
    ``{"function": ..., "module": ...}`` (called with the configuration
    and the sequence length), against the peak it names. A configuration
    that names none gets no line."""
    inputs = ctx["config"].get("cost_inputs", {})
    if not peaks or inputs.get("peak") not in peaks:
        return []
    seq = int(ctx["traffic"]["sequence_length"])
    rate = result["values"]["train_tokens_per_s_per_chip"]
    peak = float(peaks[inputs["peak"]])
    lines = []
    for name, named in inputs.items():
        if not (isinstance(named, dict) and "function" in named):
            continue
        per_token = modules.cost_function(
            ctx["spec"], named["function"], named.get("module"))(
                ctx["config"], seq)
        lines.append(
            f"mfu.{name}={100 * rate * per_token / peak:.2f}% "
            f"({per_token / 1e9:.3f} GFLOP/token: {named['function']}, "
            f"{named.get('what', '')})")
    return lines
