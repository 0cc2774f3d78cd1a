#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

Drives the two main paths once, through the entry points a user calls,
at the published widths of Qwen3-0.6B (models/presets.py) with seeded
random weights and synthetic data — no checkpoint, no network:

  kernels  first execution check of the two Mosaic kernels the main
           paths use, against the XLA references they replace: the
           Pallas flash kernel (forward + grads) vs
           ``models.layers.sdpa_attention``, and the paged-decode
           kernel vs ``paged_gather_kv`` + ``cached_sdpa_attention``,
           at the Qwen3 head geometry (16/8 heads x 128), bf16; the
           page write, the latent decode kernel and the expert kernel
           (megablox under both K / N tilings the served widths take)
           against theirs.
  train    ``train.main(argv)``: seq 8192, micro-batch 1, bf16,
           gradient checkpointing, flash attention, 5 optimizer steps.
           Every step must be finite and applied (``update_skipped ==
           0`` — the in-step guard would otherwise freeze the params
           and carry on), and the step jax lowered in that very run
           must contain the Mosaic custom call.
  serve    ``scripts/serve.py`` as a user starts it (a child process),
           paged cache, page 16; overlapping SSE requests of different
           prompt lengths from this process, which never imports jax.
           Every request must end ``ok`` with exactly its
           ``max_new_tokens`` (outcome counts are all that tells a
           served request from a Mosaic error the worker swallowed),
           ``decode_compile_count == 1``, the lowered decode step must
           contain the Mosaic custom call, SIGTERM must drain to exit 0.

On a host with four chips the same script then runs the train leg as
dp2 x tp2 and as cp2 x dp2 (ring attention, zigzag layout) — per-device
peaks reported, no device holding more than its shards after the last
step, first-step loss against the one-chip leg's — and a gateway with
four in-process replicas on four distinct devices. The one-chip legs
run there in a process that is shown one chip (``env.one_chip_env``).

One process per chip: every leg is a child process, one at a time, and
this parent never imports jax (a parent that touched jax would hold the
chip its child needs). Without a TPU the first leg fails and the script
exits non-zero with a one-line reason and no result line.

``--dry-run`` is for debugging the script itself on a CPU: tiny sizes,
virtual CPU devices, kernel checks in interpret mode, no Mosaic
assertions. It prints ``platform=cpu`` and can never pass: it exits
with ``DRY_RUN_EXIT`` and ``"ok": false``.

Last line of stdout on success:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import random
import shutil
import signal
import subprocess
import sys
import threading
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
WORKDIR = os.path.join(REPO, "chiprun_out", "chip_smoke")
DRY_RUN_EXIT = 3
MOSAIC_CALL = "tpu_custom_call"

ONE_CHIP_LEGS = ("kernels", "train", "serve")
FOUR_CHIP_LEGS = ("train-dp2tp2", "train-cp2dp2", "serve4")
LEG_TIMEOUT_S = {"kernels": 300, "train": 600, "serve": 600}

# bf16 tolerances, written before the first chip run. Inputs are N(0,1)
# in bf16; the truth is the same attention in fp32 on the upcast inputs.
# Both the kernel and its XLA reference round the probabilities to bf16
# before the PV matmul (rel 2^-9 per term) and the output to bf16
# (2^-9 of |out| <= ~4), so each sits within ~1e-2 of the truth; 2e-2
# is that with a factor two of room, and a wrong mask, page or block
# index moves outputs by O(1). Gradients are compared normalised by
# the truth's largest magnitude, same bound.
FWD_ATOL = 2e-2
GRAD_RTOL_OF_MAX = 2e-2
# First-step loss of a four-chip leg against the one-chip leg's. Same
# seeded init, but dp=2 draws two synthetic rows where the one-chip leg
# draws one, so the batches differ; at random init the loss is ln(V)
# plus row noise well under 1 %. A mis-reduced loss (summed instead of
# averaged over an axis, a rank's shard dropped) is off by >= 2x.
FIRST_LOSS_RTOL = 0.02
# After the last step a device may hold its shards of params + optimizer
# state plus the last batch, scalars and XLA's own program memory —
# not another device's state and not the unsharded init copy.
RESIDENT_SLACK_BYTES = 512 * 2**20


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class LegFailed(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise LegFailed(what)


# --------------------------------------------------------------------------
# Sizes
# --------------------------------------------------------------------------

def model_flags(dry_run: bool) -> list:
    """train.py flags of the model: Qwen3-0.6B's preset fields, or a
    tiny stand-in with the same block shape for the CPU dry run."""
    if dry_run:
        fields = dict(
            model_type="qwen3", vocab_size=512, hidden_size=64,
            intermediate_size=128, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            tie_word_embeddings=True)
    else:
        from scaletorch_tpu.models.presets import preset

        fields = preset("qwen3-0.6b")
    flags = []
    for key, value in fields.items():
        flags += [f"--{key}", str(value)]
    return flags


def train_argv(mesh: str, perf_dir: str, dry_run: bool) -> list:
    mesh_flags = {
        "": [],
        "dp2tp2": ["--data_parallel_size", "2",
                   "--tensor_parallel_size", "2"],
        "cp2dp2": ["--context_parallel_size", "2",
                   "--data_parallel_size", "2",
                   "--attention_backend", "ring", "--cp_layout", "zigzag"],
    }[mesh]
    return model_flags(dry_run) + mesh_flags + [
        "--sequence_length", "256" if dry_run else "8192",
        "--micro_batch_size", "1",
        "--gradient_accumulation_steps", "1",
        "--gradient_checkpointing", "true",
        "--dtype", "bfloat16",
        "--synthetic_data", "true",
        "--total_train_steps", "5",
        "--log_frequency", "1",
        "--seed", "0",
        "--performance_log_dir", perf_dir,
    ]


def serve_argv(replicas: int, dry_run: bool) -> list:
    # 8 slots x prefill 512 x max_seq 2048: prefill's fp32 score matrix
    # (B * Hq * P * S_max * 4 bytes) is 0.5 GB per layer — it fits; at
    # 2048 / 8192 it would be 8.6 GB (ROADMAP S3)
    shape = (["--preset", "tiny", "--max_slots", "4", "--max_seq", "128",
              "--prefill_len", "64"] if dry_run else
             ["--preset", "qwen3-0.6b", "--max_slots", "8",
              "--max_seq", "2048", "--prefill_len", "512"])
    return shape + ["--param_seed", "0",
                    "--page_size", "16", "--serve_port", "0",
                    "--serve_replicas", str(replicas)]


# --------------------------------------------------------------------------
# Child legs (these import jax)
# --------------------------------------------------------------------------

def describe_device(dry_run: bool) -> dict:
    """What jax runs on; refuses anything but a TPU outside a dry run."""
    import jax
    import jaxlib

    from scaletorch_tpu.env import configure_compile_cache
    from scaletorch_tpu.utils.device import require_tpu

    cache_dir = configure_compile_cache()
    if not dry_run:
        require_tpu("chip_smoke.py")
    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 — version string is informational
        libtpu = "unknown"
    devices = jax.devices()
    info = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": libtpu,
        "compile_cache_dir": cache_dir,
    }
    log("platform={platform} device_kind={kind!r} count={count} "
        "jax={jax} jaxlib={jaxlib} libtpu={libtpu} "
        "compile_cache_dir={compile_cache_dir}".format(**info))
    return info


def compile_counters() -> dict:
    """Totals of jax's own compile/cache monitoring events, updated in
    place for the rest of the process."""
    import jax

    totals = {"cache_hits": 0, "cache_misses": 0, "backend_compile_s": 0.0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            totals["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            totals["cache_misses"] += 1

    def on_duration(event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            totals["backend_compile_s"] += seconds

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    return totals


def leg_kernels(dry_run: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    device = describe_device(dry_run)
    from scaletorch_tpu.models.layers import (
        cached_sdpa_attention,
        sdpa_attention,
    )
    from scaletorch_tpu.ops.flash_attention import flash_attention
    from scaletorch_tpu.ops.pallas.flash import (
        flash_blocks,
        pallas_flash_attention,
    )
    from scaletorch_tpu.ops.pallas.paged_attention import (
        paged_attention,
        paged_gather_kv,
        paged_write_kv,
        pallas_paged_decode_attention,
        pallas_paged_write,
    )

    interpret = dry_run  # never True on the chip
    hq, hkv, d = 16, 8, 128
    seq = 256 if dry_run else 2048
    rng = np.random.default_rng(0)

    def normal(shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)

    def max_abs(x):
        return float(jnp.max(jnp.abs(x.astype(jnp.float32))))

    # ---- flash forward + grads vs SDPA --------------------------------
    q, k, v = normal((1, hq, seq, d)), normal((1, hkv, seq, d)), \
        normal((1, hkv, seq, d))
    w = normal((1, hq, seq, d))  # cotangent: loss = sum(out * w)

    def value_and_grads(attn, *qkv):
        def loss(q_, k_, v_):
            out = attn(q_, k_, v_)
            return jnp.sum(out.astype(jnp.float32)
                           * w.astype(jnp.float32)), out

        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(*qkv)
        return out, grads

    out_k, grads_k = value_and_grads(
        lambda *a: pallas_flash_attention(*a, causal=True,
                                          interpret=interpret), q, k, v)
    out_x, grads_x = value_and_grads(
        lambda *a: sdpa_attention(*a, causal=True), q, k, v)
    # the truth: fp32 inputs AND fp32 matmul passes (the TPU's default
    # matmul precision would round fp32 operands to bf16 again)
    with jax.default_matmul_precision("highest"):
        out_t, grads_t = value_and_grads(
            lambda *a: sdpa_attention(*a, causal=True),
            *(x.astype(jnp.float32) for x in (q, k, v)))
    flash = {
        "shape": f"B1 Hq{hq} Hkv{hkv} S{seq} D{d} bf16 causal",
        # (bq, bkv) each kernel takes at this shape: the rule's answer
        "blocks": {kind: flash_blocks(kind, seq, seq)
                   for kind in ("fwd", "dq", "dkv")},
        "fwd_max_abs_err_kernel": max_abs(out_k - out_t),
        "fwd_max_abs_err_xla_bf16": max_abs(out_x - out_t),
    }
    check(bool(jnp.all(jnp.isfinite(out_k.astype(jnp.float32)))),
          "flash forward is not finite")
    check(flash["fwd_max_abs_err_kernel"] <= FWD_ATOL,
          f"flash forward off by {flash['fwd_max_abs_err_kernel']:.3g} "
          f"> {FWD_ATOL}")
    for name, g_k, g_x, g_t in zip("qkv", grads_k, grads_x, grads_t):
        scale = max_abs(g_t)
        flash[f"d{name}_err_of_max_kernel"] = max_abs(g_k - g_t) / scale
        flash[f"d{name}_err_of_max_xla_bf16"] = max_abs(g_x - g_t) / scale
        check(flash[f"d{name}_err_of_max_kernel"] <= GRAD_RTOL_OF_MAX,
              f"flash d{name} off by "
              f"{flash[f'd{name}_err_of_max_kernel']:.3g} of max "
              f"> {GRAD_RTOL_OF_MAX}")
    log(f"flash parity: {json.dumps(flash)}")

    # ---- paged decode vs gather + cached SDPA --------------------------
    page = 16

    def paged_case(slots, max_pages, positions):
        n_pages = slots * max_pages + 1
        pool_k, pool_v = normal((n_pages, hkv, page, d)), \
            normal((n_pages, hkv, page, d))
        tables = jnp.asarray(
            rng.permutation(np.arange(1, n_pages)).reshape(slots, max_pages),
            jnp.int32)
        positions = jnp.asarray(positions, jnp.int32)
        qd = normal((slots, hq, d))

        def gather_ref(dtype):
            return cached_sdpa_attention(
                qd.astype(dtype)[:, :, None],
                paged_gather_kv(pool_k, tables).astype(dtype),
                paged_gather_kv(pool_v, tables).astype(dtype),
                positions[:, None])[:, :, 0]

        out_k = jax.jit(lambda *a: pallas_paged_decode_attention(
            *a, interpret=interpret))(qd, pool_k, pool_v, tables, positions)
        with jax.default_matmul_precision("highest"):
            out_t = gather_ref(jnp.float32)
        # a dead slot (position -1: no key) is looked past by the walk
        # and reads 0; the reference has no answer for it
        live = (positions >= 0)[:, None, None]
        paged = {
            "shape": f"B{slots} Hq{hq} Hkv{hkv} D{d} page{page} "
                     f"max_pages{max_pages} bf16",
            "max_abs_err_kernel": max_abs(jnp.where(live, out_k - out_t, 0)),
            "max_abs_err_xla_bf16": max_abs(jnp.where(
                live, gather_ref(jnp.bfloat16) - out_t, 0)),
        }
        check(bool(jnp.all(jnp.isfinite(out_k.astype(jnp.float32)))),
              f"paged decode output is not finite ({paged['shape']})")
        check(bool(jnp.all(jnp.where(live, 0, out_k) == 0)),
              f"a dead slot's output is not 0 ({paged['shape']})")
        check(paged["max_abs_err_kernel"] <= FWD_ATOL,
              f"paged decode off by {paged['max_abs_err_kernel']:.3g} "
              f"> {FWD_ATOL} ({paged['shape']})")
        log(f"paged-decode parity: {json.dumps(paged)}")
        return paged, (qd, pool_k, pool_v, tables, positions)

    max_pages = 8 if dry_run else 128          # max_seq 2048
    last = max_pages * page - 1
    paged, (qd, pool_k, pool_v, tables, positions) = paged_case(
        9, max_pages, [0, page - 1, page, 5 * page + 3, -1, last // 4,
                       last // 2, last - 1, last])
    # the serving cell's shape (qwen3-1.7b-serve: 16 slots x 96 pages),
    # ragged from 31 to the last position a slot can hold, one slot dead
    serve_pages = 6 if dry_run else 96
    serve_positions = np.linspace(
        31, serve_pages * page - 1, 16).astype(np.int32)
    serve_positions[5] = -1
    paged_serving, _ = paged_case(16, serve_pages, serve_positions)

    # ---- the page write, in place, vs the scatter: bit for bit ----------
    def write_case(slots, max_pages, rows, layers=3, layer=1):
        n_pages = slots * max_pages + 1
        pool = normal((layers, n_pages, hkv, page, d))
        tables_w = jnp.asarray(
            rng.permutation(np.arange(1, n_pages)).reshape(slots, max_pages),
            jnp.int32)
        if rows == 1:   # decode: any offset, one position past the table
            first = rng.integers(0, max_pages * page, slots)
            first[-1] = max_pages * page
        else:           # prefill: page-aligned starts
            first = rng.integers(0, max_pages // 2, slots) * page
        positions_w = jnp.asarray(first[:, None] + np.arange(rows), jnp.int32)
        mask = jnp.asarray(np.arange(slots) != 1)   # slot 1 -> TRASH
        new = normal((slots, hkv, rows, d))
        want = jax.jit(lambda *a: paged_write_kv(*a[:-1], page, a[-1],
                                                 layer=layer))(
            pool, new, positions_w, tables_w, mask)
        got = jax.jit(lambda *a: pallas_paged_write(
            *a, layer=layer, interpret=interpret))(
                pool, new, positions_w, tables_w, mask)
        same = bool(jnp.all(want[:, 1:] == got[:, 1:]))   # all but TRASH
        check(same, f"paged_write differs from the scatter "
                    f"(slots {slots}, rows {rows})")
        check(bool(jnp.all(jnp.isfinite(got[:, 0].astype(jnp.float32)))),
              "paged_write left TRASH not finite")
        read = jax.jit(lambda *a: pallas_paged_decode_attention(
            *a, layer=layer, interpret=interpret))(
                qd[:slots], got, got, tables_w, positions_w[:, 0] % page)
        read_one = jax.jit(lambda *a: pallas_paged_decode_attention(
            *a, interpret=interpret))(
                qd[:slots], got[layer], got[layer], tables_w,
                positions_w[:, 0] % page)
        check(bool(jnp.all(read == read_one)),
              "the decode kernel at a layer index differs from the kernel "
              "on that layer's pool")
        return {"slots": slots, "rows": rows, "bit_identical": same}

    write_pages = 6 if dry_run else 96
    paged_write = [write_case(8, write_pages, 1),
                   write_case(8, write_pages, (write_pages // 2) * page)]
    log(f"paged-write parity: {json.dumps(paged_write)}")

    # ---- the latent decode kernel vs the gathered form ------------------
    # 128 heads on ONE cached head of 640 (512 + 64 in whole tiles), the
    # value its first 512 columns: slots at position 0, at a page's
    # edges and deep into the table
    from scaletorch_tpu.ops.pallas.paged_attention import latent_attention

    l_pages = 6 if dry_run else 216
    l_heads, l_row, l_value = (8, 128, 64) if dry_run else (128, 640, 512)
    l_pool = 0.5 * normal((2, 8 * l_pages + 1, 1, page, l_row))
    l_q = 0.2 * normal((8, l_heads, l_row))
    l_tables = jnp.asarray(1 + rng.permutation(8 * l_pages).reshape(
        8, l_pages), jnp.int32)
    last = l_pages * page - 1
    l_positions = jnp.asarray(
        [0, page - 1, page, last // 2, last // 2 + 1, last - page, last - 1,
         last], jnp.int32)
    l_kw = dict(layer=jnp.int32(1), value_width=l_value, scale=0.0722)
    l_got = jax.jit(lambda *a: latent_attention(
        *a, kernel=True, interpret=interpret, **l_kw))(
            l_q, l_pool, l_tables, l_positions)
    l_want = jax.jit(lambda *a: latent_attention(*a, kernel=False, **l_kw))(
        l_q, l_pool, l_tables, l_positions)
    latent = {"max_abs_err": max_abs(l_got - l_want),
              "max_abs": max_abs(l_want)}
    log(f"latent-decode parity: {json.dumps(latent)}")
    check(latent["max_abs_err"] <= 0.02 * latent["max_abs"],
          f"latent_decode differs from the gathered form: {latent}")

    # ---- the flash forward at a value width of its own ------------------
    from scaletorch_tpu.ops.flash_attention import prefill_self_attention

    fq, fk = 0.3 * normal((1, hq, seq, 192)), 0.3 * normal((1, hq, seq, 192))
    fv = 0.3 * normal((1, hq, seq, 128))
    f_got = jax.jit(prefill_self_attention)(fq, fk, fv)
    f_want = jax.jit(sdpa_attention)(fq, fk, fv)
    flash_192_128 = {"max_abs_err": max_abs(f_got - f_want),
                     "max_abs": max_abs(f_want)}
    log(f"flash 192/128 parity: {json.dumps(flash_192_128)}")
    check(flash_192_128["max_abs_err"] <= 0.02 * flash_192_128["max_abs"],
          f"the flash forward at keys 192 / values 128 differs from SDPA: "
          f"{flash_192_128}")

    # ---- the expert kernel vs XLA's ragged product -----------------------
    # a decode step's rows (a few a group, one group empty, rows of no
    # group at the end) at the two K / N tilings the served widths take:
    # 1,024 (hidden 2,048) and 1,152 (hidden 2,304: up / gate on K, down
    # on N); float32 results of bfloat16 operands, both forms summing
    # bfloat16 products in float32
    from scaletorch_tpu.ops.grouped_matmul import (
        _gmm_tiling,
        pallas_matmul,
        ragged_matmul,
    )

    g_rows, g_groups = (96, 4) if dry_run else (256, 64)
    g_sizes = rng.multinomial(g_rows - 16, np.ones(g_groups) / g_groups)
    g_sizes[1] = 0
    g_sizes = jnp.asarray(g_sizes, jnp.int32)
    g_live = int(g_sizes.sum())
    grouped = []
    for g_k, g_n in ((2048, 768), (2304, 1024), (1024, 2304)):
        g_x = normal((g_rows, g_k))
        g_w = (g_k ** -0.5) * normal((g_groups, g_k, g_n))
        g_got = jax.jit(lambda *a: pallas_matmul(
            *a, out_dtype=jnp.float32, interpret=interpret))(
                g_x, g_w, g_sizes)
        g_want = jax.jit(lambda *a: ragged_matmul(
            *a, out_dtype=jnp.float32))(g_x, g_w, g_sizes)
        case = {"k": g_k, "n": g_n, "tiling": _gmm_tiling(g_rows, g_k, g_n),
                "max_abs_err": max_abs(g_got[:g_live] - g_want[:g_live]),
                "max_abs": max_abs(g_want[:g_live])}
        check(case["max_abs_err"] <= 1e-3 * case["max_abs"],
              f"the expert kernel differs from ragged_dot: {case}")
        grouped.append(case)
    log(f"grouped-matmul parity: {json.dumps(grouped)}")

    # ---- the dispatchers pick the kernels iff the platform is tpu ------
    lowered = {
        "flash": jax.jit(flash_attention).lower(q, k, v).as_text(),
        "paged": jax.jit(lambda *a: paged_attention(
            *a, page_size=page)).lower(
                qd[:, :, None], pool_k, pool_v, tables,
                positions[:, None]).as_text(),
    }
    on_tpu = device["platform"] == "tpu"
    for name, text in lowered.items():
        check((MOSAIC_CALL in text) == on_tpu,
              f"{name} dispatcher lowered "
              f"{'no ' if on_tpu else 'a '}Mosaic call on "
              f"platform {device['platform']}")
    return {"device": device, "flash": flash, "paged_decode": paged,
            "paged_decode_serving": paged_serving,
            "paged_write": paged_write,
            "latent_decode": latent, "flash_192_128": flash_192_128,
            "grouped_matmul": grouped,
            "memory_stats": {str(d.id): d.memory_stats()
                             for d in jax.devices()}}


def leg_train(mesh: str, dry_run: bool, workdir: str) -> dict:
    import jax

    device = describe_device(dry_run)
    counters = compile_counters()
    ir_dir = os.path.join(workdir, "ir")
    perf_dir = os.path.join(workdir, "perf")
    jax.config.update("jax_dump_ir_to", ir_dir)

    sys.path.insert(0, REPO)
    import train

    argv = train_argv(mesh, perf_dir, dry_run)
    log("train.main " + " ".join(argv))
    t0 = time.monotonic()
    rc = train.main(argv)
    wall_s = time.monotonic() - t0
    check(rc == 0, f"train.main returned {rc}")

    logs = glob.glob(os.path.join(perf_dir, "performance_log_proc0_*.json"))
    check(len(logs) == 1, f"expected one performance log, found {logs}")
    with open(logs[0]) as f:
        perf = json.load(f)
    records = perf["records"]
    check(len(records) >= 5, f"only {len(records)} optimizer steps logged")
    for r in records:
        check(math.isfinite(r["loss"]),
              f"step {r['step']}: loss {r['loss']} is not finite")
        check(math.isfinite(r["grad_norm"]) and r["grad_norm"] > 0,
              f"step {r['step']}: grad_norm {r['grad_norm']} is not "
              "finite and > 0")
        check(r.get("update_skipped") == 0,
              f"step {r['step']}: update_skipped={r.get('update_skipped')}"
              " — the non-finite guard froze the params")
    want_backend = "ring" if "cp" in mesh else "flash"
    check(perf["attention_backend"] == want_backend,
          f"attention_backend resolved to {perf['attention_backend']!r}, "
          f"not {want_backend!r}")

    steps_ir = glob.glob(os.path.join(ir_dir, "*jit_step*"))
    check(bool(steps_ir), f"jax dumped no train step under {ir_dir}")
    has_mosaic = any(MOSAIC_CALL in open(p).read() for p in steps_ir)
    if device["platform"] == "tpu":
        check(has_mosaic, "the lowered train step holds no Mosaic custom "
                          "call: attention did not take the Pallas kernel")

    before = perf["devices_before_first_step"]
    after = perf["devices_after_last_step"]
    if device["platform"] == "tpu":
        resident = {d["resident_bytes"] for d in after}
        check(len(resident) == 1,
              f"devices hold unequal shards of the state: {after}")
        for d in after:
            extra = d["bytes_in_use"] - d["resident_bytes"]
            check(extra <= RESIDENT_SLACK_BYTES,
                  f"device {d['id']} holds {extra / 2**20:.0f} MiB beyond "
                  f"its shards after the last step: {d}")
            check(d["peak_bytes_in_use"] <= d["bytes_limit"],
                  f"device {d['id']} peak exceeds its limit: {d}")
    for d0, d1 in zip(before, after):
        log(f"device {d0['id']}: state shards "
            f"{d1['resident_bytes'] / 2**30:.2f} GiB | before step 1: "
            f"in use {d0['bytes_in_use'] / 2**30:.2f}, peak "
            f"{d0['peak_bytes_in_use'] / 2**30:.2f} GiB | after last "
            f"step: in use {d1['bytes_in_use'] / 2**30:.2f}, peak "
            f"{d1['peak_bytes_in_use'] / 2**30:.2f}, peak reserved "
            f"{d1['peak_bytes_reserved'] / 2**30:.2f} GiB")
    log(f"train{'-' + mesh if mesh else ''}: {len(records)} steps, "
        f"loss {records[0]['loss']:.4f} -> {records[-1]['loss']:.4f}, "
        f"wall {wall_s:.1f}s, backend compile "
        f"{counters['backend_compile_s']:.1f}s, cache hits "
        f"{counters['cache_hits']} misses {counters['cache_misses']}")
    return {
        "device": device,
        "mesh": perf["mesh"],
        "losses": [r["loss"] for r in records],
        "grad_norms": [r["grad_norm"] for r in records],
        "wall_s": round(wall_s, 1),
        "compile": {k: round(v, 1) if isinstance(v, float) else v
                    for k, v in counters.items()},
        "mosaic_call_in_step": has_mosaic,
        "devices_before_first_step": before,
        "devices_after_last_step": after,
    }


# --------------------------------------------------------------------------
# Serve leg: the server is the child, this process is the client
# --------------------------------------------------------------------------

def http_json(url: str, timeout: float = 30.0) -> dict:
    with urllib.request.urlopen(url, timeout=timeout) as response:
        return json.loads(response.read())


def generate(base: str, prompt: list, max_new: int, timeout: float) -> dict:
    """One streaming request; (tokens streamed, the ``done`` events)."""
    from scaletorch_tpu.serving.protocol import (
        parse_sse_stream,
        stream_tokens,
    )

    body = json.dumps({"prompt": prompt, "max_new_tokens": max_new,
                       "stream": True}).encode()
    request = urllib.request.Request(
        f"{base}/v1/generate", data=body, method="POST")
    t0 = time.monotonic()
    with urllib.request.urlopen(request, timeout=timeout) as response:
        events = parse_sse_stream(response.read())
    return {"seconds": time.monotonic() - t0,
            "streamed": stream_tokens(events),
            "dones": [data for event, data in events if event == "done"]}


def leg_serve(replicas: int, dry_run: bool, workdir: str, env: dict,
              timeout_s: float) -> dict:
    ir_dir = os.path.join(workdir, "ir")
    vocab = 64 if dry_run else 151936
    max_new = 8 if dry_run else 32
    lengths = [5, 19, 33, 60] if dry_run else [37, 150, 301, 500]
    rng = random.Random(0)
    # 4 prompts per replica: different lengths, all in flight at once.
    # Routing is rendezvous hashing of the prompt head — deterministic,
    # so this seeded set reaches every replica every time or never.
    prompts = [[rng.randrange(vocab) for _ in range(n)]
               for _ in range(replicas) for n in lengths]

    cmd = [sys.executable, os.path.join(REPO, "scripts", "serve.py"),
           *serve_argv(replicas, dry_run)]
    log(" ".join(cmd))
    t0 = time.monotonic()
    with open(os.path.join(workdir, "server.log"), "w") as server_log:
        proc = subprocess.Popen(
            cmd, stdout=subprocess.PIPE, stderr=server_log, text=True,
            cwd=REPO, env=dict(env, JAX_DUMP_IR_TO=ir_dir))
        try:
            return _drive_server(proc, prompts, max_new, replicas, dry_run,
                                 ir_dir, t0, timeout_s)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()


def _drive_server(proc, prompts, max_new, replicas, dry_run, ir_dir, t0,
                  timeout_s) -> dict:
    deadline = t0 + timeout_s
    port = None
    ready_lines = []

    def read_stdout():
        for line in proc.stdout:
            ready_lines.append(line)

    reader = threading.Thread(target=read_stdout, daemon=True)
    reader.start()
    while time.monotonic() < deadline and port is None:
        check(proc.poll() is None,
              f"server exited early with code {proc.returncode}")
        for line in list(ready_lines):
            if line.startswith("READY port="):
                port = int(line.strip().split("=", 1)[1])
        time.sleep(0.2)
    check(port is not None, "server never printed READY")
    ready_s = time.monotonic() - t0
    base = f"http://127.0.0.1:{port}"

    health = http_json(f"{base}/healthz")
    check(health["status"] == "ok", f"/healthz says {health['status']}")
    check(len(health["replicas"]) == replicas, f"replicas: {health}")

    results = [None] * len(prompts)

    def worker(i):
        try:
            results[i] = generate(base, prompts[i], max_new,
                                  timeout=deadline - time.monotonic())
        except Exception as exc:  # noqa: BLE001 — reported below
            results[i] = {"error": repr(exc)}

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(max(0.0, deadline - time.monotonic()))
    check(not any(t.is_alive() for t in threads),
          "requests still in flight at the leg's time limit")
    for i, r in enumerate(results):
        check("error" not in r, f"request {i}: {r.get('error')}")
        check(len(r["dones"]) == 1, f"request {i}: dones {r['dones']}")
        done = r["dones"][0]
        check(done["outcome"] == "ok",
              f"request {i} (prompt {len(prompts[i])} tokens) ended "
              f"{done['outcome']!r}: {done.get('detail')}")
        check(len(done["token_ids"]) == max_new
              and r["streamed"] == done["token_ids"],
              f"request {i}: {len(done['token_ids'])} tokens, streamed "
              f"{len(r['streamed'])}, wanted {max_new}")
    # a second, sequential round on compiled steps: how long a request
    # takes once nothing compiles
    warm = generate(base, prompts[0], max_new,
                    timeout=deadline - time.monotonic())
    check(warm["dones"][0]["outcome"] == "ok", f"warm request: {warm}")

    health = http_json(f"{base}/healthz")
    check(health["status"] == "ok", f"/healthz after traffic: {health}")
    from scaletorch_tpu.serving.protocol import parse_metrics_text

    with urllib.request.urlopen(f"{base}/metrics", timeout=30) as response:
        prom = parse_metrics_text(response.read().decode())
    device_ids = []
    per_replica = {}
    for rid, rep in sorted(health["replicas"].items()):
        check(rep["alive"], f"replica {rid} is dead: {rep}")
        label = f'{{replica="{rid}"}}'
        compiles = prom[f"scaletorch_engine_decode_compile_count{label}"]
        served = prom[f"scaletorch_engine_requests_ok{label}"]
        check(compiles == 1,
              f"replica {rid}: decode_compile_count == {compiles}")
        check(served >= 1, f"replica {rid} served no request")
        check(len(rep["devices"]) == 1, f"replica {rid}: {rep['devices']}")
        dev = rep["devices"][0]
        if not dry_run:
            check(dev["platform"] == "tpu", f"replica {rid} on {dev}")
        device_ids.append(dev["id"])
        per_replica[rid] = {"device": dev, "requests_ok": served}
        log(f"replica {rid}: device {dev['id']} ({dev['kind']}), "
            f"{served:.0f} requests ok, peak "
            f"{dev['peak_bytes_in_use'] / 2**30:.2f}, peak reserved "
            f"{dev['peak_bytes_reserved'] / 2**30:.2f} GiB")
    check(len(set(device_ids)) == replicas,
          f"{replicas} replicas on devices {device_ids}")
    sent = len(prompts) + 1
    check(prom["scaletorch_http_requests_received"] == sent
          and prom["scaletorch_http_ok"] == sent,
          f"gateway ledger: received "
          f"{prom['scaletorch_http_requests_received']:.0f}, ok "
          f"{prom['scaletorch_http_ok']:.0f}, sent {sent}")

    proc.send_signal(signal.SIGTERM)
    try:
        rc = proc.wait(timeout=120)
    except subprocess.TimeoutExpired:
        raise LegFailed("server did not drain within 120 s of SIGTERM")
    check(rc == 0, f"server exited {rc} after SIGTERM, not a clean drain")

    decode_ir = glob.glob(os.path.join(ir_dir, "*jit_decode*"))
    check(bool(decode_ir), f"jax dumped no decode step under {ir_dir}")
    has_mosaic = any(MOSAIC_CALL in open(p).read() for p in decode_ir)
    if not dry_run:
        check(has_mosaic, "the lowered decode step holds no Mosaic custom "
                          "call: decode took the lax gather path")
    cold_s = max(r["seconds"] for r in results)
    log(f"serve x{replicas}: {len(prompts)} overlapping requests ok, "
        f"ready {ready_s:.1f}s, cold round (compiles included) "
        f"{cold_s:.1f}s, one warm request {warm['seconds']:.2f}s, "
        "drained to exit 0")
    first = per_replica["r0"]["device"]
    return {
        # as /healthz reports it; the count is what this leg can attest
        "device": {"platform": first["platform"], "kind": first["kind"],
                   "count": len(set(device_ids))},
        "requests": len(prompts),
        "prompt_lengths": sorted({len(p) for p in prompts}),
        "max_new_tokens": max_new,
        "ready_s": round(ready_s, 1),
        "cold_round_s": round(cold_s, 1),
        "warm_request_s": round(warm["seconds"], 2),
        "mosaic_call_in_decode": has_mosaic,
        "replicas": per_replica,
    }


# --------------------------------------------------------------------------
# Parent: one child at a time, never jax
# --------------------------------------------------------------------------

def child_env(leg: str, dry_run: bool, host_chips: int) -> dict:
    env = dict(os.environ)
    four = leg in FOUR_CHIP_LEGS
    if dry_run:
        # the CPU stands in for the chips: the device count is a flag
        env["JAX_PLATFORMS"] = "cpu"
        n = 4 if four or leg == "kernels" else 1
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    elif not four and leg != "kernels" and host_chips > 1:
        from scaletorch_tpu.env import one_chip_env

        env.update(one_chip_env(0))
    return env


def run_child_leg(leg: str, dry_run: bool, env: dict, workdir: str) -> dict:
    """A jax leg: this script again with --leg, its result in a file."""
    result_path = os.path.join(workdir, "result.json")
    cmd = [sys.executable, os.path.abspath(__file__), "--leg", leg,
           "--workdir", workdir] + (["--dry-run"] if dry_run else [])
    timeout = LEG_TIMEOUT_S[leg.split("-")[0]]
    try:
        rc = subprocess.run(cmd, env=env, cwd=REPO, timeout=timeout
                            ).returncode
    except subprocess.TimeoutExpired:
        raise LegFailed(f"no result within {timeout} s; child killed")
    result = {}
    if os.path.exists(result_path):
        with open(result_path) as f:
            result = json.load(f)
    check(rc == 0 and "failed" not in result,
          result.get("failed", f"child exited {rc} (trace above)"))
    return result


def run_leg_in_child(args) -> int:
    """--leg: the body of one jax leg, in its own process."""
    sys.path.insert(0, REPO)
    from scaletorch_tpu.utils.device import NoTpuError

    name, _, mesh = args.leg.partition("-")
    try:
        if name == "kernels":
            result = leg_kernels(args.dry_run)
        else:
            result = leg_train(mesh, args.dry_run, args.workdir)
    except (LegFailed, NoTpuError) as exc:
        # the stated reason travels to the parent; anything else is a
        # crash and keeps its traceback
        result = {"failed": str(exc)}
    with open(os.path.join(args.workdir, "result.json"), "w") as f:
        json.dump(result, f, indent=1)
    return 1 if "failed" in result else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--legs", default="",
                    help="comma-separated subset/order of legs (default: "
                         "kernels,train,serve, then train-dp2tp2,"
                         "train-cp2dp2,serve4 where jax sees >= 4 chips); "
                         "a leg may repeat, e.g. train,train to see the "
                         "second process hit the compile cache")
    ap.add_argument("--dry-run", action="store_true",
                    help="debug this script on a CPU at tiny sizes; "
                         f"always exits {DRY_RUN_EXIT}, never 0")
    ap.add_argument("--leg", default="", help=argparse.SUPPRESS)
    ap.add_argument("--workdir", default="", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.leg:
        return run_leg_in_child(args)

    try:
        import scaletorch_tpu  # noqa: F401 — the checkout, not jax
    except ImportError:
        print("chip_smoke.py: run it from the root of a checkout of the "
              "repo (scaletorch_tpu is not importable here)",
              file=sys.stderr)
        return 1
    assert "jax" not in sys.modules, "the parent must stay off jax"

    legs = [s for s in args.legs.split(",") if s] or list(ONE_CHIP_LEGS)
    explicit = bool(args.legs)
    results = {}
    failures = []
    device = None
    host_chips = 4 if any(l in FOUR_CHIP_LEGS for l in legs) else 1
    t_start = time.monotonic()
    i = 0
    while i < len(legs):
        leg = legs[i]
        i += 1
        workdir = os.path.join(WORKDIR, f"{i:02d}_{leg}")
        shutil.rmtree(workdir, ignore_errors=True)
        os.makedirs(workdir)
        env = child_env(leg, args.dry_run, host_chips)
        log(f"=== leg {leg} ===")
        t0 = time.monotonic()
        try:
            if leg.startswith("serve"):
                result = leg_serve(
                    4 if leg == "serve4" else 1, args.dry_run, workdir,
                    env, LEG_TIMEOUT_S["serve"])
            else:
                result = run_child_leg(leg, args.dry_run, env, workdir)
        except LegFailed as exc:
            print(f"chip_smoke.py: leg {leg} failed: {exc}",
                  file=sys.stderr, flush=True)
            log(f"FAILED {leg} after {time.monotonic() - t0:.0f}s: {exc}")
            if i == 1:
                # the first leg is the gate (is there a chip at all?);
                # the later ones are independent and all get their say
                return 1
            failures.append(leg)
            continue
        log(f"leg {leg} ok in {time.monotonic() - t0:.0f}s")
        results.setdefault(leg, []).append(result)
        if "device" in result and leg == "kernels":
            device = result["device"]
            host_chips = device["count"]
            if not explicit and host_chips >= 4:
                legs += FOUR_CHIP_LEGS

    if failures:
        print(f"chip_smoke.py: failed legs: {', '.join(failures)}",
              file=sys.stderr, flush=True)
        return 1

    # the four-chip train legs answer to the one-chip leg's first loss
    if "train" in results:
        first = results["train"][0]["losses"][0]
        for leg in ("train-dp2tp2", "train-cp2dp2"):
            for r in results.get(leg, []):
                got = r["losses"][0]
                if abs(got - first) > FIRST_LOSS_RTOL * abs(first):
                    print(f"chip_smoke.py: {leg} first-step loss {got:.4f} "
                          f"vs one-chip {first:.4f}: beyond "
                          f"{FIRST_LOSS_RTOL:.0%}", file=sys.stderr)
                    return 1
                log(f"{leg} first-step loss {got:.4f} vs one-chip "
                    f"{first:.4f} (within {FIRST_LOSS_RTOL:.0%})")

    with open(os.path.join(WORKDIR, "summary.json"), "w") as f:
        json.dump(results, f, indent=1)
    if device is None:
        # --legs without kernels: every other leg saw the device too
        device = next(r[0]["device"] for r in results.values())
    assert "jax" not in sys.modules, "the parent must stay off jax"
    log(f"all legs ok in {time.monotonic() - t_start:.0f}s: "
        + ", ".join(f"{k} x{len(v)}" for k, v in results.items()))
    line = {"ok": not args.dry_run,
            "device": {k: device[k] for k in ("platform", "kind", "count")}}
    if args.dry_run:
        line["dry_run"] = True
        log(f"dry run: platform={device['platform']} — not a pass")
    print(json.dumps(line), flush=True)
    return DRY_RUN_EXIT if args.dry_run else 0


if __name__ == "__main__":
    sys.exit(main())
