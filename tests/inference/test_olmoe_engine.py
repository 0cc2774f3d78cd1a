"""OLMoE through the paged engine on the CPU at a tiny size: the
engine's own jitted prefill and decode steps against the plain
reference's full forward (logits, not tokens), the routing counters
they keep, and the family served from ``scripts/serve.py``."""

import json
import os
import signal
import subprocess
import sys
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib.serve_cell import system_logit_errors
from benchmarks.reference import olmoe as reference
from scaletorch_tpu.inference import InferenceEngine, SamplingParams
from scaletorch_tpu.inference.routing_counters import (
    READ_EVERY,
    ROUTING_COUNTERS,
    RoutingCounters,
)
from tests.models.test_olmoe import (
    REPO,
    RTOL_OF_MAX,
    TINY,
    WRONG,
    seeded_params,
    tiny_config,
)

LAYERS, TOP_K = TINY["num_hidden_layers"], TINY["num_experts_per_tok"]


def make_engine(cfg, params, **kw):
    return InferenceEngine(
        params, cfg, max_slots=4, max_seq=64, prefill_len=32,
        sampling=SamplingParams(temperature=0.0), page_size=8, strict_submit=False, **kw)


@pytest.fixture(scope="module")
def checked():
    """Three prompts of 9, 20 and 32 tokens plus 8 decode positions
    through the engine's paged steps, and the reference's logits at the
    same rows (the harness's own comparison, ``serve_cell``)."""
    cfg = tiny_config()
    params = seeded_params(cfg)
    depth = 8
    lens = np.array([9, 20, 32])
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(5), (3, 32 + depth), 0, 128))
    rows = (lens[:, None] - 1 + np.arange(depth + 1)[None, :]).astype(
        np.int32)

    def logits(wrong=None):
        return reference.make_logits_fn(TINY, q_block=8, wrong=wrong)(
            params, jnp.asarray(tokens), jnp.asarray(rows))

    engine = make_engine(cfg, params)
    ref = logits()
    with jax.default_matmul_precision("highest"):
        errors = system_logit_errors(engine, tokens, lens, depth, ref)
    return engine, errors, ref, logits, int(lens.sum()), depth


def test_paged_prefill_and_decode_match_the_full_forward(checked):
    _, errors, _, _, _, _ = checked
    assert errors["all_finite"]
    assert errors["max_abs_err"] / errors["max_abs_reference"] < RTOL_OF_MAX
    assert errors["prefill_max_abs_err"] > 0  # it did compare something


@pytest.mark.parametrize("variant", WRONG)
def test_the_engine_check_rejects_each_wrong_variant(checked, variant):
    _, errors, ref, logits, _, _ = checked
    off = float(jnp.max(jnp.abs(logits(variant) - ref)))
    assert off / errors["max_abs_reference"] > 50 * RTOL_OF_MAX


def test_the_check_counted_live_rows_only(checked):
    """One prefill call of 61 live tokens in a 4 x 32 buffer and 8
    decode steps of 3 live slots of 4: rows x k x layers, nothing else,
    nothing dropped; both steps compiled once."""
    engine, _, _, _, prompt_tokens, depth = checked
    snap = engine.metrics.snapshot()
    prefill = prompt_tokens * TOP_K * LAYERS
    decode = depth * 3 * TOP_K * LAYERS
    assert snap["moe_prefill_assignments"] == prefill
    assert snap["moe_routed_assignments"] == prefill + decode
    assert snap["moe_dropped_assignments"] == 0
    assert 0 < snap["moe_expert_visits"] <= depth * LAYERS * min(
        8, 3 * TOP_K)
    assert snap["moe_peak_load_rows"] >= prefill / 8
    assert engine.decode_compile_count == 1
    assert 1 <= engine.prefill_compile_count <= len(engine.prefill_shapes)


@pytest.mark.parametrize("with_mesh", [False, True])
def test_a_short_engine_run_counts_routed_rows_and_drops_none(with_mesh):
    cfg = tiny_config()
    params = seeded_params(cfg)
    kw = {}
    if with_mesh:  # as scripts/serve.py places a replica on its device
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
        params = jax.device_put(params, NamedSharding(mesh, PartitionSpec()))
        kw["mesh"] = mesh
    engine = make_engine(cfg, params, **kw)
    prompts = [7, 12, 3, 21, 5, 9]     # six requests over four slots
    new = 6
    for n in prompts:
        engine.submit(list(range(5, 5 + n)), max_new_tokens=new)
    results = engine.run()
    assert all(r.outcome == "ok" for r in results.values())
    snap = engine.metrics.snapshot()
    # a request's first token comes from its prefill, the other five
    # from decode steps; the prompts share their head, and a page the
    # radix cache already holds is not prefilled again
    prefilled = sum(prompts) - snap["prefill_tokens_saved"]
    assert snap["prefill_tokens_saved"] > 0
    live = prefilled + len(prompts) * (new - 1)
    assert snap["moe_routed_assignments"] == live * TOP_K * LAYERS
    assert snap["moe_prefill_assignments"] == prefilled * TOP_K * LAYERS
    assert snap["moe_dropped_assignments"] == 0
    assert snap["moe_experts_touched_per_step"] == pytest.approx(
        snap["moe_expert_visits"] / (snap["decode_steps"] * LAYERS))
    assert 1.0 <= snap["moe_peak_over_mean_load"] <= 8.0
    assert engine.decode_compile_count == 1
    # one entry a shape called, under a one-device mesh too: the pool
    # is placed as the steps return it (``paged_kv_cache_shardings``)
    assert 1 <= engine.prefill_compile_count <= len(engine.prefill_shapes)
    # a second snapshot reads the same totals again
    assert engine.metrics.snapshot()["moe_routed_assignments"] == \
        snap["moe_routed_assignments"]


def test_a_dense_engine_has_no_routing_counters():
    from scaletorch_tpu.models import llama

    cfg = llama.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        dtype=jnp.float32)
    engine = make_engine(cfg, llama.init_params(jax.random.PRNGKey(0), cfg))
    assert engine.metrics.routing is None
    assert not any(k.startswith("moe_") for k in engine.metrics.snapshot())


def test_counters_survive_the_accumulators_wrap():
    """The device sums are uint32 and wrap; the host adds differences
    modulo 2**32, read at least every ``READ_EVERY`` calls."""
    counters = RoutingCounters(num_experts=8, moe_layers=2)
    big = np.uint32(2**23 + 12345)    # 256 calls stay under 2**32

    def step(accumulator):
        return "out", accumulator + jnp.full(
            len(ROUTING_COUNTERS), big, jnp.uint32)

    for _ in range(3 * READ_EVERY):
        assert counters.run(step, ()) == ("out",)
    snap = counters.snapshot(decode_steps=10)
    assert snap["moe_routed_assignments"] == 3 * READ_EVERY * int(big)
    assert snap["moe_routed_assignments"] > 2**32


def test_a_snapshot_between_two_periodic_reads_adds_nothing_twice():
    """``snapshot`` reads the newest accumulator that is ready, the
    periodic read the one three calls back: after a snapshot that one
    is the older of the two and is left alone (read, it stepped the
    totals back mod 2**32: +4,294,967,296 in a window's counters)."""
    counters = RoutingCounters(num_experts=8, moe_layers=2)

    def step(accumulator):
        return "out", accumulator + jnp.full(
            len(ROUTING_COUNTERS), 7, jnp.uint32)

    for call in range(1, 2 * READ_EVERY + 1):
        counters.run(step, ())
        if call % READ_EVERY == READ_EVERY - 1:
            # one call before a periodic read: the newest is ready
            assert counters.snapshot(1)["moe_routed_assignments"] == 7 * call
    assert counters.snapshot(1)["moe_routed_assignments"] == (
        7 * 2 * READ_EVERY)


def _http(port, path, payload=None):
    data = None if payload is None else json.dumps(payload).encode()
    with urllib.request.urlopen(urllib.request.Request(
            f"http://127.0.0.1:{port}{path}", data=data), timeout=120) as r:
        return r.read().decode()


def test_serve_cli_serves_olmoe_tiny_and_shows_the_moe_numbers():
    """``scripts/serve.py --preset olmoe-tiny``:
    one completion through the gateway, then the engine's routing
    counters on ``/metrics``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "scripts", "serve.py"),
         "--preset", "olmoe-tiny",
         "--page_size", "8", "--max_slots", "2", "--max_seq", "64",
         "--prefill_len", "16", "--serve_port", "0"],
        cwd=REPO, env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True)
    try:
        port = None
        for line in proc.stdout:
            if line.startswith("READY port="):
                port = int(line.split("=")[1])
                break
        assert port, "serve.py never printed READY"
        body = _http(port, "/v1/generate", {
            "prompt": [3, 1, 4, 1, 5, 9, 2, 6], "max_new_tokens": 5,
            "stream": False})
        answer = json.loads(body)
        assert answer["outcome"] == "ok", body
        assert len(answer["token_ids"]) == 5, body
        metrics = _http(port, "/metrics")
        wanted = (8 + 4) * 2 * 2    # prompt + 4 decode feeds, k=2, 2 layers
        routed = [l for l in metrics.splitlines()
                  if "engine_moe_routed_assignments{" in l]
        assert routed and float(routed[0].split()[-1]) == wanted, metrics
        dropped = [l for l in metrics.splitlines()
                   if "engine_moe_dropped_assignments{" in l]
        assert dropped and float(dropped[0].split()[-1]) == 0
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
