"""Qwen3-Next through the paged engine on the CPU at a tiny size, with
a SHARE of its experts (8 of 16 routed ones held): the engine's own
jitted prefill and decode steps against the plain reference's full
forward (logits, the harness's own comparison), the tokens of the plain
forward request for request through reused slots (state reset, owner
checks), the routing counters of a share (held + elsewhere, nothing
dropped), the refusals by name, and the family served from
``scripts/serve.py``."""

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
from benchmarks.reference import qwen3_next as reference
from scaletorch_tpu.inference import InferenceEngine, SamplingParams
from scaletorch_tpu.inference.disagg import DisaggregatedEngine
from scaletorch_tpu.inference.kv_cache import HybridCache
from scaletorch_tpu.inference.routing_counters import (
    ROUTING_COUNTERS,
    CountedStep,
)
from tests.inference.oracle import greedy_by_forward
from tests.inference.test_paged_engine import (
    assert_pages_conserved as assert_conserved,
)
from tests.models.test_qwen3_next import (
    RTOL_OF_MAX,
    TINY,
    WRONG,
    seeded_params,
    tiny_config,
)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
GREEDY = SamplingParams(temperature=0.0)
LAYERS, TOP_K = 8, 3


@pytest.fixture(scope="module")
def model():
    cfg = tiny_config()
    return cfg, seeded_params(cfg)


def make_engine(model, **kw):
    cfg, params = model
    kw.setdefault("max_slots", 3)
    kw.setdefault("max_seq", 96)
    kw.setdefault("prefill_len", 40)
    kw.setdefault("sampling", GREEDY)
    kw.setdefault("page_size", 8)
    kw.setdefault("strict_submit", False)
    return InferenceEngine(params, cfg, **kw)


def prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(0, 128, size=n)] for n in lengths]


# ---- logits: the harness's own comparison ------------------------------------

@pytest.fixture(scope="module")
def checked(model):
    """Three prompts of 9, 20 and 33 tokens plus 8 decode positions
    through the engine's paged steps (``serve_cell.system_logit_errors``
    calls ``engine._prefill`` / ``engine._decode`` with ``engine.cache``
    as one operand), and the reference's logits at the same rows, the
    reference given the same share."""
    cfg, params = model
    depth = 8
    lens = np.array([9, 20, 33])
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(5), (3, 48), 0, 128))
    rows = (lens[:, None] - 1 + np.arange(depth + 1)[None, :]).astype(
        np.int32)

    def logits(wrong=None):
        return reference.make_logits_fn(
            TINY, q_block=8, expert_chunk=4, wrong=wrong)(
                params, jnp.asarray(tokens), jnp.asarray(rows))

    engine = make_engine(model)
    ref = logits()
    with jax.default_matmul_precision("highest"):
        errors = system_logit_errors(engine, tokens, lens, depth, ref)
    return engine, errors, ref, logits


def test_paged_prefill_and_decode_match_the_full_forward(checked):
    engine, errors, _, _ = checked
    assert errors["all_finite"]
    assert errors["max_abs_err"] / errors["max_abs_reference"] < RTOL_OF_MAX
    assert errors["prefill_max_abs_err"] > 0  # it did compare something
    assert isinstance(engine.cache, HybridCache)
    assert isinstance(engine._decode, CountedStep)
    # a row names its slot: ONE one-row program, which the check's
    # full-shape call ran once a prompt (``decode.SlotRows``)
    assert engine.prefill_shapes == ((1, 40),)
    assert engine.decode_compile_count == 1
    assert engine.prefill_compile_count == 1


@pytest.mark.parametrize("variant", WRONG)
def test_the_engine_check_rejects_each_wrong_variant(checked, variant):
    _, errors, ref, logits = checked
    off = float(jnp.max(jnp.abs(logits(variant) - ref)))
    # a bf16 router flips a few near-ties in 62 + 24 rows: the weakest
    # departure, still a thousand times the system's own 1e-5
    factor = 20 if variant in ("bf16_router", "fp8_activations") else 50
    assert off / errors["max_abs_reference"] > factor * RTOL_OF_MAX


def test_the_check_s_steps_counted_held_and_absent_choices(checked):
    """One prefill call of 9 + 20 + 33 live rows and 8 decode steps of 3
    live slots: every live (token, choice) of every layer is counted
    once, on a held expert or on one held elsewhere; none is dropped."""
    engine, _, _, _ = checked
    snap = engine.metrics.snapshot()
    live = (9 + 20 + 33) + 8 * 3
    assert snap["moe_assignments_held"] + snap["moe_assignments_elsewhere"] \
        == live * TOP_K * LAYERS
    assert snap["moe_assignments_held"] == snap["moe_routed_assignments"] > 0
    assert snap["moe_assignments_elsewhere"] > 0
    assert snap["moe_dropped_assignments"] == 0
    assert snap["moe_experts_held"] == 8
    assert set(ROUTING_COUNTERS) <= set(snap)


# ---- tokens: the plain forward, request for request ---------------------------

def test_mixed_lengths_equal_the_oracle_through_reused_slots(model):
    """Seven requests over three slots: every later one is admitted
    into a slot whose state another request left behind, beside slots
    in mid-decode whose state a fixed-shape prefill call must not
    touch. Each gets the tokens the plain forward gives it alone, with
    absent experts in every layer."""
    cfg, params = model
    eng = make_engine(model)
    asked = prompts((5, 17, 33, 9, 21, 12, 40))
    ids = [eng.submit(p, max_new_tokens=12) for p in asked]
    results = eng.run()
    for p, rid in zip(asked, ids):
        assert results[rid].outcome == "ok"
        assert results[rid].tokens == greedy_by_forward(params, cfg, p, 12)
    snap = eng.metrics.snapshot()
    assert snap["recurrent_state_resets"] == 7
    assert snap["recurrent_state_owner_mismatches"] == 0
    # one call of the one-row program a prompt, three in the first tick
    assert snap["prefill_calls"] == 7
    assert snap["prefill_positions_run"] == 7 * 40
    assert eng.prefill_compile_count == 1
    assert snap["recurrent_state_bytes"] == (
        eng.cache.state.nbytes + eng.cache.conv.nbytes)
    assert snap["moe_dropped_assignments"] == 0
    assert snap["moe_assignments_elsewhere"] > 0
    # prompts and every generated token but each request's last, which
    # is never fed back, minus nothing: discarded run-ahead steps add
    assert snap["moe_assignments_held"] + snap["moe_assignments_elsewhere"] \
        >= (sum(map(len, asked)) + 7 * 11) * TOP_K * LAYERS
    assert 0 < snap["moe_experts_touched_per_step"] <= 8
    assert snap["prefix_hit_rate"] == 0.0
    assert eng.decode_compile_count == 1
    assert_conserved(eng)


def test_a_reused_slot_gives_what_a_fresh_engine_gives(model):
    first, second = prompts((23, 14), seed=3)
    used = make_engine(model, max_slots=1)
    used.submit(first, max_new_tokens=20)
    used.run()
    assert float(jnp.max(jnp.abs(used.cache.state))) > 0
    rid = used.submit(second, max_new_tokens=10)
    fresh = make_engine(model, max_slots=1)
    fid = fresh.submit(second, max_new_tokens=10)
    assert used.run()[rid].tokens == fresh.run()[fid].tokens
    assert used.metrics.recurrent_state_owner_mismatches == 0


def test_owner_mismatch_counts_a_step_on_another_requests_state(model):
    eng = make_engine(model, max_slots=1)
    eng.submit(prompts((6,))[0], max_new_tokens=6)
    eng.step()
    assert eng.metrics.recurrent_state_owner_mismatches == 0
    eng._state_owner[0] = -1
    eng.step()
    assert eng.metrics.recurrent_state_owner_mismatches >= 1


def test_every_expert_held_counts_nothing_elsewhere():
    from tests.models.test_qwen3_next import WHOLE

    cfg = tiny_config(WHOLE)
    eng = make_engine((cfg, seeded_params(cfg)), max_slots=2)
    rid = eng.submit(prompts((11,))[0], max_new_tokens=5)
    assert eng.run()[rid].outcome == "ok"
    snap = eng.metrics.snapshot()
    assert snap["moe_assignments_elsewhere"] == 0
    assert snap["moe_dropped_assignments"] == 0
    assert snap["moe_experts_held"] == 16
    assert snap["moe_assignments_held"] >= (11 + 4) * TOP_K * LAYERS


# ---- what is refused, by name --------------------------------------------------

def test_prefix_sharing_is_off_whatever_was_asked(model):
    eng = make_engine(model, prefix_cache=True)
    assert eng.radix is None
    shared = prompts((32,))[0]
    ids = [eng.submit(shared + [i], max_new_tokens=3) for i in range(3)]
    results = eng.run()
    assert all(results[i].outcome == "ok" for i in ids)
    assert not any(results[i].prefix_hit for i in ids)


@pytest.mark.parametrize("call", [
    lambda e: e.export_prefix_map(),
    lambda e: e.export_prefix_pages([1, 2]),
], ids=["export_prefix_map", "export_prefix_pages"])
def test_prefix_exchange_refuses_by_name(model, call):
    eng = make_engine(model)
    with pytest.raises(NotImplementedError,
                       match="snapshots of the recurrent state"):
        call(eng)


def test_the_disaggregated_engine_refuses_by_name(model):
    cfg, params = model
    with pytest.raises(NotImplementedError, match="recurrent state"):
        DisaggregatedEngine(params, cfg, disagg_split="1:1", max_slots=2,
                            max_seq=32, page_size=8)


def test_a_mesh_of_several_devices_refuses_by_name(model):
    from jax.sharding import Mesh

    cfg, params = model
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    with pytest.raises(NotImplementedError, match="several devices"):
        InferenceEngine(params, cfg, max_slots=2, max_seq=32, page_size=8,
                        mesh=mesh)


# ---- the normal path: scripts/serve.py -----------------------------------------

def test_the_published_preset_is_the_configuration_file_uncut():
    """``models/presets.py`` holds the published sizes; the benchmark's
    file differs from it in its three cuts and the two keys of the
    share, and in nothing else the program reads."""
    from scaletorch_tpu.models.presets import preset

    with open(os.path.join(REPO, "benchmarks", "configs",
                           "qwen3-next-80b-a3b-serve.json")) as f:
        config = json.load(f)
    published = preset("qwen3-next-80b-a3b")
    differs = {k for k, v in published.items() if config.get(k) != v}
    assert differs == {"num_hidden_layers", "num_experts", "vocab_size"}
    assert {k: published[k] for k in differs} == config["published"]
    assert "num_routed_experts" not in published


def _http(port, path, payload=None):
    data = None if payload is None else json.dumps(payload).encode()
    with urllib.request.urlopen(urllib.request.Request(
            f"http://127.0.0.1:{port}{path}", data=data), timeout=120) as r:
        return r.read().decode()


def test_served_from_the_command_line():
    """``scripts/serve.py --preset qwen3-next-tiny``: gateway ->
    EngineWorker -> InferenceEngine with the pool and the state in one
    cache and the routing accumulator beside them; a request gets its
    tokens and /metrics carries both families of counters."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "scripts", "serve.py"),
         "--preset", "qwen3-next-tiny",
         "--page_size", "8", "--max_slots", "2", "--max_seq", "64",
         "--prefill_len", "32", "--serve_port", "0"],
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
            "prompt": list(range(3, 20)), "max_new_tokens": 6,
            "stream": False})
        answer = json.loads(body)
        assert answer["outcome"] == "ok", body
        assert len(answer["token_ids"]) == 6, body
        metrics = _http(port, "/metrics")

        def value(name):
            rows = [l for l in metrics.splitlines() if name + "{" in l]
            assert rows, (name, metrics[-800:])
            return float(rows[0].split()[-1])

        assert value("engine_recurrent_state_resets") == 1
        assert value("engine_recurrent_state_owner_mismatches") == 0
        assert value("engine_moe_dropped_assignments") == 0
        assert value("engine_moe_experts_held") == 8
        held = value("engine_moe_assignments_held")
        elsewhere = value("engine_moe_assignments_elsewhere")
        assert held > 0 and elsewhere > 0
        assert held + elsewhere >= (17 + 5) * TOP_K * LAYERS
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
