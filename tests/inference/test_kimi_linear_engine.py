"""kimi_linear (Kimi-Linear) through the paged engine on the CPU at a
tiny size (8 KDA layers of 2 heads x 16 beside 3 latent layers of 4
heads over a 32 + 8 row stored 128 wide, 4 of 16 routed experts held):
the engine's own jitted one-row prefill and decode steps against the
plain reference's full forward across page boundaries and with padded
rows (the harness's own comparison, through the two step signatures it
calls), the tokens of the plain forward request for request through
reused slots, the cache's bytes by field, the counters, every refusal
by name, and the family served from ``scripts/serve.py``."""

import inspect
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
from benchmarks.reference import kimi_linear as reference
from scaletorch_tpu.inference import InferenceEngine, SamplingParams
from scaletorch_tpu.inference.decode import SlotRows
from scaletorch_tpu.inference.disagg import DisaggregatedEngine
from scaletorch_tpu.inference.kv_cache import (
    HybridCache,
    cache_nbytes,
    latent_cache_bytes,
    recurrent_state_bytes,
)
from scaletorch_tpu.inference.routing_counters import CountedStep
from tests.inference.oracle import last_logits
from tests.inference.test_paged_engine import (
    assert_pages_conserved as assert_conserved,
)
from tests.models.test_kimi_linear import (
    RTOL_OF_MAX,
    TINY,
    WHOLE,
    WRONG,
    ref_config,
    seeded_params,
    tiny_config,
)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
GREEDY = SamplingParams(temperature=0.0)
SPARSE_LAYERS, KDA_LAYERS, LATENT_LAYERS, TOP_K, PAGE = 10, 8, 3, 3, 8


@pytest.fixture(scope="module")
def model():
    cfg = tiny_config()
    return cfg, seeded_params(cfg)


def make_engine(model, **kw):
    cfg, params = model
    kw.setdefault("max_slots", 3)
    kw.setdefault("max_seq", 96)
    kw.setdefault("prefill_len", 48)
    kw.setdefault("sampling", GREEDY)
    kw.setdefault("page_size", PAGE)
    kw.setdefault("strict_submit", False)
    return InferenceEngine(params, cfg, **kw)


def prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(0, 128, size=n)] for n in lengths]


def greedy_by_forward(params, cfg, prompt, n):
    """``oracle.greedy_by_forward`` on one buffer width for every length
    here (one compile)."""
    seq = list(prompt)
    for _ in range(n):
        seq.append(int(np.argmax(last_logits(params, cfg, seq, pad_to=96))))
    return seq[len(prompt):]


# ---- logits: the harness's own comparison ------------------------------------

@pytest.fixture(scope="module")
def checked(model):
    """Prompts of 10, 30 and 48 tokens (padded rows beside a full one)
    plus 12 decode positions through the engine's paged steps
    (``serve_cell.system_logit_errors`` calls ``engine._prefill`` with
    the eight operands every family's step takes: the one-row program
    runs once a written row), and the reference's logits at the same
    rows, the reference given the same share."""
    cfg, params = model
    depth = 12
    lens = np.array([10, 30, 48])
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(5), (3, 64), 0, 128))
    rows = (lens[:, None] - 1 + np.arange(depth + 1)[None, :]).astype(
        np.int32)

    def logits(wrong=None):
        return reference.make_logits_fn(
            ref_config(), q_block=8, expert_chunk=2, wrong=wrong)(
                params, jnp.asarray(tokens), jnp.asarray(rows))

    engine = make_engine(model)
    ref = logits()
    with jax.default_matmul_precision("highest"):
        errors = system_logit_errors(engine, tokens, lens, depth, ref)
    return engine, errors, ref, logits


def test_one_row_prefill_and_decode_match_the_full_forward(checked):
    engine, errors, _, _ = checked
    assert errors["all_finite"]
    assert errors["max_abs_err"] / errors["max_abs_reference"] < RTOL_OF_MAX
    assert errors["prefill_max_abs_err"] > 0  # it did compare something
    assert isinstance(engine._decode, CountedStep)
    assert engine.prefill_shapes == ((1, 48),)
    assert engine.decode_compile_count == 1
    assert engine.prefill_compile_count == 1


def test_the_cache_is_one_tuple_of_a_latent_pool_and_a_state_by_slot(checked):
    """``HybridCache`` without a ``v``: the latent pool over the three
    latent layers by page, the float32 state and the convolution tail
    of the eight KDA layers by slot; the bytes by field are what the
    engine logs and the snapshot says."""
    engine, _, _, _ = checked
    cache = engine.cache
    assert isinstance(cache, HybridCache) and cache.v is None
    assert cache.k.shape == (LATENT_LAYERS, 3 * 12 + 1, 1, PAGE, 128)
    assert cache.state.shape == (KDA_LAYERS, 3, 2, 16, 16)
    assert cache.state.dtype == jnp.float32
    assert cache.conv.shape == (KDA_LAYERS, 3, 3, 96)
    assert latent_cache_bytes(cache) == cache.k.nbytes == 3 * 37 * 8 * 128 * 4
    assert recurrent_state_bytes(cache) == (
        KDA_LAYERS * 3 * (2 * 16 * 16 * 4 + 3 * 96 * 4))
    assert cache_nbytes(cache) == (latent_cache_bytes(cache)
                                   + recurrent_state_bytes(cache))
    snap = engine.metrics.snapshot()
    assert snap["latent_cache_bytes"] == latent_cache_bytes(cache)
    assert snap["recurrent_state_bytes"] == recurrent_state_bytes(cache)
    assert not [k for k in snap if k.startswith("window_")]


def test_the_two_step_signatures_are_the_harness_s(model):
    """``engine._prefill(params, tokens, tail_lens, starts, write_mask,
    tables, cache, keys)`` (``SlotRows``: the one-row program once a
    written row) and ``engine._decode(params, feed, positions, active,
    tables, cache, keys)``: what the benchmark's check calls, with one
    page table and the whole cache as one operand."""
    engine = make_engine(model)
    cache = engine.cache
    slots, pps = engine.max_slots, engine._pages_per_slot
    tables = jnp.asarray(
        (np.arange(slots * pps, dtype=np.int32) + 1).reshape(slots, pps))
    keys = jnp.zeros((slots, 2), jnp.uint32)
    ones = jnp.ones(slots, bool)
    assert isinstance(engine._prefill._step, SlotRows)
    out = engine._prefill(
        engine.params, jnp.zeros((slots, engine.prefill_len), jnp.int32),
        jnp.full((slots,), 5, jnp.int32), jnp.zeros(slots, jnp.int32), ones,
        tables, cache, keys)
    assert len(out) == 4 and isinstance(out[3], HybridCache)
    assert out[3].v is None
    out = engine._decode(
        engine.params, jnp.zeros(slots, jnp.int32),
        jnp.full((slots,), 5, jnp.int32), ones, tables, out[3], keys)
    assert len(out) == 4 and isinstance(out[3], HybridCache)
    assert out[1].shape == (slots, TINY["vocab_size"])
    assert engine.prefill_compile_count == 1


@pytest.mark.parametrize("variant", WRONG)
def test_the_engine_check_rejects_each_wrong_variant(checked, variant):
    _, errors, ref, logits = checked
    off = float(jnp.max(jnp.abs(logits(variant) - ref)))
    assert not np.isfinite(off) or \
        off / errors["max_abs_reference"] > 50 * RTOL_OF_MAX


def test_the_check_s_steps_counted_held_and_absent_choices(checked):
    """Three one-row prefill calls of 10, 30 and 48 live rows and 12
    decode steps of 3 live slots: every live (token, choice) of every
    SPARSE layer is counted once, on a held expert or on one held
    elsewhere; none is dropped."""
    engine, _, _, _ = checked
    snap = engine.metrics.snapshot()
    live = (10 + 30 + 48) + 12 * 3
    assert snap["moe_assignments_held"] + snap["moe_assignments_elsewhere"] \
        == live * TOP_K * SPARSE_LAYERS
    assert snap["moe_assignments_held"] == snap["moe_routed_assignments"] > 0
    assert snap["moe_assignments_elsewhere"] > snap["moe_assignments_held"]
    assert snap["moe_dropped_assignments"] == 0
    assert snap["moe_experts_held"] == 4


# ---- tokens: the plain forward, request for request ---------------------------

def test_mixed_lengths_equal_the_oracle_through_reused_slots(model):
    """Seven requests over three slots, prompts from 5 to 48 tokens with
    20 new tokens each: every admission is one row that names its slot,
    every later request is admitted into a slot whose state, tail and
    pages another request left full, beside slots in mid-decode. Each
    gets the tokens the plain forward (the recurrence row after row)
    gives it alone."""
    cfg, params = model
    eng = make_engine(model)
    asked = prompts((5, 30, 48, 9, 41, 12, 26))
    new = 20
    ids = [eng.submit(p, max_new_tokens=new) for p in asked]
    results = eng.run()
    for p, rid in zip(asked, ids):
        assert results[rid].outcome == "ok"
        assert results[rid].tokens == greedy_by_forward(params, cfg, p, new)
    snap = eng.metrics.snapshot()
    assert snap["prefill_calls"] == 7
    assert snap["prefill_positions_run"] == 7 * 48
    # of the rows the seven calls ran, the prompts' own tokens
    assert snap["prefill_positions_admitted"] == sum(map(len, asked))
    assert snap["recurrent_state_resets"] == 7
    assert snap["recurrent_state_owner_mismatches"] == 0
    fed = [(len(p) + t) for p in asked for t in range(new - 1)]
    assert snap["latent_keys_attended"] >= sum(n + 1 for n in fed)
    assert snap["moe_dropped_assignments"] == 0
    assert snap["moe_assignments_elsewhere"] > 0
    assert snap["moe_assignments_held"] + snap["moe_assignments_elsewhere"] \
        >= (sum(map(len, asked)) + 7 * (new - 1)) * TOP_K * SPARSE_LAYERS
    assert snap["prefix_hit_rate"] == 0.0
    assert eng.decode_compile_count == 1
    assert eng.prefill_compile_count == 1
    assert_conserved(eng)


def test_a_slot_reused_by_a_shorter_request_gives_what_a_fresh_engine_gives(
        model):
    first, second = prompts((47, 9), seed=3)
    used = make_engine(model, max_slots=1)
    used.submit(first, max_new_tokens=20)
    used.run()
    assert float(jnp.max(jnp.abs(used.cache.k))) > 0
    assert float(jnp.max(jnp.abs(used.cache.state))) > 0
    rid = used.submit(second, max_new_tokens=30)
    fresh = make_engine(model, max_slots=1)
    fid = fresh.submit(second, max_new_tokens=30)
    assert used.run()[rid].tokens == fresh.run()[fid].tokens


def test_admission_counts_one_row_a_token(model):
    eng = make_engine(model, max_slots=2)
    free = eng.allocator.free_count
    eng.submit(prompts((40,))[0], max_new_tokens=8)
    eng.step()
    assert free - eng.allocator.free_count == -(-(40 + 8) // PAGE)


def test_every_expert_held_counts_nothing_elsewhere():
    cfg = tiny_config(WHOLE)
    eng = make_engine((cfg, seeded_params(cfg)), max_slots=2)
    rid = eng.submit(prompts((11,))[0], max_new_tokens=5)
    assert eng.run()[rid].outcome == "ok"
    snap = eng.metrics.snapshot()
    assert snap["moe_assignments_elsewhere"] == 0
    assert snap["moe_dropped_assignments"] == 0
    assert snap["moe_experts_held"] == 16
    assert snap["moe_assignments_held"] >= (11 + 4) * TOP_K * SPARSE_LAYERS


def test_a_model_without_such_layers_has_none_of_the_counters():
    from scaletorch_tpu.models import llama

    cfg = llama.LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=2,
        dtype=jnp.float32)
    eng = InferenceEngine(
        llama.init_params(jax.random.PRNGKey(0), cfg), cfg, max_slots=2,
        max_seq=32, page_size=8, sampling=GREEDY)
    assert not [k for k in eng.metrics.snapshot()
                if k.startswith(("latent_", "kda_", "recurrent_"))]


def test_a_quarantine_clear_fills_the_pool_and_the_state_and_skips_no_v(
        model):
    """The masked fill over a cache whose ``v`` is absent: the named
    slot's state and tail and the named pages are filled, every other
    byte passes through, the absent field stays absent."""
    eng = make_engine(model, max_slots=2)
    rng = np.random.default_rng(1)
    cache = eng.cache._replace(
        k=jnp.asarray(rng.normal(size=eng.cache.k.shape), jnp.float32),
        state=jnp.asarray(rng.normal(size=eng.cache.state.shape),
                          jnp.float32),
        conv=jnp.asarray(rng.normal(size=eng.cache.conv.shape), jnp.float32))
    pages = np.zeros(cache.k.shape[1], bool)
    pages[[3, 4]] = True
    new = eng._fill_slots(cache, jnp.asarray(pages), 0.0,
                          jnp.asarray([False, True]))
    assert isinstance(new, HybridCache) and new.v is None
    assert not np.asarray(new.k[:, 3:5]).any()
    np.testing.assert_array_equal(np.asarray(new.k[:, 5:]),
                                  np.asarray(cache.k[:, 5:]))
    assert not np.asarray(new.state[:, 1]).any()
    assert not np.asarray(new.conv[:, 1]).any()
    np.testing.assert_array_equal(np.asarray(new.state[:, 0]),
                                  np.asarray(cache.state[:, 0]))


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


def test_the_engine_learned_no_family_s_name():
    """PR 50's table is how a family is asked: the engine reads the
    cache it was handed and the config's own properties, and names
    neither the family nor its config class."""
    from scaletorch_tpu.inference import decode, engine

    for module in (engine, decode):
        source = inspect.getsource(module).lower()
        assert "kimi" not in source, module.__name__


# ---- the normal path: scripts/serve.py -----------------------------------------

def test_the_published_preset_is_the_configuration_file_uncut():
    """``models/presets.py`` holds the published sizes; the benchmark's
    file differs from it in its cuts, and in nothing else the program
    reads."""
    from scaletorch_tpu.models.presets import preset

    with open(os.path.join(REPO, "benchmarks", "configs",
                           "kimi-linear-48b-a3b-serve.json")) as f:
        config = json.load(f)
    published = preset("kimi-linear-48b-a3b")
    differs = {k for k, v in published.items() if config.get(k) != v}
    assert differs == set(config["reduced"]) == {
        "num_hidden_layers", "linear_attn_config", "num_experts",
        "vocab_size"}
    assert {k: published[k] for k in differs} == {
        k: config["published"][k] for k in differs}
    assert "num_routed_experts" not in published
    assert config["num_routed_experts"] == published["num_experts"]
    # the cut keeps the first eight entries of the published lists
    cut, whole = config["linear_attn_config"], published["linear_attn_config"]
    for key in ("kda_layers", "full_attn_layers"):
        assert cut[key] == [i for i in whole[key] if i <= 8]
    assert {k: cut[k] for k in ("head_dim", "num_heads",
                                "short_conv_kernel_size")} == {
        k: whole[k] for k in ("head_dim", "num_heads",
                              "short_conv_kernel_size")}


def _http(port, path, payload=None):
    data = None if payload is None else json.dumps(payload).encode()
    with urllib.request.urlopen(urllib.request.Request(
            f"http://127.0.0.1:{port}{path}", data=data), timeout=120) as r:
        return r.read().decode()


def test_served_from_the_command_line():
    """``scripts/serve.py --preset kimi-linear-tiny``: gateway ->
    EngineWorker -> InferenceEngine with the latent pool and the state
    in one cache and the routing accumulator beside it; a request gets
    its tokens and /metrics carries the counters of all three."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "scripts", "serve.py"),
         "--preset", "kimi-linear-tiny",
         "--page_size", "8", "--max_slots", "2", "--max_seq", "64",
         "--prefill_len", "48", "--serve_port", "0"],
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
            "prompt": list(range(3, 43)), "max_new_tokens": 6,
            "stream": False})
        answer = json.loads(body)
        assert answer["outcome"] == "ok", body
        assert len(answer["token_ids"]) == 6, body
        metrics = _http(port, "/metrics")

        def value(name):
            rows = [l for l in metrics.splitlines() if name + "{" in l]
            assert rows, (name, metrics[-800:])
            return float(rows[0].split()[-1])

        # 3 layers x (2 slots x 8 pages + 1) x 8 rows x 128 wide, bfloat16
        assert value("engine_latent_cache_bytes") == 3 * 17 * 8 * 128 * 2
        # 8 layers x 2 slots x (2 x 16 x 16 float32 + 3 x 96 bfloat16)
        assert value("engine_recurrent_state_bytes") == 8 * 2 * (
            2 * 16 * 16 * 4 + 3 * 96 * 2)
        assert value("engine_recurrent_state_owner_mismatches") == 0
        # five decode steps at positions 40 .. 44
        assert value("engine_latent_keys_attended") >= sum(range(41, 46))
        assert value("engine_prefill_positions_admitted") == 40
        assert value("engine_moe_dropped_assignments") == 0
        assert value("engine_moe_experts_held") == 4
        held = value("engine_moe_assignments_held")
        elsewhere = value("engine_moe_assignments_elsewhere")
        assert held > 0 and elsewhere > 0
        assert held + elsewhere >= (40 + 5) * TOP_K * SPARSE_LAYERS
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
