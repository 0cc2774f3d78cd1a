"""granitemoehybrid (Granite 4.0-H) through the paged engine on the CPU at
a tiny size (m m a m m: 4 Mamba-2 layers of 4 heads x 16 on a state of 8
beside one attention layer, 4 of 8 routed experts held): the engine's own
jitted one-row prefill and decode steps against the plain reference's
full forward across page boundaries and with padded rows (the harness's
own comparison, through the two step signatures it calls), the tokens of
the plain forward request for request through slots the check left
full (reused and refilled),
the cache's bytes by field, the new counters, every refusal by name, and
the family served from ``scripts/serve.py``."""

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
from benchmarks.reference import granite_moe_hybrid as reference
from scaletorch_tpu.inference import InferenceEngine, SamplingParams
from scaletorch_tpu.inference.decode import SlotRows
from scaletorch_tpu.inference.disagg import DisaggregatedEngine
from scaletorch_tpu.inference.kv_cache import (
    HybridCache,
    cache_nbytes,
    recurrent_state_bytes,
)
from scaletorch_tpu.inference.routing_counters import CountedStep
from tests.inference.oracle import last_logits
from tests.inference.test_paged_engine import (
    assert_pages_conserved as assert_conserved,
)
from tests.models.test_granite_moe_hybrid import (
    RTOL_OF_MAX,
    TINY,
    ref_config,
    seeded_params,
    tiny_config,
)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
GREEDY = SamplingParams(temperature=0.0)
LAYERS, MAMBA_LAYERS, TOP_K, PAGE, CHUNK = 5, 4, 3, 8, 8


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
    ref = reference.make_logits_fn(ref_config(), q_block=8, expert_chunk=2)(
        params, jnp.asarray(tokens), jnp.asarray(rows))
    engine = make_engine(model)
    with jax.default_matmul_precision("highest"):
        errors = system_logit_errors(engine, tokens, lens, depth, ref)
    return engine, errors


def test_one_row_prefill_and_decode_match_the_full_forward(checked):
    engine, errors = checked
    assert errors["all_finite"]
    assert errors["max_abs_err"] / errors["max_abs_reference"] < RTOL_OF_MAX
    assert errors["prefill_max_abs_err"] > 0  # it did compare something
    assert isinstance(engine._decode, CountedStep)
    assert isinstance(engine._prefill._step, SlotRows)
    assert engine.prefill_shapes == ((1, 48),)
    assert engine.decode_compile_count == 1
    assert engine.prefill_compile_count == 1


def test_the_cache_is_a_pool_and_a_state_by_slot(checked):
    """``HybridCache`` in its third state shape: the page pool over the
    one attention layer by page, and by slot the float32 state ``[N, H
    P]`` (a head's matrix transposed, the channels on the lanes) and the
    convolution tail over x, B and C of the four Mamba-2 layers; the
    bytes by field are what the engine logs and the snapshot says."""
    engine, _ = checked
    cache = engine.cache
    assert isinstance(cache, HybridCache)
    assert cache.k.shape == cache.v.shape == (1, 3 * 12 + 1, 2, PAGE, 8)
    assert cache.state.shape == (MAMBA_LAYERS, 3, 8, 64)
    assert cache.state.dtype == jnp.float32
    assert cache.conv.shape == (MAMBA_LAYERS, 3, 3, 64 + 2 * 8)
    assert recurrent_state_bytes(cache) == (
        MAMBA_LAYERS * 3 * (8 * 64 * 4 + 3 * 80 * 4))
    assert cache_nbytes(cache) == (cache.k.nbytes + cache.v.nbytes
                                   + recurrent_state_bytes(cache))
    snap = engine.metrics.snapshot()
    assert snap["recurrent_state_bytes"] == recurrent_state_bytes(cache)
    assert not [k for k in snap if k.startswith(("window_", "latent_"))]


def test_the_check_s_steps_counted_states_chunks_keys_and_choices(checked):
    """Three one-row prefill calls of 10, 30 and 48 live rows and 12
    decode steps through the steps themselves (no tick): the routing
    accumulator counted every live (token, choice) of every layer once,
    on a held expert or on one held elsewhere, none dropped; the
    host-side counters of the tick are still 0."""
    engine, _ = checked
    snap = engine.metrics.snapshot()
    live = (10 + 30 + 48) + 12 * 3
    assert snap["moe_assignments_held"] + snap["moe_assignments_elsewhere"] \
        == live * TOP_K * LAYERS
    assert snap["moe_assignments_held"] == snap["moe_routed_assignments"] > 0
    assert snap["moe_assignments_elsewhere"] > 0
    assert snap["moe_dropped_assignments"] == 0
    assert snap["moe_experts_held"] == 4
    assert snap["ssd_state_slot_updates"] == snap["ssd_prefill_chunks"] == 0


# ---- tokens: the plain forward, request for request ---------------------------

def test_mixed_lengths_equal_the_oracle_through_reused_slots(model, checked):
    """Seven requests over the three slots THE CHECK LEFT FULL (state,
    tails and pages of three other sequences), prompts from 5 to 48
    tokens with 20 new tokens each: every admission is one row that
    names its slot, every request is admitted into a slot whose state,
    tail and pages another sequence left behind, beside slots in
    mid-decode. Each gets the tokens the plain forward (the recurrence
    row after row) gives it alone; the new counters count what the steps
    had to do."""
    cfg, params = model
    eng, _ = checked
    assert float(jnp.max(jnp.abs(eng.cache.state))) > 0
    before = eng.metrics.snapshot()
    asked = prompts((5, 30, 48, 9, 41, 12, 26))
    new = 20
    ids = [eng.submit(p, max_new_tokens=new) for p in asked]
    # the precision the check ran the same programs at: no second compile
    with jax.default_matmul_precision("highest"):
        results = eng.run()
    for p, rid in zip(asked, ids):
        assert results[rid].outcome == "ok"
        assert results[rid].tokens == greedy_by_forward(params, cfg, p, new)
    after = eng.metrics.snapshot()
    snap = {k: v - before.get(k, 0) for k, v in after.items()
            if isinstance(v, (int, float))}
    assert snap["prefill_calls"] == 7
    assert snap["prefill_positions_run"] == 7 * 48
    assert snap["prefill_positions_admitted"] == sum(map(len, asked))
    assert snap["recurrent_state_resets"] == 7
    assert after["recurrent_state_owner_mismatches"] == 0
    # a call's one row of 48 is six chunks of 8 in each Mamba-2 layer
    assert snap["ssd_prefill_chunks"] == 7 * (48 // CHUNK) * MAMBA_LAYERS
    # a slot-step of a live request advances one state a Mamba-2 layer,
    # and attends p + 1 keys in the attention layer
    fed = [(len(p) + t) for p in asked for t in range(new - 1)]
    assert snap["ssd_state_slot_updates"] >= len(fed) * MAMBA_LAYERS
    assert snap["ssd_state_slot_updates"] % MAMBA_LAYERS == 0
    assert snap["full_keys_attended"] >= sum(n + 1 for n in fed)
    assert snap["moe_dropped_assignments"] == 0
    assert snap["moe_assignments_held"] + snap["moe_assignments_elsewhere"] \
        >= (sum(map(len, asked)) + 7 * (new - 1)) * TOP_K * LAYERS
    assert after["prefix_hit_rate"] == 0.0
    # (the check's own calls, device arrays where a tick hands host
    # arrays, are the other entry of the one program)
    assert eng.decode_compile_count <= 2
    assert eng.prefill_compile_count == 1
    assert_conserved(eng)


def test_a_model_without_such_layers_has_none_of_the_counters():
    from scaletorch_tpu.inference.engine import EngineMetrics

    assert not [k for k in EngineMetrics().snapshot()
                if k.startswith(("ssd_", "recurrent_", "full_keys"))]
    assert "ssd_prefill_chunks" in EngineMetrics(ssd_layers=4).snapshot()


def test_a_quarantine_clear_fills_the_pool_and_the_state(model):
    """The masked fill over the third state shape: the named slot's
    state and tail and the named pages are filled, every other byte
    passes through."""
    eng = make_engine(model, max_slots=2)
    rng = np.random.default_rng(1)
    cache = eng.cache._replace(**{
        name: jnp.asarray(rng.normal(size=getattr(eng.cache, name).shape),
                          jnp.float32)
        for name in ("k", "v", "state", "conv")})
    pages = np.zeros(cache.k.shape[1], bool)
    pages[[3, 4]] = True
    new = eng._fill_slots(cache, jnp.asarray(pages), 0.0,
                          jnp.asarray([False, True]))
    assert isinstance(new, HybridCache)
    assert not np.asarray(new.k[:, 3:5]).any()
    np.testing.assert_array_equal(np.asarray(new.v[:, 5:]),
                                  np.asarray(cache.v[:, 5:]))
    assert not np.asarray(new.state[:, 1]).any()
    assert not np.asarray(new.conv[:, 1]).any()
    np.testing.assert_array_equal(np.asarray(new.state[:, 0]),
                                  np.asarray(cache.state[:, 0]))


# ---- what is refused, by name --------------------------------------------------

def test_prefix_sharing_is_off_whatever_was_asked(model):
    eng = make_engine(model, prefix_cache=True)
    assert eng.radix is None


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
        assert "granite" not in source, module.__name__


# ---- the normal path: scripts/serve.py -----------------------------------------

def test_the_published_preset_is_the_configuration_file_uncut():
    """``models/presets.py`` holds the published sizes; the benchmark's
    file differs from it in its cuts, and in nothing else the program
    reads."""
    from scaletorch_tpu.models.presets import preset

    with open(os.path.join(REPO, "benchmarks", "configs",
                           "granite-4.0-h-small-serve.json")) as f:
        config = json.load(f)
    published = preset("granite-4.0-h-small")
    differs = {k for k, v in published.items() if config.get(k) != v}
    # the preset names no layer_types: the published period repeated
    assert differs | {"layer_types"} == set(config["reduced"]) == {
        "num_hidden_layers", "layer_types", "num_local_experts",
        "vocab_size"}
    assert {k: published[k] for k in differs} == {
        k: config["published"][k] for k in differs}
    assert "num_routed_experts" not in published
    assert config["num_routed_experts"] == published["num_local_experts"]
    assert config["layer_types"] == config["published"]["layer_types"][:10]
    assert tiny_config(published).layer_kinds == tuple(
        config["published"]["layer_types"])


def _http(port, path, payload=None):
    data = None if payload is None else json.dumps(payload).encode()
    with urllib.request.urlopen(urllib.request.Request(
            f"http://127.0.0.1:{port}{path}", data=data), timeout=120) as r:
        return r.read().decode()


def test_served_from_the_command_line():
    """``scripts/serve.py --preset granite-moe-hybrid-tiny``: gateway ->
    EngineWorker -> InferenceEngine with the pool and the Mamba-2 state
    in one cache and the routing accumulator beside it; a request gets
    its tokens and /metrics carries the counters of all three."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "scripts", "serve.py"),
         "--preset", "granite-moe-hybrid-tiny",
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

        # 4 layers x 2 slots x ([8, 64] float32 + 3 x 80 bfloat16)
        assert value("engine_recurrent_state_bytes") == 4 * 2 * (
            8 * 64 * 4 + 3 * 80 * 2)
        assert value("engine_recurrent_state_owner_mismatches") == 0
        # five decode steps at positions 40 .. 44, one live slot
        assert value("engine_ssd_state_slot_updates") == 5 * MAMBA_LAYERS
        assert value("engine_ssd_prefill_chunks") == 6 * MAMBA_LAYERS
        assert value("engine_full_keys_attended") == sum(range(41, 46))
        assert value("engine_prefill_positions_admitted") == 40
        assert value("engine_moe_dropped_assignments") == 0
        assert value("engine_moe_experts_held") == 4
        held = value("engine_moe_assignments_held")
        elsewhere = value("engine_moe_assignments_elsewhere")
        assert held > 0 and elsewhere > 0
        assert held + elsewhere >= (40 + 5) * TOP_K * LAYERS
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
