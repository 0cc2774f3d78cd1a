"""mimo_v2_flash (MiMo-V2-Flash) through the paged engine on the CPU at
a tiny size (a window of 20, a ring of 4 pages of 8 a slot and window
layer, keys 24 wide stored at 128 beside values of 16, 2 and 4 K/V
heads, 4 of 16 routed experts held): the engine's own jitted one-row
prefill (a row NAMES its slot) and decode steps against the plain
reference's full forward, prompts shorter and longer than the ring and a
decode that wraps it (the harness's own comparison, through the two step
signatures it calls), the tokens of the plain forward request for
request through reused slots, the cache's bytes by field, the window's
counters with the pages an admission trashes, every refusal by name,
and the family served from ``scripts/serve.py``."""

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
from benchmarks.reference import mimo_v2_flash as reference
from scaletorch_tpu.inference import InferenceEngine, SamplingParams
from scaletorch_tpu.inference.decode import SlotRows
from scaletorch_tpu.inference.disagg import DisaggregatedEngine
from scaletorch_tpu.inference.kv_cache import (
    WindowCache,
    kv_cache_bytes,
    window_cache_bytes,
    window_ring_pages,
)
from scaletorch_tpu.inference.routing_counters import CountedStep
from scaletorch_tpu.models import mimo_v2_flash as mimo
from tests.inference.oracle import last_logits
from tests.inference.test_paged_engine import (
    assert_pages_conserved as assert_conserved,
)
from tests.models.test_mimo_v2_flash import (
    RTOL_OF_MAX,
    TINY,
    WRONG,
    ref_config,
    tiny_config,
)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
GREEDY = SamplingParams(temperature=0.0)
SPARSE_LAYERS, WINDOW_LAYERS, TOP_K, WINDOW, PAGE = 6, 5, 3, 20, 8
RING_PAGES = window_ring_pages(WINDOW, PAGE)                 # 4
RING_TOKENS = PAGE * RING_PAGES                              # 32


def seeded_params(cfg, seed=3):
    return jax.jit(mimo.init_params, static_argnums=1)(
        jax.random.PRNGKey(seed), cfg)


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
    """Prompts of 10, 30 and 48 tokens (under the window, past it, past
    the ring) plus 24 decode positions (the 10-token slot passes the
    window's edge, the 30-token one wraps its rings at 32, the 48-token
    one at 64) through the engine's paged steps
    (``serve_cell.system_logit_errors`` hands ``engine._prefill`` the
    full ``[slots, prefill_len]`` buffer with row b = slot b, which the
    family's ``SlotRows`` runs as one one-row call a prompt), and the
    reference's logits at the same rows, the reference given the same
    share."""
    cfg, params = model
    depth = 24
    lens = np.array([10, 30, 48])
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(5), (3, 80), 0, 128))
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


def test_paged_prefill_and_decode_match_the_full_forward(checked):
    engine, errors, _, _ = checked
    assert errors["all_finite"]
    assert errors["max_abs_err"] / errors["max_abs_reference"] < RTOL_OF_MAX
    assert errors["prefill_max_abs_err"] > 0  # it did compare something
    assert isinstance(engine._decode, CountedStep)
    assert engine.prefill_shapes == ((1, 48),)      # ONE row: it names its slot
    assert engine.decode_compile_count == 1
    assert engine.prefill_compile_count == 1


def test_the_cache_s_bytes_by_field(checked):
    """Two shapes of K/V in one cache: the pool on the full layers' 2
    K/V heads, the rings on the window layers' 4, a key stored at 128
    (24 and 104 zeros at this size) beside a value of 16."""
    engine, _, _, _ = checked
    cache, cfg = engine.cache, engine.cfg
    assert isinstance(cache, WindowCache)
    pages = 3 * 12 + 1
    assert cache.k.shape == (2, pages, 2, PAGE, 128)
    assert cache.v.shape == (2, pages, 2, PAGE, 16)
    assert cache.wk.shape == (5, 1 + 3 * RING_PAGES, 4, PAGE, 128)
    assert cache.wv.shape == (5, 1 + 3 * RING_PAGES, 4, PAGE, 16)
    assert kv_cache_bytes(cfg, pages, PAGE) == cache.k.nbytes + cache.v.nbytes
    assert engine.metrics.snapshot()["window_cache_bytes"] == (
        window_cache_bytes(cache)) == cache.wk.nbytes + cache.wv.nbytes


def test_the_two_step_signatures_are_the_harness_s(model):
    """``engine._prefill(params, tokens, tail_lens, starts, write_mask,
    tables, cache, keys)`` with the full buffer, and the same with
    ``slot_ids`` after the keys (an admission's one row);
    ``engine._decode(params, feed, positions, active, tables, cache,
    keys)``: the whole cache is one operand either way."""
    engine = make_engine(model)
    assert isinstance(engine._prefill._step, SlotRows)
    slots, pps = engine.max_slots, engine._pages_per_slot
    tables = jnp.asarray(
        (np.arange(slots * pps, dtype=np.int32) + 1).reshape(slots, pps))
    keys = jnp.zeros((slots, 2), jnp.uint32)
    ones = jnp.ones(slots, bool)
    out = engine._prefill(
        engine.params, jnp.zeros((slots, engine.prefill_len), jnp.int32),
        jnp.full((slots,), 5, jnp.int32), jnp.zeros(slots, jnp.int32), ones,
        tables, engine.cache, keys)
    assert len(out) == 4 and isinstance(out[3], WindowCache)
    one = engine._prefill(
        engine.params, jnp.zeros((1, engine.prefill_len), jnp.int32),
        jnp.full((1,), 5, jnp.int32), jnp.zeros(1, jnp.int32), ones[:1],
        tables[2:3], out[3], keys[:1], jnp.asarray([2], jnp.int32))
    assert len(one) == 4 and one[1].shape == (1, TINY["vocab_size"])
    # the named row wrote slot 2's rings as the full buffer's row 2 did
    np.testing.assert_array_equal(np.asarray(one[3].wk), np.asarray(out[3].wk))
    out = engine._decode(
        engine.params, jnp.zeros(slots, jnp.int32),
        jnp.full((slots,), 5, jnp.int32), ones, tables, one[3], keys)
    assert len(out) == 4 and isinstance(out[3], WindowCache)
    assert out[1].shape == (slots, TINY["vocab_size"])


# the departures a cache could hide (the others differ from these in
# the reference alone; tests/models/test_mimo_v2_flash.py rejects all
# eleven against the same forward)
@pytest.mark.parametrize("variant", [
    "no_sink", "window_off_by_one", "kv_heads_swapped", "one_rope_theta",
    "fp8_layers"])
def test_the_engine_check_rejects_each_wrong_variant(checked, variant):
    assert variant in WRONG
    _, errors, ref, logits = checked
    off = float(jnp.max(jnp.abs(logits(variant) - ref)))
    assert off / errors["max_abs_reference"] > 50 * RTOL_OF_MAX, variant


def test_the_check_s_steps_counted_held_and_absent_choices(checked):
    """Three one-row prefill calls of 10, 30 and 48 live rows and 24
    decode steps of 3 live slots: every live (token, choice) of every
    SPARSE layer is counted once, on a held expert or on one held
    elsewhere; none is dropped."""
    engine, _, _, _ = checked
    snap = engine.metrics.snapshot()
    live = (10 + 30 + 48) + 24 * 3
    assert snap["moe_assignments_held"] + snap["moe_assignments_elsewhere"] \
        == live * TOP_K * SPARSE_LAYERS
    assert snap["moe_assignments_held"] == snap["moe_routed_assignments"] > 0
    assert snap["moe_assignments_elsewhere"] > 0
    assert snap["moe_dropped_assignments"] == 0
    assert snap["moe_experts_held"] == 4


# ---- tokens: the plain forward, request for request ---------------------------

def test_mixed_lengths_equal_the_oracle_through_reused_slots(model):
    """Seven requests over three slots, prompts from 5 to 48 tokens with
    20 new tokens each: positions pass the window's edge and wrap the
    ring, every later request is admitted by a one-row call into a slot
    whose rings another request left full, beside slots in mid-decode
    whose rings that call must not touch. Each gets the tokens the plain
    forward gives it alone."""
    cfg, params = model
    eng = make_engine(model, prefix_cache=True)   # asked for, and off
    assert eng.radix is None
    asked = prompts((5, 30, 48, 9, 41, 12, 26))
    new = 20
    ids = [eng.submit(p, max_new_tokens=new) for p in asked]
    results = eng.run()
    for p, rid in zip(asked, ids):
        assert results[rid].outcome == "ok"
        assert results[rid].tokens == greedy_by_forward(params, cfg, p, new)
    snap = eng.metrics.snapshot()
    assert snap["window_slot_reuse_mismatches"] == 0
    # a request wraps its slot's ring when a write position passes a
    # multiple of 32: in its prompt (48, 41) or while it decodes (30 and
    # 26 at 32, 48 again at 64; 12 + 18 = 30 does not)
    assert snap["window_ring_wraps"] == 5
    # a prompt's pages past the ring's 4 go to TRASH in every window
    # layer: 48 tokens are 6 pages, 41 also 6, the others 4 or fewer
    assert snap["window_pages_trashed"] == WINDOW_LAYERS * (2 + 2)
    # ONE row a call, an admitted prompt a call
    assert snap["prefill_calls"] == 7
    assert snap["prefill_positions_run"] == 7 * 48
    steps = snap["window_keys_attended"], snap["full_keys_attended"]
    assert 0 < steps[0] < steps[1]
    fed = [(len(p) + t) for p in asked for t in range(new - 1)]
    assert steps[1] >= sum(n + 1 for n in fed)
    assert steps[0] >= sum(min(n + 1, WINDOW) for n in fed)
    assert snap["moe_dropped_assignments"] == 0
    assert snap["moe_assignments_elsewhere"] > 0
    assert snap["moe_assignments_held"] + snap["moe_assignments_elsewhere"] \
        >= (sum(map(len, asked)) + 7 * (new - 1)) * TOP_K * SPARSE_LAYERS
    assert snap["prefix_hit_rate"] == 0.0
    assert not any(results[rid].prefix_hit for rid in ids)
    assert eng.decode_compile_count == 1
    assert eng.prefill_compile_count == 1
    assert_conserved(eng)


def test_an_admission_counts_pool_pages_and_a_stranger_s_ring_is_counted(
        model):
    """A request's pages are those of the page pool (the full layers'):
    the rings are by slot and cost an admission nothing. A decode step
    on a slot whose rings another request started is counted."""
    eng = make_engine(model, max_slots=1)
    free = eng.allocator.free_count
    eng.submit(prompts((40,))[0], max_new_tokens=8)
    eng.step()
    assert free - eng.allocator.free_count == -(-(40 + 8) // PAGE)
    assert eng.metrics.window_slot_reuse_mismatches == 0
    eng._state_owner[0] = -1
    eng.step()
    assert eng.metrics.window_slot_reuse_mismatches >= 1
    assert "recurrent_state_owner_mismatches" not in eng.metrics.snapshot()


# ---- what is refused, by name --------------------------------------------------

@pytest.mark.parametrize("call", [
    lambda e: e.export_prefix_map(),
    lambda e: e.export_prefix_pages([1, 2]),
], ids=["export_prefix_map", "export_prefix_pages"])
def test_prefix_exchange_refuses_by_name(model, call):
    eng = make_engine(model)
    with pytest.raises(NotImplementedError,
                       match="window-attention layers"):
        call(eng)


def test_the_disaggregated_engine_refuses_by_name(model):
    cfg, params = model
    with pytest.raises(NotImplementedError, match="window-attention"):
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
    file differs from it in its five cuts and the two keys of the share,
    and in nothing else the program reads."""
    from scaletorch_tpu.models.presets import preset

    with open(os.path.join(REPO, "benchmarks", "configs",
                           "mimo-v2-flash-serve.json")) as f:
        config = json.load(f)
    published = preset("mimo-v2-flash")
    differs = {k for k, v in published.items() if config.get(k) != v}
    assert differs == {"num_hidden_layers", "hybrid_layer_pattern",
                       "moe_layer_freq", "n_routed_experts", "vocab_size"}
    assert differs == set(config["reduced"])
    assert {k: published[k] for k in differs} == {
        k: config["published"][k] for k in differs}
    assert "num_routed_experts" not in published
    assert config["hybrid_layer_pattern"] == published[
        "hybrid_layer_pattern"][:7]
    assert config["moe_layer_freq"] == published["moe_layer_freq"][:7]


def _http(port, path, payload=None):
    data = None if payload is None else json.dumps(payload).encode()
    with urllib.request.urlopen(urllib.request.Request(
            f"http://127.0.0.1:{port}{path}", data=data), timeout=120) as r:
        return r.read().decode()


def test_served_from_the_command_line():
    """``scripts/serve.py --preset mimo-v2-flash-tiny``: gateway ->
    EngineWorker -> InferenceEngine with the pool and the rings of two
    shapes in one cache and the routing accumulator beside them; a
    request past the window and the ring gets its tokens and /metrics
    carries the window's counters and the share's."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "scripts", "serve.py"),
         "--preset", "mimo-v2-flash-tiny",
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

        assert value("engine_window_ring_wraps") == 1     # 40 > 32
        assert value("engine_window_pages_trashed") == WINDOW_LAYERS
        assert value("engine_window_slot_reuse_mismatches") == 0
        assert value("engine_window_cache_bytes") > 0
        assert 0 < value("engine_window_keys_attended") \
            < value("engine_full_keys_attended")
        assert value("engine_prefill_positions_run") == 48
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
