"""Olmo-Hybrid through the paged engine on the CPU at a tiny size: the
engine's own jitted prefill and decode steps against the plain
reference's full forward (logits, the harness's own comparison), the
tokens of the plain forward request for request, what the run-ahead
loop, a reused slot, a quarantine and a late eos may do to a per-slot
recurrent state, the refusals by name, and the family served from
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
from benchmarks.reference import olmo_hybrid as reference
from scaletorch_tpu.inference import (
    InferenceEngine,
    SamplingParams,
    ServingFaultInjector,
)
from scaletorch_tpu.inference.disagg import DisaggregatedEngine
from scaletorch_tpu.inference.kv_cache import HybridCache
from tests.inference.oracle import greedy_by_forward
from tests.inference.test_paged_engine import (
    assert_pages_conserved as assert_conserved,
)
from tests.models.test_olmo_hybrid import (
    RTOL_OF_MAX,
    TINY,
    WRONG,
    seeded_params,
    tiny_config,
)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
GREEDY = SamplingParams(temperature=0.0)


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


def first_occurrence(tokens, k):
    assert tokens[k - 1] not in tokens[:k - 1], tokens
    return tokens[k - 1]


# ---- logits: the harness's own comparison ------------------------------------

@pytest.fixture(scope="module")
def checked(model):
    """Three prompts of 9, 20 and 33 tokens plus 8 decode positions
    through the engine's paged steps (``serve_cell.system_logit_errors``
    calls ``engine._prefill`` / ``engine._decode`` with ``engine.cache``
    as one operand), and the reference's logits at the same rows."""
    cfg, params = model
    depth = 8
    lens = np.array([9, 20, 33])
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(5), (3, 48), 0, 128))
    rows = (lens[:, None] - 1 + np.arange(depth + 1)[None, :]).astype(
        np.int32)

    def logits(wrong=None):
        return reference.make_logits_fn(TINY, q_block=8, wrong=wrong)(
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
    # a row names its slot: ONE one-row program, which the check's
    # full-shape call ran once a prompt (``decode.SlotRows``)
    assert engine.prefill_shapes == ((1, 40),)
    assert engine.decode_compile_count == 1
    assert engine.prefill_compile_count == 1


@pytest.mark.parametrize("variant", WRONG)
def test_the_engine_check_rejects_each_wrong_variant(checked, variant):
    _, errors, ref, logits = checked
    off = float(jnp.max(jnp.abs(logits(variant) - ref)))
    assert off / errors["max_abs_reference"] > 50 * RTOL_OF_MAX


# ---- tokens: the plain forward, request for request ---------------------------

def test_mixed_lengths_equal_the_oracle_through_reused_slots(model):
    """Seven requests over three slots: every later one is admitted
    into a slot whose state another request left behind, beside slots
    in mid-decode whose state a fixed-shape prefill call must not
    touch. Each gets the tokens the plain forward gives it alone."""
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
    assert snap["prefix_hit_rate"] == 0.0
    assert eng.metrics.decode_steps_ahead > eng.metrics.decode_steps // 2
    assert eng.decode_compile_count == 1
    assert_conserved(eng)


def test_a_reused_slot_gives_what_a_fresh_engine_gives(model):
    """One slot, two requests one after the other: the second starts
    from an empty state whatever the first left in the slot."""
    first, second = prompts((23, 14), seed=3)
    used = make_engine(model, max_slots=1)
    used.submit(first, max_new_tokens=20)
    used.run()
    assert float(jnp.max(jnp.abs(used.cache.state))) > 0
    rid = used.submit(second, max_new_tokens=10)
    fresh = make_engine(model, max_slots=1)
    fid = fresh.submit(second, max_new_tokens=10)
    assert used.run()[rid].tokens == fresh.run()[fid].tokens


def test_an_eos_learnt_one_step_late_leaves_the_next_request_right(model):
    """The run-ahead loop has dispatched the slot's next step when the
    eos is read: that step mutates the slot's state for nobody. The
    request queued behind gets the slot, its prefill starts the state
    from zero after that step (the prefill call is dispatched behind
    the step in flight, and the device runs them in that order), and
    its tokens are the oracle's."""
    cfg, params = model
    ending, waiting, other = prompts((11, 19, 7), seed=8)
    free = greedy_by_forward(params, cfg, ending, 12)
    eos = first_occurrence(free, 4)
    eng = make_engine(model, max_slots=2)
    a = eng.submit(ending, max_new_tokens=12, eos_id=eos)
    b = eng.submit(other, max_new_tokens=30)
    c = eng.submit(waiting, max_new_tokens=9)
    results = eng.run()
    assert results[a].tokens == free[:4]
    assert results[a].finish_reason == "eos"
    assert results[b].tokens == greedy_by_forward(params, cfg, other, 30)
    assert results[c].tokens == greedy_by_forward(params, cfg, waiting, 9)
    m = eng.metrics
    assert m.decode_slot_steps_discarded >= 1     # the late step ran
    assert m.recurrent_state_owner_mismatches == 0
    assert m.recurrent_state_resets == 3
    assert_conserved(eng)


def test_a_discarded_step_is_dispatched_before_the_admission_that_follows(
        model):
    """Call by call: when the eos is emitted a step is on the device
    for the same slot, for nobody; the next request's prefill call is
    dispatched after it (behind it, unread), so the state that step
    leaves is what the call starts over from zero."""
    cfg, params = model
    ending, waiting = prompts((11, 19), seed=8)
    eos = first_occurrence(greedy_by_forward(params, cfg, ending, 12), 4)
    eng = make_engine(model, max_slots=1)
    eng.submit(ending, max_new_tokens=12, eos_id=eos)
    rid = eng.submit(waiting, max_new_tokens=3)
    calls = []
    prefill, decode = eng._prefill, eng._decode

    def watched(name, step):
        def call(*args):
            calls.append(name)
            return step(*args)
        return call

    eng._prefill = watched("prefill", prefill)
    eng._decode = watched("decode", decode)
    results = eng.run()
    # the first request: its call, three steps for tokens two to four,
    # a fourth for nobody; then the second's call and its two steps
    assert calls == ["prefill"] + ["decode"] * 4 + ["prefill"] + [
        "decode"] * 2
    assert eng.metrics.decode_slot_steps_discarded == 1
    # (a step that runs for nobody is forgotten when its last stream
    # ends: the engine counts the second call as a cold start)
    assert eng.metrics.prefill_calls_behind_flight == 0
    assert results[rid].tokens == greedy_by_forward(params, cfg, waiting, 3)
    assert eng._state_owner[0] == rid


def test_owner_mismatch_counts_a_step_on_another_requests_state(model):
    """The counter is not decoration: forget whose state a slot holds
    and the next dispatch on it is counted."""
    eng = make_engine(model, max_slots=1)
    eng.submit(prompts((6,))[0], max_new_tokens=6)
    eng.step()
    assert eng.metrics.recurrent_state_owner_mismatches == 0
    eng._state_owner[0] = -1
    eng.step()
    assert eng.metrics.recurrent_state_owner_mismatches >= 1


# ---- quarantine reaches the slot-indexed buffers -------------------------------

def test_poison_reaches_the_state_and_quarantine_clears_it(model):
    cfg, params = model
    victim, bystander, later = prompts((9, 13, 10), seed=4)
    inj = ServingFaultInjector(nan_logits_at_step=3, nan_logits_slot=0)
    eng = make_engine(model, max_slots=2, injector=inj)
    v = eng.submit(victim, max_new_tokens=20)
    b = eng.submit(bystander, max_new_tokens=20)
    c = eng.submit(later, max_new_tokens=8)
    results = eng.run()
    assert results[v].outcome == "quarantined"
    assert results[b].outcome == "ok"
    assert results[b].tokens == greedy_by_forward(params, cfg, bystander, 20)
    # the next tenant of the poisoned slot: clean state, right tokens
    assert results[c].outcome == "ok"
    assert results[c].tokens == greedy_by_forward(params, cfg, later, 8)
    for buf in eng.cache:
        assert bool(jnp.all(jnp.isfinite(buf)))
    assert eng.decode_compile_count == 1
    assert_conserved(eng)


def test_a_forward_fn_in_the_models_place_is_told_the_rows_too(model):
    """An engine built around a ``forward_fn`` (the drills' poisoned
    forwards) serves a state-carrying model with the same row mask as
    the model's own forward: without it the rows past ``tail_len`` of a
    fixed-shape prefill would enter the recurrence, and the tokens
    would differ from the plain forward's."""
    from scaletorch_tpu.models import olmo_hybrid

    cfg, params = model
    seen = []

    def spy(*args, **kw):
        seen.append("row_mask" in kw)
        return olmo_hybrid.forward_cached(*args, **kw)

    eng = make_engine(model, forward_fn=spy)
    (prompt,) = prompts([13], seed=5)
    rid = eng.submit(prompt, max_new_tokens=6)
    tokens = eng.run()[rid].tokens
    assert seen and all(seen)
    assert tokens == greedy_by_forward(params, cfg, prompt, 6)


def test_the_fill_touches_only_the_masked_slots_state(model):
    eng = make_engine(model, max_slots=3)
    noise = eng.cache._replace(
        state=jnp.ones_like(eng.cache.state),
        conv=jnp.ones_like(eng.cache.conv))
    eng.cache = noise
    eng._fill(np.zeros(eng.num_pages, bool), [1], 0.0)
    state, conv = np.asarray(eng.cache.state), np.asarray(eng.cache.conv)
    assert (state[:, 1] == 0).all() and (conv[:, 1] == 0).all()
    assert (state[:, [0, 2]] == 1).all() and (conv[:, [0, 2]] == 1).all()


# ---- what is refused, by name --------------------------------------------------

def test_prefix_sharing_is_off_whatever_was_asked(model):
    eng = make_engine(model, prefix_cache=True)
    assert eng.radix is None
    shared = prompts((32,))[0]
    ids = [eng.submit(shared + [i], max_new_tokens=3) for i in range(3)]
    results = eng.run()
    assert all(results[i].outcome == "ok" for i in ids)
    assert not any(results[i].prefix_hit for i in ids)
    assert eng.metrics.prefill_tokens_saved == 0


@pytest.mark.parametrize("call", [
    lambda e: e.export_prefix_map(),
    lambda e: e.export_prefix_pages([1, 2]),
    lambda e: e.import_prefix_pages(
        [], {}, dtype="float32", page_shape=(2, 4, 8, 16), page_size=8),
], ids=["export_prefix_map", "export_prefix_pages", "import_prefix_pages"])
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


def test_other_families_snapshot_has_no_state_counters():
    from scaletorch_tpu.models import llama

    cfg = llama.LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        dtype=jnp.float32)
    eng = InferenceEngine(llama.init_params(jax.random.PRNGKey(0), cfg), cfg,
                          max_slots=2, max_seq=32, page_size=4)
    assert not [k for k in eng.metrics.snapshot() if "recurrent" in k]
    assert eng.radix is not None


# ---- the normal path: scripts/serve.py -----------------------------------------

def test_the_preset_is_the_sixteen_layer_cut():
    from scaletorch_tpu.config import ScaleTorchTPUArguments
    from scaletorch_tpu.models.presets import preset
    from scaletorch_tpu.trainer.trainer import build_model_config

    cfg = build_model_config(ScaleTorchTPUArguments(
        **preset("olmo-hybrid-7b"), dtype="bfloat16",
        param_dtype="bfloat16"))
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "olmo-hybrid-7b-serve.json")) as f:
        config = json.load(f)
    assert cfg.layer_kinds == tuple(config["layer_types"])
    assert cfg.num_hidden_layers == 16 and cfg.rope_theta is None
    for key in ("hidden_size", "intermediate_size", "vocab_size",
                "num_attention_heads", "num_key_value_heads",
                "linear_num_key_heads", "linear_num_value_heads",
                "linear_key_head_dim", "linear_value_head_dim",
                "linear_conv_kernel_dim", "linear_allow_neg_eigval",
                "max_position_embeddings", "rms_norm_eps"):
        assert getattr(cfg, key) == config[key], key


def _http(port, path, payload=None):
    data = None if payload is None else json.dumps(payload).encode()
    with urllib.request.urlopen(urllib.request.Request(
            f"http://127.0.0.1:{port}{path}", data=data), timeout=120) as r:
        return r.read().decode()


def test_served_from_the_command_line():
    """``scripts/serve.py --preset olmo-hybrid-tiny``: gateway ->
    EngineWorker -> InferenceEngine with the pool and the state in one
    cache; a request gets its tokens and /metrics carries the state's
    counters."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "scripts", "serve.py"),
         "--preset", "olmo-hybrid-tiny",
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
        assert value("engine_recurrent_state_bytes") > 0
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
