"""Gateway over the disaggregated engine (ISSUE 19): real HTTP SSE
bit-parity against the colocated oracle, HTTP-ledger conservation, the
per-slice /healthz block and the handoff metric families on /metrics.

Quick tier, CPU (8 virtual devices via conftest). Same harness idiom as
test_gateway.py: a real ``ServingGateway`` on an ephemeral port, urllib
clients, the colocated paged engine as the arithmetic oracle.
"""

import json
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from scaletorch_tpu.inference import (
    DisaggregatedEngine,
    InferenceEngine,
    SamplingParams,
)
from scaletorch_tpu.models import llama
from scaletorch_tpu.serving.gateway import ServingGateway
from scaletorch_tpu.serving.protocol import parse_sse_stream, stream_tokens

TINY = dict(
    vocab_size=64, hidden_size=32, intermediate_size=64,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    dtype=jnp.float32,
)
PAGE = 4


@pytest.fixture(scope="module")
def tiny_llama():
    cfg = llama.LlamaConfig(**TINY)
    return cfg, llama.init_params(jax.random.PRNGKey(0), cfg)


def engine_kw(**kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_seq", 32)
    kw.setdefault("prefill_len", 16)
    kw.setdefault("sampling", SamplingParams(temperature=0.0))
    kw.setdefault("page_size", PAGE)
    kw.setdefault("strict_submit", False)
    return kw


def make_disagg(tiny_llama, **kw):
    cfg, params = tiny_llama
    return DisaggregatedEngine(
        params, cfg, disagg_split=(4, 4), **engine_kw(**kw))


def ref_tokens(tiny_llama, prompt, n):
    """COLOCATED direct-engine oracle — parity is asserted across the
    architecture split, not disagg-vs-itself."""
    cfg, params = tiny_llama
    eng = InferenceEngine(
        params, cfg, **engine_kw())
    rid = eng.submit(prompt, max_new_tokens=n)
    return eng.run()[rid].tokens


def post(port, body, *, timeout=60):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/generate",
        data=json.dumps(body).encode(), method="POST")
    try:
        resp = urllib.request.urlopen(req, timeout=timeout)
        return resp.status, resp.read()
    except urllib.error.HTTPError as err:
        return err.code, err.read()


def get(port, path, timeout=30):
    resp = urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=timeout)
    return resp.status, resp.read()


class TestDisaggGateway:
    def test_sse_parity_healthz_and_metrics(self, tiny_llama):
        """One gateway boot covers the e2e acceptance: streamed tokens
        bit-identical to the colocated engine, exactly one terminal per
        request (HTTP conservation), the disagg block live on /healthz,
        the per-slice gauges + handoff_seconds histogram on /metrics,
        and one compile per slice program."""
        engine = make_disagg(tiny_llama)
        gw = ServingGateway(engine, port=0).start_in_thread()
        try:
            prompts = [[1, 2, 3], [7, 8, 9, 10], [4, 4, 4]]
            for prompt in prompts:
                status, raw = post(
                    gw.port,
                    {"prompt": prompt, "max_new_tokens": 6,
                     "stream": True})
                assert status == 200
                events = parse_sse_stream(raw)
                dones = [d for e, d in events if e == "done"]
                assert len(dones) == 1, events
                assert dones[0]["outcome"] == "ok"
                streamed = stream_tokens(events)
                assert streamed == dones[0]["token_ids"]
                assert streamed == ref_tokens(tiny_llama, prompt, 6)
            assert 1 <= engine.prefill_compile_count <= len(engine.prefill_shapes)
            assert engine.decode_compile_count == 1

            _, raw = get(gw.port, "/healthz")
            health = json.loads(raw)
            dis = health["replicas"]["r0"]["disagg"]
            assert dis["prefill_slice"]["devices"] == 4
            assert dis["decode_slice"]["devices"] == 4
            assert dis["handoffs"] == len(prompts)
            assert dis["handoff_failures"] == 0
            assert dis["pages_handed_off"] >= len(prompts)
            assert dis["prefill_slice"]["pages_in_use"] == 0  # drained
            assert 0.0 <= dis["prefill_slice"]["busy_fraction"] <= 1.0
            assert 0.0 <= dis["decode_slice"]["busy_fraction"] <= 1.0

            _, raw = get(gw.port, "/metrics")
            metrics = raw.decode()
            for needle in (
                'scaletorch_engine_prefill_slice_busy_fraction'
                '{replica="r0"}',
                'scaletorch_engine_decode_slice_busy_fraction'
                '{replica="r0"}',
                'scaletorch_engine_pages_handed_off{replica="r0"}',
                'scaletorch_engine_handoffs{replica="r0"} 3.0',
                'scaletorch_engine_handoff_failures{replica="r0"} 0.0',
                "# TYPE scaletorch_handoff_seconds histogram",
                'scaletorch_handoff_seconds_count{replica="r0"} 3',
            ):
                assert needle in metrics, f"missing {needle}"
        finally:
            gw.stop_sync()
        gw.metrics.check_conservation()
        engine.check_conservation()

    def test_colocated_healthz_has_no_disagg_block(self, tiny_llama):
        cfg, params = tiny_llama
        engine = InferenceEngine(
            params, cfg, **engine_kw())
        gw = ServingGateway(engine, port=0).start_in_thread()
        try:
            _, raw = get(gw.port, "/healthz")
            health = json.loads(raw)
            assert "disagg" not in health["replicas"]["r0"]
            _, raw = get(gw.port, "/metrics")
            assert "scaletorch_handoff_seconds" not in raw.decode()
        finally:
            gw.stop_sync()
