"""Traffic generators and the FLOP / byte arithmetic (no jax)."""

import json

import numpy as np
import pytest

from benchmarks.lib import costs, traffic
from benchmarks.lib.spec import Spec

SPEC = Spec()
CONFIG_06B = SPEC.config("qwen3-0.6b-train")
CONFIG_17B = SPEC.config("qwen3-1.7b-serve")
# the chat cell's mix, under whatever rate its last sweep gave it
CHAT_NAME = SPEC.workload("serve-1.7b-chat")["traffic"]
CHAT = SPEC.traffic(CHAT_NAME)
LONGGEN = SPEC.traffic("longgen-closed16")
BIG_SEED = 2**31 + 12345


# -- costs against hand-worked values ----------------------------------------

def test_param_counts_match_the_published_sizes():
    # 28 x (1024 x (2048 + 1024 + 1024 + 2048... by hand:
    # q 1024x2048, k 1024x1024, v 1024x1024, o 2048x1024 = 6,291,456;
    # mlp 3 x 1024 x 3072 = 9,437,184; per layer 15,728,640; x 28 =
    # 440,401,920; head 1024 x 151,936 = 155,582,464
    assert costs.matmul_params(CONFIG_06B) == 440_401_920 + 155_582_464
    assert costs.num_params(CONFIG_06B) == 596_049_920
    assert costs.num_params(CONFIG_17B) == 1_720_574_976


@pytest.mark.parametrize("seq", [8192, 32768])
def test_train_flops_per_token_by_hand(seq):
    matmul = 6 * 595_984_384
    attention_fwd = 2 * 2 * (seq * (seq + 1) / 2) * 128 * 16  # one layer
    want = matmul + 3 * 28 * attention_fwd / seq
    assert costs.train_flops_per_token(CONFIG_06B, seq) == pytest.approx(want)
    square = 6 * 596_049_920 + 12 * 28 * 16 * 128 * seq
    assert costs.train_flops_per_token_full_square(
        CONFIG_06B, seq) == pytest.approx(square)
    # the causal half: attention charged at (S + 1) / 2S of the square
    assert costs.train_flops_per_token(CONFIG_06B, seq) < square


def test_causal_count_at_8k_is_6_39_gflop_per_token():
    assert costs.train_flops_per_token(
        CONFIG_06B, 8192) / 1e9 == pytest.approx(6.3948, abs=1e-3)
    assert costs.train_flops_per_token_full_square(
        CONFIG_06B, 8192) / 1e9 == pytest.approx(9.2134, abs=1e-3)


@pytest.mark.parametrize("local,total", [(8192, 8192), (8192, 32768)])
def test_flash_call_flops_share(local, total):
    got = costs.flash_train_call_flops(CONFIG_06B, local, total)
    whole = 4 * (total * (total + 1) / 2) * 128 * 16
    assert got["forward"] == pytest.approx(whole * local / total)
    assert got["backward"] == pytest.approx(2 * got["forward"])


def test_kv_bytes_by_hand():
    assert costs.kv_bytes_per_token(CONFIG_17B) == 114_688  # 112 KiB
    # one layer's paged-decode call over 10,000 live tokens: K and V,
    # 8 heads x 128 x 2 bytes each
    assert costs.paged_decode_kv_bytes(CONFIG_17B, 10_000) == \
        2 * 8 * 128 * 2 * 10_000
    assert costs.weight_bytes(CONFIG_17B) == 2 * 1_720_574_976


def test_peaks_table_names_its_source_and_refuses_unknown_devices():
    peaks = SPEC.peaks("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    assert peaks["ici_bits_per_s"] == 1600e9
    table = json.load(open(SPEC.path("benchmarks", "peaks.json")))
    assert "Google Cloud" in table["source"]
    with pytest.raises(KeyError):
        SPEC.peaks("cpu")


# -- generators ---------------------------------------------------------------

@pytest.mark.parametrize("kind", ["chat", "longgen"])
def test_requests_are_deterministic_in_the_seed(kind):
    mix = CHAT if kind == "chat" else LONGGEN
    a = traffic.serve_requests(mix, 151936, BIG_SEED, 40.0)
    b = traffic.serve_requests(mix, 151936, BIG_SEED, 40.0)
    c = traffic.serve_requests(mix, 151936, BIG_SEED + 1, 40.0)
    assert a == b
    assert a != c


@pytest.mark.parametrize("kind", ["chat", "longgen"])
def test_every_seed_gets_the_same_sizes_in_another_order(kind):
    mix = CHAT if kind == "chat" else LONGGEN

    def sizes(seed):
        reqs = traffic.serve_requests(mix, 151936, seed, 40.0)
        return (sorted(len(r["prompt"]) for r in reqs),
                sorted(r["max_new_tokens"] for r in reqs))

    assert sizes(1) == sizes(BIG_SEED)


def test_chat_lengths_follow_the_stated_distributions():
    reqs = traffic.serve_requests(CHAT, 151936, 3, 400.0)
    prompts = np.array([len(r["prompt"]) for r in reqs])
    news = np.array([r["max_new_tokens"] for r in reqs])
    assert prompts.min() >= 16 and prompts.max() <= 1024
    assert news.min() >= 8 and news.max() <= 384
    assert np.median(prompts) == pytest.approx(192, rel=0.03)
    assert np.median(news) == pytest.approx(96, rel=0.03)
    # sigma 0.8: the 84th percentile is the median x e**0.8
    assert np.percentile(prompts, 84.13) == pytest.approx(
        192 * np.exp(0.8), rel=0.05)
    assert all(0 <= t < 151936 for r in reqs[:5] for t in r["prompt"])


def test_open_loop_arrivals_have_the_exponential_law_at_the_stated_rate():
    seconds = 400.0
    reqs = traffic.serve_requests(CHAT, 151936, 5, seconds)
    due = np.array([r["due_s"] for r in reqs])
    assert np.all(np.diff(due) > 0)
    span = CHAT["lead_in_s"] + seconds
    assert len(reqs) == round(CHAT["rate_per_s"] * span)
    assert due[-1] <= span + 2 / CHAT["rate_per_s"]
    gaps = np.diff(due)
    mean = 1 / CHAT["rate_per_s"]
    assert gaps.mean() == pytest.approx(mean, rel=0.02)
    assert gaps.std() == pytest.approx(mean, rel=0.1)  # exponential
    measured = [r for r in reqs if r["measured"]]
    assert all(CHAT["lead_in_s"] <= r["due_s"] < span for r in measured)
    assert len(measured) == pytest.approx(
        CHAT["rate_per_s"] * seconds, rel=0.05)


def test_open_loop_seed_permutes_one_fixed_multiset():
    """Not a Poisson process: every seed offers the same gaps and sizes
    in another order (the traffic file's note says so)."""
    a = traffic.serve_requests(CHAT, 151936, 5, 40.0)
    b = traffic.serve_requests(CHAT, 151936, BIG_SEED, 40.0)
    assert len(a) == len(b)
    for key in (lambda r: len(r["prompt"]), lambda r: r["max_new_tokens"]):
        assert sorted(map(key, a)) == sorted(map(key, b))
        assert list(map(key, a)) != list(map(key, b))
    gaps = [np.diff([r["due_s"] for r in reqs]) for reqs in (a, b)]
    # both are subsets (the first gap is halved) of one multiset
    assert np.sort(gaps[0])[-5:] == pytest.approx(np.sort(gaps[1])[-5:])
    assert "NOT a Poisson process" in CHAT["note"]


@pytest.mark.parametrize("mix", [CHAT_NAME, "longgen-closed16"])
def test_serving_mix_names_where_its_lengths_come_from(mix):
    data = SPEC.traffic(mix)
    assert len(data["lengths_source"]) > 40
    assert "--seed" in data["note"]


def test_closed_loop_has_one_client_per_slot_at_spread_phases():
    reqs = traffic.serve_requests(LONGGEN, 151936, 9, 40.0)
    clients = LONGGEN["clients"]
    assert clients == CONFIG_17B["serve"]["max_slots"]
    by_client = {}
    for r in reqs:
        by_client.setdefault(r["client"], []).append(r)
    assert sorted(by_client) == list(range(clients))
    firsts = sorted(v[0]["max_new_tokens"] for v in by_client.values())
    laters = [r["max_new_tokens"] for v in by_client.values() for r in v[1:]]
    assert min(laters) >= 384 and max(laters) <= 640
    assert firsts[0] < 64 and firsts[-1] > 300  # phases spread over 0..1
    # which requests end inside the window does not depend on the seed
    other = traffic.serve_requests(LONGGEN, 151936, 10, 40.0)
    assert firsts == sorted(r["max_new_tokens"] for r in other
                            if r["id"] % LONGGEN["requests_per_client"] == 0)
    prompts = [len(r["prompt"]) for r in reqs]
    assert min(prompts) >= 32 and max(prompts) <= 256


@pytest.mark.parametrize("name", ["train-seq8k", "train-seq32k"])
def test_train_batches_keep_the_trainers_contract(name):
    mix = SPEC.traffic(name)
    seq = mix["sequence_length"]
    a = traffic.train_batches(mix, 151936, BIG_SEED)
    b = traffic.train_batches(mix, 151936, BIG_SEED)
    assert len(a) == mix["distinct_batches"]
    for x, y in zip(a, b):
        assert x["input_ids"].shape == (1, 1, seq)
        assert x["position_ids"].shape == (1, seq)
        np.testing.assert_array_equal(x["input_ids"], y["input_ids"])
        # targets are the inputs shifted by one
        np.testing.assert_array_equal(x["input_ids"][0, 0, 1:],
                                      x["target_ids"][0, 0, :-1])
    assert not np.array_equal(a[0]["input_ids"], a[1]["input_ids"])


def test_check_prompts_span_the_mix_and_fit_the_engine():
    shape = CONFIG_17B["serve"]
    for mix in (CHAT, LONGGEN):
        tokens, lens = traffic.check_prompts(mix, 151936, BIG_SEED, 8, 64)
        assert lens.min() == mix["prompt_tokens"]["min"]
        assert lens.max() == mix["prompt_tokens"]["max"]
        assert lens.max() <= shape["prefill_len"]
        assert lens.max() + 64 <= shape["max_seq"]
        assert tokens.shape == (8, lens.max() + 64)
        assert mix["max_new_tokens"]["max"] + mix["prompt_tokens"]["max"] \
            <= shape["max_seq"]


def test_fold_seed_fits_int32():
    assert 0 <= traffic.fold_seed(2**31 + 5) < 2**31 - 1
    assert traffic.fold_seed(7) == 7
