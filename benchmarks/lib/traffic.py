"""The one general traffic generator. A mix is a data file: a ``kind``
(``train_steps``, ``closed_loop`` or ``open_loop_stratified``) and its
parameters. numpy only: the load generator process imports this.

Steadiness rule: ``--seed`` never changes the amount of work. Sizes and
inter-arrival gaps are the stratified quantiles of the stated
distribution (the same multiset for every seed); the seed permutes
their order and draws the token ids. So an open-loop mix is not a
Poisson process: its gaps have the exponential law's quantiles, every
run holds the same number of arrivals, and run-to-run spread is smaller
than real arrivals would give (which is what a bound wants, and what a
capacity plan must not read off it).
"""

from __future__ import annotations

from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent generator per (seed, stream); any non-negative seed."""
    return np.random.default_rng([int(seed), int(stream)])


def fold_seed(seed: int) -> int:
    """A seed the program's int32 paths accept (the driver's seeds pass
    2**31)."""
    return int(seed) % (2**31 - 1)


# -- stated distributions as fixed multisets ---------------------------------

def quantile_points(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_sizes(n: int, median: float, sigma: float, low: int,
                    high: int) -> np.ndarray:
    """n integer sizes: the (i + 0.5)/n quantiles of a log-normal with
    that median and sigma, clipped to [low, high]."""
    normal = NormalDist()
    z = np.array([normal.inv_cdf(float(p)) for p in quantile_points(n)])
    sizes = np.rint(median * np.exp(sigma * z)).astype(np.int64)
    return np.clip(sizes, low, high)


def uniform_sizes(n: int, low: int, high: int) -> np.ndarray:
    return np.rint(low + (high - low) * quantile_points(n)).astype(np.int64)


def exponential_gaps(n: int, rate: float) -> np.ndarray:
    """n inter-arrival gaps with the law of a Poisson process of that
    rate and none of its randomness: the stratified quantiles of
    Exp(rate), scaled so they sum to n / rate."""
    gaps = -np.log1p(-quantile_points(n))
    return gaps * (n / rate) / gaps.sum()


def sizes_from(spec: Dict[str, Any], n: int) -> np.ndarray:
    kind = spec["dist"]
    if kind == "lognormal":
        return lognormal_sizes(n, spec["median"], spec["sigma"],
                               spec["min"], spec["max"])
    if kind == "uniform":
        return uniform_sizes(n, spec["min"], spec["max"])
    if kind == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    raise ValueError(f"unknown size distribution {kind!r}")


def _tokens(rng: np.random.Generator, n: int, vocab: int) -> List[int]:
    return rng.integers(0, vocab, size=int(n)).tolist()


# -- generators ---------------------------------------------------------------

# the generator that pairs a closed loop's first-request lengths with
# their phases: a constant, not the run's seed (closed_loop_requests)
FIRST_REQUEST_PAIRING = (0, 7)


def train_batches(traffic: Dict[str, Any], vocab: int, seed: int,
                  ) -> List[Dict[str, np.ndarray]]:
    """``distinct_batches`` batches in the trainer's batch contract
    (``input_ids``/``target_ids`` [1, B, S], ``position_ids`` [1, S]);
    the window cycles through them."""
    seq = int(traffic["sequence_length"])
    rows = int(traffic["sequences_per_step"])
    batches = []
    for i in range(int(traffic.get("distinct_batches", 4))):
        toks = rng_for(seed, i).integers(
            0, vocab, size=(1, rows, seq + 1), dtype=np.int32)
        batches.append({
            "input_ids": toks[:, :, :-1],
            "target_ids": toks[:, :, 1:],
            "position_ids": np.arange(seq, dtype=np.int32)[None, :].copy(),
        })
    return batches


def open_loop_requests(traffic: Dict[str, Any], vocab: int, seed: int,
                       seconds: float) -> List[Dict[str, Any]]:
    """Arrivals at ``rate_per_s`` over lead-in + window, gaps the
    stratified exponential quantiles in seeded order. ``due_s``
    is relative to the start of the lead-in; a request is measured when
    it is due inside [lead_in_s, lead_in_s + seconds)."""
    lead_in = float(traffic["lead_in_s"])
    rate = float(traffic["rate_per_s"])
    n = max(1, int(round(rate * (lead_in + seconds))))
    order = rng_for(seed, 0)
    gaps = order.permutation(exponential_gaps(n, rate))
    prompts = order.permutation(sizes_from(traffic["prompt_tokens"], n))
    news = order.permutation(sizes_from(traffic["max_new_tokens"], n))
    due = np.cumsum(gaps) - gaps[0] * 0.5
    content = rng_for(seed, 1)
    out = []
    for i in range(n):
        out.append({
            "id": i, "client": None, "due_s": float(due[i]),
            "prompt": _tokens(content, prompts[i], vocab),
            "max_new_tokens": int(news[i]),
            "measured": bool(lead_in <= due[i] < lead_in + seconds),
        })
    return out


def closed_loop_requests(traffic: Dict[str, Any], vocab: int, seed: int,
                         seconds: float) -> List[Dict[str, Any]]:
    """``clients`` callers, each sending its next request when the last
    one ended. Every client gets ``requests_per_client`` requests. So
    that the window opens on requests at spread phases, client c's first
    request is cut to a fraction of its length. The (length, fraction)
    pairs of those first requests are the same in every run
    (``FIRST_REQUEST_PAIRING``), because they decide how many requests
    end inside the window; the seed deals them to the clients, orders
    every later request and draws the token ids."""
    clients = int(traffic["clients"])
    per_client = int(traffic["requests_per_client"])
    n = clients * per_client
    pairing = rng_for(*FIRST_REQUEST_PAIRING)
    first_news = np.ceil(
        pairing.permutation(sizes_from(traffic["max_new_tokens"], clients))
        * quantile_points(clients)).astype(np.int64)
    order = rng_for(seed, 0)
    first_news = order.permutation(np.maximum(first_news, 1))
    prompts = order.permutation(sizes_from(traffic["prompt_tokens"], n))
    later = order.permutation(
        sizes_from(traffic["max_new_tokens"], n - clients))
    content = rng_for(seed, 1)
    out = []
    for c in range(clients):
        for j in range(per_client):
            i = c * per_client + j
            new = first_news[c] if j == 0 else later[c * (per_client - 1)
                                                     + j - 1]
            out.append({
                "id": i, "client": c, "due_s": None,
                "prompt": _tokens(content, prompts[i], vocab),
                "max_new_tokens": int(new), "measured": True,
            })
    return out


def serve_requests(traffic: Dict[str, Any], vocab: int, seed: int,
                   seconds: float) -> List[Dict[str, Any]]:
    kind = traffic["kind"]
    if kind == "open_loop_stratified":
        return open_loop_requests(traffic, vocab, seed, seconds)
    if kind == "closed_loop":
        return closed_loop_requests(traffic, vocab, seed, seconds)
    raise ValueError(f"traffic kind {kind!r} is not a serving mix")


def check_prompts(traffic: Dict[str, Any], vocab: int, seed: int,
                  count: int, decode_positions: int) -> np.ndarray:
    """The seeded sample the reference check runs: ``count`` sequences
    whose prompt lengths span the mix's range (its quantiles), each
    followed by ``decode_positions`` teacher-forced tokens. Returns
    (tokens [count, max_prompt + decode_positions], prompt_lens)."""
    lens = sizes_from(traffic["prompt_tokens"], count)
    lens[0], lens[-1] = (traffic["prompt_tokens"]["min"],
                         traffic["prompt_tokens"]["max"])
    lens = rng_for(seed, 2).permutation(lens)
    width = int(lens.max()) + decode_positions
    tokens = rng_for(seed, 3).integers(
        0, vocab, size=(count, width), dtype=np.int32)
    return tokens, lens.astype(np.int32)
