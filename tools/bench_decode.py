#!/usr/bin/env python
"""Decode microbenchmark: KV-cache engine vs the retired recompute loop.

Arms over tiny CPU-friendly models (>= 512 generated tokens for the
cached-vs-recompute pair — ISSUE 4 acceptance):

  * ``recompute``: the original cache-less sampler
    (models/gpt_moe.generate_recompute) — a full O(S_max² · L) forward
    per emitted token;
  * ``cached``: the KV-cached ``generate`` — one prefill, then
    O(S_max · L) per token against the cache;
  * ``engine``: the same generation through the continuous-batching
    InferenceEngine on a Llama config (prefill + per-step jitted decode
    with host-side slot bookkeeping — the serving-loop overhead arm);
  * ``paged vs dense`` (ISSUE 10): the paged-cache engine against the
    dense one on the same request schedule — tok/s, cache HBM bytes per
    layout (``kv_cache_bytes``), and the max admissible concurrency at
    EQUAL cache HBM: the dense layout admits ``B`` requests whatever
    their length; a pool of the same bytes admits
    ``capacity // pages_per_request`` — attested by actually admitting
    them into a paged engine, not just arithmetic;
  * ``disagg vs colocated`` (ISSUE 19): the disaggregated prefill/
    decode engine (inference/disagg.py, MPMD slices + page handoff)
    against the colocated paged engine on the same schedule — tok/s,
    per-slice busy fractions, handoff pages/bytes, and the relative
    overhead of the handoff seam. Needs >= 2 chips. Exit 1 only on
    parity breakage; the < 15% overhead target is attested warn-only.

A timing tool: it refuses to run without a TPU — the tok/s of XLA's CPU
backend at toy sizes measures dispatch overhead, not the system.

Writes JSON under results/ (gitignored) and prints a table.

Usage (on the chip):
    python tools/bench_decode.py [--tokens 512]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _time_tokens(fn, n_tokens: int, repeats: int = 1):
    """(tokens/s, seconds) for fn() generating n_tokens, after a warmup
    call that eats compile time."""
    fn()  # warmup + compile
    t0 = time.perf_counter()
    for _ in range(repeats):
        fn()
    dt = (time.perf_counter() - t0) / repeats
    return n_tokens / dt, dt


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, default=512,
                    help="generated tokens per arm (>= 512 for the "
                         "acceptance run)")
    ap.add_argument("--prompt", type=int, default=8)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--embd", type=int, default=64)
    ap.add_argument("--repeats", type=int, default=1)
    ap.add_argument("--page_size", type=int, default=16,
                    help="paged-cache page size for the paged-vs-dense row")
    ap.add_argument("--out", default=os.path.join(REPO, "results",
                                                  "bench_decode.json"))
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from scaletorch_tpu.models import gpt_moe, llama
    from scaletorch_tpu.utils.device import require_tpu

    require_tpu("tools/bench_decode.py")
    from scaletorch_tpu.inference import InferenceEngine, SamplingParams

    block = args.prompt + args.tokens
    cfg = gpt_moe.GPTMoEConfig(
        block_size=block, vocab_size=256, n_layer=args.layers, n_head=4,
        n_embd=args.embd, use_moe=False,
    )
    params = gpt_moe.init_params(jax.random.PRNGKey(0), cfg)
    prompt = (jnp.arange(args.prompt, dtype=jnp.int32) % 256)[None, :]

    def run_cached():
        out = gpt_moe.generate(params, prompt, cfg,
                               max_new_tokens=args.tokens, temperature=0.0)
        jax.block_until_ready(out)
        return out

    def run_recompute():
        out = gpt_moe.generate_recompute(
            params, prompt, cfg, max_new_tokens=args.tokens, temperature=0.0)
        jax.block_until_ready(out)
        return out

    print(f"GPT block={block} L={args.layers} d={args.embd}; "
          f"{args.tokens} tokens per arm")
    cached_tps, cached_s = _time_tokens(run_cached, args.tokens,
                                        args.repeats)
    print(f"  cached    : {cached_tps:10.1f} tok/s  ({cached_s:.2f}s)")
    recomp_tps, recomp_s = _time_tokens(run_recompute, args.tokens,
                                        args.repeats)
    print(f"  recompute : {recomp_tps:10.1f} tok/s  ({recomp_s:.2f}s)")

    # sanity: both arms emit the same greedy continuation
    same = bool(jnp.array_equal(run_cached(), run_recompute()))

    # engine arm: llama tiny through the continuous-batching loop
    lcfg = llama.LlamaConfig(
        vocab_size=256, hidden_size=args.embd, intermediate_size=2 * args.embd,
        num_hidden_layers=args.layers, num_attention_heads=4,
        num_key_value_heads=2, dtype=jnp.float32,
    )
    lparams = llama.init_params(jax.random.PRNGKey(1), lcfg)

    eng = InferenceEngine(
        lparams, lcfg, max_slots=1, max_seq=block,
        prefill_len=args.prompt,
        sampling=SamplingParams(temperature=0.0),
    )

    def run_engine():
        eng.submit(list(range(1, args.prompt + 1)),
                   max_new_tokens=args.tokens)
        eng.run()

    run_engine()  # warmup: compiles the engine's prefill + decode steps
    t0 = time.perf_counter()
    run_engine()
    engine_s = time.perf_counter() - t0
    engine_tps = args.tokens / engine_s
    print(f"  engine    : {engine_tps:10.1f} tok/s  ({engine_s:.2f}s)  "
          f"[decode compiles: {eng.decode_compile_count}]")

    speedup = cached_tps / recomp_tps
    print(f"\n  cached vs recompute speedup: {speedup:.2f}x  "
          f"(greedy outputs identical: {same})")

    # ---- paged vs dense row (ISSUE 10) ---------------------------------
    from scaletorch_tpu.inference.kv_cache import ceil_div, kv_cache_bytes

    ps = args.page_size
    dense_slots, s_max = 2, 256
    # 64-token requests, but keep at least one generated token so a big
    # --prompt can't degenerate the row into zero-token requests (which
    # would zero row_tokens and spuriously trip the >= 2x gate below)
    req_prompt = args.prompt
    req_new = max(64 - req_prompt, 1)
    schedule = [(list(range(1, req_prompt + 1)), req_new),
                ([5] * req_prompt, req_new)]

    def build(layout, **kw):
        return InferenceEngine(
            lparams, lcfg, max_slots=dense_slots, max_seq=s_max,
            prefill_len=req_prompt, cache_layout=layout,
            sampling=SamplingParams(temperature=0.0), **kw)

    def serve(e):
        ids = [e.submit(p, max_new_tokens=n) for p, n in schedule]
        res = e.run()
        return [res[i].tokens for i in ids]

    dense_eng = build("dense")
    out_dense = serve(dense_eng)  # warmup/compile
    t0 = time.perf_counter()
    out_dense = serve(dense_eng)
    dense_s = time.perf_counter() - t0
    paged_eng = build("paged", page_size=ps)
    out_paged = serve(paged_eng)
    t0 = time.perf_counter()
    out_paged = serve(paged_eng)
    paged_s = time.perf_counter() - t0
    row_tokens = sum(n for _, n in schedule)
    paged_same = out_dense == out_paged

    dense_bytes = kv_cache_bytes(lcfg, dense_slots, s_max, jnp.float32)
    page_bytes = kv_cache_bytes(lcfg, 1, ps, jnp.float32, layout="paged",
                                page_size=ps, num_pages=1)
    pool_pages = dense_bytes // page_bytes       # equal-HBM pool size
    pages_per_req = ceil_div(req_prompt + req_new, ps)
    admissible_paged = max((pool_pages - 1) // pages_per_req, 0)  # - TRASH
    if admissible_paged >= 1:
        # attest: a pool of exactly that many pages really admits them
        # all concurrently (page-budget admission, not slot arithmetic)
        attest = InferenceEngine(
            lparams, lcfg, max_slots=admissible_paged, max_seq=s_max,
            prefill_len=req_prompt, cache_layout="paged", page_size=ps,
            num_pages=pool_pages, prefix_cache=False,
            sampling=SamplingParams(temperature=0.0))
        for k in range(admissible_paged):
            attest.submit([k + 1] * req_prompt, max_new_tokens=req_new)
        attest.step()
        # everything admitted within the single step was resident at
        # once — counted at admission, not after it, so one-token
        # requests that retire inside the step still attest their
        # concurrency
        concurrent = attest.metrics.requests_admitted
    else:
        # degenerate sweep geometry (page_size ~ the whole dense cache):
        # an equal-HBM pool can't hold even one request, nothing to
        # attest — report 0 and let the warn-only gate handle the ratio
        concurrent = 0
    paged_pool_bytes = kv_cache_bytes(
        lcfg, dense_slots, s_max, jnp.float32, layout="paged",
        page_size=ps, num_pages=pool_pages)
    ratio = concurrent / dense_slots

    print(f"\n  paged vs dense (B={dense_slots}, S_max={s_max}, "
          f"page={ps}, req={req_prompt + req_new} tokens):")
    print(f"    dense : {row_tokens / dense_s:10.1f} tok/s  "
          f"cache {dense_bytes / 2**20:.2f} MiB  "
          f"max concurrent {dense_slots}")
    print(f"    paged : {row_tokens / paged_s:10.1f} tok/s  "
          f"pool  {paged_pool_bytes / 2**20:.2f} MiB  "
          f"max concurrent {concurrent} at equal HBM "
          f"({ratio:.1f}x, greedy identical: {paged_same})")

    # ---- disagg vs colocated row (ISSUE 19) ----------------------------
    disagg_row = None
    if len(jax.devices()) < 2:
        print(f"\n  disagg vs colocated: skipped (needs >= 2 devices, "
              f"have {len(jax.devices())})")
    else:
        from scaletorch_tpu.inference import DisaggregatedEngine

        dis_eng = DisaggregatedEngine(
            lparams, lcfg, max_slots=dense_slots, max_seq=s_max,
            prefill_len=req_prompt, page_size=ps,
            sampling=SamplingParams(temperature=0.0))
        serve(dis_eng)  # warmup: compiles both slice programs
        dis_eng.metrics.reset_window()
        t0 = time.perf_counter()
        out_disagg = serve(dis_eng)
        disagg_s = time.perf_counter() - t0
        p_busy, d_busy = dis_eng.metrics.busy_fractions()
        disagg_same = out_disagg == out_paged
        overhead_pct = (disagg_s - paged_s) / paged_s * 100.0
        try:
            dis_eng.check_conservation()  # raises on a page leak
            conservation_ok = True
        except AssertionError:
            conservation_ok = False
        n_p = dis_eng.metrics.prefill_slice_devices
        n_d = dis_eng.metrics.decode_slice_devices
        print(f"\n  disagg vs colocated (split {n_p}:{n_d}, "
              f"page={ps}, same schedule):")
        print(f"    colocated : {row_tokens / paged_s:10.1f} tok/s")
        print(f"    disagg    : {row_tokens / disagg_s:10.1f} tok/s  "
              f"overhead {overhead_pct:+.1f}%  "
              f"busy p={p_busy:.2f} d={d_busy:.2f}  "
              f"handoff {dis_eng.metrics.pages_handed_off} pages / "
              f"{dis_eng.metrics.handoff_bytes} B  "
              f"(greedy identical: {disagg_same}, compiles "
              f"{dis_eng.prefill_compile_count}/"
              f"{dis_eng.decode_compile_count}, conservation "
              f"{'ok' if conservation_ok else 'LEAK'})")
        disagg_row = {
            "slice_split": [n_p, n_d],
            "colocated_tokens_per_s": row_tokens / paged_s,
            "disagg_tokens_per_s": row_tokens / disagg_s,
            "overhead_pct": overhead_pct,
            "prefill_busy_fraction": p_busy,
            "decode_busy_fraction": d_busy,
            "pages_handed_off": dis_eng.metrics.pages_handed_off,
            "handoff_bytes": dis_eng.metrics.handoff_bytes,
            "greedy_outputs_identical": disagg_same,
            "conservation_ok": conservation_ok,
        }

    result = {
        "config": {"block_size": block, "layers": args.layers,
                   "embd": args.embd, "tokens": args.tokens,
                   "prompt": args.prompt},
        "cached_tokens_per_s": cached_tps,
        "recompute_tokens_per_s": recomp_tps,
        "engine_tokens_per_s": engine_tps,
        "speedup_cached_vs_recompute": speedup,
        "greedy_outputs_identical": same,
        "paged_vs_dense": {
            "page_size": ps,
            "request_tokens": req_prompt + req_new,
            "dense_tokens_per_s": row_tokens / dense_s,
            "paged_tokens_per_s": row_tokens / paged_s,
            "dense_cache_bytes": dense_bytes,
            "paged_pool_bytes_at_equal_hbm": paged_pool_bytes,
            "max_concurrent_dense": dense_slots,
            "max_concurrent_paged_at_equal_hbm": concurrent,
            "concurrency_ratio": ratio,
            "greedy_outputs_identical": paged_same,
        },
        "disagg_vs_colocated": disagg_row,
        "backend": jax.default_backend(),
    }
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
    print(f"  wrote {args.out}")
    if speedup <= 1.0:
        print("  WARNING: cached decode did not beat recompute", file=sys.stderr)
        sys.exit(1)
    if not paged_same:
        print("  WARNING: paged greedy outputs diverged from dense",
              file=sys.stderr)
        sys.exit(1)
    if disagg_row is not None:
        if not disagg_row["greedy_outputs_identical"]:
            print("  WARNING: disagg greedy outputs diverged from "
                  "colocated", file=sys.stderr)
            sys.exit(1)
        if disagg_row["overhead_pct"] >= 15.0:
            # perf attestation is warn-only: CPU-sim timing jitter must
            # not flake CI; parity above is the hard gate
            print(f"  WARNING: disagg overhead "
                  f"{disagg_row['overhead_pct']:.1f}% >= 15% vs "
                  "colocated", file=sys.stderr)
    if ratio < 2.0:
        print(f"  WARNING: paged concurrency gain {ratio:.1f}x < 2x at "
              "equal HBM", file=sys.stderr)
        # the >= 2x acceptance gate is defined on the default request
        # geometry; exploratory --prompt/--page_size sweeps legitimately
        # land below it (e.g. page_size ~ request length) and only warn
        if (args.prompt == ap.get_default("prompt")
                and args.page_size == ap.get_default("page_size")):
            sys.exit(1)


if __name__ == "__main__":
    main()
