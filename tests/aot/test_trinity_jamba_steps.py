"""trinity-mini-serve's and jamba2-3b-serve's paged step programs (window
layers over rings beside the pool; Mamba layers' state beside it),
compiled for the v5e at the cells' shapes: what the cells' trace readers
look for, and no weight copied. Apart from Kimi-Linear's file: the two
costliest configurations do not share a worker.
"""

import json
import math
import os
import re

from tests.aot.programs import (
    _ARRAY,
    _PLUMBING,
    REPO,
    _mosaic_calls,
    _named,
    _pool_shaped,
    _programs_of,
    _reader_patterns,
    _short_names,
    _top_level,
)
from tests.aot.step_program_cases import (  # noqa: F401  (collected here)
    test_no_step_program_copies_a_weight,
)

CONFIGURATIONS = ["trinity-mini-serve", "jamba2-3b-serve"]


def test_trinity_steps_are_what_the_new_readers_look_for(one_chip):
    """Trinity-Mini's two step programs at the cell's shapes (8 slots x
    3,072-row prompts, ``max_seq`` 3,456, 12 window + 4 full layers, a
    128-wide router over 32 held experts). **Prefill attention runs in
    key blocks**: no array of ``slots x heads x prefill_len x max_seq``
    (or ``x prefill_len``) elements exists, of any type (the gather
    path's ``f32[8,32,3072,3456]`` would be 10.9 GB); the attention is
    the flash forward, 8 calls in the text (a period unrolled + the
    scanned period's), told by the ``(bf16 4-D, f32)`` pair
    ``serve_trinity_prefill_attn_roofline`` matches. The decode step:
    8 ``paged_decode`` calls in the text with the one 4-D result (what
    ``serve_trinity_paged_attn_roofline`` matches, and nothing else
    does), 18 decode-shaped ``gmm`` calls ``bf16[128, 1024 | 2048]``
    (``serve_trinity_expert_mlp_roofline``; the prefill's run 196,608
    rows and are not matched). Neither program has an operation that
    returns the page pool, the rings, a layer of either, or the expert
    stack; both fit the chip: arguments + scratch under 11 GB."""
    decode, prefill, pool_shape = _programs_of(one_chip, "trinity-mini-serve")
    slots, heads, rows, max_seq = 8, 32, 3072, 3456
    assert pool_shape == (4, slots * 216 + 1, 4, 16, 128)
    ring_shape = (12, slots * 129 + 1, 4, 16, 128)
    texts = {"decode": decode.as_text(), "prefill": prefill.as_text()}
    for name, text in texts.items():
        assert _pool_shaped(text, pool_shape) == {}, name
        assert _pool_shaped(text, ring_shape) == {}, name
        writes = _named(_mosaic_calls(text), "paged_write")
        # K and V: three window layers and one full layer of the
        # unrolled period, and of the scanned one
        assert len(writes) == 2 * (3 + 1) * 2, (name, len(writes))
        for stack in ("bf16[14,32,2048,1024]", "bf16[32,2048,1024]",
                      "bf16[448,2048,1024]", "bf16[14,32,1024,2048]",
                      "bf16[32,1024,2048]", "bf16[448,1024,2048]"):
            moved = [x for x in _top_level(text, stack)
                     if x[0] not in _PLUMBING | {"bitcast"}
                     and "tpu_custom_call" not in x[1]]
            assert not moved, (name, moved[:3])

    sizes = {dims: math.prod(map(int, dims.split(",")))
             for dims in set(_ARRAY.findall(texts["prefill"]))}
    scores = {slots * heads * rows * max_seq, slots * heads * rows * rows}
    assert not [d for d, n in sizes.items() if n in scores]
    assert f"f32[{slots},50048]" in texts["prefill"]      # last_logits

    patterns = {name: _reader_patterns(name) for name in (
        "serve_trinity_paged_attn_roofline",
        "serve_trinity_expert_mlp_roofline",
        "serve_trinity_prefill_attn_roofline")}

    def found(program, reader):
        return [n for n in _short_names(texts[program])
                if any(re.search(p, n) for p in patterns[reader])]

    attn = found("decode", "serve_trinity_paged_attn_roofline")
    assert len(attn) == 8 and all(
        n.startswith("paged_decode") and n.endswith("bf16[8,4,8,128]")
        for n in attn), attn
    experts = found("decode", "serve_trinity_expert_mlp_roofline")
    assert len(experts) == 18 and all(n.startswith("gmm") for n in experts)
    assert sorted(n.rsplit(" | ", 1)[1] for n in experts) == (
        ["bf16[128,1024]"] * 12 + ["bf16[128,2048]"] * 6)
    flash = found("prefill", "serve_trinity_prefill_attn_roofline")
    assert len(flash) == 8 and all(
        n.startswith("flash_fwd")
        and n.endswith("(bf16[8,32,3072,128], f32[8,32,1,3072])")
        for n in flash), flash
    assert not found("prefill", "serve_trinity_paged_attn_roofline")
    assert not found("prefill", "serve_trinity_expert_mlp_roofline")
    assert not found("decode", "serve_trinity_prefill_attn_roofline")

    cache_bytes = 2 * 2 * (math.prod(pool_shape) + math.prod(ring_shape))
    for name, program, scratch in (("decode", decode, 0.5e9),
                                   ("prefill", prefill, 2.5e9)):
        memory = program.memory_analysis()
        assert memory.alias_size_in_bytes >= cache_bytes, name
        assert memory.temp_size_in_bytes < scratch, name
        assert (memory.argument_size_in_bytes
                + memory.temp_size_in_bytes) < 11e9, name


def test_jamba_steps_are_what_the_new_readers_look_for(one_chip):
    """Jamba2-3B's two step programs at the cell's shapes (8 slots x
    3,072-row prompts, 26 Mamba layers + 2 attention layers on ONE K/V
    head). **The scan never materialises a state axis over the prompt**:
    no array of ``slots x prefill_len x channels x N`` elements exists,
    of any type or layout (``exp(dt A)`` written the obvious way is
    ``f32[8,3072,5120,16]``, 8.05 GB a layer); the scan is the Mosaic
    kernel, 2 calls in the text (the two runs of Mamba layers in a
    period), named ``ssm_scan_fwd`` and returning ``(y [8, 3072, 5120],
    the state [8, 16, 40, 128])`` with NO copy around them. Prefill attention is the flash forward on 20 query
    heads over one K/V head; the decode step's is ``paged_decode`` with
    the ``[8, 1, 20, 128]`` tile: the lax fallback is not taken for a
    head of 128. The state ``f32[26,8,16,40,128]`` is the layer loops'
    carry, written in place by one select + dynamic-update-slice fusion
    a Mamba run, never copied. The readers of the cell's new metrics
    find the kernel and the decode update by these names, and both
    programs fit the chip."""
    decode, prefill, pool_shape = _programs_of(one_chip, "jamba2-3b-serve")
    slots, rows, channels, n = 8, 3072, 5120, 16
    assert pool_shape == (2, slots * 216 + 1, 1, 16, 128)
    texts = {"decode": decode.as_text(), "prefill": prefill.as_text()}
    sizes = {dims: math.prod(map(int, dims.split(",")))
             for dims in set(_ARRAY.findall(texts["prefill"]))}
    assert not [d for d, count in sizes.items()
                if count >= slots * rows * channels * n]
    calls = _mosaic_calls(texts["prefill"])
    scans = _named(calls, "ssm_scan_fwd")
    assert len(scans) == 2, calls
    for call in scans:
        assert re.search(
            r"= \(f32\[8,3072,5120\]\S*, f32\[8,16,40,128\]\S*\) "
            r"custom-call\(", call), call
    flash = _named(calls, "flash_fwd")
    assert len(flash) == 1 and re.search(
        r"= \(bf16\[8,20,3072,128\]\S*, f32\[8,20,1,3072\]", flash[0])
    assert len(_named(calls, "paged_write")) == 2
    assert not _named(calls, "paged_decode")
    # u, dt and y go in and out as the projections hold them: nothing of
    # their size is copied, transposed or re-laid around the call (as
    # [.., 40, 128] views each was: 4.6 ms a layer on the chip)
    for shape in ("f32[8,3072,5120]", "f32[3072,8,40,128]",
                  "f32[8,3072,40,128]"):
        assert not [x for x in _top_level(texts["prefill"], shape)
                    if x[0] in ("copy", "transpose", "reshape")], shape
    calls = _mosaic_calls(texts["decode"])
    attn = _named(calls, "paged_decode")
    assert len(attn) == 1 and re.search(
        r"= bf16\[8,1,20,128\]", attn[0]), calls
    assert len(_named(calls, "paged_write")) == 2
    assert not _named(calls, "ssm_scan_fwd")
    for name, text in texts.items():
        state = [op for op, _ in _top_level(text, "f32[26,8,16,40,128]")
                 if op not in _PLUMBING]
        assert state == ["fusion"] * 2, (name, state)

    with open(os.path.join(REPO, "benchmarks", "metrics",
                           "serve_jamba_ssm_scan_share.json")) as f:
        share = json.load(f)["reducer"]

    def scan_ops(program):
        """What the share's reader would count of a program's
        operations: fusions and custom calls are what the trace's ``XLA
        Ops`` line holds of them."""
        return [name for name in _short_names(texts[program])
                if re.search(r" \| (fusion|custom-call) \| ", name)
                and any(re.search(p, name) for p in share["patterns"])
                and not any(re.search(p, name) for p in share["exclude"])]

    decode_ops = scan_ops("decode")
    # a Mamba run of the decode step: the fusion that reads the state
    # for y, and the in-place write
    assert sum(name.endswith("| kLoop | f32[8,40,128]")
               for name in decode_ops) == 2, decode_ops
    assert sum(name.endswith("f32[26,8,16,40,128]")
               for name in decode_ops) == 2, decode_ops
    assert not [name for name in decode_ops if "bf16" in name], decode_ops
    kernel = [name for name in _short_names(texts["prefill"])
              if any(re.search(p, name) for p in _reader_patterns(
                  "serve_jamba_ssm_scan_roofline"))]
    assert len(kernel) == 2 and all(
        name.startswith("ssm_scan_fwd") for name in kernel), kernel
    assert set(kernel) <= set(scan_ops("prefill"))
    assert not [name for name in _short_names(texts["decode"])
                if any(re.search(p, name) for p in _reader_patterns(
                    "serve_jamba_ssm_scan_roofline"))]

    cache_bytes = (2 * 2 * math.prod(pool_shape) + 26 * 8 * 16 * 5120 * 4
                   + 26 * 8 * 3 * 5120 * 2)
    for name, program, scratch in (("decode", decode, 16 * 2**20),
                                   ("prefill", prefill, 4e9)):
        memory = program.memory_analysis()
        assert memory.alias_size_in_bytes >= cache_bytes, name
        assert memory.temp_size_in_bytes < scratch, name
        assert (memory.argument_size_in_bytes
                + memory.temp_size_in_bytes) < 11e9, name
