"""openpangu-ultra-moe-718b-serve's and kimi-linear-48b-a3b-serve's paged
step programs, the two whose pool holds latent rows, compiled for the
v5e at the cells' shapes: what the cells' trace readers look for, the
latent kernel they were measured with, and no weight copied.
"""

import hashlib
import math
import re

import jax
import jax.numpy as jnp
import pytest

from tests.aot.programs import (
    _ARRAY,
    _PLUMBING,
    _mosaic_calls,
    _named,
    _pool_shaped,
    _programs_of,
    _reader_patterns,
    _serving_model,
    _short_names,
    _top_level,
)
from tests.aot.step_program_cases import (  # noqa: F401  (collected here)
    test_no_step_program_copies_a_weight,
)

CONFIGURATIONS = ["openpangu-ultra-moe-718b-serve", "kimi-linear-48b-a3b-serve"]


def test_pangu_steps_are_what_the_new_readers_look_for(one_chip):
    """openPangu-Ultra-MoE's two step programs at the cell's shapes (8
    slots x 3,072-row prompts, ``max_seq`` 3,456, 6 latent-attention
    layers of 128 heads, a 256-wide router over 8 held experts). **The
    decode step holds no expanded key or value per cached token and no
    gather of the pool**: its attention is ``latent_decode``, 2 calls in
    the text (the unrolled dense layer's + the scanned sparse layers'),
    the one 3-D Mosaic result ``bf16[8,128,512]``, and no array has a
    cached token's 128 heads x 128 (or 192, or 256) numbers for every
    position of a slot. The cached row is 640 wide (512 + 64 padded to
    whole tiles), written by one ``paged_write`` a layer. **The prefill
    call attends in key blocks, four groups of 32 heads one after the
    other, keys 192 and values 128 wide** (the flash
    forward's ``(bf16[8,32,3072,128], f32)``, the head count
    ``costs/pangu_ultra_moe.prefill_head_groups`` charges: no ``slots x
    heads x prefill_len x max_seq`` scores, which would be 43 GB, and no
    array of all 128 heads' expanded queries, 1.21 GB) **and
    bounds its sorted rows**: no array of ``slots x prefill_len x 8``
    rows x 7,680 (3.02 GB a copy; its blocks are ``[16384, 7680]``).
    Both fit the chip."""
    decode, prefill, pool_shape = _programs_of(
        one_chip, "openpangu-ultra-moe-718b-serve")
    slots, heads, rows, max_seq = 8, 128, 3072, 3456
    assert pool_shape == (6, slots * 216 + 1, 1, 16, 640)
    texts = {"decode": decode.as_text(), "prefill": prefill.as_text()}
    for name, text in texts.items():
        assert _pool_shaped(text, pool_shape) == {}, name
        writes = _named(_mosaic_calls(text), "paged_write")
        assert len(writes) == 2, (name, len(writes))    # ONE row a token
        for stack in ("bf16[5,8,7680,2048]", "bf16[8,7680,2048]",
                      "bf16[40,7680,2048]", "bf16[5,8,2048,7680]",
                      "bf16[8,2048,7680]", "bf16[40,2048,7680]"):
            moved = [x for x in _top_level(text, stack)
                     if x[0] not in _PLUMBING | {"bitcast"}
                     and "tpu_custom_call" not in x[1]]
            assert not moved, (name, moved[:3])

    def sizes(text):
        return {dims: math.prod(map(int, dims.split(",")))
                for dims in set(_ARRAY.findall(text))}

    # decode: nothing per cached token and head, nothing of a slot's
    # whole gathered context
    per_token_and_head = {slots * max_seq * heads * w
                          for w in (128, 192, 256, 320, 512, 576, 640)}
    gathered = {slots * max_seq * 640, slots * 216 * 16 * 640}
    found = sizes(texts["decode"])
    assert not [d for d, n in found.items()
                if n in per_token_and_head | gathered]
    assert f"f32[{slots},19200]" in texts["decode"]
    # prefill: no scores over the whole buffer, no unbounded sorted rows
    found = sizes(texts["prefill"])
    scores = {slots * heads * rows * max_seq, slots * heads * rows * rows}
    every_head = {slots * heads * rows * w for w in (192, 256)}
    assert not [d for d, n in found.items() if n in scores | every_head]
    unbounded = slots * rows * 8 * 7680
    assert not [d for d, n in found.items() if n >= unbounded]
    assert "bf16[16384,7680]" in texts["prefill"]       # a block's rows
    assert f"f32[{slots},19200]" in texts["prefill"]    # last_logits

    patterns = {name: _reader_patterns(name) for name in (
        "serve_pangu_latent_attn_mxu_roofline",
        "serve_pangu_latent_attn_hbm_roofline",
        "serve_pangu_expert_mlp_roofline",
        "serve_pangu_prefill_attn_roofline")}

    def matched(program, reader):
        return [n for n in _short_names(texts[program])
                if any(re.search(p, n) for p in patterns[reader])]

    for reader in ("serve_pangu_latent_attn_mxu_roofline",
                   "serve_pangu_latent_attn_hbm_roofline"):
        attn = matched("decode", reader)
        assert len(attn) == 2 and all(
            n.startswith("latent_decode")
            and n.endswith("bf16[8,128,512]") for n in attn), attn
        assert not matched("prefill", reader)
    experts = matched("decode", "serve_pangu_expert_mlp_roofline")
    assert len(experts) == 3 and all(n.startswith("gmm") for n in experts)
    assert sorted(n.rsplit(" | ", 1)[1] for n in experts) == (
        ["bf16[128,2048]"] * 2 + ["bf16[128,7680]"])
    assert not matched("prefill", "serve_pangu_expert_mlp_roofline")
    from benchmarks.costs import pangu_ultra_moe as costs

    config = _serving_model("openpangu-ultra-moe-718b-serve")[0]
    group = heads // costs.prefill_head_groups(config)
    flash = matched("prefill", "serve_pangu_prefill_attn_roofline")
    assert group == 32 and len(flash) == 2 and all(
        n.startswith("flash_fwd") and n.endswith(
            f"(bf16[8,{group},3072,128], f32[8,{group},1,3072])")
        for n in flash), flash
    assert not matched("decode", "serve_pangu_prefill_attn_roofline")
    assert not _named(_mosaic_calls(texts["decode"]), "paged_decode")

    cache_bytes = 2 * math.prod(pool_shape)
    chip = 15.75 * 2 ** 30
    for name, program, scratch in (("decode", decode, 0.1e9),
                                   ("prefill", prefill, 4.2e9)):
        memory = program.memory_analysis()
        assert memory.alias_size_in_bytes >= cache_bytes, name
        assert memory.temp_size_in_bytes < scratch, name
        assert (memory.argument_size_in_bytes
                + memory.temp_size_in_bytes) < 0.75 * chip, name


def test_kimi_steps_are_what_the_new_readers_look_for(one_chip):
    """Kimi-Linear's two step programs at the cell's shapes (32 slots,
    8,192-row prompts, ``max_seq`` 9,728; 6 KDA + 2 latent layers, a
    256-wide router over 64 held experts). **One cache, two kinds of
    memory**: the latent pool ``[2, 32 x 608 + 1, 1, 16, 640]`` is
    written by ONE ``paged_write`` a latent layer and returned by
    nothing else, and the state ``f32[6,32,32,128,128]`` is written in
    place (no copy returns it or a layer of it). **The decode step**
    reads the pool through ``latent_decode`` at 32 heads
    (``bf16[32,32,512]``, what ``serve_kimi_latent_attn_hbm_roofline``
    matches) and its experts through 21 ``gmm`` calls told by
    ``bf16[256,1024]`` / ``bf16[256,2304]`` (32 x 8 sorted rows:
    ``serve_kimi_expert_mlp_roofline``). **The prefill program is one
    row that names its slot**: ``s32[1,8192]`` tokens, its latent
    attention the flash forward over the row's 32 expanded heads (keys
    192, values 128 wide), no scores ``[heads, 8192, 8192]`` and none of
    the full shape's ``32 x 8192`` rows. Both fit the chip."""
    decode, prefill, pool_shape = _programs_of(
        one_chip, "kimi-linear-48b-a3b-serve")
    slots, rows = 32, 8192
    assert pool_shape == (2, slots * 608 + 1, 1, 16, 640)
    texts = {"decode": decode.as_text(), "prefill": prefill.as_text()}
    for name, text in texts.items():
        assert _pool_shaped(text, pool_shape) == {}, name
        writes = _named(_mosaic_calls(text), "paged_write")
        assert len(writes) == 2, (name, len(writes))   # one a latent layer
        for state in ("f32[6,32,32,128,128]", "f32[32,32,128,128]",
                      "f32[1,32,32,128,128]"):
            copies = [x for x in _top_level(text, state)
                      if x[0] in ("copy", "copy-start", "transpose")]
            assert not copies, (name, copies[:3])
        for stack in ("bf16[7,64,2304,1024]", "bf16[64,2304,1024]",
                      "bf16[448,2304,1024]", "bf16[7,64,1024,2304]",
                      "bf16[64,1024,2304]", "bf16[448,1024,2304]"):
            moved = [x for x in _top_level(text, stack)
                     if x[0] not in _PLUMBING | {"bitcast"}
                     and "tpu_custom_call" not in x[1]]
            assert not moved, (name, moved[:3])

    def sizes(text):
        return {dims: math.prod(map(int, dims.split(",")))
                for dims in set(_ARRAY.findall(text))}

    assert "s32[1,8192]" in texts["prefill"]
    found = sizes(texts["prefill"])
    scores = {32 * rows * rows, 32 * rows * 9728}
    full_shape = {slots * rows * 2304, slots * rows * 4096}
    assert not [d for d, n in found.items() if n in scores | full_shape]
    assert f"f32[{slots},40960]" in texts["decode"]

    patterns = {name: _reader_patterns(name) for name in (
        "serve_kimi_latent_attn_hbm_roofline",
        "serve_kimi_expert_mlp_roofline",
        "serve_kimi_kda_state_update_roofline")}

    def matched(program, reader):
        return [n for n in _short_names(texts[program])
                if any(re.search(p, n) for p in patterns[reader])]

    attn = matched("decode", "serve_kimi_latent_attn_hbm_roofline")
    assert len(attn) == 2 and all(
        n.startswith("latent_decode") and n.endswith("bf16[32,32,512]")
        for n in attn), attn
    assert not matched("prefill", "serve_kimi_latent_attn_hbm_roofline")
    experts = matched("decode", "serve_kimi_expert_mlp_roofline")
    assert experts and all(n.startswith("gmm") for n in experts)
    assert {n.rsplit(" | ", 1)[1] for n in experts} == {
        "bf16[256,1024]", "bf16[256,2304]"}
    assert not matched("prefill", "serve_kimi_expert_mlp_roofline")
    updates = matched("decode", "serve_kimi_kda_state_update_roofline")
    assert any("select_dynamic-update-slice_fusion" in n for n in updates)
    assert any("(f32[32,32,128], f32[32,32,128])" in n for n in updates)
    flash = _named(_mosaic_calls(texts["prefill"]), "flash_fwd")
    assert flash and all("bf16[1,32,8192,128]" in c for c in flash), flash
    assert not _named(_mosaic_calls(texts["decode"]), "paged_decode")

    cache_bytes = 2 * math.prod(pool_shape) + 6 * 32 * (
        32 * 128 * 128 * 4 + 3 * 12288 * 2)
    chip = 15.75 * 2 ** 30
    for name, program, scratch in (("decode", decode, 0.1e9),
                                   ("prefill", prefill, 2.4e9)):
        memory = program.memory_analysis()
        assert memory.alias_size_in_bytes >= cache_bytes, name
        assert memory.temp_size_in_bytes < scratch, name
        assert (memory.argument_size_in_bytes
                + memory.temp_size_in_bytes) < 0.75 * chip, name


def _latent_kernel_digest(slots, heads, max_pages, layers):
    """sha256 of ``latent_decode``'s traced program (the kernel's jaxpr,
    its grid and its operands; no source location is in it) at a cell's
    shape."""
    from scaletorch_tpu.ops.pallas.paged_attention import (
        pallas_latent_decode_attention,
    )

    arg = jax.ShapeDtypeStruct
    traced = jax.make_jaxpr(lambda q, pool, tables, pos: (
        pallas_latent_decode_attention(
            q, pool, tables, pos, layer=jnp.int32(1), value_width=512,
            scale=192 ** -0.5)))(
        arg((slots, heads, 640), jnp.bfloat16),
        arg((layers, slots * max_pages + 1, 1, 16, 640), jnp.bfloat16),
        arg((slots, max_pages), jnp.int32), arg((slots,), jnp.int32))
    return hashlib.sha256(str(traced).encode()).hexdigest()


@pytest.mark.parametrize("name,slots,heads,max_pages,layers,digest", [
    ("openpangu-ultra-moe-718b-serve", 8, 128, 216, 6,
     "957cbaedb8afdabafb1864902737c5cf485dd29ad0ae8e31c19e45b59fc7e713"),
    ("kimi-linear-48b-a3b-serve", 32, 32, 608, 2,
     "862da136927d7a76120782d05ab0594387b5e12f8ed4ed9e61655b175d78634b"),
], ids=["openpangu", "kimi-linear"])
def test_the_latent_cells_decode_with_the_kernel_they_were_measured_with(
        one_chip, name, slots, heads, max_pages, layers, digest):
    """PR 55 changed the dense decode kernel (its walk goes on into the
    next slot) and left ``latent_decode``, the same shape of loop beside
    it, alone: both latent cells' decode programs hold no
    ``paged_decode`` call, and the latent kernel traces to the program
    it traced to at the parent (`f9961ab`), at each cell's shape. A PR
    that means to change the latent kernel measures both cells and
    writes the new digests here."""
    decode, _, pool_shape = _programs_of(one_chip, name)
    assert pool_shape == (layers, slots * max_pages + 1, 1, 16, 640)
    calls = _mosaic_calls(decode.as_text())
    assert _named(calls, "latent_decode") and not _named(calls, "paged_decode")
    assert _latent_kernel_digest(slots, heads, max_pages, layers) == digest
