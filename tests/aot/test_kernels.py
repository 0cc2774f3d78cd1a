"""The paged kernels, the latent kernel, the Mamba kernels and (last
section) the training flash kernels, each alone, compiled for the v5e at
real widths with no chip attached: Mosaic refuses here what it would
refuse there (a slice off the tiling, too much VMEM or SMEM), which
interpret mode cannot see. Beside them what needs no configuration's
step programs: the traced programs' digests, the guard for weight copies
on text written by hand, and a narrow-headed model's two steps. The
configurations' whole step programs are this folder's other files, one
file a group of configurations. Quick tier, ~1 s a kernel case.
"""

import hashlib
import json
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

from benchmarks.lib.trace import short_name
from scaletorch_tpu.ops.pallas.flash import (
    MAX_CAUSAL_STEPS,
    causal_block_plan,
    flash_blocks,
    pallas_flash_attention,
)
from scaletorch_tpu.ops.pallas.paged_attention import (
    pallas_paged_decode_attention,
    pallas_paged_write,
)
from tests.aot.programs import (
    REPO,
    _mosaic_calls,
    _named,
    _pool_shaped,
    _serving_model,
    _serving_steps,
    _short_names,
    _weight_copies,
)
from tests.ops.test_flash_pallas import flash_call_blocks, pallas_calls


def _compiled_text(one_chip, b, hq, hkv, d, page, max_pages, dtype):
    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pool = arg((b * max_pages + 1, hkv, page, d), dtype)
    return jax.jit(pallas_paged_decode_attention).lower(
        arg((b, hq, d), dtype), pool, pool,
        arg((b, max_pages), jnp.int32), arg((b,), jnp.int32),
    ).compile().as_text()


def test_serving_shape_is_one_call_the_benchmark_can_find(one_chip):
    """qwen3-1.7b-serve: 16 slots, 16/8 heads x 128, page 16, 96 pages a
    slot. `benchmarks/metrics/serve_paged_attn_roofline.json` tells the
    kernel by its single 4-D bf16 result; a second Mosaic call, a tuple
    result or another name would make that metric count wrongly."""
    calls = _mosaic_calls(_compiled_text(
        one_chip, 16, 16, 8, 128, 16, 96, jnp.bfloat16))
    assert len(calls) == 1, calls
    assert re.search(r"%paged_decode\S* = bf16\[16,8,2,128\]", calls[0]), calls


@pytest.mark.parametrize("hq,hkv,page,max_pages,dtype", [
    (8, 8, 16, 96, jnp.bfloat16),     # MHA: one query row a KV head
    (32, 8, 16, 13, jnp.bfloat16),    # n_rep 4, a short last block
    (64, 8, 16, 13, jnp.bfloat16),    # n_rep 8
    (2, 1, 16, 96, jnp.bfloat16),     # one KV head of a tp shard
    (20, 1, 16, 216, jnp.bfloat16),   # Jamba2: 20 query rows, ONE KV head
    (16, 8, 8, 96, jnp.bfloat16),     # a page of half a bf16 tile
    (16, 8, 32, 48, jnp.bfloat16),
    (16, 8, 8, 13, jnp.float32),      # fp32 pools
], ids=["mha", "nrep4-ragged", "nrep8", "hkv1", "jamba-20-on-1", "page8",
        "page32", "fp32"])
def test_kernel_compiles_for_the_layouts_the_models_use(
        one_chip, hq, hkv, page, max_pages, dtype):
    calls = _mosaic_calls(_compiled_text(
        one_chip, 4, hq, hkv, 128, page, max_pages, dtype))
    assert len(calls) == 1, calls


@pytest.mark.parametrize("hq,hkv", [(16, 2), (4, 2)],
                         ids=["qwen3-next-group8", "group2"])
def test_kernel_compiles_for_a_256_wide_head(one_chip, hq, hkv):
    """qwen3-next-80b-a3b-serve: 16 query heads over 2 K/V heads of 256,
    16 slots x 96 pages of 16. Two lane tiles a head, 8 query rows a
    K/V head: still one call with the one 4-D bf16 result that
    ``serve_paged_attn_roofline`` tells the kernel by."""
    calls = _mosaic_calls(_compiled_text(
        one_chip, 16, hq, hkv, 256, 16, 96, jnp.bfloat16))
    assert len(calls) == 1, calls
    assert re.search(
        rf"%paged_decode\S* = bf16\[16,{hkv},{hq // hkv},256\]", calls[0]), calls


@pytest.mark.parametrize("layers,slots,hkv,rows,page,max_pages,dtype", [
    (28, 16, 8, 1, 16, 96, jnp.bfloat16),      # qwen3-1.7b-serve, decode
    (28, 16, 8, 1024, 16, 96, jnp.bfloat16),   # ... and its prefill
    (8, 16, 16, 1, 16, 96, jnp.bfloat16),      # olmoe-1b-7b-serve (MHA)
    (8, 16, 16, 1024, 16, 96, jnp.bfloat16),
    (2, 3, 8, 1000, 16, 96, jnp.bfloat16),     # a partly filled last page
    (2, 4, 1, 50, 16, 13, jnp.bfloat16),       # one KV head of a tp shard
    (2, 4, 8, 100, 8, 13, jnp.bfloat16),       # a page of half a bf16 tile
    (2, 4, 8, 1, 32, 13, jnp.bfloat16),
    (2, 4, 8, 20, 8, 13, jnp.float32),         # fp32 pools
], ids=["qwen-decode", "qwen-prefill", "olmoe-decode", "olmoe-prefill",
        "ragged-rows", "hkv1", "page8", "page32", "fp32"])
def test_page_write_compiles_and_aliases_the_pool(
        one_chip, layers, slots, hkv, rows, page, max_pages, dtype):
    """``paged_write``: one Mosaic call whose only result is the donated
    pool itself, with nothing of the pool's size beside it."""
    _page_write_aliases_the_pool(
        one_chip, layers, slots, hkv, rows, page, max_pages, dtype, 128)


@pytest.mark.parametrize("rows", [1, 512], ids=["decode", "prefill"])
def test_page_write_compiles_for_a_256_wide_head(one_chip, rows):
    """qwen3-next-80b-a3b-serve: 3 full-attention layers, 2 K/V heads of
    256."""
    _page_write_aliases_the_pool(
        one_chip, 3, 16, 2, rows, 16, 96, jnp.bfloat16, 256)


def _page_write_aliases_the_pool(one_chip, layers, slots, hkv, rows, page,
                                 max_pages, dtype, d):
    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pool = arg((layers, slots * max_pages + 1, hkv, page, d), dtype)
    compiled = jax.jit(
        lambda pool, *a: pallas_paged_write(pool, *a[:-1], layer=a[-1]),
        donate_argnums=0,
    ).lower(
        pool, arg((slots, hkv, rows, d), dtype),
        arg((slots, rows), jnp.int32), arg((slots, max_pages), jnp.int32),
        arg((slots,), jnp.bool_), arg((), jnp.int32),
    ).compile()
    calls = _mosaic_calls(compiled.as_text())
    assert len(calls) == 1 and _named(calls, "paged_write"), calls
    memory = compiled.memory_analysis()
    pool_bytes = math.prod(pool.shape) * jnp.dtype(dtype).itemsize
    assert memory.alias_size_in_bytes >= pool_bytes
    assert memory.temp_size_in_bytes < pool_bytes // 10


def test_decode_kernel_with_a_window_is_still_the_one_4d_call(one_chip):
    """trinity-mini-serve's window layers: 32 query heads over 4 K/V
    heads of 128, 8 slots, a table of 216 logical pages over a ring of
    129, the rings' buffer ``[12, 1 + 8 x 129, ...]`` read at a layer
    index. Told the window, the kernel is still one Mosaic call with the
    one 4-D bf16 result ``serve_trinity_paged_attn_roofline`` tells it
    by: the first page and the band are arithmetic on the
    scalar-prefetched position."""
    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    rings = arg((12, 1 + 8 * 129, 4, 16, 128), jnp.bfloat16)
    text = jax.jit(
        lambda q, k, v, tables, pos, layer: pallas_paged_decode_attention(
            q, k, v, tables, pos, layer=layer, window=2048)
    ).lower(
        arg((8, 32, 128), jnp.bfloat16), rings, rings,
        arg((8, 216), jnp.int32), arg((8,), jnp.int32), arg((), jnp.int32),
    ).compile().as_text()
    calls = _mosaic_calls(text)
    assert len(calls) == 1, calls
    assert re.search(r"%paged_decode\S* = bf16\[8,4,8,128\]", calls[0]), calls


@pytest.mark.parametrize("hkv,layers,pages,window", [
    (4, 2, 32 * 608 + 1, None), (8, 5, 32 * 9 + 1, 128)],
    ids=["full", "window-sink"])
def test_decode_kernel_compiles_at_two_widths_and_with_a_sink(
        one_chip, hkv, layers, pages, window):
    """mimo-v2-flash-serve's two calls alone: 64 query heads (padded to
    the stored key's 256) over 4 or 8 K/V heads, a K buffer 256 wide
    beside a V buffer of 128, the window layers' under a band of 128 and
    a float32 sink a head. One Mosaic call each whose one 4-D result has
    the VALUE's width."""
    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    sink = None if window is None else arg((64,), jnp.float32)
    text = jax.jit(
        lambda q, k, v, tables, pos, layer, sink: (
            pallas_paged_decode_attention(
                q, k, v, tables, pos, layer=layer, window=window,
                scale=192 ** -0.5, sink=sink))
    ).lower(
        arg((32, 64, 256), jnp.bfloat16),
        arg((layers, pages, hkv, 16, 256), jnp.bfloat16),
        arg((layers, pages, hkv, 16, 128), jnp.bfloat16),
        arg((32, 608), jnp.int32), arg((32,), jnp.int32),
        arg((), jnp.int32), sink,
    ).compile().as_text()
    calls = _mosaic_calls(text)
    assert len(calls) == 1, calls
    assert re.search(
        rf"%paged_decode\S* = bf16\[32,{hkv},{64 // hkv},128\]", calls[0]), calls


def _traced_digest(fn, *shapes):
    """sha256 of a function's traced program (a kernel's jaxpr, its grid
    and its operands; no source location is in it)."""
    return hashlib.sha256(str(jax.make_jaxpr(fn)(*(
        jax.ShapeDtypeStruct(shape, dt) for shape, dt in shapes
    ))).encode()).hexdigest()


def _decode_digest(slots, hq, hkv, d, max_pages, layers, window=None,
                   ring=None):
    pool = ((layers, 1 + slots * (ring or max_pages), hkv, 16, d),
            jnp.bfloat16)
    return _traced_digest(
        lambda q, k, v, t, p: pallas_paged_decode_attention(
            q, k, v, t, p, layer=jnp.int32(1), window=window),
        ((slots, hq, d), jnp.bfloat16), pool, pool,
        ((slots, max_pages), jnp.int32), ((slots,), jnp.int32))


def _flash_digest(b, hq, hkv, s, dk, dv, window=None):
    from scaletorch_tpu.ops.pallas.flash import flash_forward_with_lse

    return _traced_digest(
        lambda q, k, v: flash_forward_with_lse(
            q, k, v, causal=True, window=window),
        ((b, hq, s, dk), jnp.bfloat16), ((b, hkv, s, dk), jnp.bfloat16),
        ((b, hkv, s, dv), jnp.bfloat16))


def _flash_training_digest():
    def grads(q, k, v):
        return jax.grad(lambda q, k, v: pallas_flash_attention(
            q, k, v).astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    return _traced_digest(
        grads, ((1, 16, 8192, 128), jnp.bfloat16),
        ((1, 8, 8192, 128), jnp.bfloat16), ((1, 8, 8192, 128), jnp.bfloat16))


@pytest.mark.parametrize("digest,want", [
    (lambda: _decode_digest(16, 16, 8, 128, 96, 28),
     "046d94fb0dfc6142a1326c878c3d69afcb1d6c9b2e5d69e3d105efd4ed69a36f"),
    (lambda: _decode_digest(16, 16, 2, 256, 96, 12),
     "ba7e362e411dca4be7a27145e8976705f59d86e7a3afe02e486c93bfdb28e155"),
    (lambda: _decode_digest(8, 32, 4, 128, 216, 4),
     "0d72caf1a79f0f5a610f4aedaf3bbbe6bceef7b405b1c78502a285a691c36a73"),
    (lambda: _decode_digest(8, 32, 4, 128, 216, 12, window=2048, ring=129),
     "50dd7211c900f3421087126cd90a1fc511882ac33c99322b3b8d0f959ee9b184"),
    (_flash_training_digest,
     "e74c658a1a00225f889938675350843f602ddc170bccd408ee464ae149fab54a"),
    (lambda: _flash_digest(8, 32, 4, 3072, 128, 128, window=2048),
     "b38cb64712e0d11977e7af01c6820523ad3869c25db1c63ad9502649371e2dde"),
    (lambda: _flash_digest(8, 128, 128, 3072, 192, 128),
     "3bbf465cd93715673f249ffd8859cbdde974915a56df64eda4929b7f8f31d6c8"),
    (lambda: _flash_digest(1, 16, 8, 512, 128, 128),
     "550b2429ad94ca8d6e5b811ea603580c20c7971f73b724c36b9cef44820e8485"),
    # PR 62, computed at its parent `e199970`: the 8,192-token rows, the
    # only serving forwards long enough for the rule to have moved them
    (lambda: _flash_digest(1, 64, 4, 8192, 192, 128),
     "d11a365e000e0411a630ea6fb241202bc770304a3188e43f4dbaaf075cac487b"),
    (lambda: _flash_digest(1, 32, 32, 8192, 192, 128),
     "18733b9d3f97db2dcd0a5ef3f4ca3dbde4213b588d0ec7b57a01758a10b624ce"),
    (lambda: _flash_digest(1, 16, 8, 8192, 128, 128),
     "0d17ee40655e127933224a6a397f422069d362211fd77b9f5b2cc9a2e3aca671"),
], ids=["decode-longgen", "decode-qwen3-next", "decode-trinity-full",
        "decode-trinity-window", "flash-training-fwd-bwd",
        "flash-trinity-window", "flash-openpangu-192-128",
        "flash-longgen-row", "flash-mimo-full-row", "flash-kimi-linear-row",
        "flash-training-fwd"])
def test_callers_without_a_sink_at_one_width_trace_to_what_they_did(
        digest, want):
    """PR 59 gave the decode kernel and the flash forward a value width
    of their own and an optional sink. A caller with neither must get
    the program it had: each traced program below (the decode kernel at
    three cells' shapes and under Trinity-Mini's window, the flash
    forward and backward at the training cell's shape, the flash forward
    under Trinity-Mini's window, at openPangu's 192 / 128 heads and at
    the one-row call of the dense cells) hashes to what it hashed to at
    the parent (`7c85100`, computed there with the same functions). A PR
    that means to change one of these kernels measures the cells that
    run it and writes the new digests here. PR 62 did for the training
    cell's forward and backward: ``flash_dq`` runs in 1,024 x 512 blocks
    and ``flash_dkv`` in 1,024 x 1,024 there (``flash_blocks``), the
    forward and every serving caller in the blocks they had."""
    assert digest() == want


def test_the_latent_kernel_compiles_for_a_longer_table(one_chip):
    """``latent_decode`` alone at 128 heads over a 640-wide row with a
    table of 8,192 pages (131,072 positions, the published context): one
    Mosaic call, the pool an operand left in HBM."""
    from scaletorch_tpu.ops.pallas.paged_attention import (
        pallas_latent_decode_attention,
    )

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    text = jax.jit(lambda q, pool, tables, pos: (
        pallas_latent_decode_attention(
            q, pool, tables, pos, layer=jnp.int32(1), value_width=512,
            scale=192 ** -0.5))).lower(
        arg((2, 128, 640), jnp.bfloat16),
        arg((2, 16385, 1, 16, 640), jnp.bfloat16),
        arg((2, 8192), jnp.int32), arg((2,), jnp.int32)).compile().as_text()
    calls = _named(_mosaic_calls(text), "latent_decode")
    assert len(calls) == 1, calls


def test_the_flash_forward_takes_a_value_width_of_its_own(one_chip):
    """Keys 192 and values 128 wide (latent attention's expanded heads):
    one ``flash_fwd`` whose output and accumulator are the value's
    width; a call with one width lowers to what it lowered to."""
    from scaletorch_tpu.ops.pallas.flash import flash_forward_with_lse

    def arg(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def lowered(dk, dv):
        return jax.jit(lambda q, k, v: flash_forward_with_lse(
            q, k, v, causal=True)).lower(
            arg((1, 4, 1024, dk)), arg((1, 4, 1024, dk)),
            arg((1, 4, 1024, dv)))

    text = lowered(192, 128).compile().as_text()
    names = [n for n in _short_names(text) if n.startswith("flash_fwd")]
    assert len(names) == 1 and names[0].endswith(
        "(bf16[1,4,1024,128], f32[1,4,1,1024])"), names
    names = [n for n in _short_names(lowered(128, 128).compile().as_text())
             if n.startswith("flash_fwd")]
    assert len(names) == 1 and names[0].endswith(
        "(bf16[1,4,1024,128], f32[1,4,1,1024])"), names


@pytest.mark.parametrize("block_t", [128, 256])
def test_ssm_scan_kernel_compiles_at_the_cell_s_shape(one_chip, block_t):
    """One Mamba layer's prefill call: 8 x 3,072 rows of 5,120 channels,
    16 states held as ``[16, 40, 128]``. One Mosaic call; its scalars (B and C,
    ``[block_t, 16]`` float32 a grid step) fit SMEM at 128 and 256 rows
    a step (512 did not on the v5e: PERF.md, PR 47)."""
    from scaletorch_tpu.ops.pallas.ssm_scan import ssm_scan_fwd

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    text = jax.jit(
        lambda *xs: ssm_scan_fwd(*xs, block_t=block_t)).lower(
        arg(8, 3072, 5120), arg(8, 3072, 5120), arg(16, 40, 128),
        arg(8, 3072, 16), arg(8, 3072, 16), arg(8, 16, 40, 128),
    ).compile().as_text()
    calls = _mosaic_calls(text)
    assert len(calls) == 1 and _named(calls, "ssm_scan_fwd"), calls


def test_a_mamba2_decode_layer_advances_its_state_in_one_pass(one_chip):
    """One Mamba-2 layer of granite-4.0-h-small-serve's decode step at
    the cell's shapes (64 slots, a ``[128, 8192]`` float32 state a slot,
    nine layers in the donated buffer), compiled for the v5e: the state
    is advanced by ONE Mosaic call (``ops/pallas/ssd_update.py``) that
    the whole buffer goes into and comes out of (aliased: no copy of
    it), ``y`` comes out of the same call, and no other instruction of
    the program, fused ones included, has a result of the buffer's or of
    one layer's shape: no ``[slots, 128, 8192]`` temporary beside it.
    Written in XLA the update compiles to two fusions that each read the
    state (PERF.md, PR 61)."""
    from scaletorch_tpu.models import granite_moe_hybrid as granite

    config, cfg, init = _serving_model("granite-4.0-h-small-serve")
    slots = config["serve"]["max_slots"]
    state_shape, tail_shape = cfg.recurrent_state_shapes(slots)
    assert state_shape == (9, 64, 128, 8192)
    assert granite.update_kernel_serves(cfg)    # FORCE_PALLAS: the fixture

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = jax.eval_shape(lambda: init(jax.random.PRNGKey(0), cfg))
    layer = jax.tree.map(lambda a: arg(a.shape[1:], a.dtype),
                         params["layers"]["mamba"])

    def step(u, layer, states, tail, fresh, written):
        return granite.mamba2_decode(u, layer, cfg, states, 4, tail, fresh,
                                     written, row_mask=written[:, None])

    flags = arg((slots,), jnp.bool_)
    compiled = jax.jit(step, donate_argnums=2).lower(
        arg((slots, 1, cfg.hidden_size), cfg.dtype), layer,
        arg(state_shape, jnp.float32), arg(tail_shape[1:], cfg.dtype),
        flags, flags).compile()
    text = compiled.as_text()
    calls = _named(_mosaic_calls(text), "ssd_state_update")
    assert len(calls) == 1, calls
    # (``_pool_shaped`` asks of any ``[layers, ...]`` buffer)
    assert _pool_shaped(text, state_shape) == {}
    memory = compiled.memory_analysis()
    state_bytes = 9 * 64 * 128 * 8192 * 4
    assert memory.alias_size_in_bytes == state_bytes
    # the projections' activations and the kernel's small operands: far
    # under one layer's 268 MB of state
    assert memory.temp_size_in_bytes < 64 * 2 ** 20


def test_narrow_heads_take_the_lax_pair_in_the_same_loop(one_chip):
    """head_dim 64: no kernel serves it, so the carried loop scatters
    and gathers. What a compile found (PERF.md, PR 28): no layer is
    sliced out of the pool or stacked back (the parent did both, eight
    layer-sized operations a layer), the scatter updates the carried
    pool in place, and what is left are four whole-pool copies at the
    program's edge, because the device keeps a 64-wide pool pages-minor
    and the scatter wants rows: the pool pair once more in the scatter's
    padded layout, 2.0 times its bytes of temp (the parent: 1.4)."""
    from scaletorch_tpu.models.llama import LlamaConfig, init_params

    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=1024, intermediate_size=2816,
        num_hidden_layers=16, num_attention_heads=16, num_key_value_heads=8,
        head_dim=64, max_position_embeddings=4096, dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16)
    decode, prefill, pool_shape = _serving_steps(
        one_chip, cfg, init_params, slots=16, max_seq=1536,
        prefill_len=1024, page_size=16)
    pool_pair = 2 * 2 * math.prod(pool_shape)
    for program in (decode, prefill):
        text = program.as_text()
        # nothing Mosaic touches the pool; a call of cold prompts
        # attends in key blocks, which the flash forward does at 64
        assert not [c for c in _mosaic_calls(text) if "flash_fwd" not in c]
        assert bool(_mosaic_calls(text)) == (program is prefill)
        found = _pool_shaped(text, pool_shape)
        assert set(found) <= {"copy", "fusion", "scatter", "bitcast"}, found
        assert found.get("copy", 0) <= 4, found
        layer = ",".join(map(str, pool_shape[1:]))
        assert not re.search(
            rf"= bf16\[(1,)?{layer}\]\S* (?!parameter)", text)
    assert decode.memory_analysis().temp_size_in_bytes < 2.1 * pool_pair


_TILED = "{1,2,0:T(8,128)(2,1)}"
_FAST = "{1,2,0:T(8,128)(2,1)S(1)}"
# MiMo-V2-Flash's decode program at the parent of PR 60: seven static
# slices of the re-laid ``q_proj`` stack in one loop fusion, six results
# in HBM and one in fast memory (two and one here)
_MIMO_Q_LAYERS = f"""
%fused_computation.845 (param_0.2778: bf16[7,4096,12288]) -> (bf16[1,4096,12288], bf16[1,4096,12288], bf16[1,4096,12288]) {{
  %param_0.2778 = bf16[7,4096,12288]{_TILED} parameter(0)
  %slice.622 = bf16[1,4096,12288]{_TILED} slice(%param_0.2778), slice={{[2:3], [0:4096], [0:12288]}}
  %slice.623 = bf16[1,4096,12288]{_FAST} slice(%param_0.2778), slice={{[1:2], [0:4096], [0:12288]}}
  %slice.624 = bf16[1,4096,12288]{_TILED} slice(%param_0.2778), slice={{[0:1], [0:4096], [0:12288]}}
  ROOT %tuple.105 = (bf16[1,4096,12288]{_TILED}, bf16[1,4096,12288]{_FAST}, bf16[1,4096,12288]{_TILED}) tuple(%slice.622, %slice.623, %slice.624)
}}
ENTRY %main.143 (p: bf16[7,4096,12288]) -> bf16[32,19072] {{
  %fusion.519 = (bf16[1,4096,12288]{_TILED}, bf16[1,4096,12288]{_FAST}, bf16[1,4096,12288]{_TILED}) fusion(%bitcast.6), kind=kLoop, calls=%fused_computation.845, metadata={{op_name="jit(decode)/slice" stack_frame_id=53}}
}}"""
# a layer of ``o_proj`` sliced inside the fusion of the matmul that
# reads it, and a residual sum with a weight's shape: nothing is copied
_NO_COPIES = f"""
%fused_computation.7 (param_0.1: bf16[28,2048,2048], param_1.1: bf16[16,2048]) -> bf16[16,2048] {{
  %param_0.1 = bf16[28,2048,2048]{_TILED} parameter(0)
  %slice.583 = bf16[1,2048,2048]{_TILED} slice(%param_0.1), slice={{[3:4], [0:2048], [0:2048]}}
  ROOT %convolution.1 = bf16[16,2048]{{1,0:T(8,128)(2,1)}} convolution(%param_1.1, %slice.583), dim_labels=bf_io->bf
}}
%fused_computation.8 (param_0.2: bf16[1,2048,4096], param_1.2: bf16[1,2048,4096]) -> bf16[1,2048,4096] {{
  ROOT %add.1 = bf16[1,2048,4096]{_TILED} add(%param_0.2, %param_1.2)
}}
ENTRY %main.9 (p: bf16[28,2048,2048]) -> bf16[16,2048] {{
  %fusion.7 = bf16[16,2048]{{1,0:T(8,128)(2,1)}} fusion(%p.1, %x.1), kind=kOutput, calls=%fused_computation.7
  %fusion.8 = bf16[1,2048,4096]{_TILED} fusion(%x.2, %x.3), kind=kLoop, calls=%fused_computation.8
}}"""


@pytest.mark.parametrize("line,found", [
    # the parent's decode programs (ISSUE 48: the ledger's costliest
    # copies by name), and a copy that is no weight's
    ("  %copy.372 = bf16[16,2048,4096]{1,2,0:T(8,128)(2,1)} copy(%p.1)", 1),
    ("  %copy.61 = bf16[1,2048,2048]{1,2,0:T(8,128)(2,1)S(1)} copy(%f.2)", 1),
    ("  %copy.9 = bf16[4096,2048]{0,1:T(8,128)(2,1)S(1)} copy(%b.3)", 1),
    ("  %copy.3 = bf16[16,1024,128]{1,2,0:T(8,128)(2,1)S(1)} copy(%f.2)", 0),
    ("  %copy.4 = bf16[1,2048,128]{1,2,0:T(8,128)(2,1)} copy(%f.2)", 0),
    # jamba's x_proj stack, fetched into fast memory as it lies (the
    # parent does it too): a move, not a re-laying
    ("  %copy.95 = bf16[16,2048,4096]{2,1,0:T(8,128)(2,1)S(1)} copy(%p.1)", 0),
    # slices of a weight materialised (ISSUE 60): MiMo's seven layers of
    # ``q_proj`` a step; the one of them that lands in fast memory, alone;
    # Qwen3-1.7B's layer of ``o_proj`` under the loop's counter, the
    # weight's one reading; a slice on its own into HBM; what computes
    (_MIMO_Q_LAYERS, 1),
    (f"  %fusion.9 = bf16[1,4096,12288]{_FAST} fusion(%bitcast.6), "
     "kind=kLoop, calls=%fused_computation.9", 0),
    (f"  %constant_dynamic-slice_fusion.11 = bf16[1,2048,2048]{_FAST} "
     "fusion(%get-tuple-element.766, %get-tuple-element.728), kind=kLoop, "
     "calls=%fused_computation.69.clone.clone.clone", 0),
    (f"  %dynamic-slice.4 = bf16[1,2048,4096]{_TILED} dynamic-slice(%p.1, "
     "%i.1, %c.0, %c.0), dynamic_slice_sizes={1,2048,4096}", 1),
    (f"  %slice.5 = bf16[1,2048,128]{_TILED} slice(%p.2), "
     "slice={[3:4], [0:2048], [0:128]}", 0),
    (_NO_COPIES, 0),
], ids=["a-stack", "a-layer", "a-layer-transposed", "no-weight", "small",
        "as-it-lies", "mimo-q-proj-layers", "a-layer-into-fast-memory",
        "qwen3-o-proj-into-fast-memory", "a-slice-on-its-own",
        "a-small-slice", "inside-a-matmul-and-a-sum"])
def test_the_guard_finds_the_copies_the_parent_made(line, found):
    weights = {"q_proj": jax.ShapeDtypeStruct((16, 2048, 4096), jnp.bfloat16),
               "o_proj": jax.ShapeDtypeStruct((28, 2048, 2048), jnp.bfloat16),
               "norm": jax.ShapeDtypeStruct((28, 2048, 128), jnp.bfloat16),
               "mimo": jax.ShapeDtypeStruct((7, 4096, 12288), jnp.bfloat16)}
    text = "\n".join([
        "  %p.1 = bf16[16,2048,4096]{2,1,0:T(8,128)(2,1)} parameter(0)",
        "  %f.2 = bf16[1,2048,2048]{2,1,0:T(8,128)(2,1)S(1)} fusion(%p.1)",
        "  %b.3 = bf16[4096,2048]{1,0:T(8,128)(2,1)} bitcast(%f.2)", line])
    assert len(_weight_copies(text, weights)) == found


# ---------------------------------------------------------------------------
# the flash kernels (training): what the compiled text shows is what the
# benchmark's readers will find in a trace. They tell the three kernels
# by what each RETURNS, so a fourth kind of Mosaic call, a fused backward
# or a result of another shape would leave `train_attn_roofline` with
# nothing, or the wrong thing, to read.
# ---------------------------------------------------------------------------
def _flash_args(one_chip, hq, hkv, s, d):
    """``q, k, v`` at batch 1, bf16, on the one chip."""
    return [jax.ShapeDtypeStruct((1, heads, s, d), jnp.bfloat16,
                                 sharding=one_chip)
            for heads in (hq, hkv, hkv)]


def _flash_calls(one_chip, fn, hq, hkv, s, d):
    """The Mosaic calls of ``fn(q, k, v)`` at batch 1, bf16, as the trace
    reader names them: ``instr | opcode | target | result``."""
    text = jax.jit(fn).lower(
        *_flash_args(one_chip, hq, hkv, s, d)).compile().as_text()
    return [short_name(re.sub(r"^\s*(ROOT )?", "", line))
            for line in _mosaic_calls(text)]


def _flash_grad(**kw):
    return jax.grad(
        lambda q, k, v: jnp.sum(pallas_flash_attention(
            q, k, v, **kw).astype(jnp.float32)),
        argnums=(0, 1, 2))


def _flash_kernel(name):
    """``jvp_flash_fwd_.1 | custom-call | ...`` -> ``flash_fwd``: the
    instruction carries the kernel's name inside its autodiff scope."""
    found = re.search(r"flash_(fwd|dq|dkv)", name.split(" | ")[0])
    return found.group(0) if found else name


def _flash_kinds(calls):
    return sorted({_flash_kernel(name) for name in calls})


# what ``flash_blocks`` says at qwen3-0.6b-train's shape, written out: a
# later edit that sends the training call back to 512 x 512 fails here
TRAINING_BLOCKS = {"flash_fwd": (512, 512), "flash_dq": (1024, 512),
                   "flash_dkv": (1024, 1024)}


def test_training_shape_is_three_kernels_the_roofline_can_find(one_chip):
    """qwen3-0.6b-train at seq 8192: 1 x 16 / 8 heads x 8192 x 128, bf16,
    causal. Forward and gradient compile; the program holds the three
    kernels and no other, and the patterns of
    ``benchmarks/metrics/train_attn_roofline.json`` charge one call as
    a forward and two as one backward."""
    calls = _flash_calls(one_chip, _flash_grad(), 16, 8, 8192, 128)
    assert _flash_kinds(calls) == ["flash_dkv", "flash_dq", "flash_fwd"]
    assert len(calls) == 3, calls

    with open(os.path.join(
            REPO, "benchmarks", "metrics", "train_attn_roofline.json")) as f:
        terms = json.load(f)["reducer"]["terms"]
    found = {term["charge"]: [
        name for name in calls
        if any(re.search(p, name) for p in term["patterns"])]
        for term in terms}
    assert [_flash_kernel(n) for n in found["forward"]] == ["flash_fwd"], found
    assert _flash_kinds(found["backward"]) == ["flash_dkv", "flash_dq"], found
    assert len(found["backward"]) == 2 == terms[1]["events_per_call"], found
    assert found["forward"][0].endswith(
        "(bf16[1,16,8192,128], f32[1,16,1,8192])"), found
    # and the other reader of these calls counts exactly the same three
    with open(os.path.join(
            REPO, "benchmarks", "metrics",
            "train_attn_kernel_share.json")) as f:
        share = json.load(f)["reducer"]["patterns"]
    assert [n for n in calls if any(re.search(p, n) for p in share)] == calls

    # the blocks are the rule's (``flash_blocks``: PERF.md, PR 62), a pair
    # a kernel, and no plan at them holds a dead grid step
    traced = jax.jit(_flash_grad()).trace(
        *_flash_args(one_chip, 16, 8, 8192, 128))
    blocks = flash_call_blocks(traced.jaxpr.jaxpr)
    assert blocks == {"flash_" + kind: flash_blocks(kind, 8192, 8192)
                      for kind in ("fwd", "dq", "dkv")}
    assert blocks == TRAINING_BLOCKS, "the rule's answer at the cell's shape"
    grids = {call.params["name"]: call.params["grid_mapping"].grid
             for call in pallas_calls(traced.jaxpr.jaxpr)}
    for name, (bq, bkv) in blocks.items():
        plan = causal_block_plan(8192, 8192, bq, bkv)
        # a query block sees the key blocks up to its own last row
        assert plan.live == sum(
            -(-(i + 1) * bq // bkv) for i in range(8192 // bq))
        assert plan.dead == 0
        assert len(plan.by_query[0]) == len(plan.by_key[0]) == plan.live
        heads, rep = ((8, (2,)) if name == "flash_dkv" else (16, ()))
        assert grids[name] == (1, heads, plan.live, *rep), grids


@pytest.mark.parametrize("blocks", [None, (1024, 2048)],
                         ids=["the-rule", "1024x2048"])
def test_flash_dkv_carries_the_vmem_limit_its_blocks_compute(
        one_chip, blocks):
    """``flash_dkv`` at the cell's shape: the lowered call carries what
    ``_vmem_limit`` computes from its blocks and the backward compiles
    to its two Mosaic calls. In the rule's blocks that is None, Mosaic's
    16 MiB default (1,024 x 1,024 needs 9). At 1,024 x 2,048 the call
    needs 17 MiB (AOT, PR 62, bisected) and does not compile without
    the limit; the sum asks for 24."""
    from scaletorch_tpu.ops.pallas.flash import flash_block_backward

    q, k, v = _flash_args(one_chip, 16, 8, 8192, 128)
    lse = jax.ShapeDtypeStruct((1, 16, 8192), jnp.float32, sharding=one_chip)
    kw = dict(block_q=blocks[0], block_kv=blocks[1]) if blocks else {}
    traced = jax.jit(lambda q, k, v, out, lse, g: flash_block_backward(
        q, k, v, out, lse, g, causal=True, **kw)).trace(q, k, v, q, lse, q)
    bq, bkv = flash_call_blocks(traced.jaxpr.jaxpr)["flash_dkv"]
    assert (bq, bkv) == (blocks or flash_blocks("dkv", 8192, 8192))
    (limit,) = [call.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes
                for call in pallas_calls(traced.jaxpr.jaxpr)
                if call.params["name"] == "flash_dkv"]
    assert limit == (24 * 2 ** 20 if blocks else None)
    calls = _mosaic_calls(traced.lower().compile().as_text())
    assert len(calls) == 2 and _named(calls, "flash_dkv"), calls


def test_flash_forward_alone_is_one_call(one_chip):
    calls = _flash_calls(
        one_chip, lambda q, k, v: pallas_flash_attention(q, k, v),
        16, 8, 8192, 128)
    assert _flash_kinds(calls) == ["flash_fwd"] and len(calls) == 1, calls


@pytest.mark.parametrize("d", [128, 64, 256])
def test_flash_forward_holds_its_statistics_a_register_wide(one_chip, d):
    """The running maximum and sum are ``(bq, 128)`` float32 in VMEM,
    every lane of a row the row's value, whatever the head's width: a
    ``(bq, 1)`` scratch uses one lane of 128 in every register it
    touches and cost the forward 1.4 of its 4.0 ms on the chip (PERF.md,
    PR 38). A later edit that narrows them fails here, on a CPU."""
    traced = jax.jit(lambda q, k, v: pallas_flash_attention(q, k, v)).trace(
        *_flash_args(one_chip, 16, 8, 8192, d))
    (call,) = pallas_calls(traced.jaxpr.jaxpr)
    assert call.params["name"] == "flash_fwd"
    bq, _ = flash_blocks("fwd", 8192, 8192)
    scratch = call.params["grid_mapping"].scratch_avals
    assert [(str(ref.memory_space), ref.shape, ref.dtype)
            for ref in scratch] == [
        ("vmem", (bq, d), jnp.float32),     # the output's accumulator
        ("vmem", (bq, 128), jnp.float32),   # running max
        ("vmem", (bq, 128), jnp.float32),   # running sum
    ]
    assert len(_mosaic_calls(traced.lower().compile().as_text())) == 1


@pytest.mark.parametrize("hq,hkv,s,d,kw", [
    (16, 8, 8192, 128, dict(causal=False)),  # ring's off-diagonal hops
    (16, 8, 2048, 128, {}),     # its diagonal hop at cp 4
    (8, 1, 4096, 128, {}),      # MQA: eight query heads a key block
    (16, 16, 4096, 64, {}),     # MHA at head_dim 64
    (4, 2, 1536, 128, {}),      # three blocks a side
    (32, 8, 32768, 128, {}),    # 64 x 64 blocks, 2,080 live
    (4, 2, 131072, 128, {}),    # Ulysses at cp 4: the whole 128k sequence
    # the longest walk the tables take (flash.MAX_CAUSAL_STEPS), in SMEM
    (8, 1, 361 * 128, 128, dict(block_q=128, block_kv=128)),
], ids=["rect", "seq2k", "mqa", "mha-d64", "seq1536", "seq32k-nrep4",
        "seq128k", "longest-walk"])
def test_flash_compiles_for_its_other_callers_shapes(
        one_chip, hq, hkv, s, d, kw):
    calls = _flash_calls(one_chip, _flash_grad(**kw), hq, hkv, s, d)
    assert _flash_kinds(calls) == ["flash_dkv", "flash_dq", "flash_fwd"]
    assert len(calls) == 3, calls
    if kw.get("block_q"):
        assert causal_block_plan(s, s, 128, 128).live == 65341
        assert 65341 <= MAX_CAUSAL_STEPS < 362 * 363 // 2
