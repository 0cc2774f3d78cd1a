"""The paged-decode kernel compiled for the v5e at real widths, with no
chip attached: Mosaic refuses here what it would refuse there (a slice
off the tiling, too much VMEM), which interpret mode cannot see. One
file, one fixture, nothing at import time: only the worker that runs
this file loads the TPU compiler. Quick tier, ~1 s a case.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from scaletorch_tpu.ops.pallas.paged_attention import (
    pallas_paged_decode_attention,
)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as env:
        env.setenv("TPU_SKIP_MDS_QUERY", "1")
        env.setenv("TPU_WORKER_HOSTNAMES", "localhost")
        env.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


def _compiled_text(one_chip, b, hq, hkv, d, page, max_pages, dtype):
    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pool = arg((b * max_pages + 1, hkv, page, d), dtype)
    return jax.jit(pallas_paged_decode_attention).lower(
        arg((b, hq, d), dtype), pool, pool,
        arg((b, max_pages), jnp.int32), arg((b,), jnp.int32),
    ).compile().as_text()


def _mosaic_calls(text):
    return [line for line in text.splitlines()
            if "custom-call(" in line and "tpu_custom_call" in line]


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
    (16, 8, 8, 96, jnp.bfloat16),     # a page of half a bf16 tile
    (16, 8, 32, 48, jnp.bfloat16),
    (16, 8, 8, 13, jnp.float32),      # fp32 pools
], ids=["mha", "nrep4-ragged", "nrep8", "hkv1", "page8", "page32", "fp32"])
def test_kernel_compiles_for_the_layouts_the_models_use(
        one_chip, hq, hkv, page, max_pages, dtype):
    calls = _mosaic_calls(_compiled_text(
        one_chip, 4, hq, hkv, 128, page, max_pages, dtype))
    assert len(calls) == 1, calls
