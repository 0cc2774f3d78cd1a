"""KV-cache containers: shapes, dtypes, write semantics of the
contiguous reference cache (the page pool: test_paged_cache.py)."""

import jax.numpy as jnp
import numpy as np
import pytest

from scaletorch_tpu.inference.kv_cache import (
    KVCache,
    init_kv_cache,
    init_mla_cache,
    kv_cache_bytes,
    kv_cache_shape,
)
from scaletorch_tpu.models.attention.base import AttentionConfig
from scaletorch_tpu.models.gpt_moe import GPTMoEConfig
from scaletorch_tpu.models.layers import write_kv_cache
from scaletorch_tpu.models.llama import LlamaConfig

TINY = LlamaConfig(
    vocab_size=64, hidden_size=32, intermediate_size=64,
    num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
    dtype=jnp.float32,
)


class TestShapes:
    def test_llama_layout(self):
        assert kv_cache_shape(TINY, 2, 16) == (3, 2, 2, 16, 8)

    def test_gpt_moe_layout(self):
        cfg = GPTMoEConfig(block_size=32, n_layer=2, n_head=4, n_embd=64)
        assert kv_cache_shape(cfg, 2, 32) == (2, 2, 4, 32, 16)

    def test_unknown_config_raises(self):
        with pytest.raises(TypeError, match="no KV-cache layout"):
            kv_cache_shape(object(), 1, 8)

    def test_init_zeroed_in_compute_dtype(self):
        cache = init_kv_cache(TINY, 2, 16)
        assert isinstance(cache, KVCache)
        assert cache.k.shape == (3, 2, 2, 16, 8)
        assert cache.k.dtype == jnp.float32
        assert not np.any(np.asarray(cache.v))

    def test_bytes_accounting(self):
        """Nine pages of four tokens: k and v, three layers, two KV
        heads of eight lanes, four bytes each."""
        assert kv_cache_bytes(TINY, 9, 4) == 2 * 3 * 9 * 2 * 4 * 8 * 4
        assert kv_cache_bytes(TINY, 9, 4, dtype=jnp.bfloat16) == \
            kv_cache_bytes(TINY, 9, 4) // 2

    def test_mla_latent_only(self):
        acfg = AttentionConfig(embed_dim=64, num_heads=8, kv_lora_rank=16)
        cache = init_mla_cache(acfg, 2, 12)
        assert cache.latent.shape == (2, 12, 16)


class TestWriteKvCache:
    def test_per_slot_offsets(self):
        cache = jnp.zeros((2, 1, 8, 2))
        new = jnp.ones((2, 1, 3, 2))
        out = write_kv_cache(cache, new, jnp.array([0, 4]))
        assert np.asarray(out[0, 0, :3]).all() and not np.asarray(out[0, 0, 3:]).any()
        assert np.asarray(out[1, 0, 4:7]).all() and not np.asarray(out[1, 0, :4]).any()

    def test_write_mask_protects_slots(self):
        cache = jnp.full((2, 1, 8, 2), 7.0)
        new = jnp.ones((2, 1, 3, 2))
        out = write_kv_cache(cache, new, jnp.array([0, 0]),
                             jnp.array([True, False]))
        assert np.asarray(out[0, 0, 0, 0]) == 1.0
        np.testing.assert_array_equal(np.asarray(out[1]), 7.0)
