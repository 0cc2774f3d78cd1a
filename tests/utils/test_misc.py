"""MFU math, formatting, peak-FLOPS table."""

from types import SimpleNamespace

import jax.numpy as jnp
import pytest

from scaletorch_tpu.utils.device import (
    PEAK_BF16_FLOPS,
    get_theoretical_flops,
)
from scaletorch_tpu.utils.misc import (
    get_flops_per_token,
    get_mfu,
    get_num_params,
    to_readable_format,
)


class TestReadableFormat:
    def test_scales(self):
        assert to_readable_format(1_234) == "1.23K"
        assert to_readable_format(1_234_567) == "1.23M"
        assert to_readable_format(1.5e9) == "1.50B"
        assert to_readable_format(2e12) == "2.00T"
        assert to_readable_format(42) == "42.00"


class TestMfu:
    def test_flops_per_token_formula(self):
        # Must match the reference formula 6N + 12·L·H·Dh·S (misc.py:171)
        # so MFU numbers are comparable with BASELINE.md.
        n, l, h, d, s = 600e6, 28, 16, 128, 4096
        assert get_flops_per_token(n, l, h, d, s) == 6 * n + 12 * l * h * d * s

    def test_mfu_against_a_stated_peak(self):
        # 1 param model, no attention: 6 flops/token; 1e11 tok/s -> 6e11 flops
        mfu = get_mfu(1e11, 1, 0, 0, 0, 1, peak_flops=1e12)
        assert mfu == pytest.approx(60.0)

    def test_peak_table_is_keyed_by_device_kind(self):
        v5e = SimpleNamespace(device_kind="TPU v5 lite")
        assert get_theoretical_flops(v5e) == 197e12 \
            == PEAK_BF16_FLOPS["TPU v5 lite"]

    @pytest.mark.parametrize("kind", ["cpu", "TPU v9 mega", "NVIDIA H100"])
    def test_unknown_device_kind_raises(self, kind):
        """A device the table does not list is an error, not a default:
        no 'cpu: 1e12' row for an MFU to be printed against."""
        with pytest.raises(ValueError, match="no peak FLOP/s on record"):
            get_theoretical_flops(SimpleNamespace(device_kind=kind))

    def test_mfu_on_this_cpu_raises(self):
        # the tests run on the CPU platform: no peak, no MFU
        with pytest.raises(ValueError, match="device_kind 'cpu'"):
            get_mfu(1e11, 1, 0, 0, 0, 1)


class TestNumParams:
    def test_counts_pytree(self):
        params = {"a": jnp.ones((2, 3)), "b": {"c": jnp.ones((4,))}}
        assert get_num_params(params) == 10
