"""The device table and the refusal to measure without a chip."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import chip  # noqa: E402


def test_known_device_has_its_published_peaks():
    p = chip.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        chip.peaks("TPU v9 imaginary")


class _Dev:
    def __init__(self, platform):
        self.platform, self.device_kind = platform, "TPU v5 lite"


class _Jax:
    def __init__(self, platform, n):
        self._devs = [_Dev(platform)] * n

    def devices(self):
        return self._devs


def test_require_chips():
    with pytest.raises(chip.NoAccelerator):
        chip.require_chips(_Jax("cpu", 1), 1)
    with pytest.raises(chip.NoAccelerator):
        chip.require_chips(_Jax("tpu", 1), 4)
    assert chip.require_chips(_Jax("tpu", 4), 4) == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 4}
