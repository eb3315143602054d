"""The table of chip peaks."""
import pytest

from bench import harness


def test_v5e_peaks_from_the_table():
    p = harness.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        harness.peaks_for("TPU v99 imaginary")
