"""The table of peaks."""
import pytest

from bench import harness


def test_v5e_peaks():
    peaks = harness.peaks_for("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in peaks["source"]


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="not in bench/devices.json"):
        harness.peaks_for("TPU v9 imaginary")
