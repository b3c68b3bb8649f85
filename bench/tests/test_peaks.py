"""The table of peaks."""
import pytest

from snnbench import spec


def test_v5e_peaks_and_their_source():
    p = spec.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "v5e" in p["source"]


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(spec.SpecError):
        spec.peaks("cpu")
