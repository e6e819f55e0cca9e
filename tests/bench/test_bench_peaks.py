"""The peaks table is keyed by device kind; an unknown kind is an error."""
import pytest

from harness.peaks import peaks


def test_v5e_peaks_have_a_source():
    p = peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in p["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", ""])
def test_unknown_device_raises(kind):
    with pytest.raises(KeyError):
        peaks(kind)
