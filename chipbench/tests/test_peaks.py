import pytest

from chipbench import harness


def test_v5e_peaks_from_their_source():
    p = harness.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v6 lite", ""])
def test_unknown_device_kind_is_refused(kind):
    with pytest.raises(KeyError, match="no peaks"):
        harness.peaks(kind)
