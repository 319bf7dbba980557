import pytest

import peaks


def test_v5e_peaks_from_the_table():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5e", ""])
def test_unknown_device_is_an_error(kind):
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks(kind)


def test_roofline_share_names_its_bound():
    # 819 MB in 2 ms on an 819 GB/s chip: the bytes bound, 50%
    share, bound = peaks.roofline_share(1e9, 819e6, 2e-3, "TPU v5 lite")
    assert bound == "hbm" and share == pytest.approx(50.0)
    share, bound = peaks.roofline_share(197e12, 1.0, 4.0, "TPU v5 lite")
    assert bound == "compute" and share == pytest.approx(25.0)
    assert peaks.roofline_share(1.0, 1.0, 0.0, "TPU v5 lite") is None
