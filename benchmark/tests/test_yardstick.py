"""The table of peaks and the least-bytes counts."""

import pytest

import least_bytes
import peaks


def test_peaks_known_chip_and_unknown_is_an_error():
    v5e = peaks.peak("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary")


def test_q3_least_bytes_at_sf1():
    # 28 bytes a lineitem row (int64 key, two float64, date32) and 16 an
    # order row (int64 key, date32, int32): 192 MB at SF1.
    assert least_bytes.q3(6_000_000, 1_500_000) == 6_000_000 * 28 \
        + 1_500_000 * 16 == 192_000_000
