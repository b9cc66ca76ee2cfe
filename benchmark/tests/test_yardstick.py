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


def test_build_least_bytes_at_sf1():
    # Every value of the four index columns (28 bytes a row) read once
    # and written once: 336 MB for SF1's 6,001,642 lineitem rows.
    assert least_bytes.build(6_001_642) == 2 * 6_001_642 * 28 == 336_091_952


def test_build_readers_return_nothing_without_a_trace_or_cycles():
    import harness
    from conftest import REPO

    cycle = harness.Cycle(op={}, module=None,
                          steps=[("create", 0.0, 0.5), ("delete", 0.5, 0.6),
                                 ("vacuum", 0.6, 0.8)],
                          record={"source_rows": 1000})
    untraced = harness.Window(lake=None, sent=[], t0=0.0, t1=1.0,
                              setup_s=1.0, compiles=0, pool_before={},
                              pool_after={}, device_kind="TPU v5 lite",
                              cycles=[cycle])
    queries = harness.Window(lake=None, sent=[], t0=0.0, t1=1.0,
                             setup_s=1.0, compiles=0, pool_before={},
                             pool_after={}, device_kind="TPU v5 lite",
                             trace={"busy_s": 0.1, "idle_share": 0.9})

    def read(name, w):
        return harness._module(REPO, "metrics", name, "test_metric_").read(w)

    for name in ("device_ms_per_build", "build_roofline"):
        assert read(name, untraced) is None, name
    for name in ("device_ms_per_build", "build_roofline", "create_ms.build",
                 "delete_ms.build", "vacuum_ms.build", "build_rows_per_s"):
        assert read(name, queries) is None, name
    assert read("create_ms.build", untraced) == pytest.approx(500.0)
    assert read("vacuum_ms.build", untraced) == pytest.approx(200.0)
    assert read("build_rows_per_s", untraced) == pytest.approx(1250.0)
    traced = harness.Window(**{**untraced.__dict__,
                               "trace": {"busy_s": 0.2, "idle_share": 0.8}})
    assert read("device_ms_per_build", traced) == pytest.approx(200.0)
    # 56,000 bytes over 819 GB/s, over 0.2 s of device time.
    assert read("build_roofline", traced) == pytest.approx(
        100 * 56_000 / 819e9 / 0.2)
