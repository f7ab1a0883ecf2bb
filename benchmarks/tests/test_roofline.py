"""The roofline's arithmetic, the table of peaks and the 105% ceiling."""

import pytest

from benchmarks import roofline


def test_work_of_one_signature():
    assert roofline.DECOMPRESS_MULS == 274
    assert roofline.SCALAR_MULS == 252 * 8 + 64 * 17 + 126
    assert roofline.FIELD_MULS_PER_SIG == 3811
    assert roofline.OPS_PER_SIG == 3811 * 2048


def test_least_time_is_bound_by_operations():
    peak = roofline.peaks("TPU v5 lite")
    seconds, bound = roofline.least_seconds(10240, peak)
    assert bound == "operations"
    assert seconds == pytest.approx(10240 * 3811 * 2048 / 393e12)
    # bytes: 1.33 MB for 10,240 signatures, microseconds at 819 GB/s
    assert 10240 * roofline.BYTES_PER_SIG / peak["hbm_bytes_per_s"] < 2e-6


def test_share_and_ceiling():
    peak = roofline.peaks("TPU v5 lite")
    least, _ = roofline.least_seconds(6827, peak)
    assert roofline.share_pct(6827, least * 1000, peak) == pytest.approx(0.1)
    assert roofline.share_pct(6827, least, peak) == pytest.approx(100.0)
    assert roofline.share_pct(0, 1.0, peak) is None
    assert roofline.share_pct(6827, 0.0, peak) is None
    with pytest.raises(ValueError):
        roofline.share_pct(6827, least / 1.06, peak)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
    with pytest.raises(KeyError):
        roofline.peaks("_source")
