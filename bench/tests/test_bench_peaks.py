"""The table of published peaks, keyed by the device kind JAX reports."""
import pytest

from bench.lib import peaks


def test_v5e_peaks_from_the_published_table():
    p = peaks.load("TPU v5 lite")
    assert (p.flops, p.int8_ops, p.hbm_bytes_per_s) == (197e12, 393e12,
                                                        819e9)
    assert "TPU v5e" in p.source


def test_unknown_device_is_an_error():
    with pytest.raises(peaks.UnknownDevice):
        peaks.load("TPU v9 imaginary")
    with pytest.raises(peaks.UnknownDevice):
        peaks.load("cpu")


def test_roofline_bound_is_the_larger_of_compute_and_memory():
    p = peaks.load("TPU v5 lite")
    assert p.least_seconds(197e12, 0) == pytest.approx(1.0)
    assert p.least_seconds(1.0, 819e9 * 2) == pytest.approx(2.0)
