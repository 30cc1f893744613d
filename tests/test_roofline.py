"""Roofline peaks are keyed by the device kind JAX reports."""

import pytest

from repro.launch import roofline


def test_v5e_peaks_from_published_table():
    peak = roofline.peaks_for("TPU v5 lite")
    assert (peak.flops, peak.hbm_bw) == (197e12, 819e9)


def test_unknown_device_kind_raises():
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.peaks_for("cpu")
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.roofline_terms(1.0, 1.0, 0.0, "TPU v4")


def test_terms_name_the_bound():
    # 197 GFLOP and 8.19 GB on a v5e: 1 ms of compute, 10 ms of HBM
    t = roofline.roofline_terms(197e9, 8.19e9, 0.0, "TPU v5 lite")
    assert t["dominant"] == "memory_s"
    assert t["step_time_bound_s"] == pytest.approx(0.01)
    assert t["roofline_fraction"] == pytest.approx(0.1)
