"""Roofline peaks are looked up by device kind; unknown kinds are errors."""

import pytest

from repro.core import roofline


def test_v5e_peaks_from_the_published_table():
    p = roofline.peaks_for("TPU v5 lite")
    assert (p.flops_bf16, p.hbm_bw) == (197e12, 819e9)
    assert roofline.V5E == "TPU v5 lite"


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        roofline.peaks_for("cpu")
    terms = roofline.RooflineTerms(
        name="x", device_kind="cpu", chips=1, hlo_flops=1.0, hlo_bytes=1.0,
        collective_bytes=0.0,
    )
    with pytest.raises(KeyError):
        _ = terms.step_s


def test_terms_use_the_kinds_peaks():
    terms = roofline.RooflineTerms(
        name="x", device_kind=roofline.V5E, chips=1, hlo_flops=197e12,
        hlo_bytes=819e9 / 2, collective_bytes=0.0,
    )
    assert terms.compute_s == pytest.approx(1.0)
    assert terms.memory_s == pytest.approx(0.5)
    assert terms.bound == "compute"
