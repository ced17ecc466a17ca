"""Byte counts of the settle step and the table of peaks."""

import pytest

from bench import roofline


@pytest.mark.parametrize("nz, k, dtype, expected", [
    # the 16x16 Poisson circuit: 2048 states, at most 6 nonzeros per row
    (2048, 6, "float32", 2048 * 6 * 8 + 3 * 2048 * 4),
    (2048, 6, "bfloat16", 2048 * 6 * 6 + 3 * 2048 * 4),
    # the random sparse n=2048 sweep of the bring-up: 16384 states, K=35
    (16384, 35, "float32", 16384 * 35 * 8 + 3 * 16384 * 4),
])
def test_ell_step_bytes(nz, k, dtype, expected):
    assert roofline.ell_step_bytes(nz, k, dtype) == expected


def test_least_seconds_scales_with_steps_and_systems():
    one = roofline.sweep_least_seconds(1, 1, 2048, 6, "float32", 819e9)
    assert one == pytest.approx((2048 * 6 * 8 + 3 * 2048 * 4) / 819e9)
    assert roofline.sweep_least_seconds(700, 8, 2048, 6, "float32", 819e9) == \
        pytest.approx(700 * 8 * one)


def test_v5e_peaks_listed():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_missing_device_kind_is_an_error():
    with pytest.raises(KeyError, match="TPU v9"):
        roofline.peaks("TPU v9")
