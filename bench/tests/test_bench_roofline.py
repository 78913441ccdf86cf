"""The kernel's operations and bytes against a count by hand."""
import pytest

from benchtest import harness  # noqa: F401  (import paths)
from bench import roofline


def test_thinning_rmw_hand_count():
    # one call over a 4096-event block, six decay constants, Eq. 4:
    # words per event: in 8 + 3*6, out 7 + 3*6 + 4*6 = 75, 300 bytes;
    # plus the six decay constants once
    ops, nbytes = roofline.thinning_rmw_cost(4096, 6, "pp_vr")
    assert nbytes == 4096 * 300 + 24
    # per event: 36 scalar ops (decay, intensity, base rate, clip, draw,
    # HT weights, both control columns), 23 per decay constant (decay 3,
    # rescale 3, mean 2, variance 5, std 1, update 6, select 3) and 21 for
    # the variance tilt: 36 + 138 + 21 = 195
    assert ops == 4096 * 195
    assert roofline.thinning_rmw_cost(4096, 6, "pp")[0] == 4096 * 174


def test_share_is_bytes_bound_and_unknown_kind_fails():
    ops, nbytes = roofline.thinning_rmw_cost(4096, 6, "pp_vr")
    t = nbytes / 819e9
    assert roofline.roofline_share(ops, nbytes, t, "TPU v5 lite") == \
        pytest.approx(100.0)
    with pytest.raises(KeyError):
        roofline.roofline_share(ops, nbytes, t, "TPU v9 imaginary")
