"""Least-work counts of the Gram kernels' roofline at W1's shape."""
import pytest

from chipbench import roofline

W1 = {"n_obs": 5099, "dim_x": 15, "n_folds": 5, "n_rep": 100,
      "n_nuisance": 2}


def test_w1_least_flops_and_bytes():
    # one repetition: the Gram of all 5099 rows over 16 columns, and two
    # moments X'y over the same rows
    assert roofline.gram_rep_flops(5099, 15, 2) == \
        2 * 5099 * 16 * 16 + 2 * (2 * 5099 * 16)
    assert roofline.gram_rep_flops(5099, 15, 2) == 2_937_024
    # X once (5099 x 15 f32) and the two targets once
    assert roofline.gram_request_bytes(5099, 15, 2) == \
        4 * 5099 * 15 + 4 * 5099 * 2 == 346_732


def test_w1_request_least_time_is_flop_bound():
    fits = 1000                                 # one W1 request
    t = roofline.least_gram_s(fits, W1, "TPU v5 lite")
    flops_s = 100 * 2_937_024 / 197e12
    bytes_s = 346_732 / 819e9
    assert flops_s > bytes_s
    assert t == pytest.approx(flops_s, rel=1e-12)


def test_share_cannot_pass_100_for_a_kernel_that_does_the_work():
    fits = 1000
    least = roofline.least_gram_s(fits, W1, "TPU v5 lite")
    # a kernel doing the least work at the peak takes exactly the least
    # time; any kernel that does the work takes at least that long
    for kernel_s in (least, 2 * least, 1e3 * least):
        assert least / kernel_s <= 1.0
    # the work as implemented (every fold's Gram of its training rows,
    # per nuisance, at padded widths) is more than the least work
    padded = 100 * 5 * 2 * 2 * 8192 * 128 * 128 / 197e12
    assert padded > least


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        roofline.least_gram_s(1000, W1, "cpu")
