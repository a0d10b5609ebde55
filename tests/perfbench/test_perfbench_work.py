"""The work counts against shapes worked by hand, and the peaks table."""

import pytest

from perfbench import work


def test_scored_batch_counts():
    # 2 queries x 1000 items x rank 8
    assert work.scored_batch_flops(2, 1000, 8) == 2 * 2 * 1000 * 8
    # table 1000*8*4, queries 2*8*4, results 2*16*(4+4); no score matrix
    assert work.scored_batch_bytes(2, 1000, 8, 16) == 32000 + 64 + 256


def test_yambda_batch_is_bandwidth_bound_at_2_9_ms():
    peaks = work.peaks_for("TPU v5 lite")
    flops = work.scored_batch_flops(64, 9_390_623, 64)
    nbytes = work.scored_batch_bytes(64, 9_390_623, 64, 16)
    t, bound = work.least_seconds(flops, nbytes, peaks)
    assert bound == "bytes"
    assert t == pytest.approx(2.404e9 / 819e9, rel=1e-3)


def test_als_sweep_counts():
    # 10 ratings, 3 users, 2 items, rank 4
    gram, rhs, solve = 2 * 10 * 16, 2 * 10 * 4, (2 / 3) * 64
    assert work.als_sweep_flops(10, 3, 2, 4) == pytest.approx(
        2 * (gram + rhs) + 5 * solve
    )
    # COO 10*(4+4+4), tables (3+2)*4*4 read once and written once
    assert work.als_sweep_bytes(10, 3, 2, 4) == 120 + 2 * 80


def test_least_seconds_says_which_bound_binds():
    peaks = {"flops_per_s": 100.0, "bytes_per_s": 10.0}
    assert work.least_seconds(1000.0, 10.0, peaks) == (10.0, "flops")
    assert work.least_seconds(10.0, 1000.0, peaks) == (100.0, "bytes")


def test_unknown_device_is_an_error_not_a_default():
    assert work.peaks_for("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="not in peaks.json"):
        work.peaks_for("cpu")
