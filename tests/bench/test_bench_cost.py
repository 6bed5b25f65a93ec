"""The cost functions against hand counts at one small shape."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.cost import greedy, least_time, probe_count, ss  # noqa: E402


def test_probe_count_is_r_log2_n():
    assert probe_count(1024, 8) == 80
    assert probe_count(1, 8) == 8


def test_ss_coverage_hand_count():
    # n = 16 -> m = 8 * 4 = 32 > n, so m = 16; live 16 -> 6 -> 2.
    ops, nbytes = ss.row_work("coverage", 16, 16, 4, 8, [6, 2])
    per_pair = 4 * 4 + 2
    assert ops == 16 * 16 * per_pair + 6 * 16 * per_pair
    assert nbytes == 16 * 4 * 4 + 16 * 4


def test_ss_facility_location_uses_the_rows_as_width():
    # padded ground set of 32 (m = 8 * 5 = 40 -> 32), 20 real rows.
    ops, nbytes = ss.row_work("fl", 32, 20, 256, 8, [5])
    assert ops == 20 * 32 * (3 * 20 + 2)
    assert nbytes == 20 * 20 * 4 + 20 * 4


def test_greedy_hand_counts():
    ops, nbytes = greedy.row_work("coverage", 100, 8, 3, 5)
    assert ops == 3 * (5 * (4 * 8 + 1) + 8)
    assert nbytes == 5 * 8 * 4 + 3 * 8 * 4
    # k beyond the retained set: only |V'| steps are the algorithm's.
    ops, nbytes = greedy.row_work("fl", 10, 256, 7, 4)
    assert ops == 4 * (4 * (3 * 10 + 1) + 10)
    assert nbytes == 4 * 10 * 4 + 4 * 10 * 4


def test_least_time_names_its_bound():
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert least_time(1000.0, 10.0, peak) == pytest.approx((10.0, "compute"))
    assert least_time(100.0, 100.0, peak) == pytest.approx((10.0, "memory"))
