"""The roofline's work function, against brute counts on tiny data."""
import numpy as np
import pytest

from snnbench import roofline


@pytest.mark.parametrize("metric", ["euclidean", "angular"])
def test_window_pairs_match_a_brute_count(metric):
    rng = np.random.default_rng(0)
    base = rng.standard_normal((500, 8)).astype(np.float32) * 3
    q = rng.standard_normal((40, 8)).astype(np.float32) * 3
    r = rng.uniform(0.0, 2.0, 40)
    win = roofline.Window(base, metric)
    assert np.all(np.diff(win.alphas) >= 0)
    aq = win.project(q)
    lo, hi = win.bounds(aq, r)
    # brute: every (query, row) pair whose projections lie within r
    ab = win.project(base)
    inside = np.abs(ab[None, :] - aq[:, None]) <= r[:, None]
    assert roofline.window_pairs(lo, hi) == int(inside.sum())
    assert roofline.distinct_rows(lo, hi) == int(inside.any(axis=0).sum())


def test_the_axis_is_the_first_principal_component():
    rng = np.random.default_rng(1)
    x = rng.standard_normal((2000, 4)) * np.array([5.0, 1.0, 0.5, 0.1])
    win = roofline.Window(x.astype(np.float32), "euclidean")
    assert abs(abs(win.v[0]) - 1.0) < 1e-3


def test_distinct_rows_merges_overlapping_windows():
    lo, hi = np.array([0, 2, 10, 11]), np.array([5, 4, 12, 11])
    assert roofline.distinct_rows(lo, hi) == 7


def test_least_seconds_takes_the_larger_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    w = roofline.least_seconds(d=2, pairs_in_window=100, rows_read=1,
                               query_rows=1, result_pairs=0, peak=peak)
    assert w["flops"] == 400 and w["bytes"] == 16
    assert w["seconds"] == pytest.approx(4.0) and w["bound"] == "compute"
    w = roofline.least_seconds(d=2, pairs_in_window=1, rows_read=100,
                               query_rows=0, result_pairs=10, peak=peak)
    assert w["bytes"] == 880 and w["bound"] == "memory"
