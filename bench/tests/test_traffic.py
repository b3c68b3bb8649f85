"""The traffic generator: the same arrivals for every seed, one radius."""
import numpy as np
import pytest

from snnbench import data, reference, traffic

OPEN = {"loop": "open", "rate_per_s": 40, "schedule_seed": 1,
        "check_requests": 8, "warm_batches": [8],
        "request": {"kind": "radius", "rows": 1,
                    "neighbors": {"per": "all", "median": 16}}}


@pytest.fixture(scope="module")
def rows():
    base, pool = data.make_rows(5, 2000, 200, 8, 10, 1)
    brute = reference.BruteForce(base, "euclidean")
    yield base, pool, brute
    brute.free()


def test_every_seed_gets_the_same_arrivals_and_other_rows(rows):
    _, pool, brute = rows
    a = traffic.plan(OPEN, pool, brute, "euclidean", 3000000019, 2.0)
    b = traffic.plan(OPEN, pool, brute, "euclidean", 11, 2.0)
    assert len(a.items) == len(b.items) == 80
    np.testing.assert_array_equal(a.due, b.due)
    assert np.all(np.diff(a.due) >= 0) and 0 <= a.due[0] <= a.due[-1] < 2.0
    assert [it.rows[0] for it in a.items] != [it.rows[0] for it in b.items]
    other = traffic.plan(dict(OPEN, schedule_seed=2), pool, brute,
                         "euclidean", 11, 2.0)
    assert not np.array_equal(a.due, other.due)


def test_one_radius_for_the_run_holds_the_median_target(rows):
    base, pool, brute = rows
    p = traffic.plan(OPEN, pool, brute, "euclidean", 7, 2.0)
    assert len({it.r2 for it in p.items}) == 1
    r2 = p.items[0].r2
    q = pool[[it.rows[0] for it in p.items]].astype(np.float64)
    d2 = ((q[:, None, :] - base[None].astype(np.float64)) ** 2).sum(-1)
    counts = (d2 <= r2).sum(1)
    assert [it.expect for it in p.items] == counts.tolist()
    assert abs(np.median(counts) - 16) <= 1


def test_fixed_points_give_every_seed_the_same_rows_in_its_order():
    a_base, a_pool = data.make_rows(11, 2000, 50, 8, 10, 1, points_seed=3)
    b_base, b_pool = data.make_rows(3000000019, 2000, 50, 8, 10, 1,
                                    points_seed=3)
    np.testing.assert_array_equal(a_pool, b_pool)
    assert not np.array_equal(a_base, b_base)
    # the same rows, in another order: the same distances to every query
    order_a = np.lexsort(a_base.T)
    order_b = np.lexsort(b_base.T)
    np.testing.assert_array_equal(a_base[order_a], b_base[order_b])
    # without points_seed the seed draws other points, as before
    c_base, c_pool = data.make_rows(11, 2000, 50, 8, 10, 1)
    assert not np.array_equal(c_pool, a_pool)


def test_rows_seed_sends_the_same_rows_in_the_seed_s_order(rows):
    _, pool, _ = rows
    knn = {"loop": "open", "rate_per_s": 40, "schedule_seed": 1,
           "rows_seed": 1, "check_requests": 8, "warm_batches": [8],
           "request": {"kind": "knn", "rows": 1, "k": 10}}
    a = traffic.plan(knn, pool, None, "euclidean", 3000000019, 2.0)
    b = traffic.plan(knn, pool, None, "euclidean", 11, 2.0)
    rows_a = [it.rows.tolist() for it in a.items]
    rows_b = [it.rows.tolist() for it in b.items]
    assert sorted(rows_a) == sorted(rows_b) and rows_a != rows_b
    again = traffic.plan(knn, pool, None, "euclidean", 11, 2.0)
    assert [it.rows.tolist() for it in again.items] == rows_b
    c = traffic.plan(dict(knn, rows_seed=2), pool, None, "euclidean", 11, 2.0)
    assert [it.rows.tolist() for it in a.items] != \
        [it.rows.tolist() for it in c.items]


def test_warm_all_covers_every_request_then_the_first_burst_again(rows):
    from snnbench import harness

    _, pool, _ = rows
    knn = {"loop": "open", "rate_per_s": 40, "schedule_seed": 1,
           "rows_seed": 1, "check_requests": 8, "warm_batches": [32],
           "warm_all": True, "request": {"kind": "knn", "rows": 1, "k": 10}}
    p = traffic.plan(knn, pool, None, "euclidean", 11, 2.0)
    bursts = harness.warm_bursts(p)
    assert [len(b) for b in bursts] == [32, 32, 16, 32]
    assert sorted(id(it) for b in bursts[:-1] for it in b) == \
        sorted(id(it) for it in p.items)
    assert bursts[-1] == bursts[0]
    # the same bursts for every seed: the order of sending does not matter
    q = traffic.plan(knn, pool, None, "euclidean", 3000000019, 2.0)
    assert [[it.rows.tolist() for it in b] for b in harness.warm_bursts(q)] \
        == [[it.rows.tolist() for it in b] for b in bursts]


def test_warm_batches_run_twice_each_heaviest_first(rows):
    from snnbench import harness

    _, pool, brute = rows
    p = traffic.plan(dict(OPEN, warm_batches=[4, 8]), pool, brute,
                     "euclidean", 7, 2.0)
    bursts = harness.warm_bursts(p)
    assert [len(b) for b in bursts] == [4, 4, 8, 8]
    top = max(it.expect for it in p.items)
    assert all(b[0].expect == top for b in bursts)
