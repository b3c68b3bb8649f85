"""The DBSCAN region-query cell and the all-count cell, at a CPU size.

`pamap2.dbscan-join` sends 2,048-row blocks with one eps for the run (the
median row holds minPts = 2d = 8 neighbors); `sift1m.count-open` sends
radius-open's arrivals as counts.  Here: the plan's radius, a tiny run of
each through the harness, the bf16 control and the planted faults of
`test_faults` (and their twins for count answers, which carry no neighbor
ids), and the `order_cells` reader.
"""
import time
from types import SimpleNamespace

import numpy as np
import pytest

from repro.serving import runtime
from snnbench import control, data, harness, reference, spec, traffic
from test_faults import FAULTS

BENCHMARK = spec.load_benchmark()


def test_dbscan_join_plans_one_eps_at_the_median_of_eight():
    cfg = spec.config(BENCHMARK, "pamap2")
    mix = spec.traffic("dbscan-join")
    assert (cfg["d"], mix["request"]["neighbors"]["median"]) == (4, 8)
    a = cfg["assumed"]
    base, pool = data.make_rows(3000000019, 4000, 512, cfg["d"], 20,
                                a["mixture_seed"], a["points_seed"])
    brute = reference.BruteForce(base, cfg["metric"])
    try:
        small = dict(mix, blocks=4, request=dict(mix["request"], rows=128))
        p = traffic.plan(small, pool, brute, cfg["metric"], 11, 30.0)
        again = traffic.plan(small, pool, brute, cfg["metric"], 12, 30.0)
    finally:
        brute.free()
    assert len(p.items) == 4 and p.loop == "closed" and p.clients == 2
    assert len({it.r2 for it in p.items}) == 1
    # rows_seed: every seed sends the same blocks, in its own order
    assert sorted(it.rows.tolist() for it in p.items) == \
        sorted(it.rows.tolist() for it in again.items)
    q = pool[np.concatenate([it.rows for it in p.items])].astype(np.float64)
    d2 = ((q[:, None, :] - base[None].astype(np.float64)) ** 2).sum(-1)
    counts = (d2 <= p.items[0].r2).sum(1)
    assert abs(np.median(counts) - 8) <= 1
    assert sum(it.expect for it in p.items) == counts.sum()


def _tiny_dbscan():
    return {"config": {"n": 4000, "query_pool": 1024,
                       "assumed": {"clusters": 20, "mixture_seed": 4,
                                   "points_seed": 5}},
            "traffic": {"blocks": 4, "check_requests": 2,
                        "request": {"kind": "join", "rows": 512,
                                    "neighbors": {"per": "all",
                                                  "median": 8}}},
            "server": {"backend": "pallas-tpu", "block_rows": 128}}


def _tiny_count():
    return {"config": {"n": 3000, "query_pool": 300,
                       "assumed": {"clusters": 10, "mixture_seed": 1}},
            "traffic": {"rate_per_s": 30, "check_requests": 16,
                        "warm_batches": [8],
                        "request": {"kind": "count", "rows": 1,
                                    "neighbors": {"per": "all",
                                                  "median": 16}}},
            # the kernels' lane: the oracle's jnp matmul runs at the
            # default precision on a TPU, which is not exact there
            "server": {"backend": "pallas-tpu"}}


@pytest.mark.parametrize("name,overrides,trace", [
    ("pamap2.dbscan-join", _tiny_dbscan(), 1),
    ("sift1m.count-open", _tiny_count(), 0),
])
def test_tiny_run_of_each_new_cell(name, overrides, trace):
    args = harness.parse(["--workload", name, "--seed", "3000000019",
                          "--seconds", "1", "--trace", str(trace)])
    out = harness.run(args, time.perf_counter(), require_tpu=False,
                      overrides=overrides)
    assert out["correct"] is True and out["failed"] == 0
    want = {m["name"] for m in spec.metrics_for(BENCHMARK, name,
                                                bool(trace))}
    got = set(out["metrics"])
    assert got <= want
    if trace:
        # the CPU has no device plane; the counters are the program's own
        assert {"service_ms.dbscan", "live_cells.dbscan",
                "order_cells.dbscan"} <= got
        assert out["metrics"]["order_cells.dbscan"]["value"] > 1.0
        assert out["metrics"]["live_cells.dbscan"]["value"] < 100.0
    else:
        assert got == want == {"p50_ms", "setup_s"}


@pytest.mark.parametrize("name,overrides", [
    ("pamap2.dbscan-join", _tiny_dbscan()),
    ("sift1m.count-open", _tiny_count()),
])
def test_bf16_control_fails_and_f32_passes_in_each_new_cell(name,
                                                            overrides):
    low = control.numbers(name, 6000000015, 1.0, bf16=True,
                          overrides=overrides)
    assert low["correct"] is False
    if name == "pamap2.dbscan-join":
        assert low["checks"]["dist_gap"]["value"] > \
            3 * low["checks"]["dist_gap"]["limit"]
    else:
        # a count carries no distance: the rows' counts decide it
        assert low["checks"]["wrong_rows"]["value"] > 0
    sound = control.numbers(name, 6000000015, 1.0, bf16=False,
                            overrides=overrides)
    assert sound["correct"] is True
    assert sound["checks"]["wrong_rows"]["value"] == 0


_EMIT = runtime.TenantRuntime._emit


def _count_altered(self, req, *, counts=None, **kw):
    """Every count answer one higher."""
    return _EMIT(self, req, counts=None if counts is None else counts + 1,
                 **kw)


def _count_half_left_out(self, req, *, counts=None, **kw):
    """Every second count request answered with zero."""
    if counts is not None and req.id % 2:
        counts = np.zeros_like(counts)
    return _EMIT(self, req, counts=counts, **kw)


COUNT_FAULTS = {"sound": None, "altered": _count_altered,
                "half": _count_half_left_out}


@pytest.mark.parametrize("fault", ["sound", "altered", "half"])
@pytest.mark.parametrize("name,overrides,faults", [
    ("pamap2.dbscan-join", _tiny_dbscan(), FAULTS),
    ("sift1m.count-open", _tiny_count(), COUNT_FAULTS),
])
def test_fault_is_caught_in_each_new_cell(name, overrides, faults, fault,
                                          monkeypatch):
    if faults[fault] is not None:
        monkeypatch.setattr(runtime.TenantRuntime, "_emit", faults[fault])
    args = harness.parse(["--workload", name, "--seed", "4000000015",
                          "--seconds", "1", "--trace", "0"])
    out = harness.run(args, time.perf_counter(), require_tpu=False,
                      overrides=overrides)
    assert out["correct"] is (fault == "sound"), out["checks"]


def test_order_cells_reads_the_counters_or_nothing():
    read = spec.reader("order_cells.dbscan")
    ctx = SimpleNamespace(dispatch={"live_cells": 40, "arrival_cells": 100})
    assert read(ctx) == 2.5
    # a program without the counter (the parent of the change) gives none
    assert read(SimpleNamespace(dispatch={"live_cells": 40})) is None
    assert read(SimpleNamespace(dispatch={"live_cells": 0,
                                          "arrival_cells": 0})) is None
