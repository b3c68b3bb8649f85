"""Alpha-ordered tiles on the served path, at a low-d DBSCAN shape.

A DBSCAN client sends its region queries in 2,048-row blocks.  At d = 4
each row's sorted window is narrow, but a block tiled in arrival order
scatters those windows over every tile; the served entries
(`engine.query_csr_packed`, `join.query_counts`) then run the block sorted
by alpha (`engine.TileOrder`) and return its rows in request order.
Checked here on a d = 4 Gaussian mixture, through `SNNServer`'s
dispatcher body:

* a 2,048-row join block in random order equals a float64 brute force
  (outside the float32 band) and is bit-identical, row for row, to the
  same rows sent as 128-row requests; the same for an all-count block;
* ``arrival_cells`` exceeds ``live_cells`` for that block, and equals it
  for an all-live d = 128 batch, which keeps its order; both count only
  the segments the executor runs; a one-tile batch makes no order test;
* DBSCAN labels built from served region-query blocks equal the labels
  of the brute-force backend's graph.
"""
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs.snn_default import SNNConfig
from repro.core import engine as _engine
from repro.core import snn as _snn
from repro.core.dbscan import dbscan, labels_from_graph
from repro.serving.server import Request, SNNServer

D = 4
N = 6000
BLOCK = 2048
MIN_PTS = 2 * D
U = 2.0 ** -24


def _mixture(rng, n, d=D, clusters=20):
    centers = 2.0 * rng.normal(size=(clusters, d))
    scale = rng.uniform(0.6, 1.4, clusters)
    c = rng.integers(0, clusters, n)
    x = centers[c] + rng.normal(size=(n, d)) * scale[c][:, None]
    return x.astype(np.float32)


def _d2(q, x):
    q64, x64 = np.asarray(q, np.float64), np.asarray(x, np.float64)
    return ((q64[:, None, :] - x64[None, :, :]) ** 2).sum(-1)


def _eps(data, q):
    """The radius at which the median query row holds ``MIN_PTS``
    neighbors (halfway between its 8th and 9th distance)."""
    kth = np.sort(_d2(q, data), axis=1)[:, MIN_PTS - 1:MIN_PTS + 1]
    return float(np.sqrt(np.median(kth.mean(axis=1))))


@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(15)
    data = _mixture(rng, N)
    q = data[rng.permutation(N)[:BLOCK]] + \
        rng.normal(scale=0.05, size=(BLOCK, D)).astype(np.float32)
    return data, q, _eps(data, q)


def _serve(server, reqs):
    """One dispatcher batch of ``reqs``; their responses by id."""
    server._run_batch(reqs)
    out = {r.id: server._results.pop(r.id) for r in reqs}
    assert all(resp.error is None for resp in out.values()), out
    return out


def _rows(resp, m):
    return [(resp.indices[resp.indptr[t]:resp.indptr[t + 1]],
             resp.sq_dists[resp.indptr[t]:resp.indptr[t + 1]])
            for t in range(m)]


def _band(data, q, r):
    """Per-pair float32 rounding band of the squared threshold."""
    mu = data.astype(np.float64).mean(axis=0)
    xn = np.linalg.norm(data - mu, axis=1)
    qn = np.linalg.norm(q - mu, axis=1)
    return 2 * (D + 2) * U * ((xn[None, :] + qn[:, None]) ** 2 + r * r)


@pytest.fixture(scope="module", params=["oracle", "pallas-tpu"])
def served(request, corpus):
    """The block as one join request, as one count request, and as
    128-row requests of each, on one lane (pallas-tpu: interpret mode)."""
    data, q, eps = corpus
    server = SNNServer(data, SNNConfig(backend=request.param))
    server.index.plan()
    before = _engine.DISPATCH_STATS.snapshot()
    join = _serve(server, [Request(query=q, radius=eps, id=0)])[0]
    count = _serve(server, [Request(query=q, radius=eps, id=1,
                                    count_only=True)])[1]
    after = _engine.DISPATCH_STATS.snapshot()
    stats = {k: after[k] - before[k] for k in after}
    parts, part_counts = [], []
    for s in range(0, BLOCK, 128):
        parts += _rows(_serve(server, [Request(query=q[s:s + 128],
                                               radius=eps, id=2)])[2], 128)
        part_counts.append(_serve(server, [Request(
            query=q[s:s + 128], radius=eps, id=3, count_only=True)])[3]
            .counts)
    return {"lane": request.param, "join": join, "count": count,
            "parts": parts, "part_counts": np.concatenate(part_counts),
            "stats": stats}


def test_served_join_block_matches_float64_and_128_row_requests(served,
                                                                 corpus):
    data, q, eps = corpus
    rows = _rows(served["join"], BLOCK)
    d2 = _d2(q, data)
    band = _band(data, q, eps)
    sure_in = d2 < eps * eps - band
    maybe_in = d2 <= eps * eps + band
    sizes = []
    for t, ((ids, sq), (pids, psq)) in enumerate(zip(rows, served["parts"])):
        np.testing.assert_array_equal(ids, pids, err_msg=f"row {t}")
        np.testing.assert_array_equal(sq, psq, err_msg=f"row {t}")
        got = np.zeros(N, bool)
        got[ids] = True
        assert not (got & ~maybe_in[t]).any(), t
        assert not (sure_in[t] & ~got).any(), t
        np.testing.assert_allclose(sq, d2[t, ids], rtol=1e-4, atol=1e-4)
        sizes.append(ids.size)
    assert abs(np.median(sizes) - MIN_PTS) <= 1


def test_served_count_block_comes_back_in_request_order(served, corpus):
    data, q, eps = corpus
    counts = served["count"].counts
    np.testing.assert_array_equal(counts, served["part_counts"])
    np.testing.assert_array_equal(counts, np.diff(served["join"].indptr))
    d2 = _d2(q, data)
    band = _band(data, q, eps)
    lo = (d2 < eps * eps - band).sum(axis=1)
    hi = (d2 <= eps * eps + band).sum(axis=1)
    assert ((lo <= counts) & (counts <= hi)).all()


def test_arrival_cells_count_what_the_alpha_order_saved(served):
    stats = served["stats"]
    if served["lane"] == "oracle":
        # the oracle lane runs no grid: nothing to count
        assert stats["live_cells"] == stats["arrival_cells"] == 0
        return
    # the join block's two passes and the count block's one
    assert 0 < stats["live_cells"] < stats["arrival_cells"] \
        <= stats["grid_cells"]
    assert stats["arrival_cells"] >= 2 * stats["live_cells"]


def test_all_live_d128_batch_keeps_its_order():
    rng = np.random.default_rng(3)
    data = rng.normal(size=(2000, 128)).astype(np.float32)
    q = rng.normal(size=(256, 128)).astype(np.float32)
    server = SNNServer(data, SNNConfig(backend="pallas-tpu", block_rows=512))
    pack = server.index.plan()
    xq, aq, r, th, _ = _snn.prepare_query_predicates(server.index.parts[0],
                                                     q, 16.0)
    assert _engine.TileOrder(pack, 128, aq, r, th, None).perm is None
    _engine.DISPATCH_STATS.reset()
    resp = _serve(server, [Request(query=q, radius=16.0, id=0)])[0]
    stats = _engine.DISPATCH_STATS.snapshot()
    assert stats["live_cells"] == stats["arrival_cells"] \
        == stats["grid_cells"] > 0
    assert resp.indptr[-1] > 0


def test_order_counts_only_the_segments_that_run(corpus):
    """A delta that the box prune drops for the block, though its alphas
    overlap the block's windows, counts in neither order: the order test
    and the counters see the segments the executor runs."""
    data, q, eps = corpus
    server = SNNServer(data, SNNConfig(backend="pallas-tpu"))
    base = server.index.parts[0]
    # the same rows moved 40 along the second principal component: the
    # same alphas, a box far from every query row's
    server.append(data[:1000] + 40.0 * base.vs[1][None, :].astype(np.float32))
    pack = server.index.plan()
    assert pack.n_segments == 2
    xq, aq, r, th, _ = _snn.prepare_query_predicates(base, q, eps)
    pq = _snn.query_extra_projections(base, xq)
    mask = _engine._live_segments(pack, aq, r, th, pq, BLOCK)[-1]
    (run,) = np.flatnonzero(mask)
    (dropped,) = np.flatnonzero(~mask)
    order = np.argsort(aq, kind="stable")
    assert _engine._live_cells(pack, [dropped], aq, r, 128)[0] > 0

    def one_pass(idx, rows):
        return _engine._live_cells(pack, [idx], aq[rows], r[rows], 128)[0]

    arrival = one_pass(run, np.arange(BLOCK))
    _engine.DISPATCH_STATS.reset()
    _serve(server, [Request(query=q, radius=eps, id=0)])
    stats = _engine.DISPATCH_STATS.snapshot()
    assert stats["arrival_cells"] == 2 * arrival
    assert stats["live_cells"] == 2 * one_pass(run, order)
    # rows already in alpha order keep it: the two counters agree
    _engine.DISPATCH_STATS.reset()
    _serve(server, [Request(query=q[order[:512]], radius=eps, id=1)])
    stats = _engine.DISPATCH_STATS.snapshot()
    assert stats["arrival_cells"] == stats["live_cells"] \
        == 2 * one_pass(run, order[:512]) > 0


def test_one_tile_batch_makes_no_order_test(monkeypatch, corpus):
    data, q, eps = corpus
    # the oracle lane counts no cells, so any count here is an order test
    server = SNNServer(data, SNNConfig(backend="oracle"))
    pack = server.index.plan()

    def boom(*a, **kw):
        raise AssertionError("a one-tile batch counted cells")

    monkeypatch.setattr(_engine, "_live_cells", boom)
    aq = np.zeros(128, np.float32)
    assert _engine.TileOrder(pack, 128, aq, aq + 1.0, aq, None).perm is None
    _serve(server, [Request(query=q[:128], radius=eps, id=0)])


def test_order_span_sits_in_prep(tmp_path, corpus):
    data, q, eps = corpus
    server = SNNServer(data)
    server.index.plan()
    _serve(server, [Request(query=q, radius=eps, id=0)])  # compile first
    with jax.profiler.trace(str(tmp_path)):
        _serve(server, [Request(query=q, radius=eps, id=1)])
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    spans = [(e.name, e.start_ns, e.end_ns, dict(e.stats))
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name in ("snn.order", "snn.prep")]
    orders = [s for s in spans if s[0] == "snn.order"]
    assert len(orders) == 1
    _, t0, t1, meta = orders[0]
    assert any(s[0] == "snn.prep" and s[1] <= t0 and t1 <= s[2]
               for s in spans)
    assert meta["rows"] == BLOCK and meta["ordered"] == 1
    assert meta["arrival_cells"] > meta["live_cells"] > 0


def test_dbscan_labels_from_served_region_query_blocks(corpus):
    data, _, _ = corpus
    rng = np.random.default_rng(8)
    eps = _eps(data, data[rng.permutation(N)[:500]])
    server = SNNServer(data)
    server.index.plan()
    order = rng.permutation(N)
    rows = {}
    for j, s in enumerate(range(0, N, BLOCK)):
        ids = order[s:s + BLOCK]
        resp = _serve(server, [Request(query=data[ids], radius=eps, id=j)])[j]
        for t, i in enumerate(ids):
            rows[i] = resp.indices[resp.indptr[t]:resp.indptr[t + 1]]
    indptr = np.zeros(N + 1, np.int64)
    np.cumsum([rows[i].size for i in range(N)], out=indptr[1:])
    graph = _snn.CSRNeighbors(indptr,
                              np.concatenate([rows[i] for i in range(N)]))
    labels = labels_from_graph(graph, MIN_PTS)
    want = dbscan(data, eps, MIN_PTS, backend="brute")
    np.testing.assert_array_equal(labels, want)
    assert labels.max() >= 1 and (labels == -1).any()
