"""DISPATCH_STATS under concurrency: thread-local counters + aggregate view.

The fused serving path mutates the dispatch counters from every worker
thread that executes a batch; plain class-level ints raced (increments are
read-modify-write).  The counters are now thread-local holders registered in
a lock-guarded global list, so each thread's view is exactly its own work
and `DispatchStats.aggregate()` sums every thread that ever touched the
stats — no increment can be lost, whatever the interleaving.
"""
import threading

import numpy as np
import pytest

from repro.core import build_index, engine as _engine
from repro.core.join import query_counts, single_query


def test_counters_thread_isolated_and_aggregated():
    n_threads, bumps = 8, 500
    # reset BEFORE reading the baseline: the reset zeroes this thread's
    # prior-test counters, which would otherwise deflate the aggregate delta
    _engine.DISPATCH_STATS.reset()
    base = _engine.DispatchStats.aggregate()["kernel_launches"]
    start = threading.Barrier(n_threads)
    per_thread = {}

    def work(tid):
        _engine.DISPATCH_STATS.reset()
        start.wait()
        for _ in range(bumps):
            _engine.DISPATCH_STATS.kernel_launches += 1
        per_thread[tid] = _engine.DISPATCH_STATS.kernel_launches

    threads = [threading.Thread(target=work, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # each thread saw exactly its own increments — no cross-talk
    assert per_thread == {t: bumps for t in range(n_threads)}
    # the main thread's view is untouched by the workers
    assert _engine.DISPATCH_STATS.kernel_launches == 0
    # the aggregate lost nothing: racy class-level ints would undercount
    agg = _engine.DispatchStats.aggregate()
    assert agg["kernel_launches"] - base == n_threads * bumps


def test_concurrent_fused_serving_batches():
    # the actual serving scenario: overlapping batches through one shared
    # pack on worker threads, fused speculation active — counters must stay
    # consistent and results bit-identical to the single-threaded run
    rng = np.random.default_rng(0)
    x = rng.normal(size=(500, 6)).astype(np.float32)
    index = build_index(x, n_components=3)
    pack = _engine.pack_from_index(index)
    q = rng.normal(size=(32, 6)).astype(np.float32)
    want = single_query(index, q, 1.0, pack=pack, use_pallas=True)
    want2 = single_query(index, q, 1.0, pack=pack, use_pallas=True)  # fused
    assert np.array_equal(want.indptr, want2.indptr)

    results, snaps = {}, {}
    start = threading.Barrier(4)

    def worker(tid):
        _engine.DISPATCH_STATS.reset()
        start.wait()
        for _ in range(3):
            results[tid] = single_query(index, q, 1.0, pack=pack,
                                        use_pallas=True)
        snaps[tid] = _engine.DISPATCH_STATS.snapshot()

    threads = [threading.Thread(target=worker, args=(t,)) for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for tid, res in results.items():
        assert np.array_equal(res.indptr, want.indptr), tid
        assert np.array_equal(res.indices, want.indices), tid
        assert np.array_equal(np.asarray(res.distances),
                              np.asarray(want.distances)), tid
    # every worker's own ledger recorded its three fused queries
    for tid, snap in snaps.items():
        assert snap["kernel_launches"] >= 3, (tid, snap)
        assert snap["host_transfers"] >= 3, (tid, snap)


def _brute_runs(index, csr, block):
    """Distinct (query row, db block) pairs of a one-segment CSR result."""
    pos = np.empty(index.order.size, np.int64)
    pos[index.order] = np.arange(index.order.size)  # id -> sorted row
    rows = np.repeat(np.arange(csr.m), np.diff(csr.indptr))
    return len(set(zip(rows.tolist(), (pos[csr.indices] // block).tolist())))


@pytest.mark.parametrize("lane", ["pallas-tpu", "pallas-gpu"])
def test_placed_runs_counts_pass_two_runs(lane):
    """``placed_runs`` is the (row, block) runs pass 2 placed: the brute
    count on the exact classic call and on the fused one after it, and
    nothing where pass 2 does not run."""
    rng = np.random.default_rng(14)
    x = rng.normal(size=(700, 5)).astype(np.float32)
    index = build_index(x)
    pack = _engine.pack_from_index(index, block=128)
    q = rng.normal(size=(20, 5)).astype(np.float32)
    for fused in (False, True):  # the classic path seeds the fused one
        _engine.DISPATCH_STATS.reset()
        csr = single_query(index, q, 1.2, pack=pack, use_pallas=lane,
                           query_tile=64)
        want = _brute_runs(index, csr, 128)
        assert want > csr.m  # some rows span several blocks
        snap = _engine.DISPATCH_STATS.snapshot()
        assert snap["placed_runs"] == want, fused
        assert snap["kernel_launches"] == (1 if fused else 3)

    _engine.DISPATCH_STATS.reset()
    counts = query_counts(index, q, 1.2, block=128, query_tile=64,
                          use_pallas=lane)
    assert counts.sum() == csr.nnz  # a count-only batch: pass 1 alone
    far = (100.0 + q).astype(np.float32)  # no survivors: nothing placed
    for _ in range(2):
        assert single_query(index, far, 1.2, pack=pack, use_pallas=lane,
                            query_tile=64).nnz == 0
    snap = _engine.DISPATCH_STATS.snapshot()
    assert snap["live_cells"] > 0 and snap["placed_runs"] == 0


def _brute_fetched(pack, aq, r, n_tiles, tq):
    """Fetched cells of one pass, recounted from every (segment, tile,
    block) window test in numpy float32: the span of each tile's hit
    blocks, or one block where it hits none (padding tiles included)."""
    lo_b, hi_b = pack.block_bounds()
    hq, lq = aq + r, aq - r
    fetched = 0
    for s in range(lo_b.shape[0]):
        for t in range(n_tiles):
            rows = slice(t * tq, (t + 1) * tq)
            hit = ((hq[rows, None] >= lo_b[s][None, :])
                   & (lq[rows, None] <= hi_b[s][None, :])).any(axis=0)
            live = np.flatnonzero(hit)
            fetched += live[-1] - live[0] + 1 if live.size else 1
    return int(fetched)


def test_fetched_cells_recount_the_tiles_ranges():
    """``fetched_cells`` lies between the live and the grid cells, and
    equals a brute recount of the tiles' block ranges, in each pass."""
    from repro.core import snn as _snn

    rng = np.random.default_rng(4)
    x = rng.normal(size=(3000, 4)).astype(np.float32)
    index = build_index(x)
    pack = _engine.pack_from_index(index, block=128)
    q = x[rng.permutation(3000)[:300]] + np.float32(0.01)
    _, aq, r, _, _ = _snn.prepare_query_predicates(index, q, 0.2)
    order = np.argsort(aq, kind="stable")   # tiles in alpha order already
    q, aq, r = q[order], aq[order], r[order]
    tq, nb = 64, pack.block_bounds()[0].shape[1]
    _engine.DISPATCH_STATS.reset()
    csr = single_query(index, q, 0.2, pack=pack, use_pallas="pallas-tpu",
                       query_tile=tq)
    assert csr.nnz > 0
    snap = _engine.DISPATCH_STATS.snapshot()
    assert 0 < snap["live_cells"] <= snap["fetched_cells"] \
        < snap["grid_cells"]
    n_tiles = snap["grid_cells"] // (2 * pack.n_segments * nb)  # two passes
    pad = n_tiles * tq - aq.size
    aqp = np.pad(aq, (0, pad))
    rp = np.pad(r, (0, pad), constant_values=-np.float32(_engine._ops.BIG))
    assert snap["fetched_cells"] == 2 * _brute_fetched(pack, aqp, rp,
                                                       n_tiles, tq)


def test_all_live_d128_batch_fetches_every_cell():
    rng = np.random.default_rng(5)
    x = rng.normal(size=(2000, 128)).astype(np.float32)
    index = build_index(x)
    pack = _engine.pack_from_index(index, block=512)
    q = rng.normal(size=(256, 128)).astype(np.float32)
    _engine.DISPATCH_STATS.reset()
    single_query(index, q, 16.0, pack=pack, use_pallas="pallas-tpu")
    snap = _engine.DISPATCH_STATS.snapshot()
    assert snap["live_cells"] == snap["fetched_cells"] \
        == snap["grid_cells"] > 0


def test_fetched_cells_reader():
    """The benchmark's ``fetched_cells`` reader: fetched / grid in %, and
    nothing from a program without the counter."""
    import importlib.util
    import os
    from types import SimpleNamespace

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench", "metrics", "fetched_cells.py")
    spec = importlib.util.spec_from_file_location("fetched_cells_reader",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    def read(**dispatch):
        return mod.read(SimpleNamespace(dispatch=dispatch))

    assert read(grid_cells=400, live_cells=40, fetched_cells=50) == 12.5
    assert read(grid_cells=400, live_cells=40) is None
    assert read(grid_cells=0, live_cells=0, fetched_cells=0) is None
