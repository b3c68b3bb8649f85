"""The program's profiler spans (``snn.*``) and its pruning/kNN counters.

Span tree: radius, join, count and kNN batches served through `SNNServer`
on the pallas-tpu lane (interpret mode) under `jax.profiler.trace`; the
``.xplane.pb`` is read back with `jax.profiler.ProfileData` and every span
must sit where the serving path puts it.  With the profiler off the same
calls give bit-identical responses.

Counters: ``grid_cells`` / ``live_cells`` against a brute-force evaluation
of the kernels' own `_window_hit` over every (segment, query tile, db
block) cell, and ``knn_rounds`` / ``knn_candidates`` / ``knn_wanted``
against what the expansion loop did.
"""
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs.snn_default import SNNConfig
from repro.core import engine as _engine
from repro.core import knn as _knn
from repro.core.streaming import StreamingSNNIndex
from repro.kernels import ops as _ops
from repro.kernels import snn_query as _k
from repro.serving.server import Request, SNNServer

D = 6
SLO_MS = 1e6  # interpret-mode batches are slow: no request flushes alone
NESTED = ("snn.prep", "snn.knn.round", "snn.launch", "snn.sync",
          "snn.assemble")


# --------------------------------------------------------------------------- #
# Span tree                                                                    #
# --------------------------------------------------------------------------- #
def _data(seed=0, n=600):
    rng = np.random.default_rng(seed)
    return (rng.normal(size=(n, D)).astype(np.float32),
            rng.normal(size=(40, D)).astype(np.float32))


def _batches(qs):
    """Two batches: every kind at once, then counts alone (the count pass)."""
    mixed = [Request(query=qs[0], radius=1.2, id=0, slo_ms=SLO_MS),
             Request(query=qs[1:9], radius=np.linspace(0.8, 1.6, 8), id=1,
                     slo_ms=SLO_MS),
             Request(query=qs[9], radius=1.0, id=2, count_only=True,
                     slo_ms=SLO_MS),
             Request(query=qs[10], k=5, id=3, slo_ms=SLO_MS),
             Request(query=qs[11], k=12, id=4, slo_ms=SLO_MS)]
    counts = [Request(query=qs[12 + j], radius=0.9 + 0.1 * j, id=10 + j,
                      count_only=True, slo_ms=SLO_MS) for j in range(3)]
    return [mixed, counts]


def _serve(server, batch):
    """Queue ``batch`` on a stopped server, start it so the dispatcher
    admits the whole batch at once, and collect every response."""
    for r in batch:
        server.submit(r)
    server.start()
    try:
        return {r.id: server.result(r.id, timeout=600) for r in batch}
    finally:
        server.stop()


def _session(server, qs, extra):
    """Both batches, with an append of ``extra`` rows between them (the
    append builds and warms the next plan: ``snn.plan``)."""
    first, second = _batches(qs)
    out = _serve(server, first)
    server.append(extra)
    out.update(_serve(server, second))
    return out


def _fresh_server():
    data, qs = _data()
    server = SNNServer(data, SNNConfig(backend="pallas-tpu", block_rows=128))
    extra = np.random.default_rng(7).normal(size=(50, D)).astype(np.float32)
    # warm-up: the same session compiles every shape and ratchets the
    # fused path's capacity, so the traced session runs at steady state
    _session(server, qs, extra)
    return server, qs, extra


def _spans(log_dir):
    """Every ``snn.*`` host event: (name, start, end, thread, metadata)."""
    files = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(files) == 1
    out = []
    for plane in ProfileData.from_file(files[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("snn."):
                    out.append((e.name, e.start_ns, e.end_ns,
                                (plane.name, li), dict(e.stats)))
    return out


def _inside(child, parent):
    return (child[3] == parent[3] and parent[1] <= child[1]
            and child[2] <= parent[2])


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    server, qs, extra = _fresh_server()
    log_dir = str(tmp_path_factory.mktemp("trace"))
    before = _engine.DispatchStats.aggregate()
    with jax.profiler.trace(log_dir):
        responses = _session(server, qs, extra)
    after = _engine.DispatchStats.aggregate()
    delta = {k: after[k] - before[k] for k in after}
    return responses, _spans(log_dir), delta, qs, extra


def test_every_span_appears_in_its_place(traced):
    _, spans, _, _, _ = traced
    names = {s[0] for s in spans}
    assert names >= {"snn.await_request", "snn.admit", "snn.batch",
                     "snn.prep", "snn.knn.round", "snn.launch", "snn.sync",
                     "snn.assemble", "snn.plan"}
    batches = [s for s in spans if s[0] == "snn.batch"]
    dispatchers = {b[3] for b in batches}
    # the dispatcher's work lies inside its batches
    for s in spans:
        if s[0] in NESTED and s[3] in dispatchers:
            assert any(_inside(s, b) for b in batches), s
    # a kNN round holds its own count launch and its wait for the counts
    rounds = [s for s in spans if s[0] == "snn.knn.round"]
    for rd in rounds:
        inner = {s[0] for s in spans if s is not rd and _inside(s, rd)}
        assert {"snn.prep", "snn.launch", "snn.sync"} <= inner, inner
        assert rd[4]["active"] >= 1 and rd[4]["round"] >= 0
    # admission: the wait for a request, then the batch's admission, then
    # the batch itself, on the dispatcher thread
    for b in batches:
        admit = max((s for s in spans if s[0] == "snn.admit"
                     and s[3] == b[3] and s[2] <= b[1]),
                    key=lambda s: s[2])
        assert admit[4]["requests"] == b[4]["requests"]
        assert any(s[0] == "snn.await_request" and s[3] == b[3]
                   and s[2] <= admit[1] for s in spans)
    # launches and waits never nest in each other
    io = [s for s in spans if s[0] in ("snn.launch", "snn.sync")]
    for a in io:
        assert not any(b is not a and _inside(b, a) for b in io)
    # the append built and warmed the next generation's plan off the
    # dispatcher thread, outside every batch
    plans = [s for s in spans if s[0] == "snn.plan"]
    assert plans and all(s[3] not in dispatchers for s in plans)
    assert all(s[4]["generation"] >= 1 for s in plans)


def test_one_batch_span_per_served_batch(traced):
    responses, spans, _, _, _ = traced
    batches = sorted((s for s in spans if s[0] == "snn.batch"),
                     key=lambda s: s[1])
    first, second = _batches(np.zeros((40, D), np.float32))
    assert len(batches) == 2
    for span, reqs in zip(batches, (first, second)):
        meta = span[4]
        assert meta["requests"] == len(reqs)
        assert meta["rows"] == sum(r.rows for r in reqs)
        assert meta["kinds"] == "+".join(sorted({r.kind for r in reqs}))
        assert {responses[r.id].batch for r in reqs} == {meta["batch"]}
        assert all(responses[r.id].error is None for r in reqs)
    assert batches[1][4]["batch"] == batches[0][4]["batch"] + 1


def test_launch_and_sync_spans_match_the_counters(traced):
    _, spans, delta, _, _ = traced
    launches = [s for s in spans if s[0] == "snn.launch"]
    assert len(launches) == delta["kernel_launches"] > 0
    assert sum(s[0] == "snn.sync" for s in spans) == delta["host_transfers"]
    # a launch that met a new signature is marked, one per compile
    assert sum(s[4].get("new_signature", 0) for s in launches) \
        == delta["jit_compiles"]
    assert {s[4]["op"] for s in launches} >= {"snn_csr_fused_stacked",
                                               "snn_count_stacked"}


def test_profiler_off_gives_bit_identical_responses(traced):
    responses, _, _, qs, extra = traced
    server, _, _ = _fresh_server()
    plain = _session(server, qs, extra)
    assert plain.keys() == responses.keys()
    for rid, got in responses.items():
        want = plain[rid]
        for field in ("indices", "sq_dists", "indptr", "counts"):
            a, b = getattr(got, field), getattr(want, field)
            assert (a is None) == (b is None), (rid, field)
            if a is not None:
                np.testing.assert_array_equal(a, b, err_msg=f"{rid} {field}")
        assert got.batch == want.batch


# --------------------------------------------------------------------------- #
# Counters                                                                     #
# --------------------------------------------------------------------------- #
def _brute_cells(pack, live_idx, aqp, rp, tq):
    """(grid, live) of one stacked pass, by `_window_hit` on every cell."""
    _, al, _, _ = pack.stacked()
    al = np.asarray(al)
    bn = pack.block
    n_tiles, n_blocks = aqp.shape[0] // tq, al.shape[1] // bn
    live = 0
    for s in live_idx:
        for t in range(n_tiles):
            aq_t, r_t = aqp[t * tq:(t + 1) * tq], rp[t * tq:(t + 1) * tq]
            for b in range(n_blocks):
                live += bool(_k._window_hit(aq_t, r_t, al[s, b * bn],
                                            al[s, b * bn + bn - 1]))
    return live_idx.size * n_tiles * n_blocks, live


def _two_segment_pack():
    """S = 2 after an append: a base of 5 full blocks plus a partial one,
    and a one-block delta padded to the base's 6 blocks."""
    rng = np.random.default_rng(3)
    index = StreamingSNNIndex(rng.normal(size=(700, D)).astype(np.float32),
                              block=128)
    index.append(rng.normal(size=(60, D)).astype(np.float32) + 0.5)
    pack = index.plan()
    assert pack.n_segments == 2
    return index, pack, rng


def _padded(index, rng, m):
    """Queries with mixed radii (tiny, wide, and one far outside every
    window) padded from m rows to a whole number of 128-row tiles."""
    from repro.core import snn as _snn

    q = rng.normal(size=(m, D)).astype(np.float32)
    q[-1] += 40.0
    radius = rng.choice([0.05, 0.6, 3.0], size=m)
    xq, aq, r, th, _ = _snn.prepare_query_predicates(index.parts[0], q,
                                                     radius)
    return _ops.pad_queries(xq, aq, r, th, tq=128)


@pytest.mark.parametrize("m", [130, 40])
def test_cell_counters_match_window_hit_brute_force(m):
    index, pack, rng = _two_segment_pack()
    qp, aqp, rp, thp, _ = _padded(index, rng, m)
    live_idx = _engine._prologue(pack, qp, aqp, rp, thp, m, 128, None)[-1]
    grid, live = _brute_cells(pack, live_idx, np.asarray(aqp),
                              np.asarray(rp), 128)
    assert 0 < live < grid  # some cells pruned, some not

    def counted(fn):
        _engine.DISPATCH_STATS.reset()
        fn()
        snap = _engine.DISPATCH_STATS.snapshot()
        return snap["grid_cells"], snap["live_cells"]

    # first run: the classic count -> compact pair (two passes); second:
    # the fused single dispatch (both passes in one launch)
    for _ in range(2):
        assert counted(lambda: _engine.run_csr_packed(
            pack, qp, aqp, rp, thp, m, use_pallas=True)) \
            == (2 * grid, 2 * live)
    # pass 1 alone
    assert counted(lambda: _engine.run_counts_packed(
        pack, qp, aqp, rp, thp, m, use_pallas=True)) == (grid, live)
    # the oracle lane runs no grid
    assert counted(lambda: _engine.run_csr_packed(
        pack, qp, aqp, rp, thp, m, use_pallas=False)) == (0, 0)


def test_knn_counters_follow_the_expansion_loop(monkeypatch):
    """Seeds at 0.6 x the true k-th distance: the first count pass is short
    for every query, the doubled radius holds, so the loop runs twice."""
    rng = np.random.default_rng(5)
    data = rng.normal(size=(500, D)).astype(np.float32)
    index = StreamingSNNIndex(data)
    q = rng.normal(size=(9, D)).astype(np.float32)
    k = np.array([1, 3, 5, 8, 10, 2, 4, 6, 600])  # the last asks for > n
    xq = q - index.parts[0].mu[None, :]
    d2 = ((xq[:, None, :].astype(np.float64)
           - index.parts[0].xs[None, :, :]) ** 2).sum(-1)
    kth = np.sqrt(np.sort(d2, axis=1)[np.arange(9), np.minimum(k, 500) - 1])
    monkeypatch.setattr(_knn, "_seed_radii", lambda *a: np.zeros(9))
    monkeypatch.setattr(_knn, "_sample_estimate", lambda *a: 0.6 * kth)
    calls, totals = [], []
    count_pass, run_csr_packed = _knn._count_pass, _engine.run_csr_packed

    def spy_count(*a, **kw):
        calls.append(a[1].shape[0])
        return count_pass(*a, **kw)

    def spy_final(*a, **kw):
        out = run_csr_packed(*a, **kw)
        totals.append(int(out[0][-1]))
        return out

    monkeypatch.setattr(_knn, "_count_pass", spy_count)
    monkeypatch.setattr(_engine, "run_csr_packed", spy_final)
    _engine.DISPATCH_STATS.reset()
    idx, _ = _knn.query_knn(index, q, k, native=False)
    snap = _engine.DISPATCH_STATS.snapshot()
    assert calls == [9, 9]  # every query short once, then all converged
    assert snap["knn_rounds"] == 2
    assert snap["knn_wanted"] == int(np.minimum(k, 500).sum())
    assert len(totals) == 1
    assert snap["knn_candidates"] == totals[0] >= snap["knn_wanted"]
    assert (idx[-1] >= 0).sum() == 500
