"""The load generators, against a fake server that stalls once."""
import queue
import threading
import time

import numpy as np

from snnbench import loadgen


class FakeServer:
    """FIFO, 1 ms per request; request ``stall_at`` takes ``stall_s``."""

    def __init__(self, stall_at=-1, stall_s=0.0):
        self.q = queue.Queue()
        self.done = {}
        self.cv = threading.Condition()
        self.stall_at, self.stall_s = stall_at, stall_s
        self.th = threading.Thread(target=self._serve, daemon=True)
        self.th.start()

    def _serve(self):
        while True:
            i = self.q.get()
            if i is None:
                return
            time.sleep(self.stall_s if i == self.stall_at else 1e-3)
            with self.cv:
                self.done[i] = f"answer {i}"
                self.cv.notify_all()

    def send(self, i):
        self.q.put(i)

    def wait(self, i, timeout):
        with self.cv:
            if not self.cv.wait_for(lambda: i in self.done, timeout):
                raise TimeoutError(i)
            return self.done.pop(i)

    def close(self):
        self.q.put(None)


def test_a_stall_shows_in_the_latency_of_later_requests():
    due = np.arange(80) * 0.01          # one request every 10 ms
    srv = FakeServer(stall_at=10, stall_s=0.3)
    try:
        recs = loadgen.open_loop(srv.send, srv.wait, due,
                                 t0=time.perf_counter() + 0.01)
    finally:
        srv.close()
    lat = np.array([r.done - r.due for r in recs])
    assert all(r.resp == f"answer {r.i}" for r in recs)
    assert lat[:10].max() < 0.05
    # requests due during the stall waited for it, counted from when due
    assert lat[10] > 0.29 and lat[11] > 0.27 and lat[20] > 0.18
    assert lat[79] < 0.05               # the backlog drained
    # the generator kept its schedule: it was never held up by the stall
    lag = np.array([r.sent - r.due for r in recs])
    assert lag.max() < 0.02


def test_a_response_that_never_comes_is_recorded():
    srv = FakeServer(stall_at=2, stall_s=5.0)
    try:
        recs = loadgen.open_loop(srv.send, srv.wait, np.array([0.0, 0.01,
                                                               0.02]),
                                 t0=time.perf_counter(), grace_s=0.3)
    finally:
        srv.close()
    assert [r.error is None for r in recs] == [True, True, False]
    assert recs[2].done - recs[2].due >= 0.29


def test_closed_loop_keeps_one_request_per_client():
    srv = FakeServer()
    try:
        recs = loadgen.closed_loop(srv.send, srv.wait, 2,
                                   t0=time.perf_counter(), seconds=0.2)
    finally:
        srv.close()
    assert len(recs) > 20
    assert sorted(r.i for r in recs) == list(range(len(recs)))
    assert all(r.error is None and r.done >= r.sent for r in recs)
