"""Load generators: an open loop on a fixed schedule, and a closed loop.

Both drive a client through two callables, ``send(i)`` (submit request i;
returns at once) and ``wait(i, timeout)`` (block for its response; raise
`TimeoutError`), and record, per request, when it was due, when it was sent
and when its response was seen.

Open loop: request i is due at ``t0 + due[i]`` and is sent then, whatever
happened to the requests before it.  Its latency counts from when it was
due, so a stall in the server, or a generator that falls behind, shows in
the latency of every request that waited for it.  How late the generator
sent (``sent - due``) is recorded too.

Closed loop: each of ``clients`` threads keeps one request outstanding and
sends the next as soon as the last one is answered, until the window ends.
"""
from __future__ import annotations

import queue
import threading
import time
from dataclasses import dataclass


@dataclass
class Record:
    i: int
    due: float
    sent: float = 0.0
    done: float = 0.0           # when the response, or the give-up, came
    resp: object = None
    error: str | None = None


def _sleep_until(t: float) -> None:
    while True:
        dt = t - time.perf_counter()
        if dt <= 0:
            return
        time.sleep(dt if dt > 2e-3 else 0.0)


def open_loop(send, wait, due, *, t0: float, grace_s: float = 60.0,
              on_done=None) -> list:
    """Send request i at ``t0 + due[i]``; wait for every response.

    The last responses are waited for up to ``grace_s`` past the last due
    time.  ``on_done(record)`` runs on the collector thread as each response
    is seen, in order of sending.
    """
    recs = [Record(i, t0 + float(d)) for i, d in enumerate(due)]
    give_up = t0 + (float(due[-1]) if len(due) else 0.0) + grace_s
    sent_q: queue.Queue = queue.Queue()

    def collect():
        for _ in range(len(recs)):
            rec = sent_q.get()
            try:
                rec.resp = wait(rec.i, max(give_up - time.perf_counter(), 0.0))
            except TimeoutError as e:
                rec.error = f"no response: {e}"
            rec.done = time.perf_counter()
            if on_done is not None:
                on_done(rec)

    th = threading.Thread(target=collect, name="bench-collector", daemon=True)
    th.start()
    for rec in recs:
        _sleep_until(rec.due)
        rec.sent = time.perf_counter()
        send(rec.i)
        sent_q.put(rec)
    th.join()
    return recs


def closed_loop(send, wait, clients: int, *, t0: float, seconds: float,
                grace_s: float = 60.0, on_done=None) -> list:
    """``clients`` threads, each with one request outstanding, until
    ``t0 + seconds``; request ids count up across clients from 0."""
    recs: list = []
    lock = threading.Lock()
    t_end = t0 + seconds

    def client():
        while True:
            now = time.perf_counter()
            if now >= t_end:
                return
            with lock:
                rec = Record(len(recs), now)
                recs.append(rec)
            rec.sent = time.perf_counter()
            send(rec.i)
            try:
                rec.resp = wait(rec.i, max(t_end + grace_s
                                           - time.perf_counter(), 0.0))
            except TimeoutError as e:
                rec.error = f"no response: {e}"
            rec.done = time.perf_counter()
            if on_done is not None:
                with lock:
                    on_done(rec)

    _sleep_until(t0)
    threads = [threading.Thread(target=client, name=f"bench-client-{c}",
                                daemon=True) for c in range(clients)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    return recs
