"""The one traffic generator: a mix's parameters in, a request plan out.

A mix (``bench/traffic/<name>.json``) gives:

* ``loop``: ``"open"`` (independent users: requests are sent on a schedule
  whatever happened before) with ``rate_per_s`` and ``schedule_seed``, or
  ``"closed"`` (callers that each wait for their reply) with ``clients``;
* ``request``: ``kind`` (``radius``, ``count``, ``join`` or ``knn``),
  ``rows`` per request, and either ``k`` (kNN) or ``neighbors``: for
  ``"per": "all"`` one radius for the whole run, chosen so the median
  requested row holds ``median`` neighbors; for ``"per": "block"`` one
  radius per block chosen so its median row holds ``median`` neighbors;
* ``blocks``: how many distinct request blocks a closed loop cycles through;
* ``slo_ms``: the requests' latency budget (null: the server's default);
* ``check_requests``: how many answered requests the comparison samples;
* ``warm_batches``: the batch sizes (in requests) the warm-up drives, each
  made of the requests holding the most results: the largest batches the
  window's traffic forms, so that every shape and result capacity it
  reaches is compiled before the window opens.

Every seed gets the same number of requests and the same arrivals: an open
loop's N = rate x seconds arrival times are sorted uniform draws (a Poisson
process given N) from the mix's ``schedule_seed``, the same for every
``--seed``, so the queue sees the same bursts in every run.  The seed draws
the data and which query rows go at those times; a mix that names a
``rows_seed`` draws the query rows from it instead, the same pool rows for
every seed, and the seed only draws their order (over a configuration whose
``points_seed`` fixes the points, every run then asks the same set of
requests, each with the same work).  ``warm_all`` makes the warm-up send
every request of the plan (`harness.warm_bursts`).  Radii come from the
benchmark's own brute force (`reference.BruteForce`): the radius of a row
that should hold k neighbors lies halfway between its k-th and (k+1)-th
squared distance.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from . import reference


@dataclass
class Item:
    """One request: pool rows and either a radius (native) or k."""

    kind: str
    rows: np.ndarray
    radius: object = None       # float, or None for kNN
    r2: object = None           # index-space squared radius (float)
    k: int | None = None
    expect: int = 0             # result pairs the brute force counts


@dataclass
class Plan:
    loop: str
    items: list
    due: np.ndarray | None      # open loop: offsets (s) into the window
    clients: int
    slo_ms: float | None
    check_requests: int
    warm_batches: list
    warm_all: bool = False


def _mid_r2(kth: np.ndarray, k: np.ndarray) -> np.ndarray:
    """Squared radius halfway between the k-th and (k+1)-th distance."""
    rows = np.arange(kth.shape[0])
    return 0.5 * (kth[rows, k - 1].astype(np.float64)
                  + kth[rows, k].astype(np.float64))


def plan(mix: dict, pool: np.ndarray, brute, metric: str, seed: int,
         seconds: float) -> Plan:
    """The requests of one run of ``mix`` over ``pool`` query rows."""
    rng = np.random.default_rng([int(mix.get("rows_seed", seed))
                                 & (2**63 - 1), 7])
    req = mix["request"]
    kind, m = req["kind"], int(req.get("rows", 1))
    if mix["loop"] == "open":
        n_req = max(1, int(round(mix["rate_per_s"] * seconds)))
        sched = np.random.default_rng([int(mix["schedule_seed"]), 5])
        due = np.sort(sched.uniform(0.0, seconds, n_req))
    else:
        n_req = int(mix["blocks"])
        due = None
    rows = [rng.choice(pool.shape[0], size=m, replace=False)
            for _ in range(n_req)]
    if "rows_seed" in mix:
        order = np.random.default_rng([int(seed) & (2**63 - 1), 9])
        rows = [rows[j] for j in order.permutation(n_req)]

    if kind == "knn":
        items = [Item(kind, r, k=int(req["k"])) for r in rows]
    else:
        nb = req["neighbors"]
        med = int(nb["median"])
        flat = np.concatenate(rows)
        kth = brute.kth(pool[flat], med + 1)
        per_row = _mid_r2(kth, np.full(flat.size, med))
        if nb["per"] == "all":
            r2 = np.full(n_req, np.median(per_row))
        elif nb["per"] == "block":
            r2 = np.median(per_row.reshape(n_req, m), axis=1)
        else:
            raise ValueError(f"unknown neighbors per {nb['per']!r}")
        radius = reference.native_radius(r2, metric)
        items = [Item(kind, r, radius=float(radius[i]), r2=float(r2[i]))
                 for i, r in enumerate(rows)]
        cnt = brute.counts(pool[flat], np.repeat(r2, m)).reshape(n_req, m)
        for it, c in zip(items, cnt.sum(axis=1)):
            it.expect = int(c)
    return Plan(loop=mix["loop"], items=items, due=due,
                clients=int(mix.get("clients", 1)),
                slo_ms=mix.get("slo_ms"),
                check_requests=int(mix["check_requests"]),
                warm_batches=[int(b) for b in mix["warm_batches"]],
                warm_all=bool(mix.get("warm_all", False)))
