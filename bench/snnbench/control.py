"""The control: the plain reference in bfloat16, put in the program's place.

The configurations state float32 rows and answers exact to a float64 brute
force.  The nearest precision below is bfloat16, the step that would tempt a
later change, so the control answers the cell's own requests from the
brute force with its distance products in bfloat16, and the comparison that
decides ``correct`` must fail it.  The same brute force at float32 HIGHEST
in the program's place is the sound twin, which must pass.

    python3 bench/control.py --workload <name> --seeds 1,2,3 [--precision bf16|f32]

runs at the cell's own size on the machine it is started on (a TPU) and
prints one JSON line per seed with the compared numbers.
"""
from __future__ import annotations

import types

import numpy as np

from . import data, harness, reference, spec
from . import traffic as traffic_mod


class _Sample:
    """The requests a run would compare, answered by the brute force."""

    def __init__(self, plan, recs):
        self.plan, self.recs = plan, recs

    def item(self, i: int):
        return self.plan.items[i % len(self.plan.items)]

    def checked(self) -> list:
        return self.recs


def answers(brute: reference.BruteForce, items, pool) -> list:
    """Response-like objects for ``items``, all answered by ``brute`` in
    one pass over their rows."""
    rows = np.concatenate([it.rows for it in items])
    q = pool[rows]
    if items[0].kind == "knn":
        ids, d2 = brute.nearest(q, items[0].k)
        return [types.SimpleNamespace(indices=ids[j], sq_dists=d2[j],
                                      indptr=None, counts=None)
                for j in range(len(items))]
    r2 = np.concatenate([np.full(len(it.rows), it.r2) for it in items])
    found = brute.within(q, r2)
    out, s = [], 0
    for it in items:
        part = found[s:s + len(it.rows)]
        s += len(it.rows)
        indptr = np.zeros(len(part) + 1, np.int64)
        np.cumsum([f[0].size for f in part], out=indptr[1:])
        out.append(types.SimpleNamespace(
            indices=np.concatenate([f[0] for f in part]),
            sq_dists=np.concatenate([f[1] for f in part]),
            indptr=indptr if it.kind == "join" else None,
            counts=np.diff(indptr)))
    return out


def numbers(workload: str, seed: int, seconds: float, *, bf16: bool = True,
            overrides: dict | None = None) -> dict:
    """The compared numbers of one seed, the brute force answering."""
    overrides = overrides or {}
    root = overrides.get("root", spec.ROOT)
    bench = spec.load_benchmark(root)
    cell = spec.cell(bench, workload)
    cfg = dict(spec.config(bench, cell["config"], root))
    cfg.update(overrides.get("config", {}))
    mix = spec.traffic(cell["traffic"])
    mix.update(overrides.get("traffic", {}))
    d, metric = int(cfg["d"]), cfg["metric"]
    base, pool = data.config_rows(cfg, seed)
    exact = reference.BruteForce(base, metric)
    plan = traffic_mod.plan(mix, pool, exact, metric, seed, seconds)
    exact.free()
    rng = np.random.default_rng([int(seed) & (2**63 - 1), 13])
    n = min(plan.check_requests, len(plan.items))
    pick = set(rng.choice(len(plan.items), size=n, replace=False).tolist())
    pick.add(int(np.argmax([it.expect for it in plan.items])))
    brute = reference.BruteForce(base, metric, bf16=bf16)
    pick = sorted(pick)
    recs = [types.SimpleNamespace(i=i, resp=r) for i, r in
            zip(pick, answers(brute, [plan.items[i] for i in pick], pool))]
    brute.free()
    ref = reference.Reference(base, metric, d)
    tally = reference.Tally()
    harness.compare(ref, _Sample(plan, recs), pool, tally)
    ref.free()
    chk = harness.checks(tally, 0, cfg["limits"])
    return {"seed": seed, "precision": "bf16" if bf16 else "f32",
            "rows": tally.rows, "correct": all(v <= lim for v, lim
                                              in chk.values()),
            "checks": {k: {"value": v, "limit": lim}
                       for k, (v, lim) in chk.items()},
            "notes": tally.notes[:3]}
