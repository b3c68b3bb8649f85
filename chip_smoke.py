#!/usr/bin/env python3
"""Drive the SNN serving path once on a TPU chip at SIFT1M shape and check it.

    python3 chip_smoke.py              # one chip: the served path
    python3 chip_smoke.py --chips 4    # four chips: the sharded index only
    python3 chip_smoke.py --rehearse   # tiny CPU rehearsal (interpret mode)

One chip: an `SNNServer` over n = 1,000,000 clustered points of d = 128,
euclidean (the shape of ann-benchmarks' sift-128-euclidean), generated from
``--seed``.  Radius, count-only, kNN (k = 10) and join requests go through
``submit``/``result``; then one ``append`` of 10,000 points adds a second
segment (the stacked kernels at S = 2 and the warmed plan swap run) and a
second round of requests follows.  Every answer is compared with a float64
brute force over the same float32 rows, on the host and in chunks.

Four chips: the same index sharded over a 4-device mesh
(`core.sharded.shard_index`), counted with `make_sharded_percount_fn`, and
compared with the single-chip engine counts and the float64 reference.

Exactness: an answer may differ from the float64 reference only for pairs
whose squared distance lies within ``BAND * ((|x| + |q|)**2 + r**2)`` of
``r**2`` (norms of the centered rows): ``BAND = 2 (d + 2) 2**-24`` bounds the
float32 rounding of the engine's half-norm predicate.  Any other difference,
and any error response, fails the run.

The last line of stdout on success is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU (and outside a checkout) the script exits non-zero and prints
no result.  ``--rehearse`` runs the flow at a tiny size on the CPU with the
kernels in interpret mode; its last line names the CPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

D = 128
FULL = dict(n=1_000_000, clusters=1000, n_append=10_000, new_clusters=5,
            rounds=({"radius": 192, "count": 64, "knn": 48, "join": 40},
                    {"radius": 128, "count": 32, "knn": 32, "join": 24}),
            sharded_queries=256)
TINY = dict(n=6_000, clusters=12, n_append=600, new_clusters=2,
            rounds=({"radius": 12, "count": 6, "knn": 6, "join": 8},
                    {"radius": 10, "count": 4, "knn": 4, "join": 6}),
            sharded_queries=16)
K_NN = 10
K_REF = 1024            # reference list per query row (radii stay < 1000)
BAND = 2 * (D + 2) * 2.0 ** -24
RESULT_TIMEOUT_S = 900.0


def fail(msg: str, code: int = 1):
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def say(msg: str):
    print(msg, flush=True)


# --------------------------------------------------------------------------- #
# Data and the float64 reference                                              #
# --------------------------------------------------------------------------- #
class Mixture:
    """Gaussian clusters with heavy-tailed sizes and varied spreads."""

    def __init__(self, rng, n_clusters: int):
        self.centers = (rng.standard_normal((n_clusters, D)) * 2.0
                        ).astype(np.float32)
        self.scales = rng.uniform(0.6, 1.4, n_clusters).astype(np.float32)
        w = rng.pareto(2.0, n_clusters) + 1.0
        self.weights = w / w.sum()

    def sample(self, rng, n: int, chunk: int = 1 << 17) -> np.ndarray:
        out = np.empty((n, D), np.float32)
        for s in range(0, n, chunk):
            e = min(n, s + chunk)
            c = rng.choice(len(self.weights), size=e - s, p=self.weights)
            x = rng.standard_normal((e - s, D), dtype=np.float32)
            x *= self.scales[c, None]
            x += self.centers[c]
            out[s:e] = x
        return out


def reference(corpora, queries, k: int, chunk: int = 1 << 16):
    """(ids (m, k), d2 (m, k)) float64 nearest rows over ``corpora``.

    ``corpora`` is a list of (first_id, rows) float32 blocks; squared
    distances are computed in float64 from the float32 rows.
    """
    q = queries.astype(np.float64)
    qq = np.einsum("ij,ij->i", q, q)
    m = q.shape[0]
    best_d = np.full((m, k), np.inf)
    best_i = np.full((m, k), -1, np.int64)
    for first, rows in corpora:
        for s in range(0, rows.shape[0], chunk):
            x = rows[s:s + chunk].astype(np.float64)
            d2 = qq[:, None] + np.einsum("ij,ij->i", x, x)[None, :] \
                - 2.0 * (q @ x.T)
            np.maximum(d2, 0.0, out=d2)
            cat_d = np.concatenate([best_d, d2], axis=1)
            cat_i = np.concatenate(
                [best_i, np.broadcast_to(np.arange(first + s,
                                                   first + s + x.shape[0]),
                                         d2.shape)], axis=1)
            pick = np.argpartition(cat_d, k - 1, axis=1)[:, :k]
            best_d = np.take_along_axis(cat_d, pick, 1)
            best_i = np.take_along_axis(cat_i, pick, 1)
    order = np.argsort(best_d, axis=1, kind="stable")
    return (np.take_along_axis(best_i, order, 1),
            np.take_along_axis(best_d, order, 1))


class Ref:
    """Float64 reference for one query row plus the rounding band."""

    def __init__(self, ids, d2, cnorm, qnorm):
        self.ids, self.d2, self.cnorm, self.qnorm = ids, d2, cnorm, qnorm
        self.pos = {int(i): j for j, i in enumerate(ids)}

    def band(self, r2):
        return BAND * ((self.cnorm + self.qnorm) ** 2 + r2)


class Tally:
    def __init__(self):
        self.responses = 0
        self.errors = 0
        self.mismatches = 0
        self.band_pairs = 0
        self.band_flips = 0
        self.notes: list[str] = []

    def bad(self, what: str):
        self.mismatches += 1
        if len(self.notes) < 20:
            self.notes.append(what)


def check_ball(ref: Ref, r2: float, ids, sq, tally: Tally, what: str):
    """A radius/join row: the neighbor set and its squared distances."""
    band = ref.band(r2)
    if ref.d2[-1] <= r2 + band[-1]:
        tally.bad(f"{what}: reference list too short for r^2={r2}")
        return
    inside = ref.d2 <= r2 + band
    edge = inside & (ref.d2 >= r2 - band)
    got = np.zeros(ref.ids.shape[0], bool)
    for i, s in zip(np.asarray(ids).tolist(), np.asarray(sq).tolist()):
        j = ref.pos.get(i)
        if j is None or not inside[j]:
            tally.bad(f"{what}: id {i} is outside the ball")
            return
        if got[j]:
            tally.bad(f"{what}: id {i} returned twice")
            return
        got[j] = True
        if abs(s - ref.d2[j]) > band[j]:
            tally.bad(f"{what}: id {i} sq_dist {s} vs {ref.d2[j]}")
            return
    missing = inside & ~edge & ~got
    if missing.any():
        tally.bad(f"{what}: {int(missing.sum())} neighbors missing")
        return
    tally.band_pairs += int(edge.sum())
    tally.band_flips += int((edge & (got != (ref.d2 <= r2))).sum())


def check_count(ref: Ref, r2: float, count: int, tally: Tally, what: str):
    band = ref.band(r2)
    lo = int((ref.d2 < r2 - band).sum())
    hi = int((ref.d2 <= r2 + band).sum())
    exact = int((ref.d2 <= r2).sum())
    tally.band_pairs += hi - lo
    tally.band_flips += abs(int(count) - exact)
    if not lo <= count <= hi:
        tally.bad(f"{what}: count {count} outside [{lo}, {hi}]")


def check_knn(ref: Ref, ids, sq, tally: Tally, what: str):
    t = ref.d2[K_NN - 1]
    band = ref.band(t)
    if len(ids) != K_NN:
        tally.bad(f"{what}: {len(ids)} ids for k={K_NN}")
        return
    got = set()
    for i, s in zip(np.asarray(ids).tolist(), np.asarray(sq).tolist()):
        j = ref.pos.get(i)
        if j is None or ref.d2[j] > t + band[j] \
                or abs(s - ref.d2[j]) > band[j]:
            tally.bad(f"{what}: id {i} is not among the {K_NN} nearest")
            return
        got.add(i)
    must = ref.ids[ref.d2 < t - band]
    if not set(must.tolist()) <= got:
        tally.bad(f"{what}: a nearer neighbor is missing")
    tally.band_pairs += int((np.abs(ref.d2 - t) <= band).sum())


# --------------------------------------------------------------------------- #
# Request plans                                                               #
# --------------------------------------------------------------------------- #
def radii_for(d2: np.ndarray, rng) -> tuple[np.ndarray, np.ndarray]:
    """Per-row radii holding k in [10, 1000] reference points (log-uniform),
    each placed halfway between the k-th and (k+1)-th squared distance."""
    m = d2.shape[0]
    k = np.exp(rng.uniform(np.log(10), np.log(1000), m)).astype(np.int64)
    rows = np.arange(m)
    r2 = 0.5 * (d2[rows, k - 1] + d2[rows, k])
    return np.sqrt(r2), k


def make_round(rng, mix, new_mix, spec):
    """Query rows of one round, grouped by request kind."""
    rows = {}
    for kind in ("radius", "count", "knn", "join"):
        n = spec[kind]
        q = mix.sample(rng, n)
        if new_mix is not None:  # a quarter lands near the appended points
            k = n // 4
            q[:k] = new_mix.sample(rng, k)
        rows[kind] = q
    return rows


def run_round(server, Request, round_rows, refs, radii, tally, first_id,
              label):
    """Submit each kind as one burst, collect, and check every answer."""
    rid = first_id
    t0 = time.perf_counter()
    for kind in ("radius", "count", "knn", "join"):
        q = round_rows[kind]
        ref, rad = refs[kind], radii.get(kind)
        slo = 600_000.0  # batch analytics: fuse whatever is queued
        if kind == "join":
            reqs = [Request(query=q, radius=rad, id=rid, slo_ms=slo)]
        elif kind == "knn":
            reqs = [Request(query=q[j], k=K_NN, id=rid + j, slo_ms=slo)
                    for j in range(q.shape[0])]
        else:
            reqs = [Request(query=q[j], radius=float(rad[j]), id=rid + j,
                            count_only=kind == "count", slo_ms=slo)
                    for j in range(q.shape[0])]
        rid += len(reqs)
        for r in reqs:
            server.submit(r)
        for j, r in enumerate(reqs):
            resp = server.result(r.id, timeout=RESULT_TIMEOUT_S)
            tally.responses += 1
            what = f"{label} {kind} #{j}"
            if resp.error is not None:
                tally.errors += 1
                tally.bad(f"{what}: error response: {resp.error}")
                continue
            if kind == "join":
                for t in range(q.shape[0]):
                    lo, hi = resp.indptr[t], resp.indptr[t + 1]
                    check_ball(ref[t], float(rad[t]) ** 2,
                               resp.indices[lo:hi], resp.sq_dists[lo:hi],
                               tally, f"{what} row {t}")
            elif kind == "knn":
                check_knn(ref[j], resp.indices, resp.sq_dists, tally, what)
            elif kind == "count":
                check_count(ref[j], float(rad[j]) ** 2, int(resp.counts[0]),
                            tally, what)
            else:
                check_ball(ref[j], float(rad[j]) ** 2, resp.indices,
                           resp.sq_dists, tally, what)
    say(f"[{label}] {rid - first_id} requests answered in "
        f"{time.perf_counter() - t0:.1f} s (compiles included)")
    return rid


def build_refs(rows_by_kind, corpora, mu, rng):
    """Reference lists + radii for every kind of one round."""
    refs, radii, counts = {}, {}, []
    kinds = list(rows_by_kind)
    allq = np.concatenate([rows_by_kind[k] for k in kinds])
    ids, d2 = reference(corpora, allq, K_REF)
    cnorm = np.zeros(ids.shape)
    for first, rows in corpora:
        sel = (ids >= first) & (ids < first + rows.shape[0])
        x = rows[ids[sel] - first].astype(np.float64) - mu
        cnorm[sel] = np.sqrt(np.einsum("ij,ij->i", x, x))
    qc = allq.astype(np.float64) - mu
    qnorm = np.sqrt(np.einsum("ij,ij->i", qc, qc))
    s = 0
    for kind in kinds:
        n = rows_by_kind[kind].shape[0]
        refs[kind] = [Ref(ids[j], d2[j], cnorm[j], qnorm[j])
                      for j in range(s, s + n)]
        if kind != "knn":
            radii[kind], k = radii_for(d2[s:s + n], rng)
            counts.append(k)
        s += n
    return refs, radii, np.concatenate(counts)


# --------------------------------------------------------------------------- #
# Device facts                                                                #
# --------------------------------------------------------------------------- #
def device_info(jax):
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def peak_bytes(jax):
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()]


def in_use(jax):
    return [(d.memory_stats() or {}).get("bytes_in_use")
            for d in jax.devices()]


def start_compile_clock(jax):
    """Sum of backend compile seconds seen by this process."""
    total = [0.0, 0]

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            total[0] += duration
            total[1] += 1

    jax.monitoring.register_event_duration_secs_listener(listen)
    return total


# --------------------------------------------------------------------------- #
# Phases                                                                      #
# --------------------------------------------------------------------------- #
def one_chip(args, size, jax):
    from repro.configs.snn_default import SNNConfig
    from repro.core import engine
    from repro.kernels import registry
    from repro.serving import Request, SNNServer

    lane_name = "pallas-tpu" if args.rehearse else None
    lane = registry.resolve(lane_name)
    say(f"lane: {lane.name} interpret={getattr(lane, 'interpret', None)}")
    if not args.rehearse and (lane.name != "pallas-tpu" or lane.interpret):
        fail(f"the default lane is {lane.name} (interpret="
             f"{getattr(lane, 'interpret', None)}), not pallas-tpu on the "
             f"chip", 2)

    rng = np.random.default_rng(args.seed)
    t0 = time.perf_counter()
    mix = Mixture(rng, size["clusters"])
    data = mix.sample(rng, size["n"])
    new_mix = Mixture(rng, size["new_clusters"])
    n_new = size["n_append"]
    appended = np.concatenate([mix.sample(rng, n_new - n_new // 2),
                               new_mix.sample(rng, n_new // 2)])
    rounds = [make_round(rng, mix, None, size["rounds"][0]),
              make_round(rng, mix, new_mix, size["rounds"][1])]
    say(f"data: n={data.shape[0]} d={D} (+{n_new} appended), "
        f"{time.perf_counter() - t0:.1f} s to generate")

    cfg = SNNConfig(backend=lane_name)
    t0 = time.perf_counter()
    server = SNNServer(data, cfg)
    say(f"index built in {time.perf_counter() - t0:.1f} s")
    mu = server.index.base.mu.astype(np.float64)

    t0 = time.perf_counter()
    corpora = [[(0, data)], [(0, data), (data.shape[0], appended)]]
    plans = [build_refs(r, c, mu, rng) for r, c in zip(rounds, corpora)]
    k_all = np.concatenate([p[2] for p in plans])
    say(f"float64 reference in {time.perf_counter() - t0:.1f} s; "
        f"radius/count/join rows hold {k_all.mean():.1f} reference "
        f"neighbors on average (min {k_all.min()}, max {k_all.max()})")
    if not 10 <= k_all.mean() <= 1000:
        fail(f"mean neighbor count {k_all.mean()} outside [10, 1000]")

    tally = Tally()
    engine.DISPATCH_STATS.reset()
    server.start()
    try:
        rid = run_round(server, Request, rounds[0], plans[0][0], plans[0][1],
                        tally, 0, "before append")
        t0 = time.perf_counter()
        server.append(appended)
        plan = server.index.plan()
        say(f"append of {n_new} points (plan built and warmed) in "
            f"{time.perf_counter() - t0:.1f} s; the plan has "
            f"{plan.n_segments} segments of n_pad={plan.n_pad}")
        if plan.n_segments != 2:
            fail(f"expected 2 segments after the append, got "
                 f"{plan.n_segments}")
        run_round(server, Request, rounds[1], plans[1][0], plans[1][1],
                  tally, rid, "after append")
    finally:
        server.stop()
    return tally, engine.DISPATCH_STATS.aggregate()


def four_chips(args, size, jax):
    from jax.sharding import Mesh

    from repro.core import build_index, sharded
    from repro.core.join import query_counts

    devs = jax.devices()
    if len(devs) != 4:
        fail(f"--chips 4 needs 4 devices, found {len(devs)}", 2)
    rng = np.random.default_rng(args.seed)
    mix = Mixture(rng, size["clusters"])
    data = mix.sample(rng, size["n"])
    q = mix.sample(rng, size["sharded_queries"])
    t0 = time.perf_counter()
    index = build_index(data)
    say(f"index built in {time.perf_counter() - t0:.1f} s")
    mu = index.mu.astype(np.float64)
    rows = {"count": q}
    refs, radii, k = build_refs(rows, [(0, data)], mu, rng)
    refs, radii = refs["count"], radii["count"]
    say(f"{q.shape[0]} queries hold {k.mean():.1f} reference neighbors on "
        f"average")

    mesh = Mesh(np.asarray(devs), ("data",))
    xs, al, hn, _ = sharded.shard_index(index, mesh)
    say("shards of the sorted rows: " + ", ".join(
        f"device {s.device.id}: {s.data.shape}" for s in xs.addressable_shards))
    say(f"bytes in use per device after shard_index: {in_use(jax)}")
    xq, aq, r, th = sharded.prepare_query_arrays(index, q, radii)
    percount = sharded.make_sharded_percount_fn(mesh)
    per = np.asarray(percount(xs, al, hn, xq, aq, r, th))
    say(f"per-shard counts {per.shape}: neighbor pairs per shard "
        f"{per.sum(axis=1).tolist()}")
    mesh_counts = per.sum(axis=0)
    chip_counts = query_counts(index, q, radii)

    tally = Tally()
    for j in range(q.shape[0]):
        r2 = float(radii[j]) ** 2
        check_count(refs[j], r2, int(mesh_counts[j]), tally,
                    f"sharded count #{j}")
        check_count(refs[j], r2, int(chip_counts[j]), tally,
                    f"single-chip count #{j}")
        tally.responses += 2
    say(f"sharded vs single-chip engine counts differ on "
        f"{int((mesh_counts != chip_counts).sum())} of {q.shape[0]} queries")
    return tally, None


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny CPU run, kernels in interpret mode")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(SRC, "repro")):
        fail(f"no repro package under {SRC}: run from a checkout", 2)
    sys.path.insert(0, SRC)

    import jax

    info = device_info(jax)
    say(f"device: {info}")
    if args.rehearse:
        if info["platform"] != "cpu":
            fail("--rehearse is the CPU rehearsal; run without it here", 2)
    elif info["platform"] != "tpu":
        fail(f"JAX found no TPU (platform {info['platform']})", 2)

    from repro.launch import compile_cache
    say(f"compilation cache: {compile_cache.enable(ROOT)}")
    compile_s = start_compile_clock(jax)
    size = TINY if args.rehearse else FULL
    if args.chips == 4:
        tally, stats = four_chips(args, size, jax)
    else:
        tally, stats = one_chip(args, size, jax)

    if stats is not None:
        say(f"dispatch: {stats['kernel_launches']} kernel launches, "
            f"{stats['host_transfers']} host transfers, "
            f"{stats['jit_compiles']} new launch signatures")
    say(f"compiles: {compile_s[1]} backend compiles, "
        f"{compile_s[0]:.1f} s")
    say(f"peak bytes in use per device: {peak_bytes(jax)}")
    say(f"responses: {tally.responses}, error responses: {tally.errors}, "
        f"mismatches: {tally.mismatches}")
    say(f"band pairs (|d^2 - r^2| within the f32 band): {tally.band_pairs}, "
        f"decided unlike float64: {tally.band_flips}")
    for note in tally.notes:
        say(f"  mismatch: {note}")
    if tally.mismatches or tally.errors or not tally.responses:
        fail(f"{tally.mismatches} mismatches, {tally.errors} error responses")
    out = {"ok": True, "device": device_info(jax)}
    if args.rehearse:
        out["rehearsal"] = True
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
