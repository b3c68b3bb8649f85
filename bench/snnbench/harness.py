"""One run of one cell: set-up, the measured window, the comparison.

Set-up (counted in ``setup_s``): the data from ``--seed``, the traffic plan
and its radii, the server and its index, and a warm-up that compiles every
shape the window's traffic reaches.  The window sends the plan through
`SNNServer.submit` / `result` for ``--seconds``.  After it, the peak device
memory is read, the server is stopped and freed, and a sample of the
answers, drawn from the seed, is compared with the plain reference.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, ``breakdown`` in a traced run, and
``checks`` (each compared number with its limit).  Facts that are not
metrics (generator lag, compiles inside the window, dispatch counters,
neighbor counts) go on earlier lines, prefixed ``bench:``.
"""
from __future__ import annotations

import argparse
import gc
import json
import shutil
import sys
import tempfile
import time

import numpy as np

from . import data, loadgen, reference, roofline, spec, tracing
from . import traffic as traffic_mod

RESULT_GRACE_S = 60.0
WARM_TIMEOUT_S = 900.0
WARM_ID0 = 1 << 40


class Abort(Exception):
    """The run cannot measure: no result is printed, the exit code is 2."""


def say(msg: str) -> None:
    print(f"bench: {msg}", flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


# --------------------------------------------------------------------------- #
# Device                                                                      #
# --------------------------------------------------------------------------- #
def device_facts(jax, chips: int, require_tpu: bool) -> dict:
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise Abort(f"JAX found no TPU (platform {devs[0].platform})")
    if require_tpu and len(devs) < chips:
        raise Abort(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes(jax) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


class GcClock:
    """Garbage collections while armed: count and longest pause per
    generation."""

    def __init__(self):
        self.armed = False
        self.stats: dict = {}
        self._t = 0.0
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if not self.armed:
            return
        if phase == "start":
            self._t = time.perf_counter()
            return
        n, longest = self.stats.get(info["generation"], (0, 0.0))
        self.stats[info["generation"]] = (
            n + 1, max(longest, (time.perf_counter() - self._t) * 1e3))


class CompileClock:
    """Backend compiles seen by this process, and how many fell in a span."""

    def __init__(self, jax):
        self.count, self.seconds, self.marked = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration

    def mark(self) -> None:
        self.marked = self.count

    def since_mark(self) -> int:
        return self.count - self.marked


# --------------------------------------------------------------------------- #
# Requests                                                                    #
# --------------------------------------------------------------------------- #
def make_request(Request, item, pool, rid: int, slo_ms):
    if item.kind == "knn":
        return Request(query=pool[item.rows[0]], k=item.k, id=rid,
                       slo_ms=slo_ms)
    q = pool[item.rows] if item.kind == "join" else pool[item.rows[0]]
    return Request(query=q, radius=item.radius, id=rid, slo_ms=slo_ms,
                   count_only=item.kind == "count")


def warm_bursts(plan) -> list:
    """The warm-up's bursts (lists of items), in the order they run.

    Each burst of the mix's ``warm_batches`` sizes, made of the requests
    holding the most results, runs twice: the first run sets the fused
    path's capacity, the second compiles the fused path at it.  With
    ``warm_all`` (requests whose results the plan cannot count, as kNN's)
    bursts of the first size cover every request of the plan once, so the
    capacity ratchets to the largest the window can meet, and the first
    burst runs again to compile the fused path at it.
    """
    items = sorted(plan.items, key=lambda it: (-it.expect, int(it.rows[0])))
    if plan.warm_all:
        s = plan.warm_batches[0]
        cover = [items[j:j + s] for j in range(0, len(items), s)]
        return cover + cover[:1]
    return [[items[j % len(items)] for j in range(s)]
            for s in plan.warm_batches for _ in range(2)]


def warm_up(server, Request, plan, pool) -> list:
    """Run each burst (`warm_bursts`) through a stopped-then-started
    server, so each forms one batch.  Returns (requests, seconds) of each
    batch."""
    rid = WARM_ID0
    done = []
    for burst in warm_bursts(plan):
        t = time.perf_counter()
        reqs = [make_request(Request, it, pool, rid + j, plan.slo_ms)
                for j, it in enumerate(burst)]
        rid += len(reqs)
        for r in reqs:
            server.submit(r)
        server.start()
        try:
            for r in reqs:
                resp = server.result(r.id, timeout=WARM_TIMEOUT_S)
                if resp.error is not None:
                    raise RuntimeError(f"warm-up request failed: "
                                       f"{resp.error}")
        finally:
            server.stop()
        done.append((len(reqs), round(time.perf_counter() - t, 3)))
    return done


# --------------------------------------------------------------------------- #
# What each answered request leaves behind                                    #
# --------------------------------------------------------------------------- #
def results_of(resp) -> int:
    return int(np.asarray(resp.indices).size)


class Keeper:
    """Per-request facts, and a seeded uniform sample of whole responses
    (reservoir sampling) plus the one with the most results."""

    def __init__(self, plan, k: int, seed: int, keep_radii: bool):
        self.plan = plan
        self.k = k
        self.rng = np.random.default_rng([int(seed) & (2**63 - 1), 11])
        self.keep_radii = keep_radii
        self.sample: list = []
        self.kept: set = set()          # ids of the records in the sample
        self.largest = None
        self.seen = 0
        self.facts: dict = {}

    def item(self, i: int):
        return self.plan.items[i % len(self.plan.items)]

    def __call__(self, rec) -> None:
        it = self.item(rec.i)
        resp = rec.resp
        ok = rec.error is None and resp is not None and resp.error is None
        n_res = results_of(resp) if ok else 0
        fact = {"rows": len(it.rows), "ok": ok, "results": n_res,
                "queue_ms": resp.queue_delay_ms if ok else None,
                "service_ms": resp.service_ms if ok else None}
        if ok and self.keep_radii:
            if it.kind == "knn":
                sq = np.asarray(resp.sq_dists, np.float64)
                fact["r"] = float(np.sqrt(sq[-1])) if sq.size else 0.0
            else:
                fact["r"] = float(np.sqrt(it.r2))
        if not ok and rec.error is None:
            rec.error = resp.error if resp is not None else "no response"
        self.facts[rec.i] = fact
        if not ok:
            rec.resp = None
            return
        self.seen += 1
        dropped = []
        if len(self.sample) < self.k:
            self.sample.append(rec)
            self.kept.add(rec.i)
        else:
            j = int(self.rng.integers(self.seen))
            if j < self.k:
                dropped.append(self.sample[j])
                self.kept.discard(self.sample[j].i)
                self.sample[j] = rec
                self.kept.add(rec.i)
            else:
                dropped.append(rec)
        if self.largest is None or n_res > results_of(self.largest.resp):
            dropped.append(self.largest)
            self.largest = rec
        for old in dropped:
            if old is not None and old is not self.largest \
                    and old.i not in self.kept:
                old.resp = None

    def checked(self) -> list:
        recs = list(self.sample)
        if self.largest is not None and self.largest.i not in self.kept:
            recs.append(self.largest)
        return recs


# --------------------------------------------------------------------------- #
# The comparison                                                              #
# --------------------------------------------------------------------------- #
def compare(ref: reference.Reference, keeper: Keeper, pool,
            tally: reference.Tally) -> None:
    """Every row of every kept response against the float64 reference."""
    balls, knns = [], []
    for rec in keeper.checked():
        it = keeper.item(rec.i)
        (knns if it.kind == "knn" else balls).append((rec, it))
    if balls:
        q = np.concatenate([pool[it.rows] for _, it in balls])
        r2 = np.concatenate([np.full(len(it.rows), it.r2) for _, it in balls])
        refs = ref.balls(q, r2)
        s = 0
        for rec, it in balls:
            resp, m = rec.resp, len(it.rows)
            for t in range(m):
                what = f"request {rec.i} row {t}"
                if it.kind == "count":
                    _count_row(tally, refs[s + t], it.r2, ref.d,
                               int(resp.counts[t]), what)
                elif resp.indptr is not None:
                    lo, hi = resp.indptr[t], resp.indptr[t + 1]
                    tally.ball(refs[s + t], it.r2, ref.d,
                               resp.indices[lo:hi], resp.sq_dists[lo:hi],
                               what)
                else:
                    tally.ball(refs[s + t], it.r2, ref.d, resp.indices,
                               resp.sq_dists, what)
            s += m
    if knns:
        q = np.stack([pool[it.rows[0]] for _, it in knns])
        k = knns[0][1].k
        refs = ref.knn(q, k)
        for j, (rec, it) in enumerate(knns):
            tally.knn(refs[j], it.k, ref.d, rec.resp.indices,
                      rec.resp.sq_dists, f"request {rec.i}")


def _count_row(tally, ref_row, r2, d, count, what):
    tally.rows += 1
    band = ref_row.band(r2, d)
    lo = int((ref_row.d2 < r2 - band).sum())
    hi = int((ref_row.d2 <= r2 + band).sum())
    tally.band_pairs += hi - lo
    if not lo <= count <= hi:
        tally.wrong(f"{what}: count {count} outside [{lo}, {hi}]")


def checks(tally, unanswered: int, limits: dict) -> dict:
    """Each compared number with its limit."""
    return {"unanswered": [unanswered, limits["unanswered"]],
            "wrong_rows": [tally.wrong_rows, limits["wrong_rows"]],
            "dist_gap": [tally.dist_gap, limits["dist_gap"]]}


# --------------------------------------------------------------------------- #
# Metrics                                                                     #
# --------------------------------------------------------------------------- #
class Context:
    """What a metric reader may read (``bench/metrics/<name>.py``)."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def answered(self) -> list:
        return [f for f in self.facts.values() if f["ok"]]


def read_metrics(bench, cell_name: str, traced: bool, ctx) -> dict:
    out = {}
    for m in spec.metrics_for(bench, cell_name, traced):
        value = spec.reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def roofline_work(win: roofline.Window, keeper: Keeper, pool,
                  peak: dict, d: int) -> dict | None:
    """The least device time the window's answered requests need."""
    aq, r, pairs, qrows = [], [], 0, 0
    for i, f in keeper.facts.items():
        if not f["ok"]:
            continue
        rows = keeper.item(i).rows
        aq.append(win.project(pool[rows]))
        r.append(np.full(len(rows), f["r"]))
        pairs += f["results"]
        qrows += len(rows)
    if not aq:
        return None
    lo, hi = win.bounds(np.concatenate(aq), np.concatenate(r))
    work = roofline.least_seconds(d, roofline.window_pairs(lo, hi),
                                  roofline.distinct_rows(lo, hi), qrows,
                                  pairs, peak)
    work["pairs_in_window"] = roofline.window_pairs(lo, hi)
    return work


# --------------------------------------------------------------------------- #
# The run                                                                     #
# --------------------------------------------------------------------------- #
class Session:
    """A cell set up once: its data, its traffic, a server over the data.

    The benchmark's own brute force (for the traffic's radii) is freed
    before the server is built, so the device's peak memory is the
    program's.
    """

    def __init__(self, workload: str, seed: int, *, require_tpu: bool = True,
                 overrides: dict | None = None):
        overrides = overrides or {}
        root = overrides.get("root", spec.ROOT)
        self.bench = spec.load_benchmark(root)
        self.cell = spec.cell(self.bench, workload)
        self.cfg = dict(spec.config(self.bench, self.cell["config"], root))
        self.cfg.update(overrides.get("config", {}))
        self.mix = spec.traffic(self.cell["traffic"])
        self.mix.update(overrides.get("traffic", {}))
        self.seed = seed

        import jax

        self.jax = jax
        self.dev = device_facts(jax, int(self.cell["chips"]), require_tpu)
        self.peak = spec.peaks(self.dev["kind"]) if require_tpu else \
            overrides.get("peaks", {"bf16_flops_per_s": 1e12,
                                    "hbm_bytes_per_s": 1e11})
        from repro.configs.snn_default import SNNConfig
        from repro.core import engine
        from repro.kernels import registry
        from repro.launch import compile_cache
        from repro.serving import Request, SNNServer

        self.engine, self.registry, self.Request = engine, registry, Request
        say(f"device: {self.dev}")
        say(f"compilation cache: {compile_cache.enable(spec.ROOT)}")
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        self.clock = CompileClock(jax)
        self.gc_clock = GcClock()

        self.d, self.metric = int(self.cfg["d"]), self.cfg["metric"]
        t = time.perf_counter()
        self.base, self.pool = data.config_rows(self.cfg, seed)
        say(f"data: {self.base.shape[0]} x {self.d} base rows, "
            f"{self.pool.shape[0]} query rows, {time.perf_counter() - t:.3f} s")
        needs_radii = self.mix["request"]["kind"] != "knn"
        self.brute = reference.BruteForce(self.base, self.metric) \
            if needs_radii else None
        self.server_cfg = SNNConfig(metric=self.metric,
                                    **overrides.get("server", {}))
        self.SNNServer = SNNServer
        self.server = None

    def plan(self, seconds: float, mix: dict | None = None):
        """The requests of one window, with their radii."""
        t = time.perf_counter()
        plan = traffic_mod.plan(mix or self.mix, self.pool, self.brute,
                                self.metric, self.seed, seconds)
        expect = np.array([it.expect for it in plan.items])
        say(f"plan: {len(plan.items)} requests ({plan.loop} loop), "
            f"{time.perf_counter() - t:.3f} s; result pairs per request "
            f"mean {expect.mean():.1f}, max {expect.max()}")
        return plan

    def serve(self) -> None:
        """Build the server: the program's index and its device plan."""
        t = time.perf_counter()
        self.server = self.SNNServer(self.base, self.server_cfg)
        self.server.index.plan()
        say(f"server: index and plan built, {time.perf_counter() - t:.3f} s")

    def warm(self, plan) -> None:
        """Compile every shape ``plan`` reaches (`warm_up`)."""
        t = time.perf_counter()
        batches = warm_up(self.server, self.Request, plan, self.pool)
        say(f"warm-up: batches (requests, s) {batches}, "
            f"{time.perf_counter() - t:.3f} s, "
            f"{self.clock.count} backend compiles so far "
            f"({self.clock.seconds:.3f} s)")

    def free_brute(self) -> None:
        if self.brute is not None:
            self.brute.free()
            self.brute = None

    def drive(self, plan, keeper, seconds: float, trace_dir=None):
        """The measured window: every request of ``plan`` through the
        server.  Returns (records, t0, dispatch counts, compiles in it)."""
        jax, server = self.jax, self.server
        if plan.loop == "open":
            reqs = [make_request(self.Request, it, self.pool, i, plan.slo_ms)
                    for i, it in enumerate(plan.items)]
            send = lambda i: server.submit(reqs[i])  # noqa: E731
        else:
            send = lambda i: server.submit(make_request(  # noqa: E731
                self.Request, keeper.item(i), self.pool, i, plan.slo_ms))
        server.start()
        try:
            if trace_dir is not None:
                tracing.start(trace_dir)
            stats0 = self.engine.DISPATCH_STATS.aggregate()
            sigs0 = self.registry.compile_counts()
            self.clock.mark()
            # the heap built in set-up (the program's index and JAX's own
            # objects) is frozen, so a full collection in the window scans
            # only what the window allocates
            gc.collect()
            gc.freeze()
            self.gc_clock.armed = True
            t0 = time.perf_counter() + 0.01
            with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
                # the host clock as the window span opens and closes: it
                # places the load generator's records on the trace's clock
                self.window_pc = [time.perf_counter(), 0.0]
                if plan.loop == "open":
                    recs = loadgen.open_loop(
                        send, server.result, plan.due, t0=t0,
                        grace_s=RESULT_GRACE_S, on_done=keeper)
                else:
                    recs = loadgen.closed_loop(
                        send, server.result, plan.clients, t0=t0,
                        seconds=seconds, grace_s=RESULT_GRACE_S,
                        on_done=keeper)
                self.window_pc[1] = time.perf_counter()
            compiles = self.clock.since_mark()
            self.gc_clock.armed = False
            stats1 = self.engine.DISPATCH_STATS.aggregate()
            grown = {op: n - sigs0.get(op, 0) for op, n in
                     self.registry.compile_counts().items()
                     if n > sigs0.get(op, 0)}
            if compiles or grown:
                say(f"new launch signatures inside the window: {grown}")
            if trace_dir is not None:
                jax.profiler.stop_trace()
        finally:
            server.stop()
        return recs, t0, {k: stats1[k] - stats0[k] for k in stats0}, compiles

    def close(self) -> None:
        """Stop and free the program's state (the reference runs after)."""
        self.server.stop()
        self.server = None
        gc.callbacks.remove(self.gc_clock._cb)
        gc.unfreeze()
        gc.collect()


def run(args, t_start: float, *, require_tpu: bool = True,
        overrides: dict | None = None) -> dict:
    """One run; returns the result object (the last line's content)."""
    ses = Session(args.workload, args.seed, require_tpu=require_tpu,
                  overrides=overrides)
    plan = ses.plan(args.seconds)
    ses.free_brute()
    ses.serve()
    ses.warm(plan)
    dev, d, pool = ses.dev, ses.d, ses.pool
    win = roofline.Window(ses.base, ses.metric) if args.trace else None
    keeper = Keeper(plan, plan.check_requests, args.seed,
                    keep_radii=bool(args.trace))
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    recs, t0, dispatch, in_window_compiles = ses.drive(
        plan, keeper, args.seconds, trace_dir)
    setup_s = t0 - t_start
    dev["memory_peak_bytes"] = peak_bytes(ses.jax)
    ses.close()

    red = None
    if args.trace:
        red = tracing.Reduction(tracing.load_dir(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        pc0, pc1 = ses.window_pc
        red.set_spans(tracing.load_spans(recs, pc0, red.w0, pc1))
        dev["busy_s"] = red.busy_s
        dev["window_s"] = red.window_s

    t = time.perf_counter()
    ref = reference.Reference(ses.base, ses.metric, d)
    tally = reference.Tally()
    compare(ref, keeper, pool, tally)
    ref.free()
    unanswered = sum(1 for r in recs if r.error is not None)
    chk = checks(tally, unanswered, ses.cfg["limits"])
    correct = all(v <= lim for v, lim in chk.values())
    say(f"comparison: {tally.rows} rows of {len(keeper.checked())} requests "
        f"in {time.perf_counter() - t:.3f} s ({ref.device_s:.3f} s in the "
        f"device brute force); {tally.band_pairs} pairs in "
        f"the float32 band, {tally.band_flips} decided unlike float64")
    for note in tally.notes:
        say(f"  wrong: {note}")

    lat = np.array([r.done - r.due for r in recs]) * 1e3
    say("latency ms from the due time: " + ", ".join(
        f"p{q} {np.percentile(lat, q):.3f}" for q in (50, 90, 95, 99))
        + f", max {lat.max():.3f}")
    lag = np.array([r.sent - r.due for r in recs]) * 1e3
    say(f"generator lag ms: p50 {np.percentile(lag, 50):.3f}, "
        f"p99 {np.percentile(lag, 99):.3f}, max {lag.max():.3f}")
    say(f"compiles inside the window: {in_window_compiles}; in the process: "
        f"{ses.clock.count} ({ses.clock.seconds:.3f} s)")
    say(f"dispatch in the window: {dispatch}")
    say(f"garbage collections in the window (generation: count, longest "
        f"ms): {ses.gc_clock.stats}")
    answered = [f for f in keeper.facts.values() if f["ok"]]
    per_row = [f["results"] / f["rows"] for f in answered]
    say(f"results per query row: mean {np.mean(per_row):.1f}, "
        f"max {np.max(per_row):.0f}" if per_row else "no answers")
    say(f"memory_peak_bytes: {dev['memory_peak_bytes']}")

    ctx = Context(cell=ses.cell, config=ses.cfg, mix=ses.mix, plan=plan,
                  records=recs, facts=keeper.facts, seconds=args.seconds,
                  t0=t0, setup_s=setup_s, dispatch=dispatch, trace=red,
                  peak=ses.peak, work=None, d=d)
    out = {"correct": bool(correct), "attempted": len(recs),
           "failed": unanswered}
    if red is not None:
        ctx.work = roofline_work(win, keeper, pool, ses.peak, d)
        say(f"roofline work: {ctx.work}")
        say(f"idle seconds by load-generator span: {red.idle_by_label()}")
    out["metrics"] = read_metrics(ses.bench, ses.cell["name"],
                                  bool(args.trace), ctx)
    out["device"] = dev
    if red is not None:
        out["breakdown"] = {"device_ops": red.top_ops(10),
                            "idle_gaps": red.idle_gaps(10)}
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in chk.items()}
    for k, (v, lim) in chk.items():
        print(f"check {k}: {v} (limit {lim})", file=sys.stderr, flush=True)
    return out


def main(argv=None, *, t_start: float, require_tpu: bool = True,
         overrides: dict | None = None) -> int:
    args = parse(argv)
    try:
        out = run(args, t_start, require_tpu=require_tpu, overrides=overrides)
    except (Abort, spec.SpecError) as e:
        print(f"bench: cannot run: {e}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(out), flush=True)
    return 0


def sweep(workload: str, seed: int, rates, seconds: float) -> list:
    """Latency of an open-loop cell at each offered rate, set up once.

    For finding the knee when a cell is defined: the highest rate whose
    p99 stays within the server's default SLO with no growing backlog
    (the last fifth of the requests no slower than twice the first fifth),
    and no higher than one request per mean service time at the lowest
    rate, where each batch holds one request: past that, a stall leaves a
    backlog that one-request batches never clear.
    """
    ses = Session(workload, seed)
    ses.serve()
    rows = []
    for rate in rates:
        mix = dict(ses.mix, rate_per_s=float(rate))
        plan = ses.plan(seconds, mix)
        ses.warm(plan)
        keeper = Keeper(plan, 0, seed, keep_radii=False)
        recs, _, dispatch, compiles = ses.drive(plan, keeper, seconds)
        lat = np.array([(r.done - r.due) * 1e3 for r in recs])
        fifth = max(1, len(lat) // 5)
        row = {"rate_per_s": float(rate), "requests": len(recs),
               "failed": sum(r.error is not None for r in recs),
               "p50_ms": float(np.percentile(lat, 50)),
               "p99_ms": float(np.percentile(lat, 99)),
               "first_fifth_p50_ms": float(np.median(lat[:fifth])),
               "last_fifth_p50_ms": float(np.median(lat[-fifth:])),
               "lag_p99_ms": float(np.percentile(
                   [(r.sent - r.due) * 1e3 for r in recs], 99)),
               "service_ms": float(np.mean(
                   [f["service_ms"] for f in keeper.facts.values()
                    if f["ok"]] or [np.nan])),
               "launches": dispatch["kernel_launches"],
               "compiles_in_window": compiles}
        print(json.dumps(row), flush=True)
        rows.append(row)
    ses.close()
    return rows
