#!/usr/bin/env python3
"""Read the program's own spans (``snn.*``) over a cell's measured window.

    python3 bench/program_spans.py --workload <name> --seed <n> --seconds 30 --windows 0,1
    python3 bench/program_spans.py --span-cost

The first form sets the cell up as `bench/run.py` does (same data, plan,
server and warm-up; no comparison with the reference) and measures one
window per entry of ``--windows`` (1 = under the profiler).  Each window
prints one JSON line: p50 latency, mean service and queue time, the
dispatch counters, and for a traced window the program spans reduced by
`snnbench.spans`: milliseconds of each span name per ``snn.batch``,
``host_idle``, the device's idle seconds while each span name was open,
the share of batch time the nested spans cover, and the longest idle gaps
named by the innermost span (program or load generator) open at their
midpoint.  Untraced and traced windows of one call share the seed, so
their difference is the profiler's cost.

``--span-cost`` measures, with the profiler off, the host cost of one
span (a loop of 10^6), of one launch's instrumentation and of one
launch's cell count at the cells' shapes.
"""
import argparse
import json
import os
import shutil
import sys
import tempfile
import time
from types import SimpleNamespace

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
SPAN_LOOP = 10**6
# (query rows padded, db blocks of 512 rows) of one launch in each cell
CELL_SHAPES = {"sift1m.radius-open": (128, 1954),
               "glove100.knn-open": (128, 2312),
               "sift1m.join-backlog": (256, 1954)}


def _per_call_ns(fn, n: int) -> float:
    t = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t) / n * 1e9


def span_cost() -> dict:
    from jax.profiler import TraceAnnotation

    from repro.core import engine

    def plain():
        with TraceAnnotation("snn.prep"):
            pass

    def with_meta():
        with TraceAnnotation("snn.knn.round", round=1, active=1):
            pass

    def launch():
        with engine._Launch("snn_csr_fused_stacked"):
            pass

    out = {"profiler_on": TraceAnnotation.is_enabled(),
           "empty_loop_ns": _per_call_ns(lambda: None, SPAN_LOOP),
           "span_ns": _per_call_ns(plain, SPAN_LOOP),
           "span_with_metadata_ns": _per_call_ns(with_meta, SPAN_LOOP),
           "launch_span_and_counter_ns": _per_call_ns(launch, SPAN_LOOP)}
    rng = np.random.default_rng(0)
    for cell, (m_pad, nb) in CELL_SHAPES.items():
        al = np.sort(rng.normal(size=nb * 512).astype(np.float32))
        bounds = (al[::512][None], al[511::512][None])
        pack = SimpleNamespace(block_bounds=lambda: bounds)
        m = 256 if "join" in cell else 1   # real rows of one launch
        aq = rng.normal(size=m_pad).astype(np.float32)
        r = np.full(m_pad, -engine._ops.BIG, np.float32)
        r[:m] = 0.5
        live = np.zeros(1, np.int64)
        out[f"count_cells_us.{cell}"] = 1e-3 * _per_call_ns(
            lambda: engine._note_cells(
                engine._count_cells(pack, live, aq, r, m, 128), 1), 2000)
    return out


def window(ses, harness, plan, seconds: float, traced: bool) -> dict:
    from snnbench import spans, tracing

    keeper = harness.Keeper(plan, 0, ses.seed, keep_radii=False)
    trace_dir = tempfile.mkdtemp(prefix="bench-spans-") if traced else None
    recs, _, dispatch, compiles = ses.drive(plan, keeper, seconds, trace_dir)
    ok = [f for f in keeper.facts.values() if f["ok"]]
    out = {"workload": ses.cell["name"], "seed": ses.seed, "traced": traced,
           "requests": len(recs),
           "failed": sum(r.error is not None for r in recs),
           "p50_ms": float(np.percentile(
               [(r.done - r.due) * 1e3 for r in recs], 50)),
           "service_ms": float(np.mean([f["service_ms"] for f in ok])),
           "queue_ms": float(np.mean([f["queue_ms"] for f in ok])),
           "compiles_in_window": compiles, "dispatch": dispatch}
    if not traced:
        return out
    trace, prog = spans.load_dir(trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)
    red = tracing.Reduction(trace)
    sr = spans.SpanReduction(red, prog)
    pc0, pc1 = ses.window_pc
    red.set_spans(tracing.load_spans(recs, pc0, red.w0, pc1) + sr.spans)
    per_batch = sr.seconds_per_batch()
    out.update({
        "batches": len(sr.batches),
        "spans_per_batch": len(sr.spans) / max(len(sr.batches), 1),
        "window_s": red.window_s, "device_idle_s": red.window_s - red.busy_s,
        "prep_ms": sr.ms_per_batch("snn.prep"),
        "assemble_ms": sr.ms_per_batch("snn.assemble"),
        "host_idle": sr.host_idle(),
        "batch_coverage": sr.batch_coverage(),
        "ms_per_batch": {k: 1e3 * v for k, v in per_batch.items()},
        "idle_s_while": sr.idle_while(),
        "idle_s_by_label": red.idle_by_label(),
        "idle_gaps": red.idle_gaps(10)})
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--windows", default="1",
                    help="comma-separated: 1 traces the window, 0 does not")
    ap.add_argument("--span-cost", action="store_true")
    args = ap.parse_args()
    sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
    if args.span_cost:
        print(json.dumps(span_cost()), flush=True)
        return 0
    if args.workload is None or args.seed is None:
        ap.error("--workload and --seed are required without --span-cost")
    from snnbench import harness

    try:
        ses = harness.Session(args.workload, args.seed)
    except (harness.Abort, harness.spec.SpecError) as e:
        print(f"program_spans: cannot run: {e}", file=sys.stderr)
        return 2
    plan = ses.plan(args.seconds)
    ses.free_brute()
    ses.serve()
    ses.warm(plan)
    for flag in args.windows.split(","):
        print(json.dumps(window(ses, harness, plan, args.seconds,
                                flag.strip() == "1")), flush=True)
    ses.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
