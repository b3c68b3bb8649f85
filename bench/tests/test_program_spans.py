"""The program's own spans (``snn.*``) and the counter readers, on a
hand-built trace and on a tiny run."""
from types import SimpleNamespace

import pytest
from jax.profiler import ProfileData

from conftest import tiny
from snnbench import harness, spans, spec, tracing

# ns, one clock.  The window is [1000, 11000]; the device runs
# [1000, 5000] and [7000, 8000], so it idles in [5000, 7000] and
# [8000, 11000].  The dispatcher thread waits for a request, admits it and
# serves one batch [1600, 9600]; an XLA host event on its line is not the
# program's and is ignored.
TRACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 1 offset_ps: 6000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "snn_compact_stacked.1" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 3 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 10000000 } }
  lines { id: 4 name: "python3" timestamp_ns: 0
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 500000 }
    events { metadata_id: 3 offset_ps: 1500000 duration_ps: 100000 }
    events { metadata_id: 4 offset_ps: 1600000 duration_ps: 8000000 }
    events { metadata_id: 5 offset_ps: 1600000 duration_ps: 400000 }
    events { metadata_id: 6 offset_ps: 2000000 duration_ps: 100000 }
    events { metadata_id: 9 offset_ps: 2000000 duration_ps: 50000 }
    events { metadata_id: 7 offset_ps: 2100000 duration_ps: 3100000 }
    events { metadata_id: 8 offset_ps: 5200000 duration_ps: 700000 }
    events { metadata_id: 5 offset_ps: 5900000 duration_ps: 500000 }
    events { metadata_id: 8 offset_ps: 7500000 duration_ps: 1500000 }
    events { metadata_id: 2 offset_ps: 9600000 duration_ps: 1400000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "snn.await_request" } }
  event_metadata { key: 3 value { id: 3 name: "snn.admit#requests=1#" } }
  event_metadata { key: 4 value { id: 4
    name: "snn.batch#batch=0,requests=1,rows=1,kinds=snn-radius#" } }
  event_metadata { key: 5 value { id: 5 name: "snn.prep" } }
  event_metadata { key: 6 value { id: 6
    name: "snn.launch#op=snn_csr_fused_stacked,new_signature=1#" } }
  event_metadata { key: 7 value { id: 7 name: "snn.sync" } }
  event_metadata { key: 8 value { id: 8 name: "snn.assemble" } }
  event_metadata { key: 9 value { id: 9 name: "ThunkExecute" } }
}
"""


@pytest.fixture(scope="module")
def pdata():
    return ProfileData.from_text_proto(TRACE)


@pytest.fixture
def red(pdata):
    return tracing.Reduction(tracing.read_profile(pdata))


@pytest.fixture
def prog(pdata):
    return spans.program_spans(pdata)


def test_names_lose_their_metadata():
    assert spans.split_name("snn.prep") == ("snn.prep", {})
    assert spans.split_name("snn.batch#batch=3,kinds=snn-knn+snn-radius#") \
        == ("snn.batch", {"batch": 3, "kinds": "snn-knn+snn-radius"})
    assert spans.split_name("snn.launch#op=snn_count_stacked#") \
        == ("snn.launch", {"op": "snn_count_stacked"})


def test_program_spans_keep_name_metadata_and_thread(prog):
    assert [s.name for s in prog] == [
        "snn.await_request", "snn.admit", "snn.batch", "snn.prep",
        "snn.launch", "snn.sync", "snn.assemble", "snn.prep",
        "snn.assemble", "snn.await_request"]
    assert {s.thread for s in prog} == {"/host:CPU/1"}
    batch = prog[2]
    assert (batch.start, batch.end) == (1600, 9600)
    assert batch.meta == {"batch": 0, "requests": 1, "rows": 1,
                          "kinds": "snn-radius"}
    assert prog[4].meta == {"op": "snn_csr_fused_stacked",
                            "new_signature": 1}


def test_idle_gaps_are_named_by_the_innermost_program_span(red, prog):
    pending = [tracing.Span(tracing.PENDING_SPAN, 1000, 9700),
               tracing.Span(tracing.NO_REQUEST_SPAN, 9700, 11000)]
    red.set_spans(pending)
    assert red.idle_by_label() == pytest.approx(
        {tracing.PENDING_SPAN: 5000e-9})
    red.set_spans(pending + prog)
    # [5000, 7000]: its midpoint lies in the second prep, inside the batch
    # and the pending request; [8000, 11000]: in the batch and the pending
    # request, of which the batch is the shorter
    gaps = red.idle_gaps(10)
    assert [g[0] for g in gaps] == ["snn.batch", "snn.prep"]
    assert [g[1] for g in gaps] == pytest.approx([3000e-9, 2000e-9])
    assert red.idle_by_label() == pytest.approx(
        {"snn.batch": 3000e-9, "snn.prep": 2000e-9})


def test_idle_seconds_while_each_span_was_open(red, prog):
    sr = spans.SpanReduction(red, prog)
    assert sr.idle_while() == pytest.approx({
        "snn.batch": 3600e-9,           # [5000, 7000] + [8000, 9600]
        "snn.assemble": 1700e-9,        # [5200, 5900] + [8000, 9000]
        "snn.await_request": 1400e-9,   # [9600, 11000]
        "snn.prep": 500e-9,             # [5900, 6400]
        "snn.sync": 200e-9,             # [5000, 5200]
        "snn.admit": 0.0, "snn.launch": 0.0})


def test_span_reduction_by_hand(red, prog):
    sr = spans.SpanReduction(red, prog)
    assert len(sr.batches) == 1
    assert sr.ms_per_batch("snn.prep") == pytest.approx(900e-6)
    assert sr.ms_per_batch("snn.assemble") == pytest.approx(2200e-6)
    assert sr.seconds_per_batch()["snn.batch"] == pytest.approx(8000e-9)
    # the dispatcher works (outside snn.await_request) in [1500, 9600];
    # the device idles in 3600 ns of it, of a 10000 ns window
    assert sr.host_idle() == pytest.approx(36.0)
    # nested in the batch: [1600, 6400] and [7500, 9000] of its 8000 ns
    assert sr.batch_coverage() == pytest.approx(6300 / 8000)


def test_no_batch_reads_nothing(red):
    sr = spans.SpanReduction(red, [])
    assert sr.host_idle() is None and sr.ms_per_batch("snn.prep") is None
    assert sr.batch_coverage() is None and sr.seconds_per_batch() == {}


def test_the_counter_readers_by_hand():
    live = spec.reader("live_cells.open")
    cand = spec.reader("knn_candidates.knn")
    ctx = SimpleNamespace(dispatch={"grid_cells": 3908, "live_cells": 10,
                                    "knn_candidates": 1230,
                                    "knn_wanted": 100})
    assert live(ctx) == pytest.approx(100 * 10 / 3908)
    assert cand(ctx) == pytest.approx(12.3)
    # a program without the counters (or a window without a launch on the
    # device, or without kNN) reads nothing
    for dispatch in ({"kernel_launches": 4}, {"grid_cells": 0,
                                              "live_cells": 0,
                                              "knn_wanted": 0,
                                              "knn_candidates": 0}):
        ctx = SimpleNamespace(dispatch=dispatch)
        assert live(ctx) is None and cand(ctx) is None


@pytest.mark.parametrize("name", ["sift1m.radius-open", "glove100.knn-open"])
def test_tiny_window_reads_the_program_spans(name):
    """One traced window of a tiny cell, kernels in interpret mode."""
    import program_spans

    ses = harness.Session(name, 3000000021, require_tpu=False,
                          overrides=tiny(name, lane="pallas-tpu"))
    plan = ses.plan(1.0)
    ses.free_brute()
    ses.serve()
    ses.warm(plan)
    try:
        out = program_spans.window(ses, harness, plan, 1.0, traced=True)
    finally:
        ses.close()
    assert out["failed"] == 0 and out["batches"] >= 1
    assert out["prep_ms"] > 0 and out["assemble_ms"] > 0
    assert 0 < out["batch_coverage"] <= 1
    assert out["host_idle"] is not None
    # the window's batches each open one snn.batch span
    assert out["ms_per_batch"]["snn.batch"] > 0
    d = out["dispatch"]
    assert 0 < d["live_cells"] < d["grid_cells"]
    if "knn" in name:
        assert d["knn_rounds"] >= 1 and d["knn_candidates"] >= d["knn_wanted"]


def test_span_cost_measures_with_the_profiler_off(monkeypatch):
    import program_spans

    monkeypatch.setattr(program_spans, "SPAN_LOOP", 1000)
    out = program_spans.span_cost()
    assert out["profiler_on"] is False
    assert out["span_ns"] > 0 and out["launch_span_and_counter_ns"] > 0
    assert all(out[f"count_cells_us.{c}"] > 0
               for c in program_spans.CELL_SHAPES)
