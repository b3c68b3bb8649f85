"""The trace reduction, on a small hand-built trace."""
import pytest
from jax.profiler import ProfileData

from snnbench import tracing

# ns, one clock: the window is [1000, 11000]; device ops cover
# [1000, 3000] + [2000, 5000] (overlap) + [7000, 8000]; idle is
# [5000, 7000] and [8000, 11000]
TRACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 3000000 }
    events { metadata_id: 3 offset_ps: 6000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 4 offset_ps: 0 duration_ps: 9000000 } }
  event_metadata { key: 1 value { id: 1 name: "snn_count_stacked.3" } }
  event_metadata { key: 2 value { id: 2 name: "snn_compact_stacked.1" } }
  event_metadata { key: 3 value { id: 3 name: "fusion.7" } }
  event_metadata { key: 4 value { id: 4 name: "jit_run" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 3 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 10000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
}
"""


@pytest.fixture(scope="module")
def red():
    return tracing.Reduction(
        tracing.read_profile(ProfileData.from_text_proto(TRACE)))


def test_window_busy_and_idle(red):
    assert red.window_s == pytest.approx(10000e-9)
    assert red.busy_s == pytest.approx(5000e-9)  # [1000,5000] + [7000,8000]
    idle = 1 - red.busy_s / red.window_s
    assert idle == pytest.approx(0.5)


def test_kernel_time_by_name(red):
    assert red.kernel_s(r"snn_count") == pytest.approx(2000e-9)
    assert red.kernel_s(r"snn_compact") == pytest.approx(3000e-9)
    assert red.kernel_s(r"snn_") == pytest.approx(5000e-9)
    assert red.kernel_s(r"nothing") == 0.0
    assert red.top_ops(2)[0][0] == "snn_compact_stacked.1"


def test_gaps_are_labelled_by_the_covering_span(red):
    Span = tracing.Span
    red.set_spans([])
    assert [g[0] for g in red.idle_gaps(10)] == ["unattributed"] * 2
    red.set_spans([Span("outer", 4500, 7500), Span("inner", 5500, 6500)])
    gaps = red.idle_gaps(10)
    assert [g[1] for g in gaps] == pytest.approx([3000e-9, 2000e-9])
    assert gaps[0][0] == "unattributed"
    # both spans hold the gap's midpoint: the inner one names it
    assert gaps[1][0] == "inner"
    assert red.idle_by_label() == pytest.approx(
        {"unattributed": 3000e-9, "inner": 2000e-9})
    # a gap no span holds at its midpoint goes to the widest overlap
    assert red.label((7200, 8200)) == "outer"


def test_load_spans_split_the_window_by_outstanding_requests(red):
    """Records on the host clock land on the trace's clock: the window
    span opened at host time 50.0 s, which is 1000 ns in the trace."""
    from snnbench.loadgen import Record

    pc0 = 50.0
    recs = [Record(0, pc0, sent=pc0 + 3.5e-6, done=pc0 + 5.5e-6),
            Record(1, pc0, sent=pc0 + 4.5e-6, done=pc0 + 6.5e-6),
            Record(2, pc0, sent=pc0 + 9.8e-6, done=pc0 + 9.9e-6)]
    spans = tracing.load_spans(recs, pc0, 1000.0, pc0 + 10e-6)
    got = [(s.name, round(s.start), round(s.end)) for s in spans]
    assert got == [(tracing.NO_REQUEST_SPAN, 1000, 4500),
                   (tracing.PENDING_SPAN, 4500, 7500),
                   (tracing.NO_REQUEST_SPAN, 7500, 10800),
                   (tracing.PENDING_SPAN, 10800, 10900),
                   (tracing.NO_REQUEST_SPAN, 10900, 11000)]
    red.set_spans(spans)
    assert red.idle_by_label() == pytest.approx(
        {tracing.PENDING_SPAN: 2000e-9, tracing.NO_REQUEST_SPAN: 3000e-9})


def test_union_length_and_merge():
    iv = [(5, 7), (0, 2), (1, 3), (7, 8), (10, 10)]
    assert tracing.union_length(iv) == 6
    assert tracing.merged(iv) == [[0, 3], [5, 8], [10, 10]]
    assert tracing.union_length([]) == 0


def test_a_trace_without_the_window_span_is_refused():
    tr = tracing.Trace(ops=[tracing.Op("x", 0, 1, "/device:TPU:0")])
    with pytest.raises(RuntimeError):
        tracing.Reduction(tr)
