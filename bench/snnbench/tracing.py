"""Profiler traces: capture, the benchmark's own spans, and the reduction.

A traced run records one JAX profiler trace over its measured window.  The
reduction reads the device planes' "XLA Ops" lines (one event per operation
that ran on the device) and the benchmark's ``bench.window`` span on the
host planes, and gives:

* ``window_s``: the length of the ``bench.window`` span;
* ``busy_s``: the union of the device's operation intervals inside it,
  averaged over the devices;
* kernel time by name: the summed device durations of the operations whose
  name matches a pattern (the metric readers hold the patterns);
* the longest idle gaps, each labelled by the benchmark-side span that
  holds its midpoint (else the one that covers most of it), or
  "unattributed".

The program records no spans of its own, so the labels come from the load
generator's records (`load_spans`): whether a request was outstanding
(sent and not yet answered) while the device sat idle, or none was.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

import numpy as np

WINDOW_SPAN = "bench.window"
PENDING_SPAN = "bench.request_pending"
NO_REQUEST_SPAN = "bench.no_request"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"


@dataclass
class Op:
    name: str
    start: float    # ns
    end: float      # ns
    device: str


@dataclass
class Span:
    name: str
    start: float
    end: float


@dataclass
class Trace:
    """The events of one trace, as plain lists (ns on one clock)."""

    ops: list = field(default_factory=list)
    spans: list = field(default_factory=list)
    devices: list = field(default_factory=list)


def op_name(text: str) -> str:
    """An operation's HLO name: the device trace names each event by its
    whole HLO instruction (``%snn_count_stacked.1 = s32[...] custom-call(...)``)."""
    return text.split(" = ", 1)[0] if text.startswith("%") else text


def start(log_dir: str) -> None:
    """Start the profiler with host spans but without the Python tracer,
    which would record every Python call of the load generator."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def read_profile(pdata) -> Trace:
    """Device operations and host spans from a `jax.profiler.ProfileData`."""
    tr = Trace()
    for plane in pdata.planes:
        if DEVICE_PLANE.match(plane.name):
            tr.devices.append(plane.name)
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    tr.ops.append(Op(op_name(e.name), e.start_ns, e.end_ns,
                                     plane.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns > 0:
                        tr.spans.append(Span(e.name, e.start_ns, e.end_ns))
    return tr


def load_dir(log_dir: str) -> Trace:
    """Read the newest ``*.xplane.pb`` the profiler wrote under ``log_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not files:
        raise RuntimeError(f"the profiler wrote no trace under {log_dir}")
    return read_profile(ProfileData.from_file(files[-1]))


def merged(intervals) -> list:
    """The union of (start, end) intervals as sorted disjoint intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def union_length(intervals) -> float:
    """Total length covered by (start, end) intervals."""
    return sum(e - s for s, e in merged(intervals))


class Reduction:
    """The numbers a traced run reports, over the ``bench.window`` span."""

    def __init__(self, trace: Trace, window: tuple | None = None):
        if window is None:
            ws = [s for s in trace.spans if s.name == WINDOW_SPAN]
            if not ws:
                raise RuntimeError(f"the trace holds no {WINDOW_SPAN} span")
            window = (ws[0].start, ws[0].end)
        self.w0, self.w1 = window
        self.devices = sorted(trace.devices) or ["device"]
        self.ops = [Op(o.name, max(o.start, self.w0), min(o.end, self.w1),
                       o.device)
                    for o in trace.ops if o.end > self.w0 and o.start < self.w1]
        self.set_spans([])

    def set_spans(self, spans) -> None:
        """The spans (on the trace's clock) that label idle gaps."""
        spans = sorted((s for s in spans
                        if s.end > self.w0 and s.start < self.w1),
                       key=lambda s: s.start)
        self.span_names = [s.name for s in spans]
        self._starts = np.array([s.start for s in spans], np.float64)
        self._ends = np.array([s.end for s in spans], np.float64)
        self._longest = float((self._ends - self._starts).max()) \
            if spans else 0.0

    @property
    def window_s(self) -> float:
        return (self.w1 - self.w0) * 1e-9

    def busy_by_device(self) -> dict:
        return {d: union_length((o.start, o.end) for o in self.ops
                                if o.device == d) * 1e-9
                for d in self.devices}

    @property
    def busy_s(self) -> float:
        per = self.busy_by_device()
        return sum(per.values()) / len(per)

    def kernel_s(self, pattern: str) -> float:
        """Summed device seconds of the operations whose name matches."""
        rx = re.compile(pattern)
        return sum(o.end - o.start for o in self.ops
                   if rx.search(o.name)) * 1e-9 / len(self.devices)

    def top_ops(self, n: int = 10) -> list:
        by = {}
        for o in self.ops:
            by[o.name] = by.get(o.name, 0.0) + (o.end - o.start) * 1e-9
        return sorted(([k, v] for k, v in by.items()),
                      key=lambda kv: -kv[1])[:n]

    def gaps(self, device: str | None = None) -> list:
        """Idle intervals (start, end) of one device inside the window."""
        device = device or self.devices[0]
        busy = merged((o.start, o.end) for o in self.ops if o.device == device)
        out, t = [], self.w0
        for s, e in busy:
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if t < self.w1:
            out.append((t, self.w1))
        return out

    def label(self, gap) -> str:
        """What the host was doing in ``gap``: the innermost span holding
        its midpoint, else the span covering most of it."""
        s0, e0 = gap
        lo = np.searchsorted(self._starts, s0 - self._longest)
        hi = np.searchsorted(self._starts, e0)
        st, en = self._starts[lo:hi], self._ends[lo:hi]
        mid = 0.5 * (s0 + e0)
        holds = np.flatnonzero((st <= mid) & (en >= mid))
        if holds.size:
            return self.span_names[lo + holds[np.argmin(en[holds]
                                                        - st[holds])]]
        ov = np.minimum(e0, en) - np.maximum(s0, st)
        if not ov.size or ov.max() <= 0:
            return "unattributed"
        return self.span_names[lo + int(np.argmax(ov))]

    def idle_gaps(self, n: int = 10) -> list:
        """The ``n`` longest idle gaps as [label, seconds]."""
        gs = sorted(self.gaps(), key=lambda g: g[0] - g[1])[:n]
        return [[self.label(g), (g[1] - g[0]) * 1e-9] for g in gs]

    def idle_by_label(self) -> dict:
        """Idle seconds of the first device, summed by label."""
        out = {}
        for g in self.gaps():
            k = self.label(g)
            out[k] = out.get(k, 0.0) + (g[1] - g[0]) * 1e-9
        return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def load_spans(records, pc0: float, w0: float, pc1: float) -> list:
    """The load generator's records as spans on the trace's clock.

    ``bench.request_pending`` covers each interval in which some request
    was sent and not yet answered, ``bench.no_request`` the rest of the
    window.  ``pc0`` and ``pc1`` are the host clock (`time.perf_counter`)
    as the ``bench.window`` span opened and closed; ``w0`` is its start in
    the trace (ns)."""
    def ns(t):
        return w0 + (t - pc0) * 1e9

    busy = merged((max(r.sent, pc0), min(r.done, pc1)) for r in records
                  if r.sent and r.done > r.sent)
    spans, t = [], pc0
    for s, e in busy:
        if s > t:
            spans.append(Span(NO_REQUEST_SPAN, ns(t), ns(s)))
        spans.append(Span(PENDING_SPAN, ns(s), ns(e)))
        t = max(t, e)
    if t < pc1:
        spans.append(Span(NO_REQUEST_SPAN, ns(t), ns(pc1)))
    return spans
