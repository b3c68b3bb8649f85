"""The program's own spans in a profiler trace, read against the device.

The serving path records host spans named ``snn.*`` with
`jax.profiler.TraceAnnotation` (`repro.serving.runtime`, `repro.core.engine`,
`repro.core.knn`, `repro.core.join`, `repro.core.streaming`).  They land in
the same ``.xplane.pb`` as the device's "XLA Ops" line, on its clock, each
on the line of the host thread that opened it; metadata rides in the name
as ``name#k=v,...#`` (the profiler may also hand it over as event stats).

`program_spans` reads them; `SpanReduction` reduces them over the measured
window of a `tracing.Reduction`:

* seconds of each span name per ``snn.batch`` (``prep_ms`` and
  ``assemble_ms`` are two of them);
* the device's idle seconds while a span of each name was open;
* ``host_idle``: the window's share in which the device ran nothing while
  a dispatcher thread (one that served an ``snn.batch``) was inside a span
  other than ``snn.await_request``, i.e. working for a request;
* the share of each batch's time its nested spans cover.

The innermost-span rule of `tracing.Reduction.label` names an idle gap by
the program span open at its midpoint when there is one, since program
spans nest inside the load generator's.
"""
from __future__ import annotations

import bisect
import glob
import os
from dataclasses import dataclass, field

from . import tracing

PREFIX = "snn."
BATCH = "snn.batch"
AWAIT = "snn.await_request"


@dataclass
class ProgramSpan(tracing.Span):
    thread: str = ""
    meta: dict = field(default_factory=dict)


def _value(text: str):
    try:
        return int(text)
    except ValueError:
        return text


def split_name(text: str) -> tuple[str, dict]:
    """``"snn.batch#batch=3,kinds=snn-radius#"`` -> ``("snn.batch",
    {"batch": 3, "kinds": "snn-radius"})``."""
    name, _, rest = text.partition("#")
    meta = {}
    for pair in rest.rstrip("#").split(","):
        k, eq, v = pair.partition("=")
        if eq:
            meta[k] = _value(v)
    return name, meta


def program_spans(pdata) -> list[ProgramSpan]:
    """Every ``snn.*`` event of the host planes of a `ProfileData`, with
    its thread (``<plane>/<line index>``) and metadata."""
    out = []
    for plane in pdata.planes:
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            thread = f"{plane.name}/{li}"
            for e in line.events:
                if not e.name.startswith(PREFIX):
                    continue
                name, meta = split_name(e.name)
                meta.update((k, v) for k, v in e.stats)
                out.append(ProgramSpan(name, e.start_ns, e.end_ns, thread,
                                       meta))
    return out


def load_dir(log_dir: str):
    """(`tracing.Trace`, program spans) of the newest ``*.xplane.pb`` the
    profiler wrote under ``log_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not files:
        raise RuntimeError(f"the profiler wrote no trace under {log_dir}")
    pdata = ProfileData.from_file(files[-1])
    return tracing.read_profile(pdata), program_spans(pdata)


def overlap(a, b) -> float:
    """Length of the intersection of two sorted lists of disjoint
    (start, end) intervals."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


class SpanReduction:
    """The program's spans over the measured window of ``red``."""

    def __init__(self, red: tracing.Reduction, spans):
        self.red = red
        self.spans = [s for s in spans if s.end > red.w0 and s.start < red.w1]
        self.batches = [s for s in self.spans if s.name == BATCH]
        self.dispatchers = {s.thread for s in self.batches}
        self._gaps = red.gaps()

    def _clipped(self, s) -> tuple:
        return max(s.start, self.red.w0), min(s.end, self.red.w1)

    def seconds_per_batch(self) -> dict:
        """Summed seconds of each span name in the window, per batch."""
        if not self.batches:
            return {}
        out = {}
        for s in self.spans:
            lo, hi = self._clipped(s)
            out[s.name] = out.get(s.name, 0.0) + (hi - lo) * 1e-9
        n = len(self.batches)
        return {k: v / n for k, v in sorted(out.items(),
                                            key=lambda kv: -kv[1])}

    def ms_per_batch(self, name: str) -> float | None:
        """Summed milliseconds of ``name`` per batch (None: no batch)."""
        if not self.batches:
            return None
        return 1e3 * self.seconds_per_batch().get(name, 0.0)

    def idle_while(self) -> dict:
        """Idle seconds of the first device while a span of each name was
        open (nested names each count the same gap)."""
        by = {}
        for s in self.spans:
            by.setdefault(s.name, []).append(self._clipped(s))
        out = {k: overlap(self._gaps, tracing.merged(v)) * 1e-9
               for k, v in by.items()}
        return dict(sorted(out.items(), key=lambda kv: -kv[1]))

    def host_idle(self) -> float | None:
        """Share (%) of the window with the device idle while a dispatcher
        thread was inside a span other than ``snn.await_request``."""
        if not self.batches or self.red.window_s <= 0:
            return None
        busy_host = tracing.merged(
            self._clipped(s) for s in self.spans
            if s.thread in self.dispatchers and s.name != AWAIT)
        return 100.0 * overlap(self._gaps, busy_host) * 1e-9 \
            / self.red.window_s

    def batch_coverage(self) -> float | None:
        """Share of the batches' summed time that the spans nested in them
        (same thread, opened inside the batch) cover."""
        by_thread = {}
        for s in sorted(self.spans, key=lambda s: s.start):
            if s.name != BATCH:
                by_thread.setdefault(s.thread, []).append(s)
        starts = {t: [s.start for s in v] for t, v in by_thread.items()}
        total = covered = 0.0
        for b in self.batches:
            mine = by_thread.get(b.thread, [])
            lo = bisect.bisect_left(starts.get(b.thread, []), b.start)
            hi = bisect.bisect_left(starts.get(b.thread, []), b.end)
            covered += tracing.union_length(
                (s.start, min(s.end, b.end)) for s in mine[lo:hi])
            total += b.end - b.start
        return covered / total if total > 0 else None
