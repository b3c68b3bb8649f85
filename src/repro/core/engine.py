"""Unified multi-segment CSR execution engine: plan / execute.

A *segment* is any contiguous sorted run of database rows — a whole index,
one mesh shard's slice, or an LSM delta of a streaming index are all the
same thing here.  The engine runs the ONE two-pass exact CSR orchestration
shared by every device path:

1. **pass 1 — count**: per-segment, per-query survivor counts,
   giving a (S, m) matrix;
2. **prefix sums**: summing over segments yields the global CSR ``indptr``;
   an *exclusive* prefix over the segment axis yields each segment's
   per-query write base — segment k's survivors of query i land in slots
   ``indptr[i] + sum(per[:k, i])``;
3. **pass 2 — compact**: survivors scatter into disjoint slots of one
   shared flat array.

Two executors share that orchestration:

* the **looped** executor (`run_csr`) launches ``kernels.snn_count`` /
  ``snn_compact`` once per live segment with a host sync after each, and
  does the prefix sums in numpy — the original engine, kept as the
  cross-check oracle and as the fallback for oversized oracle batches;
* the **packed** executor (`run_csr_packed`) executes a prebuilt *plan* —
  a `SegmentPack` stacking all of an index's segments into one
  ``(S, n_pad, lanes)`` device tensor, built once per index epoch.  The
  per-segment Python prune loop becomes a single vectorized interval-
  overlap bitmask, each pass is ONE stacked-grid launch over (live
  segments × query tiles × db blocks), the prefix sums run on device
  (``jnp.cumsum``), and exactly one scalar (the total neighbor count —
  unavoidable: it sizes the flat output) crosses to the host between the
  passes, followed by the single transfer of the final CSR triple.  In
  many-segment regimes (streaming LSM indexes, `core.graph`'s narrow
  sorted chunks) this removes the S-fold dispatch + sync overhead that
  dominates small-radius queries.

Disjointness only needs each segment to be internally sorted by alpha (the
kernels emit survivors in ascending local order) — segments may overlap in
alpha range.  When they don't overlap (single index, mesh shards), the flat
result is additionally in globally ascending sorted order, bit-identical to
the host oracle ``query_radius_batch``.

Both passes must see bit-identical float32 predicate inputs: a ULP-level
disagreement between differently-compiled filters would corrupt the scatter
layout (a final ``>= 0`` check fails loudly).  Segments whose alpha range
cannot intersect any query window are skipped entirely (zero kernel
launches), which is what makes many-segment streaming indexes and
mostly-padding shards cheap.  Packed output is bit-identical to looped
output: both evaluate the same predicate pipeline per element (the stacked
matmul reduces the same d-length vectors per output element) and share the
slot formula above.

Callers normally reach this module through `core.join`, the workload
front-end layer: `join(A, B, r)` (and the point-query / self-join /
reverse / count-only front-ends built on it) owns query-side scheduling —
sorting A, chunking, permuting results back — and hands each chunk to
`run_csr_packed` / `run_counts_packed` here.  The executors never reorder
queries; the served entries (`query_csr_packed`, `core.join.query_counts`)
may run a batch of several query tiles in alpha order (`TileOrder`) and
put its rows back in the caller's order.
"""
from __future__ import annotations

import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..kernels import ops as _ops
from ..kernels import ref as _ref
from ..kernels import registry as _registry

# Padding rows carry alpha = half_norm = +BIG; anything above this threshold
# is sentinel, not data (used when recovering a segment's real alpha range).
_REAL = _ops.BIG / 2


# --------------------------------------------------------------------------- #
# Dispatch instrumentation                                                     #
# --------------------------------------------------------------------------- #
_STAT_FIELDS = ("kernel_launches", "host_transfers", "jit_compiles",
                "bytes_planned", "grid_cells", "live_cells", "fetched_cells",
                "arrival_cells", "placed_runs", "knn_rounds", "knn_candidates",
                "knn_wanted")


class _StatCounters:
    """One thread's raw counter storage (only its owner thread mutates it)."""

    __slots__ = _STAT_FIELDS

    def __init__(self) -> None:
        for f in _STAT_FIELDS:
            setattr(self, f, 0)


_AGG_LOCK = threading.Lock()
_ALL_COUNTERS: list[_StatCounters] = []


class DispatchStats(threading.local):
    """Counters for the dispatch overhead the packed plan exists to remove.

    ``kernel_launches`` counts device computations dispatched (Pallas kernel
    or jitted oracle evaluations); ``host_transfers`` counts device->host
    materializations (``np.asarray`` of a device array, including the
    scalar pass-boundary sync — the fused single-dispatch path's whole
    result tuple counts as ONE); ``jit_compiles`` counts NEW kernel launch
    signatures — (backend, op, shapes, static args) keys never seen before
    in this process, i.e. launches that forced an XLA compile
    (`kernels.registry.note_launch_signature`); ``bytes_planned`` counts
    bytes accounted by newly built static `MemoryPlan`s (one per
    (pack epoch, query bucket)).  ``grid_cells`` counts the (live segment,
    query tile, db block) cells of every stacked pass a device lane runs,
    and ``live_cells`` those whose sorted window the kernel finds hit
    (`_count_cells`): the rest skip the MXU.  ``fetched_cells`` counts the
    cells whose row block the pass fetches: each (segment, tile)'s live
    block range (`kernels.snn_query.block_ranges`), or one block for a
    tile that hits nothing; the pruned cells outside it fetch nothing.
    ``arrival_cells`` counts the live cells those passes would have run
    with each batch's rows tiled in the order they arrived, with the same
    pass multiplicity: it equals ``live_cells`` for a batch that kept its
    order and exceeds it by what `TileOrder` saved (spanned as
    ``snn.order``, with ``rows``, ``arrival_cells``, ``live_cells`` and
    ``ordered``, inside the served entries' ``snn.prep``).
    ``placed_runs`` counts the (query row, db block) runs pass 2 placed
    (`_placed_runs`): the row visits of the compaction's row loop that do
    work, so over ``tq`` x the pass-2 live cells it is the share of a
    tile's rows that a live cell places.  ``knn_rounds`` counts the kNN
    expansion loop's count passes, ``knn_candidates`` the CSR entries of
    its final pass and ``knn_wanted`` the neighbors asked for (sum of each
    query's k, capped at n), so candidates per wanted neighbor is the
    final pass's excess (`core.knn.query_knn`).

    Readers: the on-chip benchmark's metrics (``bench/metrics``:
    ``launches_per_row`` reads ``kernel_launches``, ``live_cells``,
    ``fetched_cells`` and ``knn_candidates`` read the fields of their
    names, ``order_cells`` reads ``arrival_cells`` / ``live_cells``, all
    over the measured window's `aggregate`; no metric reads
    ``placed_runs``, which the harness prints with the rest of the
    window's counters as "dispatch in the window"),
    `kernels.registry.compile_counts`
    beside ``jit_compiles``, the serving registry's memory budget
    (``bytes_planned``), `benchmarks.common.dispatch_counts` and
    ``benchmarks/check_dispatch.py`` (launches and transfers per call), and
    the tests.  Every ``kernel_launches`` increment opens one ``snn.launch``
    profiler span and every ``host_transfers`` increment one ``snn.sync``
    (`_Launch`, `_to_host`).

    Concurrency: the counters live in per-thread `_StatCounters` holders
    (``threading.local`` hands each thread its own on first touch), so the
    fused serving path's overlapping batches never race on an increment —
    each thread mutates only its own holder.  `aggregate()` sums every
    holder ever registered (the lock guards registry membership only), the
    cross-thread view the serving regression test checks.
    """

    def __init__(self) -> None:
        self._c = _StatCounters()
        with _AGG_LOCK:
            _ALL_COUNTERS.append(self._c)

    def reset(self) -> None:
        for f in _STAT_FIELDS:
            setattr(self._c, f, 0)

    def snapshot(self) -> dict:
        return {f: getattr(self._c, f) for f in _STAT_FIELDS}

    @staticmethod
    def aggregate() -> dict:
        """Sum of every thread's counters (threads that exited included).

        Per-thread ``reset()`` zeroes that thread's contribution, so the
        aggregate is "since the threads' last resets", not process lifetime.
        """
        with _AGG_LOCK:
            holders = list(_ALL_COUNTERS)
        out = dict.fromkeys(_STAT_FIELDS, 0)
        for c in holders:
            for f in _STAT_FIELDS:
                out[f] += getattr(c, f)
        return out


def _make_stat_property(field: str):
    def _get(self):
        return getattr(self._c, field)

    def _set(self, value):
        setattr(self._c, field, value)

    return property(_get, _set)


for _f in _STAT_FIELDS:
    setattr(DispatchStats, _f, _make_stat_property(_f))
del _f


DISPATCH_STATS = DispatchStats()


class _Launch(TraceAnnotation):
    """One device dispatch: a ``kernel_launches`` count and an
    ``snn.launch`` span named by ``op``.  A launch that met a new launch
    signature (`kernels.registry.note_launch_signature`: an XLA compile)
    carries ``new_signature=1``.  Like every span, it records only while
    the profiler runs."""

    def __init__(self, op: str):
        super().__init__("snn.launch", op=op)
        DISPATCH_STATS.kernel_launches += 1
        self._compiles = DISPATCH_STATS.jit_compiles

    def __exit__(self, *exc):
        if DISPATCH_STATS.jit_compiles != self._compiles:
            self.set_metadata(new_signature=1)
        return super().__exit__(*exc)


def _to_host(x):
    """Wait for a device result and copy it to the host: one
    ``host_transfers`` count and one ``snn.sync`` span.  A tuple comes back
    in one `jax.device_get`."""
    DISPATCH_STATS.host_transfers += 1
    with TraceAnnotation("snn.sync"):
        return jax.device_get(x) if isinstance(x, tuple) else np.asarray(x)


def _fetch(fn, *args, **kwargs):
    """Launch ``fn`` (a backend entry point) and wait for its result."""
    with _Launch(fn.__name__):
        out = fn(*args, **kwargs)
    return _to_host(out)


def _oracle() -> "_registry.Backend":
    """The oracle backend — the host-pruned packed paths are numpy-gather
    code and always evaluate through the jnp reference lane."""
    return _registry.get_backend("oracle")


# --------------------------------------------------------------------------- #
# Flat scratch reuse (serving hot path)                                        #
# --------------------------------------------------------------------------- #
# requests above this many flat slots are served by one-off arrays instead
# of the cached scratch: a single huge result set must not pin GBs of
# staging memory in a thread for the rest of the process
_SCRATCH_CACHE_MAX = 1 << 24


class _FlatScratch(threading.local):
    """Grow-only per-thread staging buffers for the flat CSR assembly.

    `csr_capacity` rounds every request up to a power-of-two of whole lanes
    (bounding kernel recompiles), which used to allocate-and-fill two fresh
    rounded-up arrays per call — wasteful for the serving path's many tiny
    result sets.  The scratch grows monotonically (capped at
    `_SCRATCH_CACHE_MAX` slots) and is reused across calls; results are
    copied out at their exact size, so callers still own their arrays.
    """

    ids: np.ndarray | None = None
    dh: np.ndarray | None = None

    def take(self, cap: int) -> tuple[np.ndarray, np.ndarray, bool]:
        """(ids, dh, owned): ``owned`` means the arrays are one-off (too big
        to cache) and the caller may hand out trimmed views instead of
        copying — copying a multi-GB one-off would transiently double peak
        memory in exactly the regime the cache ceiling protects."""
        if cap > _SCRATCH_CACHE_MAX:
            return (np.full(cap, -1, np.int64),
                    np.full(cap, np.float32(_ops.BIG), np.float32), True)
        if self.ids is None or self.ids.size < cap:
            self.ids = np.empty(cap, np.int64)
            self.dh = np.empty(cap, np.float32)
        ids, dh = self.ids[:cap], self.dh[:cap]
        ids.fill(-1)
        dh.fill(np.float32(_ops.BIG))
        return ids, dh, False


_SCRATCH = _FlatScratch()


@dataclasses.dataclass
class Segment:
    """One contiguous alpha-sorted run, padded and device-resident.

    Attributes:
      xs, alphas, half_norms: padded device arrays (rows to a block multiple
        with +BIG sentinels, features to the 128-lane multiple).
      ids:      (n,) original row ids for local sorted positions; sentinel
        rows inside ``n`` (pre-padded shard slices) carry -1 and can never
        survive the predicate, so they are never read.
      alpha_lo/alpha_hi: range of the *real* alphas — the segment-level
        window prune (lo > hi for an all-sentinel segment: always skipped).
      block:    row-block size the arrays were padded to (the kernel ``bn``).
      projs:    optional (ke, n_pad) EXTRA projection components (+BIG in
        padding/sentinel columns) for the k-dim box prune; None keeps every
        path bit-identical to the pre-multi-component engine.
      proj_lo/proj_hi: (ke,) float64 real ranges per component — the
        segment-level box prune.
      proj_sorted/proj_rank: (ke, n_pad) host-side per-component sorted
        values (float64) and the matching local positions — the packed
        oracle's interval-to-columns gather.
      xnorm_max: max real row norm (float64) — sizes the host-side box slack.
      block_lo/block_hi: (n_pad / block,) host copies of the first and last
        padded alpha of each row block, in the device arrays' dtype — the
        block range each kernel cell tests (`_count_cells`).
    """

    xs: jnp.ndarray
    alphas: jnp.ndarray
    half_norms: jnp.ndarray
    ids: np.ndarray
    alpha_lo: float
    alpha_hi: float
    block: int
    projs: jnp.ndarray | None = None
    proj_lo: np.ndarray | None = None
    proj_hi: np.ndarray | None = None
    proj_sorted: np.ndarray | None = None
    proj_rank: np.ndarray | None = None
    xnorm_max: float = 0.0
    block_lo: np.ndarray | None = None
    block_hi: np.ndarray | None = None

    @property
    def n(self) -> int:
        return self.ids.shape[0]

    @property
    def ke(self) -> int:
        """Number of extra projection components carried (0 = none)."""
        return 0 if self.projs is None else int(self.projs.shape[0])


def make_segment(xs, alphas, half_norms, ids, *, block: int = 512,
                 projs=None) -> Segment:
    """Pad one sorted run for the kernels and record its real alpha range.

    ``projs`` is the optional (ke, n) block of EXTRA projection components
    (`SNNIndex.projs[1:]` — component 0 is the alpha window itself).  Columns
    are padded with +BIG so no finite box interval can ever select a padding
    or sentinel row.
    """
    alphas = np.asarray(alphas)
    xs_p, al_p, hn_p, _, _ = _ops.pad_database(xs, alphas, half_norms, bn=block)
    realm = alphas < _REAL
    real = alphas[realm]
    lo = float(real[0]) if real.size else float("inf")
    hi = float(real[-1]) if real.size else float("-inf")
    pj = plo = phi = ps = pr = None
    xnorm_max = 0.0
    if projs is not None:
        big = np.float32(_ops.BIG)
        pj_np = np.asarray(projs, np.float32)
        # sentinel rows inside n (pre-padded shard slices) get +BIG as well
        pj_np = np.where(realm[None, :], pj_np, big)
        n_pad = int(al_p.shape[0])
        pj_full = np.concatenate(
            [pj_np, np.full((pj_np.shape[0], n_pad - pj_np.shape[1]), big,
                            np.float32)], axis=1)
        pj = jnp.asarray(pj_full)
        if realm.any():
            p64 = pj_np[:, realm].astype(np.float64)
            plo, phi = p64.min(axis=1), p64.max(axis=1)
            hn_real = np.asarray(half_norms, np.float64)[realm]
            xnorm_max = float(np.sqrt(max(2.0 * float(hn_real.max()), 0.0)))
        else:
            plo = np.full(pj_np.shape[0], np.inf)
            phi = np.full(pj_np.shape[0], -np.inf)
        ps = np.sort(pj_full.astype(np.float64), axis=1)
        pr = np.argsort(pj_full, axis=1, kind="stable").astype(np.int64)
    # the padded alphas as the device holds them, read block by block
    al_host = np.pad(alphas, (0, al_p.shape[0] - alphas.shape[0]),
                     constant_values=_ops.BIG).astype(al_p.dtype)
    blocks = al_host.reshape(-1, block)
    return Segment(xs_p, al_p, hn_p, np.asarray(ids, np.int64), lo, hi, block,
                   pj, plo, phi, ps, pr, xnorm_max,
                   blocks[:, 0].copy(), blocks[:, -1].copy())


def _index_extra_projs(index) -> np.ndarray | None:
    """The (ke, n) EXTRA projection rows of an index, or None (single-PC)."""
    pj = getattr(index, "projs", None)
    if pj is None or pj.shape[0] <= 1:
        return None
    return np.asarray(pj)[1:]


def segment_from_index(index, *, block: int = 512) -> Segment:
    """The whole of one `SNNIndex` (or index-shaped object) as a segment."""
    return make_segment(index.xs, index.alphas, index.half_norms, index.order,
                        block=block, projs=_index_extra_projs(index))


def segments_from_index(
    index,
    *,
    rows_per_segment: int,
    block: int = 512,
    ids: np.ndarray | None = None,
) -> list[Segment]:
    """Partition one index's sorted rows into contiguous equal-size segments.

    The point of splitting a single sorted database: `run_csr` prunes whole
    segments whose alpha range cannot touch any query window, so a query
    batch with a narrow alpha footprint (e.g. the sorted query chunks of
    `core.graph`'s self-join) only pays for the segments it can actually
    hit, at `rows_per_segment` granularity.  Segment k covers sorted rows
    ``[k * rows_per_segment, (k+1) * rows_per_segment)``; concatenating the
    segments in order reproduces the index, so segment-major engine output
    stays in globally ascending sorted order (`run_csr` docstring).

    ``ids`` overrides the per-row id map (default ``index.order``, yielding
    original row ids; pass ``np.arange(n)`` to get sorted positions back —
    the representation `core.graph`'s symmetric join works in).
    """
    n = index.n
    ids = index.order if ids is None else np.asarray(ids, np.int64)
    rs = max(int(rows_per_segment), 1)
    ep = _index_extra_projs(index)
    return [make_segment(index.xs[s:s + rs], index.alphas[s:s + rs],
                         index.half_norms[s:s + rs], ids[s:s + rs],
                         block=block,
                         projs=None if ep is None else ep[:, s:s + rs])
            for s in range(0, n, rs)]


def _qnorm64(rp, thp, m: int) -> np.ndarray:
    """(m,) float64 centered query norms recovered from the predicate pair.

    The kernels derive ``qn = sqrt(max(r^2 - 2*thresh, 0))`` in float32 for
    the box slack (`kernels.ref.norm_scales`); the host prune needs the same
    quantity.  Computed through the identical float32 expression first so the
    float64 value can only be >= what any float32 evaluation rounds to (after
    the 1e-6 relative inflation in `_box_interval_radius`).
    """
    r32 = np.asarray(rp, np.float32)[:m]
    t32 = np.asarray(thp, np.float32)[:m]
    with np.errstate(over="ignore", invalid="ignore"):
        qn = np.sqrt(np.maximum(r32 * r32 - np.float32(2.0) * t32,
                                np.float32(0.0)))
    return qn.astype(np.float64)


def _box_interval_radius(r64, qn64, xnorm_max) -> np.ndarray:
    """Float64 SUPERSET of the kernels' per-candidate box slack.

    The device test keeps ``|p_c - pq_c| <= r + BOX_EPS*(xn + qn + |r|)``
    with per-COLUMN ``xn``; substituting the segment-wide ``xnorm_max >= xn``
    and inflating by 1e-6 relative (+1e-30 absolute, so r=0 still gets slack)
    dominates every float32 rounding of the device expression.  Broadcasts
    over whatever shapes ``r64``/``qn64``/``xnorm_max`` arrive in.
    """
    return (r64 + _ref.BOX_EPS * (xnorm_max + qn64 + np.abs(r64))) \
        * (1.0 + 1e-6) + 1e-30


def _window_may_hit(seg: Segment, aq: np.ndarray, r: np.ndarray,
                    pq: np.ndarray | None = None,
                    qn: np.ndarray | None = None) -> bool:
    """Conservative host-side test: can ANY query window touch this segment?

    The kernels evaluate ``|alpha - aq| <= r`` in float32; a few-ULP slack on
    the float64 host comparison guarantees skipping never drops a pair the
    kernel would keep.  With ``pq`` ((kq, m) float64 extra query projections)
    and ``qn`` (`_qnorm64`), the test tightens to the k-dim box: a segment
    survives only if some query's box interval overlaps the segment's real
    range on EVERY component.
    """
    if seg.alpha_lo > seg.alpha_hi or aq.size == 0:
        return False
    slack = 1e-6 * (np.abs(aq) + np.abs(r)
                    + max(abs(seg.alpha_lo), abs(seg.alpha_hi)) + 1.0)
    hit = ((aq + r + slack >= seg.alpha_lo)
           & (aq - r - slack <= seg.alpha_hi))
    if pq is not None and seg.ke:
        kq = min(pq.shape[0], seg.ke)
        R = _box_interval_radius(r, qn, seg.xnorm_max)
        for c in range(kq):
            hit &= ((pq[c] + R >= seg.proj_lo[c])
                    & (pq[c] - R <= seg.proj_hi[c]))
    return bool(np.any(hit))


def run_csr(
    segments: list[Segment],
    qp, aqp, rp, thp,
    m: int,
    *,
    query_tile: int = 128,
    use_pallas: bool | str | None = None,
    memory_budget_mb: float | None = None,
    pq=None,
    mixed: bool = False,
):
    """The two-pass LOOPED orchestration over padded queries and segments.

    One kernel launch (plus host sync) per live segment per pass — the
    cross-check oracle for `run_csr_packed`, and the path of record when a
    packed oracle batch would exceed its memory budget.

    Args:
      segments: alpha-sorted runs (see `Segment`); need not be disjoint.
      qp/aqp/rp/thp: `kernels.ops.pad_queries` outputs.
      m: real (unpadded) query count.
      memory_budget_mb: oracle-path cache ceiling.  Pass-1 dense filters are
        cached for pass 2 only while their cumulative size stays under the
        budget; segments past it recompute the identical jitted filter in
        pass 2 (bit-identical by construction — same compiled function on
        the same inputs), trading one extra evaluation for bounded peak
        memory.  Each cached filter is released right after its scatter.
      pq: optional (kq, m_pad) padded extra query projections
        (`kernels.ops.pad_components`).  Effective components are
        ``min(kq, min segment ke)``; 0 reproduces the pre-box engine
        bit-for-bit.  The box only removes pairs the distance predicate
        would reject anyway, so results are unchanged — only cheaper.
      mixed: run pass-1 counts through the certified bf16 margin filter on
        the Pallas path.  The certificate makes mixed counts EQUAL to the
        f32 counts, so pass 2 (always f32) still fills every slot — the
        ``>= 0`` check at the end enforces the certificate at runtime.  The
        oracle path reuses one f32 filter for both passes regardless (its
        counts are the same numbers by the same certificate).

    Returns ``(indptr (m+1,) int64, counts (m,) int64, flat_ids (nnz,) int64,
    flat_dh (nnz,) float32)`` where ``flat_ids`` are original row ids in
    segment-major, locally-ascending order.

    ``use_pallas`` is a backend selector (`kernels.registry.resolve`):
    None = process default, True/False = device kernels / oracle, or a
    registered backend name (e.g. "pallas-gpu").
    """
    backend = _registry.resolve(use_pallas)
    aq64 = np.asarray(aqp, np.float64)[:m]
    r64 = np.asarray(rp, np.float64)[:m]
    budget = (float("inf") if memory_budget_mb is None
              else memory_budget_mb * 2**20)
    kq = 0
    if pq is not None and segments:
        kq = min([s.ke for s in segments] + [int(np.asarray(pq).shape[0])])
    pq_j = pq64 = qn64 = None
    if kq:
        pq_np = np.asarray(pq, np.float32)[:kq]
        pq_j = jnp.asarray(pq_np)
        pq64 = pq_np[:, :m].astype(np.float64)
        qn64 = _qnorm64(rp, thp, m)

    def _px(seg):
        if not kq:
            return None
        return seg.projs if seg.ke == kq else seg.projs[:kq]

    # ---- pass 1: per-segment counts --------------------------------------
    per = np.zeros((len(segments), m), np.int64)
    cached: list[np.ndarray | None] = [None] * len(segments)
    cached_bytes = 0
    live: list[int] = []
    for k, seg in enumerate(segments):
        if not _window_may_hit(seg, aq64, r64, pq64, qn64):
            continue
        live.append(k)
        if backend.device:
            per[k] = _fetch(
                backend.snn_count,
                qp, aqp, rp, thp, seg.xs, seg.alphas, seg.half_norms,
                pq_j, _px(seg), tq=query_tile, bn=seg.block,
                mixed=mixed)[:m]
        else:
            # Oracle fast path: one dense filter feeds BOTH passes (counts
            # and scatter); np.nonzero's row-major order IS the CSR order.
            dh = _fetch(
                backend.snn_filter,
                qp, aqp, rp, thp, seg.xs, seg.alphas, seg.half_norms,
                pq_j, _px(seg))[:m]
            if cached_bytes + dh.nbytes <= budget:
                cached[k] = dh
                cached_bytes += dh.nbytes
            per[k] = (dh < _ops.BIG).sum(axis=1)

    # ---- host prefix sums: global indptr + per-segment write bases -------
    counts = per.sum(axis=0)
    indptr = np.zeros(m + 1, np.int64)
    np.cumsum(counts, out=indptr[1:])
    total = int(indptr[-1])
    if total == 0:
        return indptr, counts, np.zeros(0, np.int64), np.zeros(0, np.float32)
    seg_base = np.cumsum(per, axis=0) - per  # exclusive prefix over segments

    # ---- pass 2: per-segment compaction into disjoint flat slots ---------
    cap = _ops.csr_capacity(total)
    flat_ids, flat_dh, owned = _SCRATCH.take(cap)
    off_pad = np.full(qp.shape[0] - m, total, np.int64)  # padding queries
    for k in live:
        if not per[k].any():
            cached[k] = None
            continue
        seg = segments[k]
        if backend.device:
            off_k = jnp.asarray(np.concatenate(
                [indptr[:-1] + seg_base[k], off_pad]).astype(np.int32))
            with _Launch("snn_compact"):
                fi, fd = backend.snn_compact(
                    qp, aqp, rp, thp, off_k, seg.xs, seg.alphas,
                    seg.half_norms, pq_j, _px(seg), nnz=cap, tq=query_tile,
                    bn=seg.block)
            fi = _to_host(fi)
            written = fi >= 0
            flat_ids[written] = seg.ids[fi[written]]
            flat_dh[written] = _to_host(fd)[written]
        else:
            dh = cached[k]
            if dh is None:  # over-budget segment: identical jitted recompute
                dh = _fetch(
                    backend.snn_filter,
                    qp, aqp, rp, thp, seg.xs, seg.alphas, seg.half_norms,
                    pq_j, _px(seg))[:m]
            keep = dh < _ops.BIG
            rows, cols = np.nonzero(keep)
            within = (np.cumsum(keep, axis=1) - 1)[rows, cols]
            slots = indptr[rows] + seg_base[k][rows] + within
            flat_ids[slots] = seg.ids[cols]
            flat_dh[slots] = dh[rows, cols]
            cached[k] = None  # release right after the scatter
    # both passes ran the same predicate pipeline, so every slot is written;
    # a -1 would silently alias a wrong row, so fail loudly (not an assert:
    # it must survive python -O)
    if not (flat_ids[:total] >= 0).all():
        raise RuntimeError("CSR pass-1/pass-2 disagreement")
    if owned:  # one-off arrays: the trimmed views are the caller's already
        return indptr, counts, flat_ids[:total], flat_dh[:total]
    # copy out of the reusable scratch at exact size — callers own these
    return indptr, counts, flat_ids[:total].copy(), flat_dh[:total].copy()


# --------------------------------------------------------------------------- #
# Static memory planning                                                       #
# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class MemoryPlan:
    """Static buffer-size ledger for one (pack, query-bucket) combination.

    Every buffer the packed two-pass execution touches is statically sized
    by the pack geometry (segment count, padded rows, lane width) plus the
    bucketed query-batch size and the count-pass worst case — so the sizes
    are derived ONCE per index epoch per bucket instead of re-guessed at
    runtime by `_FlatScratch`'s grow-only heuristics.  ``buffers`` maps
    buffer name -> (shape, dtype, nbytes); ``staging_cap`` is the flat CSR
    staging ceiling (`csr_capacity` of the worst-case survivor count,
    clamped to `_SCRATCH_CACHE_MAX` — beyond that the engine uses one-off
    arrays by design).  Totals land in ``DISPATCH_STATS.bytes_planned`` when
    the plan is first built (`SegmentPack.memory_plan`).
    """

    m_pad: int
    query_tile: int
    buffers: tuple
    total_bytes: int
    staging_cap: int

    def reserve(self) -> None:
        """Pre-grow this thread's flat staging to the plan's ceiling.

        Optional warm-up for latency-critical owners (serving): after this,
        no steady-state query against the planned pack/bucket ever triggers
        a staging reallocation in this thread.
        """
        if 0 < self.staging_cap <= _SCRATCH_CACHE_MAX:
            _SCRATCH.take(self.staging_cap)


def _build_memory_plan(pack: "SegmentPack", m_pad: int,
                       query_tile: int) -> MemoryPlan:
    """Derive every packed-execution buffer size from the pack geometry."""
    S = pack.n_segments
    n_pad = pack.n_pad
    d_pad = int(pack.segments[0].xs.shape[1]) if pack.segments else 0
    ke = pack.ke
    n_real = int(sum(s.n for s in pack.segments))
    bufs: list[tuple] = []

    def add(name, shape, dtype):
        nbytes = int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize
        bufs.append((name, tuple(int(v) for v in shape),
                     np.dtype(dtype).name, int(nbytes)))

    # device-resident pack representations (once per epoch)
    add("stacked_xs", (S, n_pad, d_pad), np.float32)
    add("stacked_alphas", (S, n_pad), np.float32)
    add("stacked_half_norms", (S, n_pad), np.float32)
    add("stacked_ids", (S, n_pad), np.int64)
    if ke:
        add("stacked_projs", (S, ke, n_pad), np.float32)
    # per-batch query operands at the bucketed size
    add("queries", (m_pad, d_pad), np.float32)
    add("query_alpha", (m_pad,), np.float32)
    add("query_radius", (m_pad,), np.float32)
    add("query_thresh", (m_pad,), np.float32)
    if ke:
        add("query_projs", (ke, m_pad), np.float32)
    # pass-boundary buffers: counts, device prefix sums, write bases
    add("counts", (S, m_pad), np.int32)
    add("indptr", (m_pad + 1,), np.int32)
    add("offsets", (S, m_pad), np.int32)
    # flat CSR outputs: worst case = every real row survives for every query
    nnz_cap = _ops.csr_capacity(m_pad * max(n_real, 0) + 1)
    add("csr_flat_idx", (nnz_cap,), np.int32)
    add("csr_flat_dh", (nnz_cap,), np.float32)
    staging_cap = min(nnz_cap, _SCRATCH_CACHE_MAX)
    add("csr_staging_ids", (staging_cap,), np.int64)
    add("csr_staging_dh", (staging_cap,), np.float32)
    # candidate-compaction tiles (oracle kq path): per query tile one padded
    # row of candidate concat positions; worst case every live row survives
    # the box.  The gathered payload (features/alpha/half-norm per candidate)
    # is data-dependent and bounded by cand_tiles x (d_trim + 2) lanes — it
    # rides the staging budget, not a dedicated buffer.
    ptile = min(query_tile, _PRUNED_TILE)
    if ke and ptile and m_pad % ptile == 0:
        T = m_pad // ptile
        ccap_worst = _ops.csr_capacity(S * n_pad)
        add("cand_tiles", (T, ccap_worst), np.int64)
    # fused-dispatch speculation outputs: the flat CSR pair at the ratcheted
    # capacity (worst case = nnz_cap, same power-of-two ladder)
    add("fused_spec_idx", (min(nnz_cap, _SCRATCH_CACHE_MAX),), np.int32)
    add("fused_spec_dh", (min(nnz_cap, _SCRATCH_CACHE_MAX),), np.float32)
    total = sum(b[3] for b in bufs)
    return MemoryPlan(int(m_pad), int(query_tile), tuple(bufs), int(total),
                      int(staging_cap))


# --------------------------------------------------------------------------- #
# The packed plan: SegmentPack + stacked execution                             #
# --------------------------------------------------------------------------- #
@dataclasses.dataclass
class SegmentPack:
    """A device-resident execution *plan*: every segment of an index, packed.

    Built once per index epoch and reused across query batches (every chunk
    of a graph build, every serving request of an index generation).  Two
    device representations are built lazily, because each executor wants a
    different shape and most deployments only ever touch one:

    * **stacked** (`stacked()`): every segment padded to the pack-wide row
      count ``n_pad`` (+BIG sentinels keep extra rows inert) and stacked
      into ``(S, n_pad, lanes)`` tensors — what the stacked-grid Pallas
      kernels consume.  Sentinel-padding blocks are pruned per grid cell,
      so uniform padding costs skipped cells, not math.
    * **concat** (`concat()`): the segments' own padded arrays concatenated
      ragged into ``(sum n_pad_k, lanes)`` — what the CPU oracle consumes.
      No uniform padding: a streaming index whose base dwarfs its deltas
      would otherwise pay S x base-size dense-filter work.

    Attributes:
      segments: the source per-segment views (also the looped cross-check
        oracle and the memory-budget fallback).
      alpha_lo / alpha_hi: (S,) float64 real alpha ranges — the inputs of
        the vectorized interval-overlap prune (`live_mask`).
      block: the kernel row-block size every segment was padded to.
      epoch: build generation — owners bump it when the plan is rebuilt or
        extended so caches (serving, graph chunks) can validate reuse.
      ke: extra projection components shared by EVERY segment (the min over
        segments; 0 when any segment lacks them — the box prune only runs
        on components all segments can answer for).
      proj_lo / proj_hi: (S, ke) float64 per-segment real component ranges;
        xnorm_max: (S,) float64 per-segment max row norms — the vectorized
        box prune's inputs (None when ``ke == 0``).
    """

    segments: list[Segment]
    alpha_lo: np.ndarray
    alpha_hi: np.ndarray
    block: int
    epoch: int = 0
    ke: int = 0
    proj_lo: np.ndarray | None = None
    proj_hi: np.ndarray | None = None
    xnorm_max: np.ndarray | None = None
    _stacked: tuple | None = dataclasses.field(
        default=None, repr=False, compare=False)
    _concat: tuple | None = dataclasses.field(
        default=None, repr=False, compare=False)
    _stacked_px: jnp.ndarray | None = dataclasses.field(
        default=None, repr=False, compare=False)
    _concat_px: jnp.ndarray | None = dataclasses.field(
        default=None, repr=False, compare=False)
    _pruned: dict = dataclasses.field(
        default_factory=dict, repr=False, compare=False)
    _plans: dict = dataclasses.field(
        default_factory=dict, repr=False, compare=False)
    # capacity-speculation history for the fused single-dispatch device
    # path: (m_pad, query_tile, live set, kq) -> {"nnz_cap": ...}.  Dies
    # with the pack, so a rebuilt/extended epoch re-learns honestly.
    _spec: dict = dataclasses.field(
        default_factory=dict, repr=False, compare=False)
    # capacity hints adopted from a predecessor plan (double-buffered
    # epochs): (m_pad, query_tile, kq) -> nnz_cap.  Consulted only when a
    # live-set key has no learned capacity of its own — the new generation
    # starts fused instead of paying O(log nnz) ratchet misses again.
    _spec_hint: dict = dataclasses.field(
        default_factory=dict, repr=False, compare=False)
    _bounds: tuple | None = dataclasses.field(
        default=None, repr=False, compare=False)

    @property
    def n_segments(self) -> int:
        return len(self.segments)

    @property
    def n_pad(self) -> int:
        """Padded rows of the largest segment (the stacked row count)."""
        return max((s.xs.shape[0] for s in self.segments), default=0)

    @classmethod
    def build(cls, segments: list[Segment], *, epoch: int = 0) -> "SegmentPack":
        """Plan over ``segments`` (uniform block and lane padding required)."""
        segments = list(segments)
        if segments:
            block = segments[0].block
            d_pad = segments[0].xs.shape[1]
            for s in segments:
                if s.block != block or s.xs.shape[1] != d_pad:
                    raise ValueError("SegmentPack needs uniform block and "
                                     "lane padding across segments")
        else:
            block = 0
        lo = np.asarray([s.alpha_lo for s in segments], np.float64)
        hi = np.asarray([s.alpha_hi for s in segments], np.float64)
        ke = min((s.ke for s in segments), default=0)
        plo = phi = xnm = None
        if ke:
            plo = np.stack([np.asarray(s.proj_lo[:ke], np.float64)
                            for s in segments])
            phi = np.stack([np.asarray(s.proj_hi[:ke], np.float64)
                            for s in segments])
            xnm = np.asarray([s.xnorm_max for s in segments], np.float64)
        return cls(segments, lo, hi, block, epoch, ke, plo, phi, xnm)

    def memory_plan(self, m_pad: int, query_tile: int = 128) -> MemoryPlan:
        """The static `MemoryPlan` for a bucketed batch size (memoized).

        Built once per (pack, bucket) and reused for every batch that pads
        to the same ``m_pad``; first build accounts its bytes in
        ``DISPATCH_STATS.bytes_planned``.
        """
        key = (int(m_pad), int(query_tile))
        hit = self._plans.get(key)
        if hit is not None:
            return hit
        plan = _build_memory_plan(self, int(m_pad), int(query_tile))
        self._plans[key] = plan
        DISPATCH_STATS.bytes_planned += plan.total_bytes
        return plan

    def planned_bytes(self) -> int:
        """Total bytes of every `MemoryPlan` built on this pack so far.

        The multi-tenant registry's accounting unit: what admitting this
        plan (its device representations plus every bucketed batch shape it
        has served) costs against the device-memory budget.  Zero until the
        first query/warm builds a memory plan.
        """
        return sum(p.total_bytes for p in self._plans.values())

    def adopt_spec(self, prev: "SegmentPack") -> None:
        """Inherit ``prev``'s learned fused nnz capacities as hints.

        The double-buffered epoch handoff: a rebuilt/merged plan serves the
        same workload distribution its predecessor did, so the predecessor's
        ratcheted capacities are the right opening speculation.  Hints key on
        (m_pad, query_tile, kq) only — the live-segment sets differ across
        generations by construction — and are consulted when a live-set key
        has no capacity of its own; a real overflow still ratchets honestly.
        """
        for key, cap in prev._spec_hint.items():
            if cap:
                self._spec_hint[key] = max(self._spec_hint.get(key, 0), cap)
        for (m_pad, tile, _live, kq), rec in prev._spec.items():
            cap = rec.get("nnz_cap", 0)
            if cap:
                key = (m_pad, tile, kq)
                self._spec_hint[key] = max(self._spec_hint.get(key, 0), cap)

    def stacked(self):
        """(xs (S, n_pad, d), alphas (S, n_pad), half_norms (S, n_pad),
        ids (S, n_pad) host int64 with -1 padding) — built on first use."""
        if self._stacked is None:
            if not self.segments:
                z2 = jnp.zeros((0, 0), jnp.float32)
                return (jnp.zeros((0, 0, 0), jnp.float32), z2, z2,
                        np.zeros((0, 0), np.int64))
            n_pad = self.n_pad
            if len(self.segments) == 1:  # zero-copy: reshape, don't restack
                s = self.segments[0]
                xs, al, hn = s.xs[None], s.alphas[None], s.half_norms[None]
            else:
                big = np.float32(_ops.BIG)
                xs = jnp.stack([jnp.pad(s.xs, ((0, n_pad - s.xs.shape[0]),
                                               (0, 0)))
                                for s in self.segments])
                al = jnp.stack([jnp.pad(s.alphas,
                                        (0, n_pad - s.alphas.shape[0]),
                                        constant_values=big)
                                for s in self.segments])
                hn = jnp.stack([jnp.pad(s.half_norms,
                                        (0, n_pad - s.half_norms.shape[0]),
                                        constant_values=big)
                                for s in self.segments])
            ids = np.full((self.n_segments, n_pad), -1, np.int64)
            for k, s in enumerate(self.segments):
                ids[k, :s.n] = s.ids
            self._stacked = (xs, al, hn, ids)
        return self._stacked

    def block_bounds(self) -> tuple[np.ndarray, np.ndarray]:
        """(a_lo, a_hi): (S, n_pad / block) host arrays of the first and
        last alpha of every row block of `stacked()`, +BIG past a segment's
        own rows — the block ranges the stacked kernels' cells test.  Built
        once per plan from the segments' host copies."""
        if self._bounds is None:
            nb = self.n_pad // self.block
            dt = self.segments[0].block_lo.dtype
            lo = np.full((self.n_segments, nb), _ops.BIG, dt)
            hi = np.full((self.n_segments, nb), _ops.BIG, dt)
            for k, s in enumerate(self.segments):
                lo[k, :s.block_lo.size] = s.block_lo
                hi[k, :s.block_hi.size] = s.block_hi
            self._bounds = (lo, hi)
        return self._bounds

    def concat(self):
        """(xs (N, d), alphas (N,), half_norms (N,), ids (N,) host int64,
        starts (S+1,) host row offsets) — the ragged oracle representation,
        built on first use (zero-copy for a single-segment pack)."""
        if self._concat is None:
            segs = self.segments
            if not segs:
                z1 = jnp.zeros(0, jnp.float32)
                return (jnp.zeros((0, 0), jnp.float32), z1, z1,
                        np.zeros(0, np.int64), np.zeros(1, np.int64))
            sizes = [s.xs.shape[0] for s in segs]
            starts = np.zeros(len(segs) + 1, np.int64)
            np.cumsum(sizes, out=starts[1:])
            if len(segs) == 1:
                xs, al, hn = segs[0].xs, segs[0].alphas, segs[0].half_norms
            else:
                xs = jnp.concatenate([s.xs for s in segs])
                al = jnp.concatenate([s.alphas for s in segs])
                hn = jnp.concatenate([s.half_norms for s in segs])
            ids = np.full(int(starts[-1]), -1, np.int64)
            for k, s in enumerate(segs):
                ids[starts[k]:starts[k] + s.n] = s.ids
            self._concat = (xs, al, hn, ids, starts)
        return self._concat

    def stacked_projs(self) -> jnp.ndarray | None:
        """(S, ke, n_pad) extra projections stacked to match `stacked()`
        (+BIG in the uniform padding), or None when ``ke == 0``."""
        if not self.ke:
            return None
        if self._stacked_px is None:
            n_pad = self.n_pad
            big = np.float32(_ops.BIG)
            if len(self.segments) == 1:
                self._stacked_px = self.segments[0].projs[:self.ke][None]
            else:
                self._stacked_px = jnp.stack(
                    [jnp.pad(s.projs[:self.ke],
                             ((0, 0), (0, n_pad - s.projs.shape[1])),
                             constant_values=big)
                     for s in self.segments])
        return self._stacked_px

    def concat_projs(self) -> jnp.ndarray | None:
        """(ke, sum n_pad_k) extra projections concatenated to match
        `concat()`'s row order, or None when ``ke == 0``."""
        if not self.ke:
            return None
        if self._concat_px is None:
            segs = self.segments
            if len(segs) == 1:
                self._concat_px = segs[0].projs[:self.ke]
            else:
                self._concat_px = jnp.concatenate(
                    [s.projs[:self.ke] for s in segs], axis=1)
        return self._concat_px

    def extend(self, new_segments: list[Segment]) -> "SegmentPack":
        """A NEW plan with ``new_segments`` appended (incremental epoch).

        The LSM append path: already-built device representations are
        extended by one concatenation each (the base's buffers are reused,
        not re-padded); representations not yet built stay lazy.  The
        receiver is never mutated — owners publish the returned pack in one
        snapshot swap.
        """
        if not new_segments:
            return self
        # build() validates block/lane uniformity over the combined list
        out = SegmentPack.build(self.segments + list(new_segments),
                                epoch=self.epoch + 1)
        if self._concat is not None:
            tail = SegmentPack.build(list(new_segments)).concat()
            xs, al, hn, ids, starts = self._concat
            out._concat = (jnp.concatenate([xs, tail[0]]),
                           jnp.concatenate([al, tail[1]]),
                           jnp.concatenate([hn, tail[2]]),
                           np.concatenate([ids, tail[3]]),
                           np.concatenate([starts,
                                           starts[-1] + tail[4][1:]]))
        if (self._stacked is not None
                and max(s.xs.shape[0] for s in new_segments) <= self.n_pad):
            tail_pack = SegmentPack.build(list(new_segments))
            txs, tal, thn, tids = tail_pack.stacked()
            pad = self.n_pad - tail_pack.n_pad
            big = np.float32(_ops.BIG)
            xs, al, hn, ids = self._stacked
            out._stacked = (
                jnp.concatenate([xs, jnp.pad(txs, ((0, 0), (0, pad),
                                                   (0, 0)))]),
                jnp.concatenate([al, jnp.pad(tal, ((0, 0), (0, pad)),
                                             constant_values=big)]),
                jnp.concatenate([hn, jnp.pad(thn, ((0, 0), (0, pad)),
                                             constant_values=big)]),
                np.concatenate([ids, np.pad(tids, ((0, 0), (0, pad)),
                                            constant_values=-1)]))
        return out

    def live_mask(self, aq: np.ndarray, r: np.ndarray,
                  pq: np.ndarray | None = None,
                  qn: np.ndarray | None = None) -> np.ndarray:
        """Vectorized `_window_may_hit` over every segment at once.

        One (S, m) float64 broadcast replaces the per-segment Python loop;
        decision-identical to the scalar test (same formula, same float64
        arithmetic), so packed and looped engines prune the same segments.
        ``pq``/``qn`` (see `_window_may_hit`) tighten the test to the k-dim
        box when the pack carries extra components.
        """
        S = self.n_segments
        if S == 0 or aq.size == 0:
            return np.zeros(S, bool)
        nonempty = self.alpha_lo <= self.alpha_hi
        amax = np.maximum(np.abs(self.alpha_lo), np.abs(self.alpha_hi))
        amax = np.where(nonempty, amax, 0.0)  # keep the slack finite
        slack = 1e-6 * ((np.abs(aq) + np.abs(r))[None, :]
                        + amax[:, None] + 1.0)
        hit = ((aq[None, :] + r[None, :] + slack >= self.alpha_lo[:, None])
               & (aq[None, :] - r[None, :] - slack <= self.alpha_hi[:, None]))
        if pq is not None and self.ke:
            kq = min(int(pq.shape[0]), self.ke)
            R = _box_interval_radius(r[None, :], qn[None, :],
                                     self.xnorm_max[:, None])  # (S, m)
            for c in range(kq):
                hit &= ((pq[c][None, :] + R >= self.proj_lo[:, c:c + 1])
                        & (pq[c][None, :] - R <= self.proj_hi[:, c:c + 1]))
        return hit.any(axis=1) & nonempty


def pack_from_index(index, *, block: int = 512, epoch: int = 0) -> SegmentPack:
    """The whole of one index as a single-segment plan."""
    return SegmentPack.build([segment_from_index(index, block=block)],
                             epoch=epoch)


def _prologue(pack: SegmentPack, qp, aqp, rp, thp, m: int, query_tile: int,
              pq, first_seg: int = 0):
    """The packed executors' shared host prologue, spanned as ``snn.prep``:
    the batch bucket's memory plan, the effective box components and which
    segments are live.  Returns (kq, pq_np, pq64, qn64, live_idx).

    `run_csr_packed` and `run_counts_packed` MUST agree on the live set
    (and on the gathers after it) — the kNN front-end validates radii
    against standalone counts and relies on the final count→compact
    execution seeing the identical predicate inputs.
    """
    with TraceAnnotation("snn.prep"):
        if pack.segments:
            pack.memory_plan(int(qp.shape[0]), query_tile)
        kq, pq_np, pq64, qn64, mask = _live_segments(pack, aqp, rp, thp, pq,
                                                     m)
        if first_seg:
            mask[:first_seg] = False
    return kq, pq_np, pq64, qn64, np.nonzero(mask)[0]


def _live_segments(pack: SegmentPack, aq, r, th, pq, m: int):
    """(kq, pq_np, pq64, qn64, mask) for the ``m`` real rows of a batch in
    any row order: the box prune's effective component count, its query
    projections in float32 and float64 and the float64 query norms (all
    None when the pack or the batch carries no extra components), and
    which segments some row's window may hit (`SegmentPack.live_mask`)."""
    kq = 0
    if pq is not None and pack.ke:
        kq = min(pack.ke, int(np.asarray(pq).shape[0]))
    pq_np = pq64 = qn64 = None
    if kq:
        pq_np = np.asarray(pq, np.float32)[:kq]
        pq64 = pq_np[:, :m].astype(np.float64)
        qn64 = _qnorm64(r, th, m)
    aq64 = np.asarray(aq, np.float64)[:m]
    r64 = np.asarray(r, np.float64)[:m]
    return kq, pq_np, pq64, qn64, pack.live_mask(aq64, r64, pq64, qn64)


def _count_cells(pack: SegmentPack, live_idx: np.ndarray, aqp, rp, m: int,
                 query_tile: int) -> tuple[int, int, int]:
    """(grid cells, live cells, fetched cells) of ONE stacked pass over
    ``live_idx``.

    The grid is (live segments x query tiles x db blocks); the live and
    fetched cells are `_live_cells` of the ``m`` real rows (padding rows,
    r = -BIG, hit no block), plus one fetched block for each tile of
    padding rows alone.
    """
    nb = pack.block_bounds()[0].shape[1]
    n_tiles = int(aqp.shape[0]) // query_tile
    live, fetched = _live_cells(pack, live_idx, np.asarray(aqp)[:m],
                                np.asarray(rp)[:m], query_tile)
    pad_tiles = n_tiles - -(-m // query_tile)
    return (int(live_idx.size) * n_tiles * nb, live,
            fetched + int(live_idx.size) * pad_tiles)


def _live_cells(pack: SegmentPack, live_idx: np.ndarray, aq: np.ndarray,
                r: np.ndarray, query_tile: int) -> tuple[int, int]:
    """(live, fetched) cells of ONE stacked pass over ``live_idx`` for the
    rows ``aq`` / ``r`` (host float32), tiled in the order given.

    A cell is live when the kernel's `_window_hit` holds: some row of the
    tile has ``aq + r >= a_lo`` and ``aq - r <= a_hi`` for the block's
    first and last alpha, in the device arrays' float32.  Alphas ascend
    within a segment, so both bounds ascend with the block and each row
    hits the contiguous run of blocks ``[searchsorted(a_hi, aq - r),
    searchsorted(a_lo, aq + r, "right"))``; a tile's live blocks are the
    union of its rows' runs.  The blocks a tile fetches span them, from
    the first run's start to the last run's end, as the kernels' clamped
    index maps fetch (`kernels.snn_query.block_ranges`); a tile whose
    rows hit nothing fetches one.  O(m log blocks) per segment.
    """
    lo_b, hi_b = pack.block_bounds()
    lo_q, hi_q = aq - r, aq + r
    nb = lo_b.shape[1]
    n_tiles = -(-aq.shape[0] // query_tile)
    live = fetched = 0
    for k in live_idx:
        first = np.searchsorted(hi_b[k], lo_q, side="left")
        last = np.searchsorted(lo_b[k], hi_q, side="right")
        rows = np.flatnonzero(first < last)
        if not rows.size:
            fetched += n_tiles
            continue
        # rows ascend, so each tile's rows with runs lie together
        tile = rows // query_tile
        starts = np.flatnonzero(np.r_[True, tile[1:] != tile[:-1]])
        fetched += int((np.maximum.reduceat(last[rows], starts)
                        - np.minimum.reduceat(first[rows], starts)).sum())
        fetched += n_tiles - starts.size
        # tile t's runs shifted by t * nb stay apart from other tiles', so
        # the union of all shifted runs sums the tiles' unions
        off = tile * nb
        s, e = first[rows] + off, last[rows] + off
        order = np.argsort(s)
        s, e = s[order], e[order]
        reach = np.maximum.accumulate(e)
        live += int(e[0] - s[0]
                    + np.maximum(e[1:] - np.maximum(s[1:], reach[:-1]),
                                 0).sum())
    return live, fetched


def _note_cells(cells: tuple[int, int, int], passes: int) -> None:
    grid, live, fetched = cells
    DISPATCH_STATS.grid_cells += passes * grid
    DISPATCH_STATS.live_cells += passes * live
    DISPATCH_STATS.fetched_cells += passes * fetched
    DISPATCH_STATS.arrival_cells += passes * live


# --------------------------------------------------------------------------- #
# Row order of a served batch                                                  #
# --------------------------------------------------------------------------- #
def indptr_from_counts(counts: np.ndarray) -> np.ndarray:
    out = np.zeros(counts.size + 1, np.int64)
    np.cumsum(counts, out=out[1:])
    return out


def permute_rows(indptr, indices, distances, dest):
    """Reorder CSR rows: input row i becomes output row ``dest[i]``.

    One O(nnz) gather; used to undo a query sort (``dest = index.order``
    for the self-join, the alpha argsort for a bichromatic join or a
    served batch) so public results are in the caller's original row order.
    """
    counts = np.diff(indptr)
    counts_out = np.empty_like(counts)
    counts_out[dest] = counts
    out_indptr = indptr_from_counts(counts_out)
    pos = np.repeat(out_indptr[:-1][dest] - indptr[:-1], counts) \
        + np.arange(indices.size)
    out_idx = np.empty_like(indices)
    out_idx[pos] = indices
    out_d = None
    if distances is not None:
        out_d = np.empty_like(distances)
        out_d[pos] = distances
    return out_indptr, out_idx, out_d


class TileOrder:
    """The order a served batch's rows run in, and its undoing.

    A served batch is tiled in the order its rows arrived.  Where each
    row's sorted window is narrow but the rows are scattered, as in a
    DBSCAN client's block of region queries at low d, the union of a
    tile's windows still covers most db blocks; in alpha order neighboring
    windows share a tile.  For a batch of more than one query tile, both
    orders' live cells of one pass are counted (`_live_cells`) over the
    segments `_prologue` will run, and the alpha order (a stable argsort)
    is taken when it saves at least one tile's worth of cells: the live
    segments' db blocks.  A one-tile batch pays one comparison.  Each
    row's results do not depend on the tile it runs in, so the rows put
    back (`csr`, `counts`) are bit-identical to the arrival order's.

    Spanned as ``snn.order`` (``rows``, ``arrival_cells``, ``live_cells``,
    ``fetched``: the cells of one pass that fetch their blocks in the
    order taken, over the batch's own tiles, and ``ordered``).  The
    executors add their live cells to ``DISPATCH_STATS.arrival_cells``
    (`_note_cells`); putting the rows back adds what the order saved in
    each pass that ran since (this thread's ``live_cells`` grew by passes
    x the sorted order's live cells).
    """

    def __init__(self, pack: SegmentPack, query_tile: int, aq: np.ndarray,
                 r: np.ndarray, th: np.ndarray, pq):
        self.perm = None            # None: the rows run as they arrived
        m = int(aq.shape[0])
        if m <= query_tile or not pack.segments:
            return
        with TraceAnnotation("snn.order", rows=m) as span:
            live_idx = np.flatnonzero(_live_segments(pack, aq, r, th, pq,
                                                     m)[-1])
            perm = np.argsort(aq, kind="stable")
            arrival, fetched = _live_cells(pack, live_idx, aq, r,
                                           query_tile)
            live, sorted_fetched = _live_cells(pack, live_idx, aq[perm],
                                               r[perm], query_tile)
            nb = pack.block_bounds()[0].shape[1]
            ordered = bool(live_idx.size) and \
                arrival - live >= live_idx.size * nb
            if ordered:
                fetched = sorted_fetched
            span.set_metadata(arrival_cells=arrival,
                              live_cells=live if ordered else arrival,
                              fetched=fetched, ordered=int(ordered))
        if ordered:
            self.perm = perm
            self._cells = (live, arrival)
            self._live0 = DISPATCH_STATS.live_cells

    def rows(self, xq, aq, r, th, pq):
        """The batch's predicate arrays in run order (``pq``: (ke, m) or
        None)."""
        p = self.perm
        if p is None:
            return xq, aq, r, th, pq
        return xq[p], aq[p], r[p], th[p], None if pq is None else pq[:, p]

    def _note_saved(self) -> None:
        live, arrival = self._cells
        ran = DISPATCH_STATS.live_cells - self._live0
        DISPATCH_STATS.arrival_cells += ran // live * (arrival - live)

    def csr(self, indptr, counts, ids, dh):
        """A run-order CSR result (`run_csr_packed`'s) in request order."""
        if self.perm is None:
            return indptr, counts, ids, dh
        self._note_saved()
        indptr, ids, dh = permute_rows(indptr, ids, dh, self.perm)
        return indptr, np.diff(indptr), ids, dh

    def counts(self, counts: np.ndarray) -> np.ndarray:
        """Run-order counts (`run_counts_packed`'s) in request order."""
        if self.perm is None:
            return counts
        self._note_saved()
        out = np.empty_like(counts)
        out[self.perm] = counts
        return out


def _placed_runs(indptr, fi, bn: int) -> int:
    """(query row, db block) runs in a pass-2 result.

    ``fi`` holds pack-flat positions (``s * n_pad + row``, n_pad a multiple
    of ``bn``), so ``fi // bn`` names the (segment, db block) of each slot.
    A run starts at every non-empty row's first slot and wherever the block
    changes inside a row.  One vectorised pass over the ``indptr[-1]``
    slots.
    """
    nnz = int(indptr[-1])
    if not nnz:
        return 0
    blk = fi[:nnz] // bn
    new = np.empty(nnz, bool)
    new[0] = True
    np.not_equal(blk[1:], blk[:-1], out=new[1:])
    starts = indptr[:-1]
    new[starts[starts < nnz]] = True
    return int(np.count_nonzero(new))


def _gather_live_concat(pack: SegmentPack, live_idx: np.ndarray,
                        with_px: bool = False):
    """(xs, alphas, half_norms, ids, sizes[, projs]) of the live segments'
    rows from the pack's ragged concat rep (zero-copy when every segment is
    live).  ``with_px`` appends the matching (ke, rows) projection slice
    (None when the pack has no extra components)."""
    xs_c, al_c, hn_c, ids_c, starts_c = pack.concat()
    px_c = pack.concat_projs() if with_px else None
    if live_idx.size == pack.n_segments:
        out = (xs_c, al_c, hn_c, ids_c, np.diff(starts_c))
        return out + (px_c,) if with_px else out
    # one device gather of the live segments' row ranges
    sizes = np.diff(starts_c)[live_idx]
    rows_sel = np.concatenate(
        [np.arange(starts_c[k], starts_c[k + 1]) for k in live_idx])
    sel = jnp.asarray(rows_sel)
    out = (xs_c[sel], al_c[sel], hn_c[sel], ids_c[rows_sel], sizes)
    if with_px:
        return out + (None if px_c is None else px_c[:, sel],)
    return out


def _gather_live_stacked(pack: SegmentPack, live_idx: np.ndarray,
                         with_px: bool = False):
    """(xs, alphas, half_norms, ids[, projs]) of the live slabs from the
    pack's stacked rep (zero-copy when every segment is live)."""
    xs, al, hn, ids = pack.stacked()
    px = pack.stacked_projs() if with_px else None
    if live_idx.size < pack.n_segments:
        sel = jnp.asarray(live_idx)
        xs, al, hn = xs[sel], al[sel], hn[sel]
        ids = ids[live_idx]
        if px is not None:
            px = px[sel]
    return (xs, al, hn, ids, px) if with_px else (xs, al, hn, ids)


def _tile_candidates(pack: SegmentPack, live_idx: np.ndarray,
                     starts_l: np.ndarray, al_np: np.ndarray,
                     t0: int, tm: int, aq64, r64, pq64, qn64) -> np.ndarray:
    """Concat-row candidate columns for the query tile ``[t0, t0 + tm)``.

    The host mirror of the kernels' conjunctive box test: per live segment,
    a diff-array union of the tile's per-query float64 intervals over the
    segment's sorted alphas (component 0), intersected with the rank-space
    interval unions of every extra component via ``proj_sorted``/
    ``proj_rank``.  Every interval is a SUPERSET of the float32 device
    predicate (`_box_interval_radius`; component 0 needs only the relative
    inflation — a correctly-rounded subtract has bounded relative error), so
    the returned columns cover every pair either pass could keep.  Ascending
    order (segments in pack order, local positions ascending) keeps the
    downstream scatter in CSR order.
    """
    aq_t = aq64[t0:t0 + tm]
    r_t = r64[t0:t0 + tm]
    R0_t = r_t * (1.0 + 1e-6) + 1e-30
    qn_t = qn64[t0:t0 + tm]
    kq = pq64.shape[0]
    out = []
    for j, k in enumerate(live_idx):
        seg = pack.segments[k]
        if seg.alpha_lo > seg.alpha_hi:
            continue
        Rb_t = _box_interval_radius(r_t, qn_t, seg.xnorm_max)
        sel = (aq_t + R0_t >= seg.alpha_lo) & (aq_t - R0_t <= seg.alpha_hi)
        for c in range(kq):
            sel &= ((pq64[c, t0:t0 + tm] + Rb_t >= seg.proj_lo[c])
                    & (pq64[c, t0:t0 + tm] - Rb_t <= seg.proj_hi[c]))
        if not sel.any():
            continue
        s0, s1 = int(starts_l[j]), int(starts_l[j + 1])
        n_loc = s1 - s0
        al_loc = al_np[s0:s1]
        # component 0: intervals directly on the sorted alphas.  Empty
        # intervals (kNN's r = -1 "done" rows) mark hi before lo and the
        # running sum never goes positive — naturally excluded.
        lo_i = np.searchsorted(al_loc, aq_t[sel] - R0_t[sel], side="left")
        hi_i = np.searchsorted(al_loc, aq_t[sel] + R0_t[sel], side="right")
        mark = np.zeros(n_loc + 1, np.int64)
        np.add.at(mark, lo_i, 1)
        np.add.at(mark, hi_i, -1)
        inmask = np.cumsum(mark[:n_loc]) > 0
        for c in range(kq):
            psc, prc = seg.proj_sorted[c], seg.proj_rank[c]
            pqc = pq64[c, t0:t0 + tm][sel]
            lo_i = np.searchsorted(psc, pqc - Rb_t[sel], side="left")
            hi_i = np.searchsorted(psc, pqc + Rb_t[sel], side="right")
            markc = np.zeros(n_loc + 1, np.int64)
            np.add.at(markc, lo_i, 1)
            np.add.at(markc, hi_i, -1)
            in_c = np.zeros(n_loc, bool)
            in_c[prc[np.cumsum(markc[:n_loc]) > 0]] = True
            inmask &= in_c
        cand_local = np.flatnonzero(inmask)
        if cand_local.size:
            out.append(s0 + cand_local)
    if not out:
        return np.zeros(0, np.int64)
    return np.concatenate(out)


def _pruned_setup(pack: SegmentPack, live_idx: np.ndarray, kq: int):
    """Shared prologue of the candidate-pruned packed oracle paths.

    Appends ONE +BIG sentinel row to the live concat arrays: power-of-two
    candidate padding points every unused slot at it, and no predicate can
    ever keep it.  The sentinel-extended device arrays depend only on the
    pack, the live-segment set and ``kq``, so they are memoized on the pack
    (an execution *plan*): repeated batches — the kNN expansion loop, graph
    chunks, serving — pay the O(N) concat once, not per launch."""
    key = (live_idx.tobytes(), kq)
    hit = pack._pruned.get(key)
    if hit is not None:
        return hit
    xs_c, al_c, hn_c, ids, sizes, px_c = _gather_live_concat(
        pack, live_idx, with_px=True)
    starts_l = np.zeros(live_idx.size + 1, np.int64)
    np.cumsum(sizes, out=starts_l[1:])
    al_np = np.asarray(al_c)
    big = np.float32(_ops.BIG)
    # host copies: the candidate gathers below run in numpy (XLA's CPU
    # gather is serial and pathological for this access pattern; fancy
    # indexing is the fast spelling) and only the gathered submatrix is
    # shipped to the jitted filter
    xs_s = np.concatenate([np.asarray(xs_c),
                           np.zeros((1, xs_c.shape[1]), np.float32)])
    al_s = np.concatenate([al_np, np.full(1, big, np.float32)])
    hn_s = np.concatenate([np.asarray(hn_c), np.full(1, big, np.float32)])
    px_s = np.concatenate([np.asarray(px_c[:kq]),
                           np.full((kq, 1), big, np.float32)], axis=1)
    # trailing zero-column trim for the compacted gather: every column past
    # the real feature width is exactly 0.0 in BOTH queries and database
    # (lane padding), and dropping trailing +0.0 terms from a float sum is
    # exact — so the compacted tiles contract d_trim lanes instead of the
    # padded 128 while staying bit-identical.  O(N x lanes) scan, memoized.
    nz = np.flatnonzero(np.any(xs_s != 0.0, axis=0))
    d_trim = int(nz[-1]) + 1 if nz.size else 1
    xs_t = np.ascontiguousarray(xs_s[:, :d_trim])
    out = (xs_s, al_s, hn_s, px_s, ids, starts_l, al_np, xs_t)
    if len(pack._pruned) >= 8:  # live sets vary per batch; bound the memos
        pack._pruned.clear()
    pack._pruned[key] = out
    return out


# Candidate-generation tile: the pruned oracle paths form PER-TILE interval
# UNIONS across the tile's queries, so a wide tile (128 alpha-sorted queries
# spanning many clusters) inflates every union toward the whole database.
# Narrow tiles keep the unions near the per-query boxes; the jitted filter
# cost is per-element, so more (smaller) launches cost only dispatch.
_PRUNED_TILE = 16


def _run_csr_packed_pruned(pack, qp, aqp, rp, thp, m, live_idx, *,
                           query_tile, pq_np, pq64, qn64, kq, mixed):
    """Packed-oracle CSR with host candidate pruning (the kq > 0 path).

    Instead of one dense (m_pad, N) filter, each query tile evaluates the
    SAME jitted filter on only the columns its k-dim box intervals can
    reach (`_tile_candidates`).  The d-length contraction per element is
    shape-independent, so every kept pair carries the identical float32
    dhalf as the dense path — output stays bit-identical while the work
    drops to the survivors of the box.  With ``mixed``, pass-1 counts come
    from the certified bf16 margin filter on the same submatrix; the
    certificate makes them equal to the f32 counts, which the scatter
    verifies at runtime.
    """
    aq64 = np.asarray(aqp, np.float64)
    r64 = np.asarray(rp, np.float64)
    pq_j = jnp.asarray(pq_np)
    xs_s, al_s, hn_s, px_s, ids, starts_l, al_np, _ = _pruned_setup(
        pack, live_idx, kq)
    L = int(live_idx.size)
    sent = int(al_np.shape[0])  # index of the appended sentinel row
    m_pad = int(qp.shape[0])
    counts_pad = np.zeros(m_pad, np.int64)
    ptile = min(query_tile, _PRUNED_TILE)
    rows_l, cols_l, dh_l = [], [], []
    for t0 in range(0, m, ptile):
        tm = min(ptile, m - t0)
        cand = _tile_candidates(pack, live_idx, starts_l, al_np, t0, tm,
                                aq64, r64, pq64, qn64)
        if cand.size == 0:
            continue
        cap_c = _ops.csr_capacity(cand.size)
        cand_p = np.full(cap_c, sent, np.int64)
        cand_p[:cand.size] = cand
        t1 = t0 + ptile
        q_t, aq_t, r_t, th_t = qp[t0:t1], aqp[t0:t1], rp[t0:t1], thp[t0:t1]
        sub = (jnp.asarray(xs_s[cand_p]), jnp.asarray(al_s[cand_p]),
               jnp.asarray(hn_s[cand_p]))
        pq_t, px_t = pq_j[:, t0:t1], jnp.asarray(px_s[:, cand_p])
        dh_t = _fetch(_oracle().snn_filter, q_t, aq_t, r_t, th_t, *sub,
                      pq_t, px_t)[:tm]
        keep_t = dh_t < _ops.BIG
        if mixed:
            cnt_t = _fetch(_oracle().snn_count, q_t, aq_t, r_t, th_t, *sub,
                           pq_t, px_t, mixed=True)[:tm]
        else:
            cnt_t = keep_t.sum(axis=1)
        counts_pad[t0:t0 + tm] = cnt_t
        tr, tc = np.nonzero(keep_t)
        rows_l.append(t0 + tr.astype(np.int64))
        cols_l.append(cand_p[tc])
        dh_l.append(dh_t[tr, tc])

    counts = counts_pad[:m]
    indptr = np.zeros(m + 1, np.int64)
    np.cumsum(counts, out=indptr[1:])
    total = int(indptr[-1])
    rows = np.concatenate(rows_l) if rows_l else np.zeros(0, np.int64)
    if total == 0 and rows.size == 0:
        return indptr, counts, np.zeros(0, np.int64), np.zeros(0, np.float32)
    if rows.size != total:  # a broken mixed certificate fails loudly
        raise RuntimeError("CSR pass-1/pass-2 disagreement (packed)")
    cols = np.concatenate(cols_l)
    dh_vals = np.concatenate(dh_l)
    seg_of = np.searchsorted(starts_l, cols, side="right") - 1
    gk = rows * np.int64(L) + seg_of
    per = np.bincount(gk, minlength=m_pad * L).reshape(m_pad, L).T
    seg_base = np.cumsum(per, axis=0) - per
    gstart = np.flatnonzero(np.r_[True, gk[1:] != gk[:-1]])
    within = np.arange(gk.size, dtype=np.int64) \
        - np.repeat(gstart, np.diff(np.r_[gstart, gk.size]))
    slots = indptr[rows] + seg_base[seg_of, rows] + within
    flat_ids, flat_dh, owned = _SCRATCH.take(total + 1)
    flat_ids[slots] = ids[cols]
    flat_dh[slots] = dh_vals
    if not (flat_ids[:total] >= 0).all():
        raise RuntimeError("CSR pass-1/pass-2 disagreement (packed)")
    if owned:
        return indptr, counts, flat_ids[:total], flat_dh[:total]
    return indptr, counts, flat_ids[:total].copy(), flat_dh[:total].copy()


def _run_counts_packed_pruned(pack, qp, aqp, rp, thp, m, live_idx, *,
                              query_tile, pq_np, pq64, qn64, kq, mixed):
    """Pass 1 only, candidate-pruned: the counts twin of
    `_run_csr_packed_pruned` (same tiles, same gathered submatrices, same
    count expressions — the counts-parity contract)."""
    aq64 = np.asarray(aqp, np.float64)
    r64 = np.asarray(rp, np.float64)
    pq_j = jnp.asarray(pq_np)
    xs_s, al_s, hn_s, px_s, _, starts_l, al_np, _ = _pruned_setup(
        pack, live_idx, kq)
    sent = int(al_np.shape[0])
    counts = np.zeros(m, np.int64)
    ptile = min(query_tile, _PRUNED_TILE)
    for t0 in range(0, m, ptile):
        tm = min(ptile, m - t0)
        cand = _tile_candidates(pack, live_idx, starts_l, al_np, t0, tm,
                                aq64, r64, pq64, qn64)
        if cand.size == 0:
            continue
        cap_c = _ops.csr_capacity(cand.size)
        cand_p = np.full(cap_c, sent, np.int64)
        cand_p[:cand.size] = cand
        t1 = t0 + ptile
        counts[t0:t0 + tm] = _fetch(
            _oracle().snn_count,
            qp[t0:t1], aqp[t0:t1], rp[t0:t1], thp[t0:t1],
            jnp.asarray(xs_s[cand_p]), jnp.asarray(al_s[cand_p]),
            jnp.asarray(hn_s[cand_p]),
            pq_j[:, t0:t1], jnp.asarray(px_s[:, cand_p]),
            mixed=mixed)[:tm]
    return counts


def _compacted_candidate_tiles(pack, live_idx, starts_l, al_np, m, ptile,
                               aq64, r64, pq64, qn64, sent):
    """Every query tile's candidate matrix at once: (T, ccap) int64.

    Rows are `_tile_candidates` outputs (ascending concat positions — the
    CSR order), padded to a shared power-of-two capacity with the sentinel
    row index so one static tile shape serves the whole batch.  Returns
    ``(cand_p, T, ccap)``; ``cand_p`` is None when no tile has candidates.
    """
    T = (m + ptile - 1) // ptile
    cands = []
    cmax = 0
    for t in range(T):
        t0 = t * ptile
        tm = min(ptile, m - t0)
        c = _tile_candidates(pack, live_idx, starts_l, al_np, t0, tm,
                             aq64, r64, pq64, qn64)
        cands.append(c)
        cmax = max(cmax, int(c.size))
    if cmax == 0:
        return None, T, 0
    ccap = _ops.csr_capacity(cmax)  # power-of-two: O(log) compiled shapes
    cand_p = np.full((T, ccap), sent, np.int64)
    for t, c in enumerate(cands):
        cand_p[t, :c.size] = c
    return cand_p, T, ccap


def _compacted_query_tiles(qp, aqp, rp, thp, pq_np, kq, T, ptile, d_trim):
    """Device-side reshapes of the padded query operands into (T, ptile)
    tiles (and the feature trim — trailing zero columns contribute exact
    +0.0 terms, so trimming them is bit-exact)."""
    mt = T * ptile
    qt = qp[:mt, :d_trim].reshape(T, ptile, d_trim)
    aqt = aqp[:mt].reshape(T, ptile)
    rt = rp[:mt].reshape(T, ptile)
    tht = thp[:mt].reshape(T, ptile)
    pqt = jnp.asarray(pq_np)[:, :mt].reshape(kq, T, ptile)
    return qt, aqt, rt, tht, pqt


def _run_csr_packed_compacted(pack, qp, aqp, rp, thp, m, live_idx, *,
                              query_tile, pq_np, pq64, qn64, kq, mixed):
    """Packed-oracle CSR with candidate COMPACTION: pruning as skipped FLOPs.

    The successor of `_run_csr_packed_pruned` (kept as the ``compacted=False``
    escape hatch): the same host candidate generation, but all query tiles'
    surviving rows are gathered into one dense (T, ptile, ccap) tile batch
    and evaluated by a SINGLE batched launch (`snn_filter_tiles`) — 1 kernel
    launch + 1 host transfer per packed query instead of one pair per tile,
    and the distance GEMM only touches gathered candidate rows.  Output is
    bit-identical to the dense and masked-prune paths: the batched
    contraction reduces the same d-length vectors per kept pair
    (`kernels.ref._tiles_body`), and the scatter uses the same slot formula.
    """
    aq64 = np.asarray(aqp, np.float64)
    r64 = np.asarray(rp, np.float64)
    xs_s, al_s, hn_s, px_s, ids, starts_l, al_np, xs_t = _pruned_setup(
        pack, live_idx, kq)
    L = int(live_idx.size)
    sent = int(al_np.shape[0])
    m_pad = int(qp.shape[0])
    ptile = min(query_tile, _PRUNED_TILE)
    cand_p, T, ccap = _compacted_candidate_tiles(
        pack, live_idx, starts_l, al_np, m, ptile, aq64, r64, pq64, qn64,
        sent)
    counts = np.zeros(m, np.int64)
    indptr = np.zeros(m + 1, np.int64)
    if cand_p is None:
        return indptr, counts, np.zeros(0, np.int64), np.zeros(0, np.float32)
    qt, aqt, rt, tht, pqt = _compacted_query_tiles(
        qp, aqp, rp, thp, pq_np, kq, T, ptile, xs_t.shape[1])
    # host gathers (numpy fancy indexing — the fast spelling; XLA's CPU
    # gather is pathological for this access pattern), shipped once
    xt = jnp.asarray(xs_t[cand_p])
    alt = jnp.asarray(al_s[cand_p])
    hnt = jnp.asarray(hn_s[cand_p])
    pxt = jnp.asarray(px_s[:, cand_p])
    dh_t = _fetch(_oracle().snn_filter_tiles, qt, aqt, rt, tht,
                  xt, alt, hnt, pqt, pxt)
    keep_t = dh_t < _ops.BIG
    if mixed:
        cnt_t = _fetch(_oracle().snn_count_tiles,
                       qt, aqt, rt, tht, xt, alt, hnt, pqt, pxt, mixed=True)
    else:
        cnt_t = keep_t.sum(axis=2)
    counts[:] = cnt_t.reshape(T * ptile)[:m]
    np.cumsum(counts, out=indptr[1:])
    total = int(indptr[-1])
    # np.nonzero is row-major: per query ascending candidate slots, i.e.
    # ascending concat positions — the CSR order
    tt, pp, cc = np.nonzero(keep_t)
    rows = (tt.astype(np.int64) * ptile + pp)
    if total == 0 and rows.size == 0:
        return indptr, counts, np.zeros(0, np.int64), np.zeros(0, np.float32)
    if rows.size != total:  # a broken mixed certificate fails loudly
        raise RuntimeError("CSR pass-1/pass-2 disagreement (packed)")
    cols = cand_p[tt, cc]
    dh_vals = dh_t[tt, pp, cc]
    seg_of = np.searchsorted(starts_l, cols, side="right") - 1
    gk = rows * np.int64(L) + seg_of
    per = np.bincount(gk, minlength=m_pad * L).reshape(m_pad, L).T
    seg_base = np.cumsum(per, axis=0) - per
    gstart = np.flatnonzero(np.r_[True, gk[1:] != gk[:-1]])
    within = np.arange(gk.size, dtype=np.int64) \
        - np.repeat(gstart, np.diff(np.r_[gstart, gk.size]))
    slots = indptr[rows] + seg_base[seg_of, rows] + within
    flat_ids, flat_dh, owned = _SCRATCH.take(total + 1)
    flat_ids[slots] = ids[cols]
    flat_dh[slots] = dh_vals
    if not (flat_ids[:total] >= 0).all():
        raise RuntimeError("CSR pass-1/pass-2 disagreement (packed)")
    if owned:
        return indptr, counts, flat_ids[:total], flat_dh[:total]
    return indptr, counts, flat_ids[:total].copy(), flat_dh[:total].copy()


def _run_counts_packed_compacted(pack, qp, aqp, rp, thp, m, live_idx, *,
                                 query_tile, pq_np, pq64, qn64, kq, mixed):
    """Pass 1 only, candidate-compacted: ONE batched tile count launch
    (the counts twin of `_run_csr_packed_compacted` — same candidate tiles,
    same gathered payload, same count expressions)."""
    aq64 = np.asarray(aqp, np.float64)
    r64 = np.asarray(rp, np.float64)
    xs_s, al_s, hn_s, px_s, _, starts_l, al_np, xs_t = _pruned_setup(
        pack, live_idx, kq)
    sent = int(al_np.shape[0])
    ptile = min(query_tile, _PRUNED_TILE)
    cand_p, T, ccap = _compacted_candidate_tiles(
        pack, live_idx, starts_l, al_np, m, ptile, aq64, r64, pq64, qn64,
        sent)
    if cand_p is None:
        return np.zeros(m, np.int64)
    qt, aqt, rt, tht, pqt = _compacted_query_tiles(
        qp, aqp, rp, thp, pq_np, kq, T, ptile, xs_t.shape[1])
    xt = jnp.asarray(xs_t[cand_p])
    alt = jnp.asarray(al_s[cand_p])
    hnt = jnp.asarray(hn_s[cand_p])
    pxt = jnp.asarray(px_s[:, cand_p])
    cnt_t = _fetch(_oracle().snn_count_tiles,
                   qt, aqt, rt, tht, xt, alt, hnt, pqt, pxt, mixed=mixed)
    return cnt_t.reshape(T * ptile)[:m].astype(np.int64)


def run_csr_packed(
    pack: SegmentPack,
    qp, aqp, rp, thp,
    m: int,
    *,
    query_tile: int = 128,
    use_pallas: bool | str | None = None,
    first_seg: int = 0,
    memory_budget_mb: float | None = None,
    pq=None,
    mixed: bool = False,
    compacted: bool | None = None,
    fused: bool = True,
):
    """Execute a `SegmentPack` plan: the two passes as single launches.

    Same contract and bit-identical output as `run_csr` over
    ``pack.segments`` — but the prune is one vectorized bitmask and each
    pass is ONE launch, however many segments are live:

    * **Pallas** (TPU): pass 1 is one stacked-grid count launch over (live
      segments x query tiles x db blocks) on the pack's `stacked()` rep;
      the prefix sums (global ``indptr`` + segment-axis exclusive write
      bases) run on device (``jnp.cumsum``); pass 2 is one stacked
      compaction launch.  One small pass-boundary transfer (the row
      offsets — the total must reach the host because it sizes the flat
      output) plus the final CSR-triple transfer.
    * **Oracle** (CPU): one dense-filter evaluation over the pack's ragged
      `concat()` rep feeds BOTH passes; counts, prefix sums and the
      scatter are vectorized numpy over the whole stack (host and device
      are the same memory on CPU, the filter view is zero-copy, and XLA's
      serial CPU scatter is pathological — numpy fancy indexing is the
      fast spelling of the identical slot formula).

    Args:
      first_seg: ignore segments before this pack position (the triangular
        schedule of `core.graph`'s symmetric self-join).
      memory_budget_mb: oracle-path ceiling.  The packed oracle holds ONE
        dense (m_pad, live rows) filter for both passes; when that would
        exceed the budget, execution falls back to the looped `run_csr`
        (budgeted, cache-releasing) over the live segments.

    Flat totals are int32 on the Pallas path (~2^31 pair ceiling); use the
    looped engine for result sets beyond that.

    ``pq`` ((kq, m_pad) padded extra query projections) and ``mixed`` are
    the packed twins of `run_csr`'s: the prune tightens to the k-dim box
    and — on the oracle path — the dense filter is replaced by per-tile
    candidate gathers (`_run_csr_packed_pruned`), with identical output.
    ``use_pallas`` is a backend selector (`kernels.registry.resolve`).
    """
    backend = _registry.resolve(use_pallas)
    kq, pq_np, pq64, qn64, live_idx = _prologue(
        pack, qp, aqp, rp, thp, m, query_tile, pq, first_seg)
    indptr0 = np.zeros(m + 1, np.int64)
    if live_idx.size == 0:
        return (indptr0, np.zeros(m, np.int64), np.zeros(0, np.int64),
                np.zeros(0, np.float32))
    L = int(live_idx.size)

    if backend.device:
        return _execute_stacked(pack, qp, aqp, rp, thp, m, live_idx,
                                query_tile=query_tile,
                                pq=None if not kq else jnp.asarray(pq_np),
                                mixed=mixed, backend=backend, fused=fused)
    if kq:
        if memory_budget_mb is not None:
            rows_all = int(sum(pack.segments[k].xs.shape[0]
                               for k in live_idx))
            # conservative: the pruned path's largest possible tile gather
            if query_tile * (rows_all + 1) * 4 > memory_budget_mb * 2**20:
                return run_csr([pack.segments[k] for k in live_idx],
                               qp, aqp, rp, thp, m, query_tile=query_tile,
                               use_pallas=backend,
                               memory_budget_mb=memory_budget_mb,
                               pq=jnp.asarray(pq_np), mixed=mixed)
        # compacted (default): ONE batched candidate-tile launch; the
        # escape hatch (compacted=False) keeps the per-tile masked prune
        if compacted is None or compacted:
            return _run_csr_packed_compacted(
                pack, qp, aqp, rp, thp, m, live_idx, query_tile=query_tile,
                pq_np=pq_np, pq64=pq64, qn64=qn64, kq=kq, mixed=mixed)
        return _run_csr_packed_pruned(pack, qp, aqp, rp, thp, m, live_idx,
                                      query_tile=query_tile, pq_np=pq_np,
                                      pq64=pq64, qn64=qn64, kq=kq,
                                      mixed=mixed)
    xs_c, al_c, hn_c, ids, sizes = _gather_live_concat(pack, live_idx)
    n_live_rows = int(sizes.sum())
    if memory_budget_mb is not None \
            and qp.shape[0] * n_live_rows * 4 > memory_budget_mb * 2**20:
        return run_csr([pack.segments[k] for k in live_idx],
                       qp, aqp, rp, thp, m, query_tile=query_tile,
                       use_pallas=backend, memory_budget_mb=memory_budget_mb)

    # ---- pass 1: ONE filter launch over the ragged concatenation ---------
    # evaluated once and reused for the compaction — counts and scatter
    # cannot disagree
    dh_np = _fetch(backend.snn_filter,
                   qp, aqp, rp, thp, xs_c, al_c, hn_c)  # zero-copy on CPU
    keep = dh_np < _ops.BIG

    # ---- prefix sums (vectorized; host == device memory on CPU) ----------
    # One pass over the survivor coordinates yields the per-(query, segment)
    # count matrix in O(nnz): np.nonzero is row-major, so survivors arrive
    # per query row in ascending (segment, local row) order — the CSR order.
    starts_l = np.zeros(L + 1, np.int64)
    np.cumsum(sizes, out=starts_l[1:])
    rows, cols = np.nonzero(keep)
    seg_of = np.searchsorted(starts_l, cols, side="right") - 1
    gk = rows * np.int64(L) + seg_of      # non-decreasing in nonzero order
    per = np.bincount(gk, minlength=keep.shape[0] * L) \
        .reshape(keep.shape[0], L).T      # (L, m_pad)
    counts = per[:, :m].sum(axis=0)
    indptr = np.zeros(m + 1, np.int64)
    np.cumsum(counts, out=indptr[1:])
    total = int(indptr[-1])
    if total == 0:
        return indptr, counts, np.zeros(0, np.int64), np.zeros(0, np.float32)
    seg_base = np.cumsum(per, axis=0) - per  # exclusive prefix over segments

    # ---- pass 2: ONE vectorized scatter over the whole stack -------------
    # an O(nnz) group-rank replaces a dense per-cell cumsum
    gstart = np.flatnonzero(np.r_[True, gk[1:] != gk[:-1]])
    within = np.arange(gk.size, dtype=np.int64) \
        - np.repeat(gstart, np.diff(np.r_[gstart, gk.size]))
    slots = indptr[rows] + seg_base[seg_of, rows] + within
    flat_ids, flat_dh, owned = _SCRATCH.take(total + 1)
    flat_ids[slots] = ids[cols]
    flat_dh[slots] = dh_np[rows, cols]
    if not (flat_ids[:total] >= 0).all():
        raise RuntimeError("CSR pass-1/pass-2 disagreement (packed)")
    if owned:  # one-off arrays: the trimmed views are the caller's already
        return indptr, counts, flat_ids[:total], flat_dh[:total]
    return indptr, counts, flat_ids[:total].copy(), flat_dh[:total].copy()


def run_counts_packed(
    pack: SegmentPack,
    qp, aqp, rp, thp,
    m: int,
    *,
    query_tile: int = 128,
    use_pallas: bool | str | None = None,
    memory_budget_mb: float | None = None,
    pq=None,
    mixed: bool = False,
    compacted: bool | None = None,
) -> np.ndarray:
    """Pass 1 ONLY: per-query survivor counts (m,) int64 over a plan.

    The count phase of `run_csr_packed` as a standalone launch — what
    iterative radius searches need (the kNN front-end's expansion loop only
    learns whether each query's ball holds enough points, and defers the
    compaction until every radius has converged).  Evaluates the identical
    predicate pipeline as `run_csr_packed`'s pass 1 on the same inputs: a
    per-query radius vector whose counts satisfy a caller here yields the
    exact same counts inside the final count→compact execution.  That
    contract extends to ``pq``/``mixed``: the same tiles, gathers and count
    expressions run here as in pass 1 there.  ``use_pallas`` is a backend
    selector (`kernels.registry.resolve`).
    """
    backend = _registry.resolve(use_pallas)
    kq, pq_np, pq64, qn64, live_idx = _prologue(
        pack, qp, aqp, rp, thp, m, query_tile, pq)
    if live_idx.size == 0:
        return np.zeros(m, np.int64)

    if backend.device:
        with TraceAnnotation("snn.prep"):
            xs, al, hn, _, px = _gather_live_stacked(pack, live_idx,
                                                     with_px=True)
            pq_j = None
            if kq:
                pq_j = jnp.asarray(pq_np)
                if px.shape[1] != kq:
                    px = px[:, :kq]
            else:
                px = None
            _note_cells(_count_cells(pack, live_idx, aqp, rp, m, query_tile),
                        1)
        with _Launch("snn_count_stacked"):
            per = backend.snn_count_stacked(qp, aqp, rp, thp, xs, al, hn,
                                            pq_j, px, tq=query_tile,
                                            bn=pack.block, mixed=mixed)
        return _to_host(per).sum(axis=0)[:m].astype(np.int64)

    if kq:
        if compacted is None or compacted:
            return _run_counts_packed_compacted(
                pack, qp, aqp, rp, thp, m, live_idx, query_tile=query_tile,
                pq_np=pq_np, pq64=pq64, qn64=qn64, kq=kq, mixed=mixed)
        return _run_counts_packed_pruned(pack, qp, aqp, rp, thp, m, live_idx,
                                         query_tile=query_tile, pq_np=pq_np,
                                         pq64=pq64, qn64=qn64, kq=kq,
                                         mixed=mixed)
    xs_c, al_c, hn_c, _, sizes = _gather_live_concat(pack, live_idx)
    n_live_rows = int(sizes.sum())
    if memory_budget_mb is not None \
            and qp.shape[0] * n_live_rows * 4 > memory_budget_mb * 2**20:
        # per-segment loop bounds the transient dense filter to one segment
        counts = np.zeros(m, np.int64)
        for k in live_idx:
            seg = pack.segments[k]
            counts += _fetch(
                backend.snn_count,
                qp, aqp, rp, thp, seg.xs, seg.alphas, seg.half_norms,
                tq=query_tile, bn=seg.block, mixed=mixed)[:m]
        return counts
    if mixed:
        return _fetch(backend.snn_count, qp, aqp, rp, thp, xs_c, al_c, hn_c,
                      mixed=True)[:m].astype(np.int64)
    dh = _fetch(backend.snn_filter, qp, aqp, rp, thp, xs_c, al_c, hn_c)[:m]
    return (dh < _ops.BIG).sum(axis=1).astype(np.int64)


def _execute_stacked(pack: SegmentPack, qp, aqp, rp, thp, m: int,
                     live_idx: np.ndarray, *, query_tile: int,
                     pq=None, mixed: bool = False, backend=None,
                     fused: bool = True):
    """The device executor of `run_csr_packed`: stacked-grid kernels with
    on-device prefix sums (see `run_csr_packed` docstring).  ``pq`` arrives
    already sliced to the effective component count; the matching stacked
    projections are gathered here.  ``mixed`` applies to pass 1 only —
    pass 2 always verifies in f32.  ``backend`` is the resolved device lane
    (default: the historical pallas-tpu kernels).

    With ``fused`` (the default) a capacity-speculation fast path runs:
    once a batch shape has executed classically, its nnz capacity is
    recorded on the pack (`SegmentPack._spec`) and subsequent batches chain
    count → device prefix → compact in ONE dispatch
    (`Backend.snn_csr_fused_stacked`) whose whole result tuple comes back
    as ONE host materialization — no pass-boundary sync.  When a batch
    overflows the speculated capacity the device reports it in the same
    tuple (no extra transfer), the classical two-dispatch path re-runs with
    exact sizes, and the recorded capacity ratchets up (power-of-two
    bucketed, so it converges after O(log nnz) misses)."""
    if backend is None:
        backend = _registry.get_backend("pallas-tpu")
    with TraceAnnotation("snn.prep"):
        xs, al, hn, ids, px = _gather_live_stacked(pack, live_idx,
                                                   with_px=True)
        kq = 0 if pq is None else int(pq.shape[0])
        if kq:
            if px.shape[1] != kq:
                px = px[:, :kq]
        else:
            px = None
        spec = pack._spec.setdefault(
            (int(qp.shape[0]), int(query_tile), live_idx.tobytes(), kq), {})
        nnz_spec = spec.get("nnz_cap", 0)
        if not nnz_spec:
            # a fresh live-set key opens at the predecessor plan's
            # ratcheted capacity (adopt_spec) instead of falling back to
            # the classic path
            nnz_spec = pack._spec_hint.get(
                (int(qp.shape[0]), int(query_tile), kq), 0)
        cells = _count_cells(pack, live_idx, aqp, rp, m, query_tile)

    # ---- speculative fused single-dispatch fast path ---------------------
    if fused and nnz_spec:
        with _Launch("snn_csr_fused_stacked"):
            out = backend.snn_csr_fused_stacked(
                qp, aqp, rp, thp, xs, al, hn, pq, px,
                nnz_cap=nnz_spec, tq=query_tile, bn=pack.block, mixed=mixed)
        # the fused result tuple materializes in one device_get
        indptr_pad, fi, fd, total_spec = _to_host(out)
        total = int(indptr_pad[m])
        spec["nnz_cap"] = max(nnz_spec, _ops.csr_capacity(total))
        fits = int(total_spec) + 1 <= nnz_spec
        _note_cells(cells, 2 if fits else 1)  # the compact ran if it fit
        if fits:
            DISPATCH_STATS.placed_runs += _placed_runs(indptr_pad, fi,
                                                       pack.block)
        if total + 1 <= nnz_spec and int(total_spec) == int(indptr_pad[-1]):
            with TraceAnnotation("snn.assemble"):
                indptr = indptr_pad[:m + 1].astype(np.int64)
                counts = np.diff(indptr)
                if total == 0:
                    return (indptr, counts, np.zeros(0, np.int64),
                            np.zeros(0, np.float32))
                fi = fi[:total]
                if not (fi >= 0).all():
                    raise RuntimeError(
                        "CSR pass-1/pass-2 disagreement (packed)")
                return (indptr, counts, ids.reshape(-1)[fi],
                        np.ascontiguousarray(fd[:total]))
        # speculation overflow: fall through to the exact-sized classic path

    # ---- pass 1: ONE stacked count launch --------------------------------
    with _Launch("snn_count_stacked"):
        per = backend.snn_count_stacked(qp, aqp, rp, thp, xs, al, hn, pq, px,
                                        tq=query_tile, bn=pack.block,
                                        mixed=mixed)

    # ---- device prefix sums + the one pass-boundary sync -----------------
    with _Launch("stacked_prefix"):
        _, indptr_dev, offsets_dev = _ref.stacked_prefix(per)
    indptr_pad = _to_host(indptr_dev)  # (m_pad + 1,) int32
    total = int(indptr_pad[m])
    # seed/ratchet the speculation capacity for the next batch of this shape
    spec["nnz_cap"] = max(spec.get("nnz_cap", 0), _ops.csr_capacity(total))
    indptr = indptr_pad[:m + 1].astype(np.int64)
    counts = np.diff(indptr)
    if total == 0:
        _note_cells(cells, 1)
        return indptr, counts, np.zeros(0, np.int64), np.zeros(0, np.float32)

    # ---- pass 2: ONE stacked compaction launch ---------------------------
    cap = _ops.csr_capacity(total)
    _note_cells(cells, 2)
    with _Launch("snn_compact_stacked"):
        fi, fd = backend.snn_compact_stacked(
            qp, aqp, rp, thp, offsets_dev, xs, al, hn, pq, px,
            nnz=cap, tq=query_tile, bn=pack.block)
    fi = _to_host(fi)[:total]
    DISPATCH_STATS.placed_runs += _placed_runs(indptr, fi, pack.block)
    if not (fi >= 0).all():
        raise RuntimeError("CSR pass-1/pass-2 disagreement (packed)")
    flat_dh = _to_host(fd)[:total].copy()
    with TraceAnnotation("snn.assemble"):
        flat_ids = ids.reshape(-1)[fi]
    return indptr, counts, flat_ids, flat_dh


def query_csr(
    index,
    segments: list[Segment],
    q: np.ndarray,
    radius,
    return_distance: bool = True,
    *,
    query_tile: int = 128,
    use_pallas: bool | str | None = None,
    native: bool = True,
    mixed: bool = False,
    bucket: bool = False,
):
    """Full CSR query over ``segments``: predicates from ``index`` (the owner
    of mu/v1/metric/xi), then `run_csr`, then distance finalization.

    ``radius`` is a scalar or a per-query (m,) vector in the native metric
    (`snn.prepare_queries`).  This is the single entry every front-end
    (single-device, sharded, streaming, serving) routes through.  Extra
    query projections (the k-dim box prune) are derived from ``index`` when
    it carries a multi-component basis; ``mixed`` opts pass 1 into the
    certified bf16 margin filter.  ``bucket`` pads the batch to the
    geometric query-bucket ladder (`kernels.ops.bucket_rows`) so varying
    batch sizes reuse O(log m) compiled shapes.  All three leave results
    bit-identical.
    """
    from . import snn as _snn  # deferred: snn imports this module lazily too

    xq, aq, r, th, qsq = _snn.prepare_query_predicates(index, q, radius)
    m = xq.shape[0]
    qp, aqp, rp, thp, _ = _ops.pad_queries(xq, aq, r, th, tq=query_tile,
                                           bucket=bucket)
    pq = _snn.query_extra_projections(index, xq)
    pqp = None if pq is None else _ops.pad_components(pq, qp.shape[0])
    indptr, counts, ids, dh = run_csr(segments, qp, aqp, rp, thp, m,
                                      query_tile=query_tile,
                                      use_pallas=use_pallas,
                                      pq=pqp, mixed=mixed)
    return _snn.csr_finalize(index, indptr, ids, dh, xq, qsq, counts,
                             return_distance, native)


def query_csr_packed(
    index,
    pack: SegmentPack,
    q: np.ndarray,
    radius,
    return_distance: bool = True,
    *,
    query_tile: int = 128,
    use_pallas: bool | str | None = None,
    native: bool = True,
    memory_budget_mb: float | None = None,
    mixed: bool = False,
    bucket: bool = False,
    compacted: bool | None = None,
    fused: bool = True,
):
    """`query_csr` executed through a prebuilt `SegmentPack` plan.

    The packed twin of `query_csr`: predicates from ``index`` (the owner of
    mu/v1/metric/xi), then `run_csr_packed`, then distance finalization.
    Front-ends that own a long-lived index (streaming snapshots, serving
    generations, graph builds) build the pack once per epoch and route every
    query batch through here.  ``mixed``, ``bucket`` and the index-derived
    box projections behave as in `query_csr`.  A batch of several query
    tiles may run in alpha order (`TileOrder`); its rows come back in the
    caller's order.
    """
    from . import snn as _snn  # deferred: snn imports this module lazily too

    with TraceAnnotation("snn.prep"):
        xq, aq, r, th, qsq = _snn.prepare_query_predicates(index, q, radius)
        m = xq.shape[0]
        pq = _snn.query_extra_projections(index, xq)
        order = TileOrder(pack, query_tile, aq, r, th, pq)
        xo, aq, r, th, pq = order.rows(xq, aq, r, th, pq)
        qp, aqp, rp, thp, _ = _ops.pad_queries(xo, aq, r, th, tq=query_tile,
                                               bucket=bucket)
        pqp = None if pq is None else _ops.pad_components(pq, qp.shape[0])
    indptr, counts, ids, dh = run_csr_packed(
        pack, qp, aqp, rp, thp, m, query_tile=query_tile,
        use_pallas=use_pallas, memory_budget_mb=memory_budget_mb,
        pq=pqp, mixed=mixed, compacted=compacted, fused=fused)
    with TraceAnnotation("snn.assemble"):
        indptr, counts, ids, dh = order.csr(indptr, counts, ids, dh)
        return _snn.csr_finalize(index, indptr, ids, dh, xq, qsq, counts,
                                 return_distance, native)


# --------------------------------------------------------------------------- #
# Plan warming (double-buffered epochs)                                        #
# --------------------------------------------------------------------------- #
def warm_plan(
    pack: SegmentPack,
    *,
    m_pads=(128,),
    query_tile: int = 128,
    use_pallas: bool | str | None = None,
    mixed: bool = False,
    compacted: bool | None = None,
    fused: bool = True,
    spec_from: SegmentPack | None = None,
) -> SegmentPack:
    """Prime a plan so its FIRST real query costs steady-state work.

    The double-buffered epoch hook: a mutator (append/rebuild) builds the
    next generation's pack and calls this on its own thread BEFORE the
    atomic publish, so the serving thread never pays the warmup.  For each
    bucketed batch size in ``m_pads`` one zero-match priming dispatch runs
    through `run_csr_packed`: one synthetic query row per segment sits at
    that segment's ``alpha_lo`` with radius 0 (every segment live, so the
    full stacked/concat representation materializes on device and the real
    launch signatures compile) while the half-norm threshold is the
    match-nothing sentinel ``-BIG`` (the predicate keeps no rows, so the
    priming output is empty and free).  Builds + reserves the static
    `MemoryPlan` per bucket, and — via ``spec_from`` → `adopt_spec` — seeds
    the fused-dispatch capacity speculation from the predecessor plan so
    the first post-swap batch runs the one-dispatch fast path instead of
    re-ratcheting.

    Warming never changes any query result.  It runs the same kernels a
    query would, so a failure here is raised to the caller: a plan that
    cannot warm could not answer either.
    """
    with TraceAnnotation("snn.plan", generation=pack.epoch):
        if spec_from is not None:
            pack.adopt_spec(spec_from)
        S = pack.n_segments
        if S == 0 or pack.n_pad == 0:
            return pack
        d_pad = int(pack.segments[0].xs.shape[1])
        nonempty = pack.alpha_lo <= pack.alpha_hi
        aq_seg = np.where(nonempty, pack.alpha_lo, 0.0).astype(np.float32)
        pq_seg = None
        if pack.ke:
            # one box-prune operand per segment too, so the pruned/compacted
            # oracle executors and the kernels' pq plumbing warm as well
            pq_seg = np.where(nonempty[:, None],
                              np.asarray(pack.proj_lo, np.float64),
                              0.0).astype(np.float32)  # (S, ke)
        for m_pad in sorted({int(b) for b in m_pads if int(b) > 0}):
            # cycle the per-segment rows to fill the bucket
            reps = -(-m_pad // S)
            aq = np.tile(aq_seg, reps)[:m_pad]
            qp = jnp.asarray(np.zeros((m_pad, d_pad), np.float32))
            rp = jnp.asarray(np.zeros(m_pad, np.float32))
            thp = jnp.asarray(np.full(m_pad, -_ops.BIG, np.float32))
            pq = None
            if pq_seg is not None:
                pq = np.tile(pq_seg, (reps, 1))[:m_pad].T  # (ke, m_pad)
            pack.memory_plan(m_pad, query_tile).reserve()
            run_csr_packed(pack, qp, jnp.asarray(aq), rp, thp, m_pad,
                           query_tile=query_tile, use_pallas=use_pallas,
                           pq=pq, mixed=mixed, compacted=compacted,
                           fused=fused)
    return pack
