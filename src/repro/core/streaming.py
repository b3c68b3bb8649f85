"""Streaming (LSM-style) SNN index: sublinear appends, exact queries.

`SNNServer.rebuild`-style online updates used to re-center, re-run power
iteration and re-sort the *entire* database per append.  This module keeps
the paper's exactness while making appends O(b log b + segments) for a
b-point batch:

* the **base** index is a normal `snn.SNNIndex`;
* an `append` projects the new points onto the base's *frozen* ``mu``/``v1``
  and sorts only the batch, producing a small **delta** segment (itself an
  `SNNIndex` sharing mu/v1/metric/xi, with `order` holding global row ids);
* queries run the identical predicate pipeline across base + deltas through
  `core.engine` (one count → prefix-sum → compact orchestration), so results
  are exact and bit-identical *as neighbor sets* to a fresh index over the
  concatenated data;
* a size-ratio trigger merge-sorts the deltas into the base — a vectorized
  two-pointer merge of already-sorted runs (two `searchsorted` calls + one
  scatter, O(n + b log n)), no re-sort, no power iteration;
* only when the database outgrows ``rebuild_ratio`` × its size at the last
  full build does a real `build_index` run (fresh mu/v1/xi).

Why frozen mu/v1 stays exact: the Cauchy–Schwarz window argument
(`snn._window`, docs/architecture.md) holds for ANY fixed direction with
``||v1|| <= 1`` and any fixed centering — accuracy of v1 only *tightens* the
window, never the correctness.  The one genuinely global statistic is the
mips lift's xi (max raw norm): appends that exceed it invalidate the lift,
so they trigger an immediate full re-index.

Thread-safety: writers (append/rebuild) serialize on a mutation lock and do
all heavy work — batch transform/sort, delta merges, even full re-indexes —
*outside* the short state lock, publishing an immutable ``(parts, segments,
plan)`` snapshot tuple in one locked swap.  Queries read one snapshot and
never observe a half-applied append, and they never wait on index
construction: no serving gap even across a full rebuild.

The ``plan`` is the engine's device-resident `SegmentPack` (stacked
segments, see `core.engine`): built lazily on first query, *extended* by one
slab concatenation on each delta append (an incremental pack epoch — the
base's device stack is reused, not rebuilt), and invalidated (None) by
merges and rebuilds, whose next query builds a fresh epoch.  Packed queries
run one stacked launch per pass over base + all deltas instead of one
launch (plus host sync) per segment.
"""
from __future__ import annotations

import threading

import numpy as np
from jax.profiler import TraceAnnotation

from . import engine as _engine
from . import metrics as _metrics
# direct module-path import: the package-level `join` export is the function
from .join import query_counts as _join_query_counts
from .join import single_query as _join_single_query
from . import snn as _snn


def _as_batch(a: np.ndarray, d: int | None = None) -> np.ndarray:
    """Normalize seed/append input to (b, d) rows.

    A 1-D ``(k,)`` array is one point; a 1-D *empty* array is zero points —
    of width ``d`` when a width is already known, else width 0, which marks
    "no width committed yet" (``np.atleast_2d`` used to turn ``(0,)`` into
    ``(1, 0)``, poisoning ``d`` so the first real append was rejected).
    """
    if a.ndim == 1:
        a = a.reshape(1, -1) if a.size else a.reshape(0, d or 0)
    if a.ndim != 2:
        raise ValueError(f"expected (b, d) or (d,) points, got shape {a.shape}")
    return a


def merge_sorted_indexes(a: _snn.SNNIndex, b: _snn.SNNIndex) -> _snn.SNNIndex:
    """Stable merge of two alpha-sorted runs sharing mu/v1/metric/xi.

    O(n) scatter after two binary-search passes; ``a``'s rows precede equal-
    alpha rows of ``b`` (append order, matching a stable re-sort).
    """
    na, nb = a.n, b.n
    pos_a = np.arange(na) + np.searchsorted(b.alphas, a.alphas, side="left")
    pos_b = np.arange(nb) + np.searchsorted(a.alphas, b.alphas, side="right")
    n = na + nb
    xs = np.empty((n, a.d), a.xs.dtype)
    al = np.empty(n, a.alphas.dtype)
    hn = np.empty(n, a.half_norms.dtype)
    od = np.empty(n, np.int64)
    for pos, src in ((pos_a, a), (pos_b, b)):
        xs[pos] = src.xs
        al[pos] = src.alphas
        hn[pos] = src.half_norms
        od[pos] = src.order
    # merge the per-point projections on the shared frozen basis (deltas are
    # projected onto the base's vs, so rows agree); a disagreeing component
    # count keeps only the common prefix — the box bound stays valid for any
    # prefix of the basis
    kx = min(a.vs.shape[0], b.vs.shape[0])
    pj = np.empty((kx, n), np.float32)
    pj[:, pos_a] = np.asarray(a.projs)[:kx]
    pj[:, pos_b] = np.asarray(b.projs)[:kx]
    return _snn.SNNIndex(a.mu, a.v1, xs, al, hn, od, a.metric, a.xi,
                         vs=np.asarray(a.vs)[:kx], projs=pj)


class StreamingSNNIndex:
    """An SNN index that absorbs appends as LSM-style delta segments.

    Exposes the same query surface as the module-level functions
    (`query_radius_csr`, `query_radius_batch`, `query_radius_fixed`,
    `query_counts`) evaluated over base + deltas; all of them are exact at
    every moment of the append/merge/rebuild lifecycle.
    """

    def __init__(
        self,
        data: np.ndarray,
        metric: str = "euclidean",
        n_iter: int = 64,
        block: int = 512,
        delta_ratio: float = 0.25,
        max_deltas: int = 4,
        rebuild_ratio: float = 4.0,
    ):
        self.metric = metric
        self.n_iter = n_iter
        self.block = block
        self.delta_ratio = float(delta_ratio)
        self.max_deltas = int(max_deltas)
        self.rebuild_ratio = float(rebuild_ratio)
        # double-buffered plan epochs (off by default; serving turns it on):
        # mutators build AND warm the next generation's SegmentPack on their
        # own thread before the atomic publish (`set_plan_warming`)
        self._warm = False
        self._warm_kwargs: dict = {}
        self._warm_buckets = (128,)
        self._warmer = None
        # _mutate serializes writers for their whole (possibly heavy) run;
        # _lock guards only the published state and is never held across work
        self._mutate = threading.Lock()
        self._lock = threading.Lock()
        # raw rows as a list of chunks: append is O(1) in index size (the
        # O(n) concatenation is deferred to the rare `raw` materialization)
        # np.array copies: the seed must not alias a caller-mutable buffer
        self._raw_parts = [_as_batch(np.array(data, dtype=np.float32))]
        base = _snn.build_index(self._raw_parts[0], metric=metric,
                                n_iter=n_iter)
        self._n_at_build = base.n
        # generation counts snapshot publishes; the cached SegmentPack plan
        # is tagged with it, so stale plans are impossible by construction
        # (a new generation publishes with plan=None or an extended plan)
        self._generation = 0
        # published snapshot: (parts, segments, plan); parts[0] is the base,
        # segments[i] the lazily-built engine Segment for parts[i], and plan
        # the lazily-built `engine.SegmentPack` over all of them
        self._state: tuple[tuple[_snn.SNNIndex, ...],
                           tuple[_engine.Segment | None, ...],
                           _engine.SegmentPack | None] = ((base,), (None,),
                                                         None)

    # ------------------------------------------------------------ metadata
    @property
    def base(self) -> _snn.SNNIndex:
        return self._state[0][0]

    @property
    def parts(self) -> tuple[_snn.SNNIndex, ...]:
        """Current (base, *deltas) snapshot — read-only."""
        return self._state[0]

    @property
    def n(self) -> int:
        return sum(p.n for p in self._state[0])

    @property
    def d(self) -> int:
        return self._raw_parts[0].shape[1]

    @property
    def raw(self) -> np.ndarray:
        """All points in original (append) order (materialized lazily)."""
        with self._lock:
            if len(self._raw_parts) > 1:
                self._raw_parts = [np.concatenate(self._raw_parts)]
            return self._raw_parts[0]

    @property
    def generation(self) -> int:
        """Snapshot publish counter — bumps on every append/merge/rebuild.

        The serving layer exposes this as the index generation its cached
        plan is valid for; any cached `SegmentPack` built for generation g
        is dead the moment generation g+1 publishes (the publish itself
        swaps the plan to None or to the incrementally-extended pack).
        """
        return self._generation

    # ------------------------------------------------- double-buffered plans
    def set_plan_warming(self, enabled: bool = True, *,
                         m_pads=(128,), warmer=None, **warm_kwargs) -> None:
        """Turn on double-buffered plan epochs for this index's mutators.

        With warming on, `append`/`rebuild` construct the next generation's
        segments + `SegmentPack` AND run `engine.warm_plan`'s zero-match
        priming dispatch (per bucketed batch size in ``m_pads`` — an
        iterable, or a callable returning one so owners can report the
        ladder buckets actually seen) on the MUTATOR thread, then publish
        the already-warm snapshot atomically — readers never observe a plan
        that still owes construction or compile work.  ``warm_kwargs``
        forward to `engine.warm_plan` (query_tile/use_pallas/...);
        ``warmer`` replaces the default entirely with
        ``warmer(plan, spec_from)``.
        """
        self._warm = bool(enabled)
        self._warm_buckets = m_pads
        self._warmer = warmer
        self._warm_kwargs = dict(warm_kwargs)

    def _prime(self, plan: _engine.SegmentPack,
               spec_from: _engine.SegmentPack | None = None) -> None:
        """Warm ``plan`` pre-publish (mutator thread).

        A failure propagates and the mutation does not publish: warming
        runs the very kernels the next query would, so a plan that cannot
        warm cannot answer either.
        """
        if self._warmer is not None:
            self._warmer(plan, spec_from)
            return
        buckets = (self._warm_buckets() if callable(self._warm_buckets)
                   else self._warm_buckets)
        _engine.warm_plan(plan, m_pads=tuple(buckets) or (128,),
                          spec_from=spec_from, **self._warm_kwargs)

    def _next_plan(self, parts: tuple):
        """(segments, plan) for a snapshot about to publish.

        Lazy (all-None, plan=None) unless warming is on; warmed plans adopt
        the outgoing generation's fused capacity speculation
        (`SegmentPack.adopt_spec`) so the first post-swap batch stays on the
        one-dispatch fast path.
        """
        if not self._warm:
            return tuple(None for _ in parts), None
        prev_plan = self._state[2]
        with TraceAnnotation("snn.plan", generation=self._generation + 1):
            segs = tuple(_engine.segment_from_index(p, block=self.block)
                         for p in parts)
            plan = _engine.SegmentPack.build(list(segs),
                                             epoch=self._generation + 1)
            self._prime(plan, spec_from=prev_plan)
        return segs, plan

    def plan_bytes(self) -> int:
        """`MemoryPlan`-accounted bytes of the published plan (0 if none).

        The registry's device-memory unit: the static per-bucket buffer
        ledgers the plan has materialized (`SegmentPack.planned_bytes`).
        """
        with self._lock:
            plan = self._state[2]
        return 0 if plan is None else plan.planned_bytes()

    def drop_plan(self) -> None:
        """Release the cached device plan + segments (registry eviction).

        The parts (and therefore every answer) are untouched — the next
        query rebuilds the `SegmentPack` from the same immutable parts, so
        results after re-admission are bit-identical to before eviction.
        Does not bump `generation`: the index content did not change.
        """
        with self._lock:
            parts = self._state[0]
            self._state = (parts, tuple(None for _ in parts), None)

    # ------------------------------------------------------------ snapshot
    # leaves-per-part layout for state_leaves/from_state (checkpointing):
    _PART_LEAVES = 8  # mu, v1, xs, alphas, half_norms, order, vs, projs

    def state_leaves(self) -> tuple[list[np.ndarray], dict]:
        """Flat array leaves + JSON-scalar extras capturing the EXACT state.

        A restored replica must answer bit-identically, so the snapshot
        carries the exact per-part arrays — frozen mu/v1, the sorted rows,
        the extra-component projections, and the segment-major row order —
        rather than re-deriving anything from ``raw``: a fresh `build_index`
        over raw would legitimately pick a different v1 sign / row order on
        an index that held base + deltas and permute CSR row contents.

        Layout: ``leaves[0]`` is raw (append order); each part then
        contributes `_PART_LEAVES` arrays in field order.  ``extra`` holds
        every scalar needed by `from_state` (metric, per-part xi, tuning
        knobs, generation).  The pair is exactly what
        `ft.checkpoint.CheckpointManager.save` / ``restore_flat`` move.
        """
        with self._mutate:
            raw = self.raw
            with self._lock:
                parts = self._state[0]
            leaves: list[np.ndarray] = [raw]
            xi = []
            for p in parts:
                leaves += [np.asarray(p.mu), np.asarray(p.v1),
                           np.asarray(p.xs), np.asarray(p.alphas),
                           np.asarray(p.half_norms), np.asarray(p.order),
                           np.asarray(p.vs), np.asarray(p.projs)]
                xi.append(float(p.xi))
            extra = {
                "metric": self.metric, "n_iter": self.n_iter,
                "block": self.block, "delta_ratio": self.delta_ratio,
                "max_deltas": self.max_deltas,
                "rebuild_ratio": self.rebuild_ratio,
                "n_at_build": int(self._n_at_build),
                "generation": int(self._generation),
                "n_parts": len(parts), "xi": xi,
            }
            return leaves, extra

    @classmethod
    def from_state(cls, leaves, extra: dict) -> "StreamingSNNIndex":
        """Reconstruct the exact snapshot a `state_leaves` call captured.

        No power iteration, no sorting: the parts are reassembled from
        their saved arrays, so every query on the restored index is
        bit-identical to the original at the same generation.
        """
        self = cls.__new__(cls)
        self.metric = extra["metric"]
        self.n_iter = int(extra["n_iter"])
        self.block = int(extra["block"])
        self.delta_ratio = float(extra["delta_ratio"])
        self.max_deltas = int(extra["max_deltas"])
        self.rebuild_ratio = float(extra["rebuild_ratio"])
        self._warm = False
        self._warm_kwargs = {}
        self._warm_buckets = (128,)
        self._warmer = None
        self._mutate = threading.Lock()
        self._lock = threading.Lock()
        self._raw_parts = [np.asarray(leaves[0], dtype=np.float32)]
        k = cls._PART_LEAVES
        parts = []
        for i in range(int(extra["n_parts"])):
            mu, v1, xs, al, hn, od, vs, pj = leaves[1 + i * k:1 + (i + 1) * k]
            parts.append(_snn.SNNIndex(
                np.asarray(mu), np.asarray(v1), np.asarray(xs),
                np.asarray(al), np.asarray(hn),
                np.asarray(od, dtype=np.int64), extra["metric"],
                float(extra["xi"][i]), vs=np.asarray(vs),
                projs=np.asarray(pj)))
        self._n_at_build = int(extra["n_at_build"])
        self._generation = int(extra["generation"])
        self._state = (tuple(parts), tuple(None for _ in parts), None)
        return self

    # ------------------------------------------------------------- updates
    def append(self, points: np.ndarray) -> None:
        """Absorb a batch: O(b log b + segments) between compactions.

        No power iteration and no full re-sort happen here; at most a linear
        delta merge (size-ratio trigger) or — past ``rebuild_ratio`` growth or
        a mips-lift overflow — one full re-index.  All of it runs outside the
        state lock: concurrent queries keep answering against the previous
        snapshot until the one-assignment publish.
        """
        # np.array copies: the delta must not alias a caller-mutable buffer
        pts = _as_batch(np.array(points, dtype=np.float32), self.d)
        with self._mutate:
            # width validation runs under _mutate: a concurrent first append
            # may have just committed the width of an empty seed, and a
            # stale check here would let a second width slip through
            width_free = self.n == 0 and self.d == 0  # width-unknown seed
            if pts.shape[1] != self.d and not width_free:
                # reject BEFORE touching any state (and before the
                # empty-batch return: a wrong-width batch is a bug even
                # when it has no rows)
                raise ValueError(f"append expects (b, {self.d}) points, "
                                 f"got {pts.shape}")
            if pts.shape[0] == 0:
                return
            with self._lock:
                raw_before = list(self._raw_parts)
                if width_free and self._raw_parts[0].shape[1] != pts.shape[1]:
                    # the first real batch commits the width of an empty seed
                    self._raw_parts = [np.zeros((0, pts.shape[1]), np.float32)]
                parts = list(self._state[0])
                self._raw_parts.append(pts)
            try:
                self._absorb(pts, parts)
            except BaseException:
                # nothing was published: drop the rows again so `raw` keeps
                # matching the served parts
                with self._lock:
                    self._raw_parts = raw_before
                raise

    def _absorb(self, pts: np.ndarray, parts: list) -> None:
        """`append`'s work after the raw rows are recorded (caller holds
        ``_mutate``): delta build, merge or re-index, then the publish."""
        base = parts[0]
        start_id = sum(p.n for p in parts)
        if base.n == 0:
            # an empty base has no meaningful mu/v1 to freeze; the first
            # real batch IS the build
            self._full_rebuild()
            return
        if self.metric == "mips":
            if float(np.einsum("ij,ij->i", pts, pts).max()) > base.xi**2:
                # the frozen lift cannot represent a larger-norm point
                self._full_rebuild()
                return
        t, _ = _metrics.transform_data(pts, self.metric, xi=base.xi)
        x = (t - base.mu[None, :]).astype(base.xs.dtype)
        al = x @ base.v1
        loc = np.argsort(al, kind="stable")
        xs = np.ascontiguousarray(x[loc])
        als = np.ascontiguousarray(al[loc])
        # project onto the base's FROZEN extra components too: the box
        # bound (like the window) is valid for any fixed ||v|| <= 1
        # direction, so deltas inherit the base's basis unchanged and
        # packed queries keep pruning across base + deltas uniformly
        base_vs = np.asarray(base.vs)
        projs = np.concatenate(
            [als[None, :],
             (xs @ base_vs[1:].T).T.astype(np.float32)]) \
            if base_vs.shape[0] > 1 else als[None, :]
        delta = _snn.SNNIndex(
            base.mu, base.v1, xs, als,
            0.5 * np.einsum("ij,ij->i", xs, xs),
            (start_id + loc).astype(np.int64),
            self.metric, base.xi,
            vs=base_vs, projs=projs)
        parts.append(delta)
        n_total = start_id + delta.n
        if n_total >= self.rebuild_ratio * max(self._n_at_build, 1):
            self._full_rebuild()
            return
        n_delta = sum(p.n for p in parts[1:])
        if (len(parts) - 1 > self.max_deltas
                or n_delta > self.delta_ratio * max(base.n, 1)):
            merged = parts[0]
            for p in parts[1:]:
                merged = merge_sorted_indexes(merged, p)
            segs, plan = self._next_plan((merged,))
            with self._lock:
                self._generation += 1
                self._state = ((merged,), segs, plan)
        else:
            # incremental plan epoch: pad-stack the delta's segment now
            # (outside the state lock) and extend the cached plan with
            # one slab concatenation — queries on the new snapshot reuse
            # the base's device-resident stack instead of rebuilding it
            seg_delta = _engine.segment_from_index(delta,
                                                  block=self.block)
            # read as late as possible: a plan a racing query built
            # during the heavy batch work above is seen here and
            # extended rather than dropped.  (If the read is None, the
            # publish follows within microseconds — a query completing
            # a build inside that window loses only its cache
            # write-back, never correctness.)
            with self._lock:
                prev_plan = self._state[2]
            if prev_plan is not None:
                new_plan = prev_plan.extend([seg_delta])
            elif self._warm:
                # nothing live to extend — build the next epoch whole so
                # the publish still carries a warm plan (first append
                # after a drop_plan/eviction, or a never-queried index)
                segs_now = tuple(
                    s if s is not None
                    else _engine.segment_from_index(p, block=self.block)
                    for p, s in zip(parts[:-1], self._state[1]))
                new_plan = _engine.SegmentPack.build(
                    [*segs_now, seg_delta], epoch=self._generation + 1)
            else:
                new_plan = None
            if self._warm and new_plan is not None:
                # double-buffered epoch: compile/adopt-spec on THIS
                # (mutator) thread before anyone can observe the plan
                self._prime(new_plan, spec_from=prev_plan)
            with self._lock:
                # re-read the segment cache at publish time: _mutate
                # guarantees parts didn't change, but a query may have
                # filled segments since we started — keep its work
                self._generation += 1
                self._state = (tuple(parts),
                               (*self._state[1], seg_delta), new_plan)

    def _full_rebuild(self) -> None:
        """Build a fresh base (caller holds ``_mutate``) and publish it."""
        base = _snn.build_index(self.raw, metric=self.metric,
                                n_iter=self.n_iter)
        segs, plan = self._next_plan((base,))
        with self._lock:
            self._n_at_build = base.n
            self._generation += 1
            self._state = ((base,), segs, plan)

    def rebuild(self) -> None:
        """Force a full re-index (fresh mu/v1/xi) of everything appended."""
        with self._mutate:
            self._full_rebuild()

    # ------------------------------------------------------------- queries
    def _parts(self) -> tuple[_snn.SNNIndex, ...]:
        """Consistent parts snapshot for the host paths — no segment builds."""
        with self._lock:
            return self._state[0]

    def _snapshot(self):
        """Parts + segments + the `SegmentPack` plan, building what's missing.

        Segment/plan construction (an O(n) pad-copy + device transfer for a
        fresh base) runs OUTSIDE the state lock — concurrent queries and
        appends never stall on it; two racing queries at worst build the
        same plan twice, and the cache write-back is dropped if a writer
        published new parts in the meantime.
        """
        with self._lock:
            parts, segs, plan = self._state
        if any(s is None for s in segs) or plan is None:
            with TraceAnnotation("snn.plan", generation=self._generation):
                segs = tuple(
                    s if s is not None
                    else _engine.segment_from_index(p, block=self.block)
                    for p, s in zip(parts, segs))
                if plan is None:
                    plan = _engine.SegmentPack.build(list(segs),
                                                     epoch=self._generation)
            with self._lock:
                if self._state[0] is parts:
                    self._state = (parts, segs, plan)
        return parts, list(segs), plan

    def plan(self) -> _engine.SegmentPack:
        """The current snapshot's `SegmentPack` (built on first use)."""
        return self._snapshot()[2]

    def query_radius_csr(self, q: np.ndarray, radius,
                         return_distance: bool = True, *,
                         query_tile: int = 128,
                         use_pallas: bool | str | None = None,
                         native: bool = True,
                         packed: bool = True,
                         mixed: bool = False,
                         bucket: bool = True,
                         compacted: bool | None = None,
                         fused: bool = True) -> _snn.CSRNeighbors:
        """Exact CSR results over base + deltas via the unified engine.

        ``radius`` is a scalar or a per-query (m,) vector in the native
        metric (`snn.query_radius_csr` contract — mixed-radius batches cost
        one dispatch).  Row contents are segment-major (base first, then
        deltas in append
        order), ascending in sorted position within each segment.
        ``packed=True`` (default) executes the snapshot's cached
        `SegmentPack` plan — one stacked launch per pass over base + all
        live deltas; ``packed=False`` keeps the per-segment looped executor.
        Delegates to `core.join.single_query` (a point-query batch is a
        single-chunk bichromatic join) with this snapshot's plan/segments.
        """
        parts, segs, plan = self._snapshot()
        return _join_single_query(parts[0], q, radius, return_distance,
                                  pack=plan, segments=segs,
                                  query_tile=query_tile,
                                  use_pallas=use_pallas, native=native,
                                  packed=packed, mixed=mixed, bucket=bucket,
                                  compacted=compacted, fused=fused)

    def query_counts_device(self, q: np.ndarray, radius, *,
                            query_tile: int = 128,
                            use_pallas: bool | str | None = None,
                            memory_budget_mb: float | None = None,
                            mixed: bool = False,
                            bucket: bool = True,
                            compacted: bool | None = None) -> np.ndarray:
        """Exact per-query neighbor counts over base + deltas — pass 1 only.

        The count-only analytics front-end (`core.join.query_counts`)
        evaluated on this snapshot's cached plan: one
        `engine.run_counts_packed` launch group, no compact pass, no CSR
        staging.  Counts equal ``np.diff(query_radius_csr(...).indptr)``
        exactly (identical predicate pipeline), at O(m) output memory.
        """
        return _join_query_counts(self, q, radius, query_tile=query_tile,
                                  use_pallas=use_pallas,
                                  memory_budget_mb=memory_budget_mb,
                                  mixed=mixed, bucket=bucket,
                                  compacted=compacted)

    def query_knn(self, q: np.ndarray, k, return_distance: bool = True, *,
                  native: bool = True, query_tile: int = 128,
                  use_pallas: bool | str | None = None,
                  memory_budget_mb: float | None = None,
                  bucket: bool = True):
        """Exact k nearest neighbors over base + deltas (`core.knn`).

        Runs the per-query radius-expansion search against this snapshot's
        cached `SegmentPack` plan — the same plan the radius path executes —
        so kNN serving shares the index generation's device-resident state.
        ``k`` is a scalar or per-query (m,) vector.
        """
        from . import knn as _knn

        return _knn.query_knn(self, q, k, return_distance, native=native,
                              query_tile=query_tile, use_pallas=use_pallas,
                              memory_budget_mb=memory_budget_mb,
                              bucket=bucket)

    def query_radius_batch(self, q: np.ndarray, radius,
                           return_distance: bool = True,
                           group_size: int = 64) -> list:
        """Host Algorithm-2 path over every segment, merged per query."""
        parts = self._parts()
        outs = [_snn.query_radius_batch(p, q, radius, return_distance,
                                        group_size) for p in parts]
        if len(outs) == 1:
            return outs[0]
        merged = []
        for per_q in zip(*outs):
            if return_distance:
                merged.append((np.concatenate([i for i, _ in per_q]),
                               np.concatenate([d for _, d in per_q])))
            else:
                merged.append(np.concatenate(per_q))
        return merged

    def query_counts(self, q: np.ndarray, radius,
                     group_size: int = 64) -> np.ndarray:
        parts = self._parts()
        return sum(_snn.query_counts(p, q, radius, group_size) for p in parts)

    def query_radius_fixed(self, q: np.ndarray, radius, max_neighbors: int):
        """Fixed-shape (K-bounded) results merged across segments.

        Per-segment `snn.query_radius_fixed` top-Ks are concatenated and
        re-truncated to the K best by squared distance; ``counts`` stays the
        exact total, so truncation remains detectable.
        """
        parts = self._parts()
        outs = [_snn.query_radius_fixed(p, q, radius, max_neighbors,
                                        block=self.block) for p in parts]
        if len(outs) == 1:
            return outs[0]
        idx = np.concatenate([o[0] for o in outs], axis=1)
        sq = np.concatenate([o[1] for o in outs], axis=1)
        valid = np.concatenate([o[2] for o in outs], axis=1)
        counts = np.sum([o[3] for o in outs], axis=0)
        k = min(max_neighbors, idx.shape[1])
        pick = np.argsort(np.where(valid, sq, np.inf), axis=1,
                          kind="stable")[:, :k]
        return (np.take_along_axis(idx, pick, 1),
                np.take_along_axis(sq, pick, 1),
                np.take_along_axis(valid, pick, 1), counts)
