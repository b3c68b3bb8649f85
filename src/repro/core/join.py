"""Bichromatic eps-join core: the one scheduling loop every workload runs on.

The sorted-window machinery underneath this library — projection intervals,
segment-level window pruning, count -> prefix-sum -> compact CSR — is
workload-agnostic: nothing in it cares whether the queries are user points,
the database itself, or a different dataset entirely.  This module owns that
machinery as the PRIMITIVE ``join(A, B, r)`` and every public workload is a
thin front-end over it:

* **point queries** (`snn.query_radius_csr`, `streaming`, the `knn` expand
  pass) are a join whose A block is one chunk: `single_query` /
  `count_pass` delegate straight to the packed/looped engine executors;
* **the self-join graph** (`graph.build_neighbor_graph`) is ``join(X, X,
  eps)`` where the query sort is the index's own order, plus the symmetric
  triangular schedule and mirror merge (`mirror_merge`) that only a
  self-join can exploit;
* **bichromatic joins** (`join`) lift B once into segments (or one
  `engine.SegmentPack` plan), sort A's queries by their alpha score, and
  stream alpha-adjacent chunks through the engine — each chunk spans a
  narrow projection window, so the interval-overlap prune discards almost
  every B segment before any kernel launch (the same schedule `graph.py`
  pioneered, generalized to A != B);
* **reverse neighbors** (`reverse_neighbors`) transpose the join CSR: with
  per-point radii as A's per-query radius vector, row j of the transpose is
  exactly "which points hold target j inside their own ball" — the exact
  counterpart of LSH-based reverse search (Arthur & Oudot, PAPERS.md);
* **count-only analytics** (`query_counts`, `join_counts`,
  `degree_histogram`) stop after pass 1 (`engine.run_counts_packed`): range
  counting and degree statistics never materialize a CSR, never run the
  compact pass, and never allocate flat outputs.

Everything here preserves the engine's exactness contract: per-row results
are bit-identical to evaluating that row alone, whatever the chunking
(schedule invariance), and pass-1 counts always equal pass-2 row lengths.

The multi-host roadmap item builds directly on this core: a remote shard is
a contiguous B-window (a run of segments), and an A-chunk routes to the
O(1) shards its alpha interval overlaps — `chunked_join` is the single-host
degenerate case of that partition/halo schedule (Raulet et al., PAPERS.md).
"""
from __future__ import annotations

import numpy as np
from jax.profiler import TraceAnnotation

from ..kernels import ops as _ops
from . import engine as _engine
from . import snn as _snn


# --------------------------------------------------------------------------- #
# CSR plumbing                                                                 #
# --------------------------------------------------------------------------- #
def indptr_from_counts(counts: np.ndarray) -> np.ndarray:
    out = np.zeros(counts.size + 1, np.int64)
    np.cumsum(counts, out=out[1:])
    return out


def permute_rows(indptr, indices, distances, dest):
    """Reorder CSR rows: input row i becomes output row ``dest[i]``.

    One O(nnz) gather; used to undo a query sort (``dest = index.order``
    for the self-join, the alpha argsort for a bichromatic join) so public
    results are in the caller's original row order.
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


def transpose_csr(indptr, cols, dists, n_cols: int):
    """Exact CSR transpose: (rows -> cols) becomes (cols -> rows).

    Output row j lists every input row whose neighbor list contains j, in
    ascending input-row order (the stable sort preserves the row-major flat
    order).  Distances move with their pair unchanged — d(i, j) is the same
    number from either side of the transpose.
    """
    rows = np.repeat(np.arange(indptr.size - 1, dtype=np.int64),
                     np.diff(indptr))
    order = np.argsort(cols, kind="stable")
    out_indptr = indptr_from_counts(
        np.bincount(cols, minlength=n_cols).astype(np.int64))
    out_d = None if dists is None else dists[order]
    return out_indptr, rows[order], out_d


def mirror_merge(indptr, cols, dists, chunk: int):
    """Complete a block-upper-triangular self-join with its mirror pairs.

    Input rows/cols are sorted positions; every pair (i, j) whose column
    falls in a LATER query chunk than its row was evaluated exactly once, so
    its mirror (j, i) is added here (intra-chunk pairs were evaluated in
    both directions already).  Mirrored neighbors of row j all precede j's
    chunk and are inserted ahead of the direct ones in ascending source
    order, so merged rows stay ascending in sorted position — the invariant
    every other engine path guarantees.  Distances mirror verbatim — valid
    because native-metric distances (and non-native squared Euclidean for
    the query-independent transforms) are symmetric in exact arithmetic;
    the one asymmetric combination (mips with ``native=False``, whose
    lifted distance depends on which point is the query) is rejected in
    `graph.build_neighbor_graph` before this runs.
    """
    n = indptr.size - 1
    counts_d = np.diff(indptr)
    rows = np.repeat(np.arange(n, dtype=np.int64), counts_d)
    cross = (cols // chunk) > (rows // chunk)
    rows_m, cols_m = cols[cross], rows[cross]
    d_m = dists[cross] if dists is not None else None
    src = np.argsort(rows_m, kind="stable")  # group by target row, keep order
    rows_m, cols_m = rows_m[src], cols_m[src]
    counts_m = np.bincount(rows_m, minlength=n).astype(np.int64)
    indptr_m = indptr_from_counts(counts_m)
    out_indptr = indptr_from_counts(counts_m + counts_d)
    start = out_indptr[:-1]
    pos_m = np.repeat(start - indptr_m[:-1], counts_m) + np.arange(rows_m.size)
    pos_d = np.repeat(start + counts_m - indptr[:-1], counts_d) \
        + np.arange(cols.size)
    out_cols = np.empty(rows_m.size + cols.size, np.int64)
    out_cols[pos_m] = cols_m
    out_cols[pos_d] = cols
    out_d = None
    if dists is not None:
        out_d = np.empty(out_cols.size, dists.dtype)
        out_d[pos_m] = d_m[src]
        out_d[pos_d] = dists
    return out_indptr, out_cols, out_d


# --------------------------------------------------------------------------- #
# The chunked join loop (the core)                                             #
# --------------------------------------------------------------------------- #
def chunked_join(index, segments, xq, aq, r, th, *, query_chunk: int,
                 segs_per_chunk: int, query_tile: int, use_pallas,
                 packed: bool = True, memory_budget_mb=None,
                 mixed: bool = False, compacted: bool | None = None,
                 fused: bool = True):
    """Run alpha-sorted query chunks through the engine over ``segments``.

    ``xq``/``aq``/``r``/``th`` are the float32 predicate inputs of
    `snn.prepare_query_predicates`, already sorted ascending by ``aq`` —
    the caller owns the sort (the self-join reuses the index's own order;
    `join` argsorts A's scores).  Sorting is what makes the schedule pay:
    a chunk of alpha-adjacent queries spans a narrow projection window, so
    the segment-level interval-overlap prune discards almost every B
    segment before any kernel launch.

    ``packed=True`` (default) builds ONE `engine.SegmentPack` plan for the
    whole join and executes every chunk through `engine.run_csr_packed` —
    the stack, padding and device transfer happen once, and each chunk pays
    two stacked launches instead of two per live segment (the biggest
    throughput win of the plan/execute split: a join has m/query_chunk
    chunks all querying the same segments).  ``packed=False`` keeps the
    looped `engine.run_csr` cross-check path.

    ``segs_per_chunk > 0`` turns on the triangular schedule: chunk k only
    sees segments from its own first segment onward (requires chunks and
    segments to tile the sorted order with ``query_chunk`` an exact multiple
    of the segment size) — only meaningful when the queries ARE the
    database, i.e. the self-join.  Returns chunk-major (= ascending sorted
    row) ``(counts, flat_ids, flat_dh)``.
    """
    m = xq.shape[0]
    aq64 = np.asarray(aq, np.float64)
    r64 = np.asarray(r, np.float64)
    counts = np.zeros(m, np.int64)
    ids_parts: list[np.ndarray] = []
    dh_parts: list[np.ndarray] = []
    pack = _engine.SegmentPack.build(segments) if packed else None
    # the extra pruning projections come from B's basis — computed once for
    # the whole join, sliced per chunk
    pq_full = _snn.query_extra_projections(index, xq)
    pq64_full = (None if pq_full is None
                 else np.asarray(pq_full, np.float64))
    for c0 in range(0, m, query_chunk):
        c1 = min(c0 + query_chunk, m)
        k0 = (c0 // query_chunk) * segs_per_chunk if segs_per_chunk else 0
        qp, aqp, rp, thp, _ = _ops.pad_queries(
            xq[c0:c1], aq[c0:c1], r[c0:c1], th[c0:c1], tq=query_tile)
        pqp = (None if pq_full is None
               else _ops.pad_components(pq_full[:, c0:c1], qp.shape[0]))
        if packed:
            # the vectorized interval-overlap prune inside the packed
            # executor plays the role of the per-segment window loop
            _, cnt, ids, dh = _engine.run_csr_packed(
                pack, qp, aqp, rp, thp, c1 - c0,
                query_tile=query_tile, use_pallas=use_pallas,
                first_seg=k0, memory_budget_mb=memory_budget_mb,
                pq=pqp, mixed=mixed, compacted=compacted, fused=fused)
        else:
            # the schedule: alpha-adjacent queries span a narrow window, so
            # most segments fail this interval test and never launch
            if pq64_full is None:
                live = [s for s in segments[k0:]
                        if _engine._window_may_hit(s, aq64[c0:c1],
                                                   r64[c0:c1])]
            else:
                qn64 = _engine._qnorm64(rp, thp, c1 - c0)
                live = [s for s in segments[k0:]
                        if _engine._window_may_hit(
                            s, aq64[c0:c1], r64[c0:c1],
                            pq64_full[:, c0:c1], qn64)]
            _, cnt, ids, dh = _engine.run_csr(
                live, qp, aqp, rp, thp, c1 - c0,
                query_tile=query_tile, use_pallas=use_pallas,
                memory_budget_mb=memory_budget_mb, pq=pqp, mixed=mixed)
        counts[c0:c1] = cnt
        ids_parts.append(ids)
        dh_parts.append(dh)
    flat_ids = (np.concatenate(ids_parts) if ids_parts
                else np.zeros(0, np.int64))
    flat_dh = (np.concatenate(dh_parts) if dh_parts
               else np.zeros(0, np.float32))
    return counts, flat_ids, flat_dh


def resolve_chunk(n: int, query_chunk: int | None, memory_budget_mb,
                  align: int | None, block: int) -> int:
    """Pick the query chunk size: explicit, or sized to a memory budget.

    The budget bounds the worst case of the oracle (CPU) path — one cached
    dense float32 filter of shape (chunk, n_padded) per chunk when every
    segment is live — which is also a safe proxy for device-memory pressure
    on TPU (flat CSR outputs scale with the same product).  A budget is a
    CEILING: it floors the derived chunk, never inflates it.

    ``align`` is the segment size the symmetric triangular schedule needs
    chunks to tile in whole multiples of (None when any chunk size works:
    the plain, sharded, and bichromatic schedules).  Alignment floors to
    whole segments — again never inflating a budgeted chunk — except that
    one segment is the minimum a chunk can be.
    """
    if memory_budget_mb is not None:
        n_pad = _ops.round_up(n, block)
        cs = int(memory_budget_mb * 2**20) // (4 * n_pad)
    else:
        cs = int(query_chunk) if query_chunk else 2048
    cs = max(cs, 1)
    if align:
        cs = max(cs // align, 1) * align
    return cs


def sorted_join_csr(index, segments, q_sorted, radius, *, symmetric: bool,
                    query_chunk: int, segs_per_chunk: int, query_tile: int,
                    use_pallas, return_distance: bool, native: bool,
                    dest: np.ndarray, packed: bool = True,
                    memory_budget_mb=None, mixed: bool = False,
                    compacted: bool | None = None, fused: bool = True):
    """Shared tail of the self-join and bichromatic builders.

    ``q_sorted`` are raw query points already in ascending-alpha order and
    ``dest`` maps each sorted row back to its public row (``dest[i]`` is
    where sorted row i lands): the self-join passes ``index.order``, `join`
    passes its own argsort.  Prepares predicates, runs the chunk loop,
    finalizes distances, optionally mirror-completes the triangular
    schedule, and unsorts the rows.
    """
    xq, aq, r, th, qsq = _snn.prepare_query_predicates(index, q_sorted, radius)
    counts, flat_ids, flat_dh = chunked_join(
        index, segments, xq, aq, r, th, query_chunk=query_chunk,
        segs_per_chunk=segs_per_chunk if symmetric else 0,
        query_tile=query_tile, use_pallas=use_pallas, packed=packed,
        memory_budget_mb=memory_budget_mb, mixed=mixed,
        compacted=compacted, fused=fused)
    indptr = indptr_from_counts(counts)
    fin = _snn.csr_finalize(index, indptr, flat_ids, flat_dh, xq, qsq, counts,
                            return_distance, native)
    cols, dists = fin.indices, fin.distances
    if symmetric:
        indptr, cols, dists = mirror_merge(indptr, cols, dists, query_chunk)
        cols = index.order[cols]  # sorted positions -> original ids
    indptr, cols, dists = permute_rows(indptr, cols, dists, dest)
    return _snn.CSRNeighbors(indptr, cols, dists)


# --------------------------------------------------------------------------- #
# Resolution helpers shared by the thin front-ends                             #
# --------------------------------------------------------------------------- #
def _as_rows(a: np.ndarray) -> np.ndarray:
    a = np.asarray(a)
    return a[None, :] if a.ndim == 1 else a


def _resolve_pack(index, block: int):
    """(owner, pack) for an `SNNIndex` or a `streaming.StreamingSNNIndex`.

    ``owner`` holds the mu/v1/metric/xi every predicate derives from (the
    streaming base freezes them); ``pack`` is the device-resident execution
    plan (the streaming snapshot's cached plan, or a fresh one-segment pack).
    """
    if hasattr(index, "plan") and hasattr(index, "parts"):  # streaming
        parts, _, pack = index._snapshot()
        return parts[0], pack
    return index, _engine.pack_from_index(index, block=block)


def _checked_radius(radius, m: int):
    """Validate a scalar-or-(m,) radius BEFORE any query sort touches it."""
    if np.ndim(radius) == 0:
        return radius, None
    r = np.asarray(radius, np.float64)
    if r.shape != (m,):
        raise ValueError(f"radius must be a scalar or a per-row ({m},) "
                         f"vector; got shape {r.shape}")
    return r, r


def _empty_csr(m: int, return_distance: bool) -> _snn.CSRNeighbors:
    return _snn.CSRNeighbors(
        np.zeros(m + 1, np.int64), np.zeros(0, np.int64),
        np.zeros(0, np.float64) if return_distance else None)


# --------------------------------------------------------------------------- #
# Point queries as single-chunk joins                                          #
# --------------------------------------------------------------------------- #
def single_query(index, q, radius, return_distance: bool = True, *,
                 pack=None, segments=None, block: int = 512,
                 query_tile: int = 128, use_pallas=None, native: bool = True,
                 packed: bool = True, mixed: bool = False,
                 bucket: bool = True, compacted: bool | None = None,
                 fused: bool = True) -> _snn.CSRNeighbors:
    """A point-query batch is a bichromatic join whose A side is one chunk.

    This is the front-end `snn.query_radius_csr` and the streaming index
    delegate to: no chunk loop, no query sort (a serving batch has no
    exploitable order), just the engine's packed (or looped) executor over
    a prebuilt ``pack`` (or ``segments``) — bit-identical to the historical
    direct calls by construction, because these ARE those calls.
    """
    if packed:
        if pack is None:
            pack = _engine.pack_from_index(index, block=block)
        return _engine.query_csr_packed(
            index, pack, q, radius, return_distance, query_tile=query_tile,
            use_pallas=use_pallas, native=native, mixed=mixed, bucket=bucket,
            compacted=compacted, fused=fused)
    if segments is None:
        segments = [_engine.segment_from_index(index, block=block)]
    return _engine.query_csr(
        index, segments, q, radius, return_distance, query_tile=query_tile,
        use_pallas=use_pallas, native=native, mixed=mixed, bucket=bucket)


def count_pass(pack, xq, aq, qsq, r, *, query_tile: int = 128,
               use_pallas=None, memory_budget_mb=None, pq=None,
               mixed: bool = False, bucket: bool = True,
               compacted: bool | None = None) -> np.ndarray:
    """One engine count launch for prepared queries under Euclidean ``r``.

    The pass-1-only join primitive (`engine.run_counts_packed`): no compact
    pass, no flat outputs.  The kNN expansion loop re-enters this with a
    shrinking active subset each round — bucketed padding keeps that at
    O(log m) compiled shapes instead of one per round.
    """
    with TraceAnnotation("snn.prep"):
        thresh = ((r * r - qsq) / 2.0).astype(np.float32)
        qp, aqp, rp, thp, m = _ops.pad_queries(xq, aq, r.astype(np.float32),
                                               thresh, tq=query_tile,
                                               bucket=bucket)
        pqp = None if pq is None else _ops.pad_components(pq, qp.shape[0])
    return _engine.run_counts_packed(pack, qp, aqp, rp, thp, m,
                                     query_tile=query_tile,
                                     use_pallas=use_pallas,
                                     memory_budget_mb=memory_budget_mb,
                                     pq=pqp, mixed=mixed,
                                     compacted=compacted)


def query_counts(index, q, radius, *, block: int = 512,
                 query_tile: int = 128, use_pallas=None,
                 memory_budget_mb=None, mixed: bool = False,
                 bucket: bool = True,
                 compacted: bool | None = None) -> np.ndarray:
    """Exact neighbor counts per query — pass 1 only, no CSR staging.

    The count-only analytics front-end: range counting, occupancy checks,
    and density estimates need ``|B ∩ ball(q, r)|``, not the membership
    list, so this stops after `engine.run_counts_packed` — no prefix sums,
    no compact launch, no flat id/distance allocation.  Counts are computed
    by the identical predicate pipeline as `snn.query_radius_csr`, so they
    equal ``np.diff(csr.indptr)`` of the full query exactly.

    ``index`` is an `snn.SNNIndex` or a `streaming.StreamingSNNIndex`
    (counts run over base + deltas through the cached plan); ``radius`` is
    a scalar or per-query (m,) vector in the native metric.
    """
    with TraceAnnotation("snn.prep"):
        owner, pack = _resolve_pack(index, block)
        xq, aq, r32, th, qsq = _snn.prepare_query_predicates(owner, q,
                                                             radius)
        qp, aqp, rp, thp, m = _ops.pad_queries(xq, aq, r32, th,
                                               tq=query_tile, bucket=bucket)
        pq = _snn.query_extra_projections(owner, xq)
        pqp = None if pq is None else _ops.pad_components(pq, qp.shape[0])
    return _engine.run_counts_packed(pack, qp, aqp, rp, thp, m,
                                     query_tile=query_tile,
                                     use_pallas=use_pallas,
                                     memory_budget_mb=memory_budget_mb,
                                     pq=pqp, mixed=mixed,
                                     compacted=compacted)


# --------------------------------------------------------------------------- #
# The public bichromatic join                                                  #
# --------------------------------------------------------------------------- #
def join(
    a: np.ndarray,
    b: np.ndarray | None,
    radius,
    *,
    metric: str = "euclidean",
    b_index: _snn.SNNIndex | None = None,
    return_distance: bool = True,
    query_chunk: int | None = 2048,
    memory_budget_mb: float | None = None,
    segment_rows: int | None = None,
    block: int = 512,
    query_tile: int = 128,
    use_pallas: bool | str | None = None,
    native: bool = True,
    n_iter: int = 64,
    packed: bool = True,
    mixed: bool = False,
    compacted: bool | None = None,
    fused: bool = True,
) -> _snn.CSRNeighbors:
    """Exact bichromatic eps-join: row i lists every b within radius of a[i].

    B is lifted ONCE (index build + one `engine.SegmentPack` plan), then A's
    rows stream through the sorted-chunk schedule: queries are processed in
    ascending order of their projection score, so each chunk spans a narrow
    alpha window and the segment-level interval-overlap prune discards most
    of B per chunk before any kernel launch.  Row contents and distances
    are bit-identical per row to ``query_radius_csr(b_index, a, radius)`` —
    the schedule is a reordering, never a different computation.

    Args:
      a: (ma, da) query-side points (or one (d,) point) in the raw metric
        space.
      b: (nb, d) database-side points; may be None when ``b_index`` is given.
      radius: scalar or per-A-row (ma,) vector in the native metric (the
        inner-product threshold for mips — note mips is asymmetric: a is
        the query side of ``p.q >= S``).
      b_index: prebuilt `snn.SNNIndex` over exactly ``b`` — lift B once,
        join many A batches against it.
      query_chunk / memory_budget_mb / segment_rows / block / query_tile /
        use_pallas / native / packed / mixed: exactly `build_neighbor_graph`'s
        knobs (the self-join is this function with A = B = X plus the
        triangular symmetric schedule).

    Returns:
      `CSRNeighbors` with ``ma`` rows; column ids are original B row ids,
      ascending in B's sorted order within each row; ``distances`` (iff
      ``return_distance``) in B's native metric (``native=False`` leaves
      squared Euclidean in index space).
    """
    a = _as_rows(a)
    index = b_index
    if index is None:
        if b is None:
            raise ValueError("join needs b points or a prebuilt b_index")
        index = _snn.build_index(np.asarray(b), metric=metric, n_iter=n_iter)
    m = a.shape[0]
    radius, rvec = _checked_radius(radius, m)
    if index.n == 0 or m == 0:
        return _empty_csr(m, return_distance)
    # sort A by its alpha score so chunks are alpha-adjacent; float64 scores
    # match prepare_query_predicates' float32 aq in ORDER for our purposes —
    # any order is exact, sorted order is merely fast, so the cheap argsort
    # of the float32 scores is the right choice
    tq = _metricsafe_scores(index, a)
    qord = np.argsort(tq, kind="stable")
    r_sorted = radius if rvec is None else rvec[qord]
    sr = max(int(segment_rows), 1) if segment_rows is not None else block
    cs = resolve_chunk(index.n, query_chunk, memory_budget_mb, None, block)
    segments = _engine.segments_from_index(index, rows_per_segment=sr,
                                           block=block)
    return sorted_join_csr(
        index, segments, a[qord], r_sorted, symmetric=False, query_chunk=cs,
        segs_per_chunk=0, query_tile=query_tile, use_pallas=use_pallas,
        return_distance=return_distance, native=native, dest=qord,
        packed=packed, memory_budget_mb=memory_budget_mb, mixed=mixed,
        compacted=compacted, fused=fused)


def _metricsafe_scores(index, a: np.ndarray) -> np.ndarray:
    """A-side alpha scores for the schedule sort (row-wise, order only).

    Computed exactly as `snn.prepare_query_predicates` computes ``aq``
    (transform, center, project on v1) — each row's score depends only on
    that row, so sorting the raw rows first and preparing after yields the
    same per-row predicates the unsorted batch would see.
    """
    from . import metrics as _metrics

    tq = _metrics.transform_query(a, index.metric)
    xq = (tq - index.mu[None, :]).astype(np.float32)
    return (xq @ index.v1).astype(np.float32)


def join_counts(
    a: np.ndarray,
    b: np.ndarray | None,
    radius,
    *,
    metric: str = "euclidean",
    b_index: _snn.SNNIndex | None = None,
    query_chunk: int | None = 2048,
    memory_budget_mb: float | None = None,
    segment_rows: int | None = None,
    block: int = 512,
    query_tile: int = 128,
    use_pallas: bool | str | None = None,
    n_iter: int = 64,
    mixed: bool = False,
    compacted: bool | None = None,
) -> np.ndarray:
    """Count-only bichromatic join: ``|ball(a[i], r_i) ∩ B|`` per A row.

    The pure pass-1 twin of `join`: the same sorted-chunk schedule, but
    every chunk runs `engine.run_counts_packed` and nothing is compacted —
    range counting over arbitrarily large A at O(m) output memory.  Counts
    equal ``np.diff(join(...).indptr)`` exactly (identical predicates).
    """
    a = _as_rows(a)
    index = b_index
    if index is None:
        if b is None:
            raise ValueError("join_counts needs b points or a b_index")
        index = _snn.build_index(np.asarray(b), metric=metric, n_iter=n_iter)
    m = a.shape[0]
    radius, rvec = _checked_radius(radius, m)
    if index.n == 0 or m == 0:
        return np.zeros(m, np.int64)
    qord = np.argsort(_metricsafe_scores(index, a), kind="stable")
    r_sorted = radius if rvec is None else rvec[qord]
    sr = max(int(segment_rows), 1) if segment_rows is not None else block
    cs = resolve_chunk(index.n, query_chunk, memory_budget_mb, None, block)
    segments = _engine.segments_from_index(index, rows_per_segment=sr,
                                           block=block)
    pack = _engine.SegmentPack.build(segments)
    xq, aq, r32, th, _ = _snn.prepare_query_predicates(index, a[qord],
                                                       r_sorted)
    pq_full = _snn.query_extra_projections(index, xq)
    counts_sorted = np.zeros(m, np.int64)
    for c0 in range(0, m, cs):
        c1 = min(c0 + cs, m)
        qp, aqp, rp, thp, _ = _ops.pad_queries(
            xq[c0:c1], aq[c0:c1], r32[c0:c1], th[c0:c1], tq=query_tile)
        pqp = (None if pq_full is None
               else _ops.pad_components(pq_full[:, c0:c1], qp.shape[0]))
        counts_sorted[c0:c1] = _engine.run_counts_packed(
            pack, qp, aqp, rp, thp, c1 - c0, query_tile=query_tile,
            use_pallas=use_pallas, memory_budget_mb=memory_budget_mb,
            pq=pqp, mixed=mixed, compacted=compacted)
    out = np.empty(m, np.int64)
    out[qord] = counts_sorted
    return out


def degree_histogram(
    x: np.ndarray,
    eps,
    *,
    metric: str = "euclidean",
    index: _snn.SNNIndex | None = None,
    query_chunk: int | None = 2048,
    memory_budget_mb: float | None = None,
    block: int = 512,
    query_tile: int = 128,
    use_pallas: bool | str | None = None,
    n_iter: int = 64,
    mixed: bool = False,
) -> tuple[np.ndarray, np.ndarray]:
    """Degree distribution of the eps-graph WITHOUT building the graph.

    ``degrees[i] = |ball(x[i], eps)|`` (self included, as in the graph) via
    the count-only self-join — no CSR, no compact pass, O(n) memory however
    dense the graph is.  Returns ``(hist, degrees)`` where ``hist[k]`` is
    the number of points with exactly k neighbors: the DBSCAN-tuning view
    (core points at min_samples) and the percolation view in one pass-1
    sweep.
    """
    x = _as_rows(x)
    if index is None:
        index = _snn.build_index(x, metric=metric, n_iter=n_iter)
    degrees = join_counts(x, None, eps, b_index=index,
                          query_chunk=query_chunk,
                          memory_budget_mb=memory_budget_mb, block=block,
                          query_tile=query_tile, use_pallas=use_pallas,
                          mixed=mixed)
    hist = np.bincount(degrees) if degrees.size else np.zeros(0, np.int64)
    return hist, degrees


# --------------------------------------------------------------------------- #
# Reverse neighbors                                                            #
# --------------------------------------------------------------------------- #
def reverse_neighbors(
    points: np.ndarray,
    targets: np.ndarray,
    radii,
    *,
    metric: str = "euclidean",
    target_index: _snn.SNNIndex | None = None,
    return_distance: bool = False,
    query_chunk: int | None = 2048,
    memory_budget_mb: float | None = None,
    segment_rows: int | None = None,
    block: int = 512,
    query_tile: int = 128,
    use_pallas: bool | str | None = None,
    native: bool = True,
    n_iter: int = 64,
    packed: bool = True,
    mixed: bool = False,
) -> _snn.CSRNeighbors:
    """Exact reverse eps-neighbors: which points hold each target in range.

    Row j of the result lists every i with ``d(points[i], targets[j]) <=
    radii[i]`` — each POINT owns its radius (the per-point radius vectors of
    the variable-density graph), and the question is asked from the target's
    side: "whose ball am I inside?".  This is the transposed bichromatic
    join ``join(points, targets, radii)`` — exact, unlike LSH-based reverse
    search (Arthur & Oudot, PAPERS.md), because the forward join is exact
    and transposition is lossless.

    ``points`` are raw metric-space rows (for mips the point is the QUERY
    side of ``p.q >= S``, so reconstructing points from a lifted index would
    be lossy — pass the raw array).  ``radii`` is a scalar or per-point
    (n_points,) vector in the native metric.  Column ids in each row are
    point row ids, ascending; distances (iff ``return_distance``) mirror
    the forward pair's value unchanged.
    """
    points = _as_rows(points)
    targets = _as_rows(targets)
    fwd = join(points, targets, radii, metric=metric, b_index=target_index,
               return_distance=return_distance, query_chunk=query_chunk,
               memory_budget_mb=memory_budget_mb, segment_rows=segment_rows,
               block=block, query_tile=query_tile, use_pallas=use_pallas,
               native=native, n_iter=n_iter, packed=packed, mixed=mixed)
    n_targets = targets.shape[0] if target_index is None else target_index.n
    indptr, rows, dists = transpose_csr(fwd.indptr, fwd.indices,
                                        fwd.distances, n_targets)
    return _snn.CSRNeighbors(indptr, rows, dists)
