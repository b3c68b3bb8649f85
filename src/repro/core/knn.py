"""Exact k-nearest-neighbor search on the sorted-projection index.

The paper's machinery is a fixed-radius search, but its pruning predicate is
per-query — and with the per-query radius vector threaded through the whole
engine, exact kNN becomes a small front-end instead of a new index
structure (contrast Hyvönen et al.'s tuned approximate indexes and Wang et
al.'s DP construction, PAPERS.md): find, for every query, any radius whose
ball provably holds >= k points, then take the k nearest inside that ball.
If ``count(q, r) >= k`` then the k-th smallest distance inside the ball is
<= r, and every point outside the ball is farther than r — so the k nearest
inside the ball are the k nearest globally.  Exactness never depends on how
the radii were found.

The search for the radii is where the sorted projection pays off twice:

* **seed** — by Cauchy–Schwarz, ``|alpha_p - alpha_q| <= ||p - q||`` for the
  unit projection direction, so the k-th smallest *projection gap*
  ``|alpha_i - alpha_q|`` (read off the sorted alphas with two binary
  searches per query) is a lower bound on the true k-th neighbor distance —
  a data-adapted starting radius, per query.  Because that bound collapses
  in higher dimensions, it is combined with a strided-sample distance
  estimate (`_sample_estimate`) that stays within a small constant factor
  of the true radius;
* **expand** — one engine COUNT pass (`engine.run_counts_packed`; no
  compaction, no flat output) checks all queries at once; only the
  under-filled queries' radii double (a per-query update — impossible under
  a scalar-radius contract), and only they re-enter the next count pass.
  Counts are monotone in r and the radii are capped by a diameter bound, so
  the loop terminates; in practice the seed is tight and 0–2 doublings
  suffice.

One final count→compact execution (`engine.run_csr_packed`) materializes
every converged ball as CSR, survivor distances are re-derived in float64
from the candidate vectors (stabilizing the top-k order against the float32
half-norm cancellation), and a per-row select emits the k nearest.  The
final radii carry a small relative margin so a float32 boundary rounding
cannot exclude a true neighbor whose distance sits exactly at the validated
radius.

`engine.DISPATCH_STATS` counts the loop's count passes (``knn_rounds``),
the final pass's CSR entries (``knn_candidates``) and the neighbors asked
for (``knn_wanted``); the host work runs in ``snn.prep`` /
``snn.knn.round`` / ``snn.assemble`` profiler spans.

Works over a plain `snn.SNNIndex` or a `streaming.StreamingSNNIndex`
snapshot (base + LSM deltas through the same plan the radius path uses).
For mips, "k nearest" means the k largest inner products (the lifted
Euclidean distance is a monotone transform); for cosine/angular the
transforms are monotone too, so kNN in index space is kNN in the metric.
"""
from __future__ import annotations

import numpy as np
from jax.profiler import TraceAnnotation

from ..kernels import ops as _ops
from . import engine as _engine
from .join import count_pass as _join_count_pass
from . import metrics as _metrics
from . import snn as _snn

# final-pass radius inflation: absorbs float32 predicate rounding at the
# ball boundary (counts are monotone in r, so the margin only ever adds
# candidates, never drops one)
_RADIUS_MARGIN = 1e-3


def _resolve(index, block: int):
    """(owner, parts, pack) for an `SNNIndex` or a streaming index.

    ``owner`` holds the mu/v1/metric/xi every predicate derives from (the
    streaming base freezes them, so its first part is the owner); ``parts``
    are the alpha-sorted runs the seed reads; ``pack`` is the execution plan.
    """
    if hasattr(index, "plan") and hasattr(index, "parts"):  # streaming
        parts, _, pack = index._snapshot()
        return parts[0], list(parts), pack
    return index, [index], _engine.pack_from_index(index, block=block)


def _seed_radii(parts, aq: np.ndarray, k_eff: np.ndarray) -> np.ndarray:
    """Per-query k-th smallest projection gap over the union of sorted runs.

    For each part, the k nearest alphas to ``aq[i]`` lie inside the window
    of 2*K sorted positions around ``searchsorted(alphas, aq[i])`` — so the
    k-th smallest gap of the union is found inside the concatenation of
    those windows.  Out-of-range window slots read +inf (never a clipped
    duplicate, which would bias the seed low for nothing).
    """
    m = aq.shape[0]
    K = int(k_eff.max()) if m else 0
    if K == 0:
        return np.zeros(m, np.float64)
    aq64 = np.asarray(aq, np.float64)
    offs = np.arange(-K, K)
    gap_cols = []
    for p in parts:
        if p.n == 0:
            continue
        al = np.asarray(p.alphas, np.float64)
        pos = np.searchsorted(al, aq64)
        idx = pos[:, None] + offs[None, :]
        ok = (idx >= 0) & (idx < p.n)
        gaps = np.where(ok, np.abs(al[np.clip(idx, 0, p.n - 1)]
                                   - aq64[:, None]), np.inf)
        gap_cols.append(gaps)
    if not gap_cols:
        return np.zeros(m, np.float64)
    allg = np.sort(np.concatenate(gap_cols, axis=1), axis=1)
    return allg[np.arange(m), k_eff - 1]


def _sample_estimate(parts, xq: np.ndarray, k_eff: np.ndarray,
                     n_total: int, sample: int = 256) -> np.ndarray:
    """Data-driven starting radii from a strided database sample.

    The projection-gap seed is a provable lower bound but collapses in
    higher dimensions (alpha gaps shrink like 1/n while true distances
    don't), costing the expansion loop ~log2(true/seed) count passes.  The
    distance from each query to the ``ceil(k * S / n)``-th nearest of S
    evenly-strided sorted rows estimates the k-th neighbor distance with a
    dimension-robust bias of roughly ``(n / (k S))^(1/d)`` — close to 1 —
    so ``max(lower bound, estimate)`` usually converges in 0–2 passes.
    Purely advisory: over- or under-shooting costs work, never exactness.
    """
    m = xq.shape[0]
    rows = []
    for p in parts:
        if p.n:
            stride = max(p.n * len(parts) // sample, 1)
            rows.append(np.asarray(p.xs)[::stride])
    if not rows:
        return np.zeros(m, np.float64)
    s = np.concatenate(rows).astype(np.float64)
    xq64 = xq.astype(np.float64)
    sq = (np.einsum("ij,ij->i", xq64, xq64)[:, None]
          + np.einsum("ij,ij->i", s, s)[None, :] - 2.0 * (xq64 @ s.T))
    sq = np.sort(np.maximum(sq, 0.0), axis=1)
    k_s = np.clip((k_eff * sq.shape[1] + n_total - 1) // max(n_total, 1),
                  1, sq.shape[1])
    return np.sqrt(sq[np.arange(m), k_s - 1])


# the expansion loop's count primitive is the join core's pass-1-only
# front-end (`core.join.count_pass`): each round is a single-chunk
# count-only join of the still-active queries against the whole pack.
# Bucketed padding matters most here — the loop re-enters with a shrinking
# active subset each round, and without the ladder every round's batch size
# would compile a fresh count executable.
_count_pass = _join_count_pass


def _fetch_rows(parts, ids: np.ndarray) -> np.ndarray:
    """Candidate vectors (len(ids), d) in index space, by original id.

    Every part's ``order`` maps its sorted rows to original ids; inverting
    the union once is O(n) without the O(n*d) cost of materializing the
    concatenated database.
    """
    n_total = sum(p.n for p in parts)
    part_of = np.empty(n_total, np.int32)
    local = np.empty(n_total, np.int64)
    for j, p in enumerate(parts):
        part_of[p.order] = j
        local[p.order] = np.arange(p.n)
    d = parts[0].xs.shape[1]
    out = np.empty((ids.shape[0], d), np.float32)
    for j, p in enumerate(parts):
        sel = part_of[ids] == j
        if sel.any():
            out[sel] = np.asarray(p.xs)[local[ids[sel]]]
    return out


def query_knn(
    index,
    q: np.ndarray,
    k,
    return_distance: bool = True,
    *,
    native: bool = True,
    block: int = 512,
    query_tile: int = 128,
    use_pallas: bool | str | None = None,
    memory_budget_mb: float | None = None,
    max_rounds: int = 100,
    mixed: bool = False,
    bucket: bool = True,
):
    """Exact k nearest neighbors of each query (indices and distances).

    Args:
      index: `snn.SNNIndex` or `streaming.StreamingSNNIndex`.
      q: (m, d) or (d,) queries in the raw metric space.
      k: neighbors per query — a scalar or a per-query (m,) int vector
        (mixed-k batches run as one fused search, exactly like mixed radii).
      return_distance: also return the (m, K) distances.
      native: distances in the index's metric (euclidean distance, cosine
        distance, angle, or inner product for mips — for mips the columns
        descend, largest inner product first); False leaves them as squared
        Euclidean in index space.
      block / query_tile / use_pallas / memory_budget_mb / bucket: engine
        knobs, as in `snn.query_radius_csr` (``bucket`` pads the shrinking
        expansion-loop batches onto the geometric ladder, so the loop costs
        O(log m) compiles instead of one per round).

    Returns:
      ``indices`` (m, K) int64 with K = max(k): column j is the (j+1)-th
      nearest neighbor's original row id, distances ascending (ties broken
      by id).  When a query asks for more neighbors than the database holds
      (k > n), the tail columns carry id -1 and distance +inf.
      With ``return_distance`` the result is ``(indices, distances)``.
    """
    with TraceAnnotation("snn.prep"):
        owner, parts, pack = _resolve(index, block)
        tq_ = _metrics.transform_query(np.asarray(q), owner.metric)
        xq = (tq_ - owner.mu[None, :]).astype(np.float32)
        m = xq.shape[0]
        n_total = sum(p.n for p in parts)

        k_arr = np.asarray(k, np.int64)
        k_arr = np.full(m, int(k_arr), np.int64) if k_arr.ndim == 0 \
            else k_arr
        if k_arr.shape != (m,):
            raise ValueError(f"k must be a scalar or per-query ({m},) "
                             f"vector; got shape {k_arr.shape}")
        if (k_arr < 0).any():
            raise ValueError("k must be >= 0")
        K_out = int(k_arr.max()) if m else 0
        out_idx = np.full((m, K_out), -1, np.int64)
        out_sq = np.full((m, K_out), np.inf, np.float64)
        k_eff = np.minimum(k_arr, n_total)

    if m and n_total and k_eff.max() > 0:
        stats = _engine.DISPATCH_STATS
        stats.knn_wanted += int(k_eff.sum())
        with TraceAnnotation("snn.prep"):
            # the predicate inputs the engine sees (float32, computed ONCE)
            # and their float64 twins for the seed/cap arithmetic
            aq = (xq @ owner.v1).astype(np.float32)
            pq = _snn.query_extra_projections(owner, xq)
            qsq32 = np.einsum("ij,ij->i", xq, xq)
            aq64 = (xq.astype(np.float64) @ owner.v1.astype(np.float64))
            qsq64 = np.einsum("ij,ij->i", xq.astype(np.float64), xq)
            # diameter bound in centered index space: every distance is at
            # most max ||x|| + ||q||; inflated so float32 boundary rounding
            # at the cap still admits all n points (the loop's termination
            # guarantee)
            max_half = max((float(np.max(p.half_norms)) if p.n else 0.0)
                           for p in parts)
            ub = (np.sqrt(2.0 * max(max_half, 0.0)) + np.sqrt(qsq64)) \
                * 1.01 + 1e-6

            r = np.minimum(
                np.maximum(_seed_radii(parts, aq64, np.maximum(k_eff, 1)),
                           _sample_estimate(parts, xq, np.maximum(k_eff, 1),
                                            n_total)),
                ub)
            active = np.nonzero(k_eff > 0)[0]
        for rnd in range(max_rounds):
            with TraceAnnotation("snn.knn.round", round=rnd,
                                 active=int(active.size)):
                counts = _count_pass(
                    pack, xq[active], aq[active], qsq32[active], r[active],
                    query_tile=query_tile, use_pallas=use_pallas,
                    memory_budget_mb=memory_budget_mb,
                    pq=None if pq is None else pq[:, active],
                    mixed=mixed, bucket=bucket)
            stats.knn_rounds += 1
            short = counts < k_eff[active]
            if not short.any():
                break
            grow = active[short]
            already_capped = r[grow] >= ub[grow]
            r[grow] = np.minimum(
                np.where(r[grow] > 0, 2.0 * r[grow], 1e-3 * ub[grow]),
                ub[grow])
            if already_capped.all():
                break  # cannot hold: nothing left to expand
            active = grow

        # final count->compact on the converged radii (+margin); the engine
        # recounts internally with the same predicate pipeline, so every row
        # is complete — the loop above was advisory, not load-bearing
        with TraceAnnotation("snn.prep"):
            r_fin = np.where(k_eff > 0, r * (1.0 + _RADIUS_MARGIN), 0.0)
            # k == 0 rows must match nothing at all (not even themselves)
            r_fin[k_eff == 0] = -1.0
            thresh = ((r_fin * r_fin - qsq32) / 2.0).astype(np.float32)
            thresh[k_eff == 0] = np.float32(-_ops.BIG)
            qp, aqp, rp, thp, _ = _ops.pad_queries(
                xq, aq, r_fin.astype(np.float32), thresh, tq=query_tile,
                bucket=bucket)
            pqp = None if pq is None else _ops.pad_components(pq,
                                                              qp.shape[0])
        indptr, _, flat_ids, _ = _engine.run_csr_packed(
            pack, qp, aqp, rp, thp, m, query_tile=query_tile,
            use_pallas=use_pallas, memory_budget_mb=memory_budget_mb,
            pq=pqp, mixed=mixed)
        stats.knn_candidates += int(indptr[-1])

        # float64 distance refinement on the survivors: the half-norm trick
        # loses low bits to cancellation exactly where kNN ordering needs
        # them; recomputing ||x - q||^2 from the candidate vectors keeps the
        # select stable against float32 near-ties
        with TraceAnnotation("snn.assemble"):
            vecs = _fetch_rows(parts, flat_ids).astype(np.float64)
            rows = np.repeat(np.arange(m), np.diff(indptr))
            diff = vecs - xq.astype(np.float64)[rows]
            sq = np.einsum("ij,ij->i", diff, diff)
            for i in range(m):
                s, e = int(indptr[i]), int(indptr[i + 1])
                kk = min(int(k_eff[i]), e - s)
                if kk == 0:
                    continue
                order = np.lexsort((flat_ids[s:e], sq[s:e]))[:kk]
                out_idx[i, :kk] = flat_ids[s:e][order]
                out_sq[i, :kk] = sq[s:e][order]

    if not return_distance:
        return out_idx
    if not native:
        return out_idx, out_sq
    with TraceAnnotation("snn.assemble"):
        return out_idx, _metrics.native_knn_distances(
            out_idx, out_sq, owner.metric, owner.xi, tq_)
