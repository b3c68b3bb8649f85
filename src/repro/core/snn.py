"""SNN — sorting-based exact fixed-radius near-neighbor search (paper Alg. 1 & 2).

Three query paths are provided:

* the **host path** (`query_radius`, `query_radius_batch`): exact, variable-length
  results, BLAS (numpy matmul) over the contiguous sorted window — a faithful
  implementation of the paper's Algorithm 2 including the grouped level-3 BLAS
  batch trick.
* the **fixed-shape path** (`query_radius_fixed`): jit-friendly block-pruned
  filter used on TPU; dense (m, n) intermediate and K-truncated output.
* the **two-pass CSR path** (`query_radius_csr`): the device engine of record —
  a single-chunk front-end over the bichromatic join core (`core.join`, which
  drives `core.engine`: pass-1 count, host prefix sum, pass-2 compaction
  scattering survivors straight into their CSR slots).  Exact
  variable-length results with peak device memory O(total_neighbors + m)
  instead of O(m * n).  The same join core serves the sharded
  (`core.sharded`), streaming (`core.streaming`), graph (`core.graph`) and
  reverse/count-only (`core.join`) front-ends.

The index is built with a jit-compiled power iteration for the first principal
component.  Exactness of SNN never depends on the accuracy of v1 (any direction
yields a valid Cauchy–Schwarz window); v1 only tightens the window.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import metrics as _metrics


# --------------------------------------------------------------------------- #
# Index                                                                        #
# --------------------------------------------------------------------------- #
@dataclasses.dataclass
class SNNIndex:
    """Output of Algorithm 1 (plus bookkeeping to undo the sort).

    Attributes:
      mu:         (d,) empirical mean of the (transformed) data.
      v1:         (d,) first principal direction (unit norm).
      xs:         (n, d) centered data, sorted ascending by alpha.
      alphas:     (n,) sorted scores ``xs @ v1``.
      half_norms: (n,) ``(x.x)/2`` per sorted row.
      order:      (n,) original row index of each sorted row.
      metric:     one of metrics.VALID_METRICS.
      xi:         max raw-data norm (mips lift only).
      vs:         (k, d) pruning directions, row 0 is exactly ``v1``.  Any
                  basis is VALID (each row has norm <= 1, so every row yields
                  a Cauchy–Schwarz bound); accuracy only tightens the box.
      projs:      (k, n) per-sorted-row projections ``xs @ vs[c]``; row 0 is
                  bit-for-bit equal to ``alphas``, so single-component
                  behavior is identical to historical builds.
    """

    mu: np.ndarray
    v1: np.ndarray
    xs: np.ndarray
    alphas: np.ndarray
    half_norms: np.ndarray
    order: np.ndarray
    metric: str = "euclidean"
    xi: float = 0.0
    vs: np.ndarray | None = None
    projs: np.ndarray | None = None

    def __post_init__(self):
        # legacy constructions (tests, streaming deltas before PR 6) omit the
        # multi-component fields; degrade to the single-component basis
        if self.vs is None:
            self.vs = np.asarray(self.v1)[None, :]
        if self.projs is None:
            self.projs = np.asarray(self.alphas)[None, :]

    @property
    def n(self) -> int:
        return self.xs.shape[0]

    @property
    def d(self) -> int:
        return self.xs.shape[1]

    def prepare_queries(self, q: np.ndarray, radius) -> tuple[np.ndarray, np.ndarray]:
        """Transform+center queries; return (xq (m,d), per-query Euclidean radii).

        ``radius`` is a scalar (broadcast) or a per-query (m,) vector in the
        native metric — the canonical representation every query path below
        this point works in is the per-query vector.
        """
        tq = _metrics.transform_query(np.asarray(q), self.metric)
        r = _metrics.euclidean_radius(radius, tq, self.metric, self.xi)
        return (tq - self.mu[None, :]).astype(self.xs.dtype), r.astype(np.float64)


@partial(jax.jit, static_argnames=("n_iter",))
def _power_iteration(x: jnp.ndarray, n_iter: int = 64) -> jnp.ndarray:
    """First right singular vector of centered x via power iteration on X^T X.

    O(n d) per iteration; deterministic start from the dimension of largest
    variance so the result is reproducible.
    """
    var = jnp.var(x, axis=0)
    v0 = jax.nn.one_hot(jnp.argmax(var), x.shape[1], dtype=x.dtype)

    def body(_, v):
        w = x.T @ (x @ v)
        return w / jnp.maximum(jnp.linalg.norm(w), 1e-30)

    v = jax.lax.fori_loop(0, n_iter, body, v0)
    # Fix the sign for determinism: largest-|component| is positive.
    s = jnp.sign(v[jnp.argmax(jnp.abs(v))])
    return v * jnp.where(s == 0, 1.0, s)


def _extra_components(xs: np.ndarray, v1: np.ndarray, alphas: np.ndarray,
                      n_components: int, n_iter: int) -> tuple[np.ndarray, np.ndarray]:
    """Deflation power iteration for components 2..k over the sorted data.

    Row 0 of the returned (vs, projs) reuses ``v1``/``alphas`` verbatim, so
    component-0 behavior (windows, thresholds) is bit-identical to
    single-component builds.  Each deflated direction has norm <= 1 (the
    normalization divides by max(||w||, 1e-30)), which is all the
    Cauchy–Schwarz box bound needs — imperfect deflation or convergence only
    makes the box looser, never wrong.
    """
    n, d = xs.shape
    k = max(1, min(int(n_components), max(d, 1)))
    vs = [np.asarray(v1)]
    projs = [np.asarray(alphas)]
    if k > 1:
        xj = jnp.asarray(xs)
        vj = jnp.asarray(v1)
        resid = xj - jnp.asarray(alphas)[:, None] * vj[None, :]
        for _ in range(k - 1):
            vc = _power_iteration(resid, n_iter=n_iter)
            vs.append(np.asarray(vc))
            # project the ORIGINAL data: exact orthogonality is not required.
            # HIGHEST: these projections meet the numpy-projected queries in
            # the box test, so they must be f32-accurate on every platform
            projs.append(np.asarray(jnp.matmul(
                xj, vc, precision=jax.lax.Precision.HIGHEST)))
            resid = resid - (resid @ vc)[:, None] * vc[None, :]
    return (np.ascontiguousarray(np.stack(vs)),
            np.ascontiguousarray(np.stack(projs)))


def build_index(
    p: np.ndarray,
    metric: str = "euclidean",
    n_iter: int = 64,
    dtype=np.float32,
    n_components: int = 3,
) -> SNNIndex:
    """Algorithm 1: center, score by first PC, sort, precompute half-norms.

    ``n_components`` extra principal directions (deflation power iteration)
    are stored for the k-dim box prune; clamped to [1, max(d, 1)].  Component
    0 is always the historical v1/alphas pair, so results are identical for
    any setting — extra components only prune more work.
    """
    x_raw, xi = _metrics.transform_data(np.asarray(p), metric)
    x_raw = x_raw.astype(dtype)
    # an empty database has no mean; zeros keep every downstream predicate
    # finite (a NaN mu would poison query centering even though the result
    # set is necessarily empty)
    mu = x_raw.mean(axis=0) if x_raw.shape[0] else np.zeros(x_raw.shape[1], dtype)
    x = x_raw - mu[None, :]
    if x.shape[0] == 0 or x.shape[1] == 0:
        # n == 0: nothing to sort; d == 0: every point is the origin and
        # power iteration has no dimension to pick — alphas are all zero
        # (v1 = 0 still yields a valid Cauchy–Schwarz window)
        n, d = x.shape
        return SNNIndex(mu, np.zeros(d, dtype), x, np.zeros(n, dtype),
                        np.zeros(n, dtype), np.arange(n, dtype=np.int64),
                        metric, xi)
    v1 = np.asarray(_power_iteration(jnp.asarray(x), n_iter=n_iter))
    alphas = x @ v1
    order = np.argsort(alphas, kind="stable")
    xs = np.ascontiguousarray(x[order])
    alphas = np.ascontiguousarray(alphas[order])
    half_norms = 0.5 * np.einsum("ij,ij->i", xs, xs)
    vs, projs = _extra_components(xs, v1, alphas, n_components, n_iter)
    return SNNIndex(mu, v1, xs, alphas, half_norms, order.astype(np.int64),
                    metric, xi, vs, projs)


# --------------------------------------------------------------------------- #
# Exact host queries (Algorithm 2)                                             #
# --------------------------------------------------------------------------- #
def _window(index: SNNIndex, aq: np.ndarray, r: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    lo = np.searchsorted(index.alphas, aq - r, side="left")
    hi = np.searchsorted(index.alphas, aq + r, side="right")
    return lo, hi


def query_radius(
    index: SNNIndex, q: np.ndarray, radius, return_distance: bool = True
):
    """Exact radius query for a single query point.

    Returns (indices, distances) into the ORIGINAL data ordering; distances are
    in the native metric (euclidean distance, cosine distance, angle, or inner
    product for mips).
    """
    xq, r = index.prepare_queries(q, radius)
    xq, r = xq[0], float(r[0])
    aq = float(xq @ index.v1)
    lo, hi = _window(index, np.asarray([aq]), np.asarray([r]))
    lo, hi = int(lo[0]), int(hi[0])
    if hi <= lo:
        out_i = np.zeros(0, np.int64)
        return (out_i, np.zeros(0, np.float64)) if return_distance else out_i
    win = index.xs[lo:hi]
    # Paper eq. (4): half-norm form, one GEMV over the contiguous window.
    dhalf = index.half_norms[lo:hi] - win @ xq
    qsq = float(xq @ xq)
    keep = dhalf <= (r * r - qsq) / 2.0
    sel = np.nonzero(keep)[0] + lo
    out_i = index.order[sel]
    if not return_distance:
        return out_i
    sq = np.maximum(2.0 * dhalf[keep] + qsq, 0.0)
    return out_i, _native_distance(index, sq, xq)


def _native_distance(index: SNNIndex, sq_eucl: np.ndarray, xq: np.ndarray) -> np.ndarray:
    """Convert squared Euclidean distances (in index space) to the native metric."""
    return _native_distance_csr(index, sq_eucl, xq[None, :],
                                np.asarray([sq_eucl.shape[0]]))


def query_radius_batch(
    index: SNNIndex,
    q: np.ndarray,
    radius,
    return_distance: bool = True,
    group_size: int = 64,
):
    """Exact batched radius query (paper §4, level-3 BLAS variant).

    Queries are sorted by their alpha score and processed in groups; each group
    computes one GEMM over the union of its members' windows.  Returns a list of
    per-query results in the original query order.  ``radius`` is a scalar or a
    per-query (m,) vector in the native metric — the pruning predicate is
    per-query, so nothing here ever assumes a shared radius.
    """
    xq, r = index.prepare_queries(q, radius)
    m = xq.shape[0]
    aq = xq @ index.v1
    lo, hi = _window(index, aq, r)
    qord = np.argsort(aq, kind="stable")
    results: list = [None] * m
    qsq = np.einsum("ij,ij->i", xq, xq)
    for g0 in range(0, m, group_size):
        grp = qord[g0 : g0 + group_size]
        glo, ghi = int(lo[grp].min()), int(hi[grp].max())
        if ghi <= glo:
            for qi in grp:
                e = np.zeros(0, np.int64)
                results[qi] = (e, np.zeros(0, np.float64)) if return_distance else e
            continue
        win = index.xs[glo:ghi]
        # one GEMM for the whole group: (ghi-glo, d) @ (d, |grp|)
        dhalf = index.half_norms[glo:ghi, None] - win @ xq[grp].T
        for k, qi in enumerate(grp):
            s, e = lo[qi] - glo, hi[qi] - glo
            dh = dhalf[s:e, k]
            keep = dh <= (r[qi] * r[qi] - qsq[qi]) / 2.0
            sel = np.nonzero(keep)[0] + lo[qi]
            oi = index.order[sel]
            if return_distance:
                sqd = np.maximum(2.0 * dh[keep] + qsq[qi], 0.0)
                results[qi] = (oi, _native_distance(index, sqd, xq[qi]))
            else:
                results[qi] = oi
    return results


def query_counts(index: SNNIndex, q: np.ndarray, radius, group_size: int = 64) -> np.ndarray:
    """Number of neighbors within radius for each query (exact, batched)."""
    res = query_radius_batch(index, q, radius, return_distance=False, group_size=group_size)
    return np.asarray([len(r) for r in res], dtype=np.int64)


# --------------------------------------------------------------------------- #
# Fixed-shape (jit / TPU) path                                                 #
# --------------------------------------------------------------------------- #
@partial(jax.jit, static_argnames=("block",))
def _blocked_filter(xs, alphas, half_norms, xq, aq, r, block: int):
    """Pure-jnp block-pruned filter; the oracle for kernels/snn_query.

    Returns (m, n_padded) halved squared distances with +inf outside the window /
    radius.  Blocks that cannot intersect any query window still cost a masked
    matmul here (XLA has no dynamic skip) — the Pallas kernel adds the true skip.
    """
    n, d = xs.shape
    m = xq.shape[0]
    dhalf = half_norms[None, :] - xq @ xs.T  # (m, n)
    inwin = jnp.abs(alphas[None, :] - aq[:, None]) <= r[:, None]
    qsq = jnp.sum(xq * xq, axis=1)
    keep = inwin & (dhalf <= ((r * r - qsq) / 2.0)[:, None])
    big = jnp.asarray(jnp.finfo(dhalf.dtype).max / 8, dhalf.dtype)
    return jnp.where(keep, dhalf, big)


def query_radius_fixed(index: SNNIndex, q: np.ndarray, radius, max_neighbors: int,
                       block: int = 512):
    """Fixed-shape query: returns (indices (m,K), sq_dists (m,K), valid (m,K)).

    K = max_neighbors; results are the K nearest within the radius (exact as long
    as the true neighbor count <= K; the count output lets callers detect
    truncation).  ``radius`` is a scalar or per-query (m,) vector in the native
    metric.  This is the API the serving fallback and TPU top-K path use.
    """
    from ..kernels import ops as _ops

    if index.n == 0:
        # ``order[idx % n]`` below would divide by zero; an empty database
        # has well-defined results: K = min(max_neighbors, 0) = 0 columns
        m = _metrics.transform_query(np.asarray(q), index.metric).shape[0]
        return (np.zeros((m, 0), np.int64), np.zeros((m, 0), np.float64),
                np.zeros((m, 0), bool), np.zeros(m, np.int64))
    # one padding contract for every path: rows to a block multiple with the
    # +BIG sentinel, features to the 128-lane multiple (zeros: dot-neutral)
    xs, al, hn, _, d = _ops.pad_database(index.xs, index.alphas,
                                         index.half_norms, bn=block)
    xq, r = index.prepare_queries(q, radius)
    xq = jnp.asarray(np.pad(xq, ((0, 0), (0, xs.shape[1] - d))))
    aq = xq @ jnp.asarray(np.pad(index.v1, (0, xs.shape[1] - d)))
    rj = jnp.asarray(r, xq.dtype)
    dhalf = _blocked_filter(xs, al, hn, xq, aq, rj, block)
    big = jnp.finfo(dhalf.dtype).max / 8
    counts = jnp.sum(dhalf < big, axis=1)
    neg = -dhalf
    # top_k requires k <= padded n; a clamped K loses nothing (there are only
    # n candidates) and keeps small databases working with large-K configs
    k = min(max_neighbors, xs.shape[0])
    vals, idx = jax.lax.top_k(neg, k)  # largest -dhalf = smallest dist
    valid = vals > -big
    qsq = jnp.sum(xq * xq, axis=1)
    sq = jnp.maximum(2.0 * (-vals) + qsq[:, None], 0.0)
    order = jnp.asarray(index.order)
    out_idx = jnp.where(valid, order[idx % index.n], -1)
    return np.asarray(out_idx), np.asarray(jnp.where(valid, sq, np.inf)), \
        np.asarray(valid), np.asarray(counts)


# --------------------------------------------------------------------------- #
# Two-pass exact CSR engine                                                    #
# --------------------------------------------------------------------------- #
@dataclasses.dataclass
class CSRNeighbors:
    """Exact variable-length radius results in CSR form.

    Query i's neighbors occupy the flat slice ``indptr[i]:indptr[i+1]``.
    ``indices`` are original (pre-sort) row ids; within each row they ascend in
    sorted-database order, the same order `query_radius_batch` emits.
    ``distances`` (if requested) are in the index's native metric.
    """

    indptr: np.ndarray
    indices: np.ndarray
    distances: np.ndarray | None = None

    @property
    def m(self) -> int:
        return self.indptr.shape[0] - 1

    @property
    def nnz(self) -> int:
        return int(self.indptr[-1])

    def row(self, i: int):
        s, e = int(self.indptr[i]), int(self.indptr[i + 1])
        if self.distances is None:
            return self.indices[s:e]
        return self.indices[s:e], self.distances[s:e]

    def tolist(self) -> list:
        """Per-query results, matching the `query_radius_batch` return shape."""
        return [self.row(i) for i in range(self.m)]


def prepare_query_predicates(index: SNNIndex, q: np.ndarray, radius):
    """Float32 predicate inputs (xq, aq, r, thresh, qsq) for the device paths.

    Every device path (single, sharded, serving) must derive its window and
    half-norm tests from THIS computation: pass-1/pass-2 agreement of the CSR
    engine relies on both passes seeing bit-identical inputs.
    """
    xq, r = index.prepare_queries(q, radius)
    aq = (xq @ index.v1).astype(np.float32)
    qsq = np.einsum("ij,ij->i", xq, xq)
    thresh = ((r * r - qsq) / 2.0).astype(np.float32)
    return xq, aq, r.astype(np.float32), thresh, qsq


def query_extra_projections(index: SNNIndex, xq: np.ndarray) -> np.ndarray | None:
    """(ke, m) float32 EXTRA-component query projections for the box prune.

    ``xq`` is the centered index-space query block from `prepare_queries` /
    `prepare_query_predicates`.  Component 0 (``xq @ v1``) is deliberately NOT
    included: the engine's alpha window already covers it, and keeping it out
    preserves the historical ``aq`` values bit-for-bit (a (m, d) @ (d,) gemv
    and a column of a gemm may round differently).  Returns None when the
    index carries no extra components — the signal for every downstream layer
    to take the exact pre-multi-component code path.
    """
    vs = getattr(index, "vs", None)
    if vs is None or vs.shape[0] <= 1:
        return None
    return np.ascontiguousarray(
        (np.asarray(xq) @ vs[1:].T).T.astype(np.float32))


def _native_distance_csr(index: SNNIndex, sq_eucl: np.ndarray, xq: np.ndarray,
                         counts: np.ndarray) -> np.ndarray:
    """Vectorized `_native_distance` over a flat CSR distance array."""
    qsq_raw = None
    if index.metric == "mips":
        # index space is centered (and lifted); undo to recover ||q||^2
        qraw = xq + index.mu[None, :]
        qsq_raw = np.repeat(np.einsum("ij,ij->i", qraw, qraw), counts)
    return _metrics.native_distance(sq_eucl, index.metric, index.xi, qsq_raw)


def query_radius_csr(
    index: SNNIndex,
    q: np.ndarray,
    radius,
    return_distance: bool = True,
    block: int = 512,
    query_tile: int = 128,
    use_pallas: bool | str | None = None,
    native: bool = True,
    packed: bool = True,
    mixed: bool = False,
    bucket: bool = True,
    compacted: bool | None = None,
    fused: bool = True,
) -> CSRNeighbors:
    """Exact device radius query with CSR output (two passes, no (m, n) array).

    ``radius`` is a scalar or a per-query (m,) vector in the native metric:
    the per-query vector is the engine's canonical representation (the paper's
    window ``[alpha_q - r_q, alpha_q + r_q]`` never required a shared radius),
    and a scalar is just the broadcast convenience.  Mixed-radius batches cost
    exactly one engine dispatch, same as uniform ones — the contract the fused
    serving path and the kNN front-end (`core.knn`) are built on.

    A single-segment front-end over `core.engine`: pass 1 produces per-query
    neighbor counts, the prefix sums turn them into CSR row offsets, and pass
    2 re-runs the identical block-pruned filter and scatters each survivor
    into its final CSR slot.  Both passes see the same window + half-norm
    tests on the same float32 inputs, so pass-2 survivors are exactly the
    pass-1 counted points and every CSR row is filled completely — no
    truncation, no recount.

    ``packed=True`` (the default) executes through the plan/execute engine
    (`engine.query_csr_packed` over a one-segment `SegmentPack`, prefix sums
    on device); ``packed=False`` keeps the looped executor — the cross-check
    oracle, bit-identical by construction.  ``use_pallas=None`` dispatches to
    the Pallas kernels on TPU; elsewhere a single dense-filter evaluation
    feeds both passes (correctness reference, not the memory story; pass
    ``use_pallas=True`` off-TPU to force the kernels through interpret mode).

    ``mixed=True`` runs pass 1 (counts) with bf16 dot products under the
    margin certificate (kernels.ref module docstring); pass 2 stays f32, and
    the engine's pass-1/pass-2 agreement check then *validates* the
    certificate at runtime — the CSR output is bit-identical either way.

    ``bucket=True`` (the default) pads the batch to the geometric bucket
    ladder (`kernels.ops.bucket_rows`) so a stream of varying batch sizes
    reuses O(log m) compiled shapes; padding rows match nothing, so results
    are bit-identical to exact-multiple padding.

    ``compacted`` / ``fused`` (both on by default) are the sparse-execution
    knobs: candidate compaction evaluates the distance contraction only on
    gathered box survivors (the packed oracle's kq path), and the fused
    device path chains count → prefix → compact in one dispatch under
    capacity speculation (`engine._execute_stacked`).  Both are pure
    execution-strategy switches — output stays bit-identical; pass
    ``compacted=False`` / ``fused=False`` to pin the PR-6-era paths.

    Structurally, a point-query batch is the bichromatic join whose A side
    is a single chunk — this function delegates to `core.join.single_query`
    (imported lazily: the join core imports this module at load time), the
    same front-end the streaming index serves through.
    """
    from .join import single_query as _single_query

    return _single_query(index, q, radius, return_distance,
                         block=block, query_tile=query_tile,
                         use_pallas=use_pallas, native=native,
                         packed=packed, mixed=mixed, bucket=bucket,
                         compacted=compacted, fused=fused)


def csr_finalize(index: SNNIndex, indptr, indices, fd, xq, qsq, counts,
                 return_distance: bool, native: bool = True) -> CSRNeighbors:
    """Wrap flat original-id positions + dhalf values into a `CSRNeighbors`.

    ``native=False`` leaves distances as squared Euclidean in index space (the
    fixed-shape path's convention) instead of converting to the metric.
    """
    indices = np.asarray(indices, np.int64)
    if not return_distance:
        return CSRNeighbors(indptr, indices, None)
    fd = np.asarray(fd)
    sq = np.maximum(2.0 * fd.astype(np.float64) + np.repeat(qsq, counts), 0.0)
    if not native:
        return CSRNeighbors(indptr, indices, sq)
    return CSRNeighbors(indptr, indices, _native_distance_csr(index, sq, xq, counts))
