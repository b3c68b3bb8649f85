"""Two-pass exact CSR engine: equivalence to the host Algorithm-2 oracle.

`query_radius_csr` (pass-1 count + prefix sum + pass-2 Pallas compaction, run
in interpret mode here) must return bit-identical index sequences and matching
distances to `query_radius_batch` — across metrics, block-misaligned n,
empty-result queries and both kernel/oracle dispatches.
"""
import numpy as np
import pytest
from _hyp_compat import given, settings, st

from repro.core import (build_index, query_radius_batch, query_radius_csr)
from repro.core.sharded import prepare_query_arrays
from repro.kernels import ops, ref
from repro.kernels.snn_query import snn_compact, snn_count

# hypothesis-heavy full-lane suite: excluded from the fail-fast CI smoke lane
pytestmark = pytest.mark.slow


def _assert_csr_matches_batch(index, q, radius, csr, atol=1e-5):
    want = query_radius_batch(index, q, radius)
    assert csr.m == q.shape[0]
    assert csr.indptr[0] == 0 and csr.nnz == sum(len(i) for i, _ in want)
    for i in range(csr.m):
        wi, wd = want[i]
        gi, gd = csr.row(i)
        # bit-identical ids in identical (ascending sorted-db) order
        assert gi.tolist() == wi.tolist(), i
        np.testing.assert_allclose(gd, wd, atol=atol)


# derandomize: the engine evaluates its radius test on f32 inputs while the
# host oracle keeps the threshold in f64 — for a fresh random draw a pair
# sitting exactly between the two thresholds could (measure-zero but nonzero)
# split the paths, and exact-equality assertions must not be flaky in CI.
@settings(max_examples=10, deadline=None, derandomize=True)
@given(seed=st.integers(0, 10_000), n=st.integers(2, 700),
       rscale=st.floats(0.2, 2.0),
       metric=st.sampled_from(["euclidean", "cosine", "angular", "mips"]))
def test_csr_matches_batch_property(seed, n, rscale, metric):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 7)).astype(np.float32) + 0.1
    q = rng.normal(size=(9, 7)).astype(np.float32) + 0.1
    radius = {"euclidean": 1.5 * rscale, "cosine": 0.3 * rscale,
              "angular": 0.6 * rscale, "mips": rscale}[metric]
    index = build_index(x, metric=metric)
    for use_pallas in (False, True):  # jnp oracle and interpret-mode kernels
        csr = query_radius_csr(index, q, radius, block=128, query_tile=64,
                               use_pallas=use_pallas)
        _assert_csr_matches_batch(index, q, radius, csr)


@pytest.mark.parametrize("n", [1, 127, 128, 129, 513])  # not block multiples
def test_csr_block_misaligned_n(n):
    rng = np.random.default_rng(n)
    x = rng.normal(size=(n, 5)).astype(np.float32)
    q = rng.normal(size=(6, 5)).astype(np.float32)
    index = build_index(x)
    csr = query_radius_csr(index, q, 2.0, block=128, query_tile=64,
                           use_pallas=True)
    _assert_csr_matches_batch(index, q, 2.0, csr)


def test_csr_empty_results_and_mixed():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(300, 6)).astype(np.float32)
    # half the queries are far away -> empty rows interleaved with full ones
    q = np.concatenate([rng.normal(size=(5, 6)), 100.0 + rng.normal(size=(5, 6))],
                       0).astype(np.float32)[np.argsort(rng.random(10))]
    index = build_index(x)
    csr = query_radius_csr(index, q, 2.0, block=128, query_tile=64,
                           use_pallas=True)
    _assert_csr_matches_batch(index, q, 2.0, csr)
    assert any(len(csr.row(i)[0]) == 0 for i in range(10))
    assert any(len(csr.row(i)[0]) > 0 for i in range(10))


def test_csr_all_empty():
    rng = np.random.default_rng(1)
    x = rng.normal(size=(100, 4)).astype(np.float32)
    index = build_index(x)
    q = (100.0 + rng.normal(size=(3, 4))).astype(np.float32)
    for use_pallas in (False, True):
        csr = query_radius_csr(index, q, 0.5, use_pallas=use_pallas)
        assert csr.nnz == 0 and csr.m == 3
        assert csr.indices.size == 0 and csr.distances.size == 0


def test_csr_whole_database_radius():
    """Huge radius: every CSR row is the full database."""
    rng = np.random.default_rng(2)
    x = rng.normal(size=(150, 5)).astype(np.float32)
    index = build_index(x)
    q = rng.normal(size=(4, 5)).astype(np.float32)
    csr = query_radius_csr(index, q, 1e6, block=128, query_tile=64,
                           use_pallas=True)
    assert csr.nnz == 4 * 150
    for i in range(4):
        assert sorted(csr.row(i)[0].tolist()) == list(range(150))


def test_csr_native_false_returns_sq_euclidean():
    rng = np.random.default_rng(3)
    x = rng.normal(size=(200, 6)).astype(np.float32)
    q = rng.normal(size=(5, 6)).astype(np.float32)
    index = build_index(x)
    sq = query_radius_csr(index, q, 2.0, native=False)
    nat = query_radius_csr(index, q, 2.0)
    np.testing.assert_allclose(np.sqrt(sq.distances), nat.distances, atol=1e-6)


def _compact_args(seed, n, d, m, radius, tq=64, bn=128):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    q = rng.normal(size=(m, d)).astype(np.float32)
    index = build_index(x)
    xs, al, hn, _, _ = ops.pad_database(index.xs, index.alphas,
                                        index.half_norms, bn=bn)
    xq, aq, r, th = prepare_query_arrays(index, q, radius)
    qp, aqp, rp, thp, _ = ops.pad_queries(
        np.asarray(xq), np.asarray(aq), np.asarray(r), np.asarray(th), tq=tq)
    cnt = np.asarray(snn_count(qp, aqp, rp, thp, xs, al, hn,
                               tq=tq, bn=bn, interpret=True))[:m]
    indptr = np.concatenate([[0], np.cumsum(cnt)]).astype(np.int64)
    total = int(indptr[-1])
    cap = ops.csr_capacity(total)
    import jax.numpy as jnp
    off = jnp.asarray(np.concatenate(
        [indptr[:-1], np.full(qp.shape[0] - m, total)]).astype(np.int32))
    return (qp, aqp, rp, thp, off, xs, al, hn), cap


@pytest.mark.parametrize("n,d,m,radius", [(700, 12, 23, 2.0), (129, 5, 7, 1.0),
                                          (1024, 40, 64, 3.5)])
def test_compact_kernel_matches_ref(n, d, m, radius):
    """Interpret-mode Pallas compaction == jnp scatter oracle, slot for slot."""
    args, cap = _compact_args(0, n, d, m, radius)
    ik, dk = snn_compact(*args, nnz=cap, tq=64, bn=128, interpret=True)
    ir, dr = ref.snn_compact_ref(*args, nnz=cap)
    assert np.asarray(ik).tolist() == np.asarray(ir).tolist()
    np.testing.assert_allclose(np.asarray(dk), np.asarray(dr), rtol=1e-6)


def _placement_inputs(rng, n, m, radius, bn, shift=0.0):
    """Sorted euclidean rows and queries in the kernels' padded layout.

    alpha is the first coordinate, so rows ascend in alpha, and ``thresh``
    is the half-norm test of |x - q| <= r.  ``radius`` is one value a query
    row; NaN marks a row as padding (r = thresh = -BIG, matches nothing).
    """
    d = 6
    x = rng.normal(size=(n, d)).astype(np.float32) + np.float32(shift)
    x = x[np.argsort(x[:, 0], kind="stable")]
    q = rng.normal(size=(m, d)).astype(np.float32)
    r = np.asarray(radius, np.float32)
    th = (r * r - (q * q).sum(1)) / np.float32(2)
    pad = np.isnan(r)
    r, th = np.where(pad, -ops.BIG, r), np.where(pad, -ops.BIG, th)
    xs, al, hn, _, _ = ops.pad_database(x, x[:, 0], (x * x).sum(1) / 2, bn=bn)
    qp, aqp, rp, thp, _ = ops.pad_queries(q, q[:, 0], r.astype(np.float32),
                                          th.astype(np.float32), tq=128)
    return (qp, aqp, rp, thp), (xs, al, hn)


def _runs(idx, offsets, counts, bn):
    """(first slot, last slot, length) of every (query row, db block) run."""
    out = []
    for o, c in zip(offsets, counts):
        cols = np.asarray(idx[o:o + c])
        for b in np.unique(cols // bn):
            slots = o + np.flatnonzero(cols // bn == b)
            out.append((int(slots[0]), int(slots[-1]), slots.size))
    return out


@pytest.mark.parametrize("case", ["lone_row", "group_edges", "tile_crossing",
                                  "full_block", "empty_second_segment"])
def test_compact_placement_matches_ref(case):
    """Pass 2 places only the rows with survivors: every edge of the row
    loop lands each run in the oracle's slots, slot for slot."""
    from repro.kernels.snn_query import snn_compact_stacked
    import jax.numpy as jnp

    rng = np.random.default_rng(14)
    tq, bn, n = 128, 128, 512
    nan = np.float32("nan")
    if case == "lone_row":  # one real row; pad_queries adds 127 padding rows
        radius = [2.5]
    elif case == "group_edges":  # survivors only in rows 7, 8 and tq - 1
        radius = np.full(tq, nan)
        radius[[7, 8, tq - 1]] = 2.5
    else:  # one row that takes every db row, beside a padding row
        radius = [50.0, nan]
    qa, xa = _placement_inputs(rng, n, len(radius), radius, bn)
    if case == "empty_second_segment":
        _, xb = _placement_inputs(rng, n, 1, [1.0], bn, shift=100.0)
        stack = tuple(jnp.stack([a, b]) for a, b in zip(xa, xb))
        per = ref.snn_count_stacked_ref(*qa, *stack, n_seg=2)
        assert int(per[0].sum()) == n and int(per[1].sum()) == 0
        _, indptr, off = ref.stacked_prefix(per)
        cap = ops.csr_capacity(int(indptr[-1]))
        ik, dk = snn_compact_stacked(*qa, off, *stack, nnz=cap, tq=tq, bn=bn,
                                     interpret=True)
        ir, dr = ref.snn_compact_stacked_ref(*qa, off, *stack, n_seg=2,
                                             nnz=cap)
        counts, offsets = np.asarray(per[0]), np.asarray(off[0])
    else:
        counts = np.asarray(ref.snn_count_ref(*qa, *xa))
        # the crossing case starts its row 4 slots short of the second
        # 1,024-slot output tile, so its first block's run spans both
        start = 1020 if case == "tile_crossing" else 0
        offsets = start + np.concatenate([[0], np.cumsum(counts)[:-1]])
        total = start + int(counts.sum())
        offsets[counts == 0] = total  # padding rows: any slot below nnz
        cap = ops.csr_capacity(total)
        off = jnp.asarray(offsets.astype(np.int32))
        ik, dk = snn_compact(*qa, off, *xa, nnz=cap, tq=tq, bn=bn,
                             interpret=True)
        ir, dr = ref.snn_compact_ref(*qa, off, *xa, nnz=cap)
    assert np.asarray(ik).tolist() == np.asarray(ir).tolist()
    np.testing.assert_allclose(np.asarray(dk), np.asarray(dr), rtol=1e-6)

    # the case exercised the edge it names
    runs = _runs(np.asarray(ik), offsets, counts, bn)
    assert runs
    active = np.flatnonzero(counts).tolist()
    if case == "lone_row":
        assert active == [0]
    elif case == "group_edges":
        assert active == [7, 8, tq - 1]
    elif case == "tile_crossing":
        assert any(a >> 10 != b >> 10 for a, b, _ in runs)
    else:
        assert any(c == bn for _, _, c in runs)


def test_csr_capacity_bucketing():
    assert ops.csr_capacity(0) == 128
    assert ops.csr_capacity(127) == 128
    assert ops.csr_capacity(128) == 256     # +1 trash slot forces next bucket
    assert ops.csr_capacity(1000) == 1024
    assert ops.csr_capacity(1024) == 2048
