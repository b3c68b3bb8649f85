"""Live block ranges of the stacked kernels (`snn_query.block_ranges`).

Each (segment, query tile) of a stacked grid gets the range [lo, hi] of
db blocks that some row of the tile hits; the kernels' row-block index
maps clamp into it, so a pruned cell outside the range fetches no block.
Checked here:

* on random float32 stacks (several segments, +BIG padding rows and
  blocks, padding query rows with r = -BIG, a tile that hits nothing,
  windows ending exactly on a block's first or last alpha), every cell
  that a numpy float32 `_window_hit` finds hit lies in its tile's range,
  and both ends of a non-empty range are hit themselves;
* in interpret mode, on a d = 4 stack of two segments with narrow
  windows, where most cells lie outside their tile's range, the stacked
  count and compaction kernels and the fused chain of the pallas-tpu lane
  equal `kernels.ref`'s stacked references bit for bit, with the rows
  tiled in alpha order and in arrival order.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels import ops, ref, registry, snn_query

BIG = np.float32(snn_query.BIG)


def _hits(aq, r, alphas, tq, bn):
    """(S, tiles, blocks) bool: the kernels' `_window_hit`, in numpy f32."""
    n_seg, n = alphas.shape
    blocks = alphas.reshape(n_seg, n // bn, bn)
    a_lo, a_hi = blocks[:, :, 0], blocks[:, :, -1]
    hq = (aq + r).reshape(-1, tq)            # float32 throughout
    lq = (aq - r).reshape(-1, tq)
    return ((hq[None, :, :, None] >= a_lo[:, None, None, :])
            & (lq[None, :, :, None] <= a_hi[:, None, None, :])).any(axis=2)


def _ranges(aq, r, alphas, tq, bn):
    lo, hi = snn_query.block_ranges(jnp.asarray(aq), jnp.asarray(r),
                                    jnp.asarray(alphas), tq=tq, bn=bn)
    return np.asarray(lo), np.asarray(hi)


def _random_stack(rng, n_seg, nb, bn):
    """Ascending alphas a segment, with a ragged tail of +BIG padding rows
    (whole padding blocks in some segments), and a few repeated alphas."""
    al = np.full((n_seg, nb * bn), BIG, np.float32)
    for s in range(n_seg):
        n = int(rng.integers(1, nb * bn + 1))
        v = rng.normal(size=n).astype(np.float32)
        v[rng.random(n) < 0.1] = v[0]
        al[s, :n] = np.sort(v)
    al[0, (nb - 2) * bn:] = BIG       # two whole padding blocks
    return al


def _queries(rng, al, tiles, tq, bn):
    """Query rows: narrow windows, padding rows, one tile of padding rows
    alone, one tile whose windows miss every block, and windows that end
    exactly on a block's first or last alpha."""
    m = tiles * tq
    aq = rng.normal(size=m).astype(np.float32)
    r = rng.uniform(0.0, 0.2, size=m).astype(np.float32)
    r[rng.random(m) < 0.2] = -BIG
    r[:tq] = -BIG                               # tile 0: padding rows only
    aq[tq:2 * tq] = np.float32(-50.0)          # tile 1: below every alpha
    r[tq:2 * tq] = np.float32(1.0)
    edges = al[0].reshape(-1, bn)[:-2]          # real blocks of segment 0
    for k in range(2 * tq, m, 7):               # windows ending on an edge
        b = int(rng.integers(edges.shape[0]))
        rk = np.float32(0.125)
        if k % 2:
            aq[k] = edges[b, -1] + rk           # aq - r == the last alpha
        else:
            aq[k] = edges[b, 0] - rk            # aq + r == the first alpha
        r[k] = rk
    return aq, r


@pytest.mark.parametrize("seed", range(6))
def test_ranges_hold_every_hit_cell_and_end_on_hits(seed):
    rng = np.random.default_rng(seed)
    tq, bn = 16, 8
    al = _random_stack(rng, n_seg=3, nb=12, bn=bn)
    aq, r = _queries(rng, al, tiles=6, tq=tq, bn=bn)
    lo, hi = _ranges(aq, r, al, tq, bn)
    hit = _hits(aq, r, al, tq, bn)
    nb = al.shape[1] // bn
    assert not hit[:, 0].any() and not hit[:, 1].any()
    n_edge = 0
    for s, t in np.ndindex(*lo.shape):
        live = np.flatnonzero(hit[s, t])
        if not live.size:
            # the empty range: no cell in it, and one block to fetch
            assert (lo[s, t], hi[s, t]) == (nb, nb - 1)
            continue
        assert lo[s, t] <= live[0] and live[-1] <= hi[s, t]
        # tight: the range's ends are hit cells
        assert hit[s, t, lo[s, t]] and hit[s, t, hi[s, t]]
        n_edge += 1
    assert n_edge > 0


def test_window_exactly_on_a_block_edge_hits_it():
    """``aq + r`` equal to a block's first alpha, or ``aq - r`` equal to
    its last, hits that block (``>=`` / ``<=``); one ulp off, it does not.
    Blocks of alphas [0, 2, 4, 6], [8, ..., 14], ...; one real row."""
    tq, bn = 8, 4
    al = np.arange(4 * bn, dtype=np.float32)[None, :] * np.float32(2.0)
    r = np.full(tq, -BIG, np.float32)
    r[0] = np.float32(0.5)
    below, above = np.float32(0.0), np.float32(100.0)
    cases = [(np.float32(7.5), (1, 1)),                  # aq + r == 8
             (np.nextafter(np.float32(7.5), below), (4, 3)),
             (np.float32(6.5), (0, 0)),                  # aq - r == 6
             (np.nextafter(np.float32(6.5), above), (4, 3))]
    for a, want in cases:
        aq = np.full(tq, 100.0, np.float32)
        aq[0] = a
        lo, hi = _ranges(aq, r, al, tq, bn)
        assert (lo[0, 0], hi[0, 0]) == want, a
        live = np.flatnonzero(_hits(aq, r, al, tq, bn)[0, 0])
        assert live.tolist() == list(range(want[0], want[1] + 1)), a


# --------------------------------------------------------------------------- #
# The kernels over narrow windows                                              #
# --------------------------------------------------------------------------- #
TQ, BN, D = 128, 128, 4


def _segment(rng, n, n_pad):
    """A sorted d = 4 segment padded to ``n_pad`` rows: alpha is the first
    coordinate, rows past ``n`` carry +BIG."""
    x = rng.normal(size=(n, D)).astype(np.float32)
    x = x[np.argsort(x[:, 0], kind="stable")]
    xs, al, hn, _, _ = ops.pad_database(x, x[:, 0], (x * x).sum(1) / 2,
                                        bn=n_pad)
    return x, (xs, al, hn)


@pytest.fixture(scope="module")
def narrow():
    """Two segments of 64 blocks (the second ends in padding blocks) and
    984 query rows near the first one's points, radius 0.1, in alpha order
    and in arrival order."""
    rng = np.random.default_rng(17)
    n_pad = 64 * BN
    x, seg0 = _segment(rng, n_pad - 5, n_pad)
    _, seg1 = _segment(rng, 3000, n_pad)
    stack = tuple(jnp.stack(parts) for parts in zip(seg0, seg1))
    m = 8 * TQ - 40
    q = x[rng.permutation(x.shape[0])[:m]] \
        + rng.normal(scale=0.01, size=(m, D)).astype(np.float32)
    r = np.full(m, 0.1, np.float32)
    th = (r * r - (q * q).sum(1)) / np.float32(2)
    orders = {}
    for name, rows in (("alpha", np.argsort(q[:, 0], kind="stable")),
                       ("arrival", np.arange(m))):
        qq = q[rows]
        orders[name] = ops.pad_queries(qq, qq[:, 0], r[rows], th[rows],
                                       tq=TQ)[:4]
    return stack, orders


def _outside_share(qa, stack):
    _, aq, r, _ = qa
    lo, hi = _ranges(np.asarray(aq), np.asarray(r), np.asarray(stack[1]),
                     TQ, BN)
    nb = stack[1].shape[1] // BN
    inside = np.where(hi >= lo, hi - lo + 1, 0).sum()
    return 1.0 - inside / (lo.size * nb)


@pytest.mark.parametrize("order", ["alpha", "arrival"])
def test_stacked_kernels_match_ref_over_narrow_windows(narrow, order):
    stack, orders = narrow
    qa = orders[order]
    if order == "alpha":
        assert _outside_share(qa, stack) >= 0.8
    per = snn_query.snn_count_stacked(*qa, *stack, tq=TQ, bn=BN,
                                      interpret=True)
    want = ref.snn_count_stacked_ref(*qa, *stack, n_seg=2)
    np.testing.assert_array_equal(np.asarray(per), np.asarray(want))
    assert int(np.asarray(per).sum()) > 0
    _, indptr, off = ref.stacked_prefix(want)
    cap = ops.csr_capacity(int(indptr[-1]))
    ik, dk = snn_query.snn_compact_stacked(*qa, off, *stack, nnz=cap,
                                           tq=TQ, bn=BN, interpret=True)
    ir, dr = ref.snn_compact_stacked_ref(*qa, off, *stack, n_seg=2, nnz=cap)
    np.testing.assert_array_equal(np.asarray(ik), np.asarray(ir))
    np.testing.assert_array_equal(np.asarray(dk), np.asarray(dr))


def test_fused_chain_matches_ref_over_narrow_windows(narrow):
    stack, orders = narrow
    qa = orders["alpha"]
    lane = registry.get_backend("pallas-tpu")
    cap = 1 << 14
    indptr, fi, fd, total = lane.snn_csr_fused_stacked(
        *qa, *stack, nnz_cap=cap, tq=TQ, bn=BN)
    want = ref.snn_count_stacked_ref(*qa, *stack, n_seg=2)
    _, w_indptr, off = ref.stacked_prefix(want)
    assert int(total) + 1 <= cap  # the compaction ran
    ir, dr = ref.snn_compact_stacked_ref(*qa, off, *stack, n_seg=2, nnz=cap)
    np.testing.assert_array_equal(np.asarray(indptr), np.asarray(w_indptr))
    np.testing.assert_array_equal(np.asarray(fi), np.asarray(ir))
    np.testing.assert_array_equal(np.asarray(fd), np.asarray(dr))
