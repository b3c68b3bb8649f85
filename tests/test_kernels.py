"""Per-kernel Pallas (interpret=True) vs pure-jnp oracle sweeps."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build_index, query_counts
from repro.core.sharded import prepare_query_arrays
from repro.kernels import ops, ref
from repro.kernels.embedding_bag import embedding_bag as bag_kernel
from repro.kernels.snn_query import snn_count, snn_filter


def _setup(seed, n, d, m, radius, dtype=np.float32):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(dtype)
    q = rng.normal(size=(m, d)).astype(dtype)
    index = build_index(x)
    xs, al, hn, n0, d0 = ops.pad_database(index.xs, index.alphas,
                                          index.half_norms, bn=128)
    xq, aq, r, th = prepare_query_arrays(index, q, radius)
    qp, aqp, rp, thp, m0 = ops.pad_queries(
        np.asarray(xq), np.asarray(aq), np.asarray(r), np.asarray(th), tq=64)
    return index, q, (qp, aqp, rp, thp, xs, al, hn)


@pytest.mark.parametrize("n,d,m", [(100, 4, 7), (1000, 20, 37), (513, 129, 64),
                                   (2048, 64, 128), (300, 3, 1)])
@pytest.mark.parametrize("radius", [0.5, 2.0, 8.0])
def test_snn_filter_kernel_matches_ref(n, d, m, radius):
    _, _, args = _setup(0, n, d, m, radius)
    out_k = snn_filter(*args, tq=64, bn=128, interpret=True)
    out_r = ref.snn_filter_ref(*args)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-6, atol=1e-5)


@pytest.mark.parametrize("n,d,m", [(500, 10, 16), (1024, 32, 64)])
@pytest.mark.parametrize("radius", [1.0, 4.0])
def test_snn_count_kernel_matches_ref_and_exact(n, d, m, radius):
    index, q, args = _setup(1, n, d, m, radius)
    cnt_k = np.asarray(snn_count(*args, tq=64, bn=128, interpret=True))
    cnt_r = np.asarray(ref.snn_count_ref(*args))
    assert (cnt_k == cnt_r).all()
    exact = query_counts(index, q, radius)
    assert (cnt_k[:m] == exact).all()


def test_snn_kernel_block_pruning_no_false_negatives():
    """Pruned blocks must never hide true neighbors (exactness across tiles)."""
    rng = np.random.default_rng(7)
    # elongated data -> tight windows -> most blocks pruned
    x = np.concatenate([rng.normal(size=(2000, 1)) * 10,
                        rng.normal(size=(2000, 7)) * 0.1], axis=1).astype(np.float32)
    q = x[rng.integers(0, 2000, 33)] + 0.01
    index, qq, args = _setup(7, 10, 8, 3, 1.0)  # shape helper only
    index = build_index(x)
    from repro.core.sharded import prepare_query_arrays as pq
    from repro.kernels import ops as _ops
    xs, al, hn, _, _ = _ops.pad_database(index.xs, index.alphas,
                                         index.half_norms, bn=128)
    xq, aq, r, th = pq(index, q, 0.5)
    qp, aqp, rp, thp, m0 = _ops.pad_queries(
        np.asarray(xq), np.asarray(aq), np.asarray(r), np.asarray(th), tq=64)
    cnt = np.asarray(snn_count(qp, aqp, rp, thp, xs, al, hn,
                               tq=64, bn=128, interpret=True))[:33]
    exact = query_counts(index, q, 0.5)
    assert (cnt == exact).all()


@pytest.mark.parametrize("bn,t_off", [(128, 0), (512, 1023), (512, 77)])
def test_place_run_moves_every_bit(bn, t_off):
    """The pass-2 one-hot move reproduces each survivor's column and dhalf bit
    pattern exactly (sign, exponent and low mantissa bytes included) at slot
    ``t_off + rank`` of the two-tile window, and writes nothing else."""
    from repro.kernels.snn_query import _place_run

    rng = np.random.default_rng(bn + t_off)
    keep = rng.random(bn) < 0.3
    rank = np.where(keep, np.cumsum(keep) - 1, -1).astype(np.int32)
    bits = rng.integers(-2**31, 2**31, bn, dtype=np.int64).astype(np.int32)
    local_w, bits_w = _place_run(jnp.asarray(rank[None]),
                                 jnp.asarray(bits[None]), t_off)
    local_w = np.asarray(local_w).reshape(-1)
    bits_w = np.asarray(bits_w).reshape(-1)
    slots = t_off + rank[keep]
    np.testing.assert_array_equal(local_w[slots], np.flatnonzero(keep))
    np.testing.assert_array_equal(bits_w[slots], bits[keep])
    rest = np.ones(local_w.size, bool)
    rest[slots] = False
    assert not local_w[rest].any() and not bits_w[rest].any()


@pytest.mark.parametrize("v,d,b,f", [(50, 128, 16, 5), (10, 128, 3, 1),
                                     (200, 256, 32, 9), (64, 128, 64, 4)])
def test_embedding_bag_kernel_matches_ref(v, d, b, f):
    rng = np.random.default_rng(0)
    table = rng.normal(size=(v, d)).astype(np.float32)
    ids = rng.integers(-1, v, size=(b, f)).astype(np.int32)
    out_k = bag_kernel(jnp.asarray(ids), jnp.asarray(table), interpret=True)
    out_r = ref.embedding_bag_ref(jnp.asarray(ids), jnp.asarray(table))
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-6, atol=1e-5)


def test_embedding_bag_all_padding_row():
    table = np.eye(4, 128, dtype=np.float32)
    ids = np.full((2, 3), -1, np.int32)
    out = bag_kernel(jnp.asarray(ids), jnp.asarray(table), interpret=True)
    assert np.abs(np.asarray(out)).sum() == 0


def test_embedding_bag_mean_mode():
    rng = np.random.default_rng(1)
    table = rng.normal(size=(20, 128)).astype(np.float32)
    ids = np.array([[0, 1, -1], [2, -1, -1]], np.int32)
    out = np.asarray(ops.embedding_bag(jnp.asarray(ids), jnp.asarray(table),
                                       mode="mean", use_pallas=True))
    np.testing.assert_allclose(out[0], (table[0] + table[1]) / 2, rtol=1e-5)
    np.testing.assert_allclose(out[1], table[2], rtol=1e-5)


def test_bf16_database_filter():
    """dtype sweep: bf16 db/queries still agree with the bf16 oracle."""
    rng = np.random.default_rng(5)
    x = rng.normal(size=(256, 16)).astype(np.float32)
    index = build_index(x)
    from repro.core.sharded import prepare_query_arrays as pq
    xs, al, hn, _, _ = ops.pad_database(index.xs, index.alphas,
                                        index.half_norms, bn=128)
    xq, aq, r, th = pq(index, x[:8], 2.0)
    qp, aqp, rp, thp, _ = ops.pad_queries(
        np.asarray(xq), np.asarray(aq), np.asarray(r), np.asarray(th), tq=64)
    xsb = xs.astype(jnp.bfloat16).astype(jnp.float32)
    qpb = qp.astype(jnp.bfloat16).astype(jnp.float32)
    out_k = snn_filter(qpb, aqp, rp, thp, xsb, al, hn, tq=64, bn=128,
                       interpret=True)
    out_r = ref.snn_filter_ref(qpb, aqp, rp, thp, xsb, al, hn)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-5, atol=1e-4)
