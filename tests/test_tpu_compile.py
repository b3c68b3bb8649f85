"""The main path's Pallas kernels compile for a TPU v5e chip.

Interpret mode (every other kernel test) cannot see what the TPU compiler
refuses: block shapes that break the (8, 128) tiling, primitives Mosaic
cannot lower, more VMEM than a core has.  These tests lower and compile the
kernels for a described ``v5e:2x2`` topology (no chip attached) at real
widths: d = 128, n_pad from 8192, a 128-query tile.  Nothing runs, so they
say nothing about results; `chip_smoke.py` checks those on the chip.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import registry, snn_query

D, N_PAD, M, KE = 128, 8192, 256, 2
TQ, BN = 128, 512


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    return make


def _queries(spec):
    v = spec((M,))
    return spec((M, D)), v, v, v


def _stack(spec, n_seg, box):
    rows = (spec((n_seg, N_PAD, D)), spec((n_seg, N_PAD)),
            spec((n_seg, N_PAD)))
    proj = (spec((KE, M)), spec((n_seg, KE, N_PAD))) if box else (None, None)
    return rows, proj


def _compile(fn, *args):
    """Lower + compile for the described chip; the kernel must be inside."""
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("n_seg,box,mixed", [
    (1, False, False), (4, False, False), (4, True, False), (4, True, True),
    (1, True, True)])
def test_count_stacked_compiles(spec, n_seg, box, mixed):
    (xs, al, hn), (pq, px) = _stack(spec, n_seg, box)

    def fn(q, aq, r, th, xs, al, hn, pq, px):
        return snn_query.snn_count_stacked(q, aq, r, th, xs, al, hn, pq, px,
                                           tq=TQ, bn=BN, interpret=False,
                                           mixed=mixed)

    _compile(fn, *_queries(spec), xs, al, hn, pq, px)


@pytest.mark.parametrize("n_seg,box,nnz", [
    (1, False, 1 << 12), (4, True, 1 << 12), (2, True, snn_query.MAX_NNZ)])
def test_compact_stacked_compiles(spec, n_seg, box, nnz):
    (xs, al, hn), (pq, px) = _stack(spec, n_seg, box)

    def fn(q, aq, r, th, off, xs, al, hn, pq, px):
        return snn_query.snn_compact_stacked(q, aq, r, th, off, xs, al, hn,
                                             pq, px, nnz=nnz, tq=TQ, bn=BN,
                                             interpret=False)

    _compile(fn, *_queries(spec), spec((n_seg, M), jnp.int32),
             xs, al, hn, pq, px)


def test_compact_over_the_ceiling_is_refused():
    with pytest.raises(ValueError, match="MAX_NNZ"):
        snn_query._compact_outputs(2 * snn_query.MAX_NNZ, TQ, BN)


@pytest.mark.parametrize("kernel", ["count", "compact", "compact_box"])
def test_single_segment_kernels_compile(spec, kernel):
    xs, row = spec((N_PAD, D)), spec((N_PAD,))
    if kernel == "count":
        def fn(q, aq, r, th, xs, al, hn):
            return snn_query.snn_count(q, aq, r, th, xs, al, hn, tq=TQ,
                                       bn=BN, interpret=False)

        _compile(fn, *_queries(spec), xs, row, row)
    else:
        proj = ((spec((KE, M)), spec((KE, N_PAD))) if kernel == "compact_box"
                else (None, None))

        def fn(q, aq, r, th, off, xs, al, hn, pq, px):
            return snn_query.snn_compact(q, aq, r, th, off, xs, al, hn,
                                         pq, px, nnz=1 << 12, tq=TQ, bn=BN,
                                         interpret=False)

        _compile(fn, *_queries(spec), spec((M,), jnp.int32), xs, row, row,
                 *proj)


@pytest.mark.parametrize("n_seg,mixed", [(1, False), (2, True)])
def test_fused_chain_compiles(spec, monkeypatch, n_seg, mixed):
    """count -> device prefix -> speculative compact in one program, as the
    pallas-tpu lane builds it (its interpret flag turned off here)."""
    monkeypatch.setattr(registry.get_backend("pallas-tpu"), "interpret",
                        False)
    # bypass the lru cache: a cached chain may hold an interpret-mode trace
    fn = registry._fused_csr_fn.__wrapped__("pallas-tpu", 1 << 14, TQ, BN,
                                            mixed)
    (xs, al, hn), (pq, px) = _stack(spec, n_seg, True)
    _compile(fn, *_queries(spec), xs, al, hn, pq, px)


def test_block_ranges_read_the_block_ends_alone(spec):
    """At the DBSCAN cell's shape (2,048 query rows, 7,521 blocks of 512)
    the range table reads two strided slices of the alpha stack: no
    instruction makes a stack-sized array (a relayout of 15 MB a launch)."""
    n = 7521 * BN
    v = spec((2048,))

    def fn(aq, r, al):
        return snn_query.block_ranges(aq, r, al, tq=TQ, bn=BN)

    text = jax.jit(fn).lower(v, v, spec((1, n))).compile().as_text()
    made = [line for line in text.splitlines()
            if f"{n}]" in line.split("=", 1)[-1].split("(", 1)[0]
            and "parameter(" not in line]
    assert not made, made[:3]
