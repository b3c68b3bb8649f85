"""Pallas GPU (Triton-lowered) kernels for the SNN query hot loop.

Same math as `kernels.snn_query` — both lanes call the SAME shared
``_tile_body`` predicate pipeline on the same (tq, bn) block shapes, so the
masked distances and keep decisions are bit-identical — but re-orchestrated
for Triton's execution model, where every grid cell is an independent
parallel program:

* no ``pl.when`` block-skip or zero-init: a cell cannot know whether another
  cell ran, so each kernel writes its whole output block unconditionally
  (the window prune is subsumed by ``inwin`` inside ``_tile_body``; block
  skipping on GPU is future Triton work and does not affect outputs);
* no cross-cell VMEM cursor or output accumulation: the TPU count kernel
  accumulates over a sequential block axis, here each (block, query-tile)
  cell writes its own PARTIAL count row of a (num_blocks, m) output that the
  wrapper sums — one extra (num_blocks, m) int32 intermediate buys full grid
  parallelism;
* compaction replaces the sequential cursor with a deterministic address
  plan: a per-(block, query) count pass feeds an exclusive prefix over the
  block axis, giving every cell a precomputed write base; the scatter kernel
  then stores each survivor at ``base + rank-within-block`` — disjoint slots
  across cells, so the scatter is race-free.  Pruned pairs land in the flat
  trash slot (racy garbage by design); the wrapper restores its sentinel.
  The GPU compact thus pays one extra count pass where the TPU lane pays a
  sequential grid — the classic parallel-scan trade;
* the mixed-precision count drops ``lax.cond`` (divergent control flow):
  the exact f32 verify matmul runs unconditionally and in-band candidates
  are merged with ``jnp.where`` — counts still provably equal f32 counts
  (``definite`` and ``band`` are disjoint predicates, same formulas as the
  TPU lane).

Off-GPU these kernels run in Pallas interpret mode — that is how CPU CI
certifies the lane bit-identical to the TPU kernels and the numpy oracle
(`tests/test_registry.py`, `tests/test_exactness_certificate.py`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import MIX_EPS, box_mask, norm_scales
from .snn_query import (  # noqa: F401  (BIG re-exported for parity)
    BIG,
    _grid_specs,
    _split_rest,
    _stacked_grid_specs,
    _tile_body,
)


def _count_tile_nobranch(q, aq, r, th, x, al, hn, pq, px, mix):
    """Per-query survivor counts (tq,) int32, branch-free.

    ``mix=True`` evaluates the same bf16 margin-certificate formulas as the
    TPU ``_count_tile`` but runs the f32 verify matmul unconditionally and
    merges with ``jnp.where`` instead of ``lax.cond`` (which Triton may not
    lower).  ``definite`` and ``band`` are disjoint, so the merged count
    equals the TPU lane's ``definite + verified`` exactly.
    """
    if not mix:
        keep, _ = _tile_body(q, aq, r, th, x, al, hn, pq, px)
        return jnp.sum(keep.astype(jnp.int32), axis=1)
    aqc = aq[0, :][:, None]
    rc = r[0, :][:, None]
    thc = th[0, :][:, None]
    geom = jnp.abs(al - aqc) <= rc
    if pq is not None:
        geom = geom & box_mask(pq, px, r[0, :], th[0, :], hn[0, :])
    s16 = jax.lax.dot_general(
        q.astype(jnp.bfloat16), x.astype(jnp.bfloat16),
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dh16 = hn - s16
    xn, qn = norm_scales(r[0, :], th[0, :], hn[0, :])
    margin = MIX_EPS * xn[None, :] * qn[:, None]
    definite = geom & (dh16 <= thc - margin)
    band = geom & (dh16 > thc - margin) & (dh16 <= thc + margin)
    s32 = jax.lax.dot_general(
        q, x,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    verified = band & ((hn - s32) <= thc)
    return jnp.sum(jnp.where(definite | verified, 1, 0).astype(jnp.int32),
                   axis=1)


def _filter_kernel(q_ref, aq_ref, r_ref, th_ref, x_ref, al_ref, hn_ref,
                   *rest):
    pq_ref, px_ref, (out_ref,) = _split_rest(rest, 1)
    keep, dhalf = _tile_body(
        q_ref[...], aq_ref[...], r_ref[...], th_ref[...], x_ref[...],
        al_ref[...], hn_ref[...],
        None if pq_ref is None else pq_ref[...],
        None if px_ref is None else px_ref[...])
    out_ref[...] = jnp.where(keep, dhalf, BIG)


def _count_kernel(mix, q_ref, aq_ref, r_ref, th_ref, x_ref, al_ref, hn_ref,
                  *rest):
    """Partial counts: each cell owns row ``bi`` of the (num_blocks, m) out."""
    pq_ref, px_ref, (out_ref,) = _split_rest(rest, 1)
    cnt = _count_tile_nobranch(
        q_ref[...], aq_ref[...], r_ref[...], th_ref[...], x_ref[...],
        al_ref[...], hn_ref[...],
        None if pq_ref is None else pq_ref[...],
        None if px_ref is None else px_ref[...], mix)
    out_ref[...] = cnt[None, :]


def _count_stacked_kernel(mix, q_ref, aq_ref, r_ref, th_ref, x_ref, al_ref,
                          hn_ref, *rest):
    pq_ref, px_ref, (out_ref,) = _split_rest(rest, 1)
    cnt = _count_tile_nobranch(
        q_ref[...], aq_ref[...], r_ref[...], th_ref[...], x_ref[0],
        al_ref[0], hn_ref[0],
        None if pq_ref is None else pq_ref[...],
        None if px_ref is None else px_ref[0], mix)
    out_ref[...] = cnt[None, None, :]


@functools.partial(jax.jit, static_argnames=("tq", "bn", "interpret"))
def snn_filter(q, aq, r, thresh, xs, alphas, half_norms, pq=None, px=None, *,
               tq: int = 128, bn: int = 512, interpret: bool = True):
    """Masked halved sq. distances (m, n); same contract as the TPU lane."""
    m, d = q.shape
    n = xs.shape[0]
    ke = 0 if pq is None else pq.shape[0]
    grid, in_specs = _grid_specs(m, n, d, tq, bn, ke)
    args = (q, aq[None, :], r[None, :], thresh[None, :], xs,
            alphas[None, :], half_norms[None, :])
    if ke:
        args += (pq, px)
    return pl.pallas_call(
        _filter_kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tq, bn), lambda qi, bi: (qi, bi)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(*args)


def _partial_counts(q, aq, r, thresh, xs, alphas, half_norms, pq, px,
                    tq, bn, interpret, mixed):
    """(num_blocks, m) int32 per-(db block, query) survivor counts."""
    m, d = q.shape
    n = xs.shape[0]
    ke = 0 if pq is None else pq.shape[0]
    grid, in_specs = _grid_specs(m, n, d, tq, bn, ke)
    args = (q, aq[None, :], r[None, :], thresh[None, :], xs,
            alphas[None, :], half_norms[None, :])
    if ke:
        args += (pq, px)
    return pl.pallas_call(
        functools.partial(_count_kernel, mixed),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, tq), lambda qi, bi: (bi, qi)),
        out_shape=jax.ShapeDtypeStruct((n // bn, m), jnp.int32),
        interpret=interpret,
    )(*args)


@functools.partial(jax.jit, static_argnames=("tq", "bn", "interpret", "mixed"))
def snn_count(q, aq, r, thresh, xs, alphas, half_norms, pq=None, px=None, *,
              tq: int = 128, bn: int = 512, interpret: bool = True,
              mixed: bool = False):
    """Per-query neighbor counts (m,) int32 (partial-count sum)."""
    per_block = _partial_counts(q, aq, r, thresh, xs, alphas, half_norms,
                                pq, px, tq, bn, interpret, mixed)
    return jnp.sum(per_block, axis=0, dtype=jnp.int32)


def _partial_counts_stacked(q, aq, r, thresh, xs, alphas, half_norms, pq, px,
                            tq, bn, interpret, mixed):
    """(S, num_blocks, m) int32 per-(segment, block, query) counts."""
    m, d = q.shape
    n_seg, n, _ = xs.shape
    ke = 0 if pq is None else pq.shape[0]
    grid, in_specs = _stacked_grid_specs(n_seg, m, n, d, tq, bn, ke)
    args = (q, aq[None, :], r[None, :], thresh[None, :], xs,
            alphas[:, None, :], half_norms[:, None, :])
    if ke:
        args += (pq, px)
    return pl.pallas_call(
        functools.partial(_count_stacked_kernel, mixed),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, tq), lambda s, qi, bi: (s, bi, qi)),
        out_shape=jax.ShapeDtypeStruct((n_seg, n // bn, m), jnp.int32),
        interpret=interpret,
    )(*args)


@functools.partial(jax.jit, static_argnames=("tq", "bn", "interpret", "mixed"))
def snn_count_stacked(q, aq, r, thresh, xs, alphas, half_norms,
                      pq=None, px=None, *,
                      tq: int = 128, bn: int = 512, interpret: bool = True,
                      mixed: bool = False):
    """Per-(segment, query) survivor counts (S, m) int32 in one launch."""
    per_block = _partial_counts_stacked(q, aq, r, thresh, xs, alphas,
                                        half_norms, pq, px, tq, bn,
                                        interpret, mixed)
    return jnp.sum(per_block, axis=1, dtype=jnp.int32)


# --------------------------------------------------------------------------- #
# Pass-2 CSR compaction (parallel scatter at precomputed bases)                #
# --------------------------------------------------------------------------- #
def _scatter_kernel(q_ref, aq_ref, r_ref, th_ref, base_ref,
                    x_ref, al_ref, hn_ref, *rest):
    """Scatter one cell's survivors at precomputed per-query bases.

    ``base_ref`` carries this (block, query-tile) cell's write bases (global
    CSR offset + exclusive block prefix), so every cell's survivor slots are
    disjoint — no cursor, no sequential grid.  Pruned pairs store to the
    trash slot (racy garbage; sentinel restored by the wrapper).
    """
    pq_ref, px_ref, (_idx0, _dh0, idx_ref, dh_ref) = _split_rest(rest, 4)
    bi = pl.program_id(1)
    bn = x_ref.shape[0]
    trash = idx_ref.shape[1] - 1
    keep, dhalf = _tile_body(
        q_ref[...], aq_ref[...], r_ref[...], th_ref[...], x_ref[...],
        al_ref[...], hn_ref[...],
        None if pq_ref is None else pq_ref[...],
        None if px_ref is None else px_ref[...])
    keep_i = keep.astype(jnp.int32)
    within = jnp.cumsum(keep_i, axis=1) - 1
    base = base_ref[0, :]
    col0 = bi * bn

    def row_body(k, _):
        pos = jnp.where(keep[k], base[k] + within[k], trash)

        def el_body(j, __):
            idx_ref[0, pl.ds(pos[j], 1)] = (col0 + j)[None].astype(jnp.int32)
            dh_ref[0, pl.ds(pos[j], 1)] = dhalf[k, j][None]
            return 0

        return jax.lax.fori_loop(0, bn, el_body, 0)

    jax.lax.fori_loop(0, keep.shape[0], row_body, 0)


def _scatter_stacked_kernel(q_ref, aq_ref, r_ref, th_ref, base_ref,
                            x_ref, al_ref, hn_ref, *rest):
    """`_scatter_kernel` with a leading segment grid axis (pack-flat cols)."""
    pq_ref, px_ref, (_idx0, _dh0, idx_ref, dh_ref) = _split_rest(rest, 4)
    si = pl.program_id(0)
    bi = pl.program_id(2)
    bn = x_ref.shape[1]
    n_pad = pl.num_programs(2) * bn
    trash = idx_ref.shape[1] - 1
    keep, dhalf = _tile_body(
        q_ref[...], aq_ref[...], r_ref[...], th_ref[...], x_ref[0],
        al_ref[0], hn_ref[0],
        None if pq_ref is None else pq_ref[...],
        None if px_ref is None else px_ref[0])
    keep_i = keep.astype(jnp.int32)
    within = jnp.cumsum(keep_i, axis=1) - 1
    base = base_ref[0, 0, :]
    col0 = si * n_pad + bi * bn

    def row_body(k, _):
        pos = jnp.where(keep[k], base[k] + within[k], trash)

        def el_body(j, __):
            idx_ref[0, pl.ds(pos[j], 1)] = (col0 + j)[None].astype(jnp.int32)
            dh_ref[0, pl.ds(pos[j], 1)] = dhalf[k, j][None]
            return 0

        return jax.lax.fori_loop(0, bn, el_body, 0)

    jax.lax.fori_loop(0, keep.shape[0], row_body, 0)


@functools.partial(jax.jit, static_argnames=("nnz", "tq", "bn", "interpret"))
def snn_compact(q, aq, r, thresh, offsets, xs, alphas, half_norms,
                pq=None, px=None, *,
                nnz: int, tq: int = 128, bn: int = 512,
                interpret: bool = True):
    """Pass-2 CSR compaction, parallel-grid edition.

    Identical contract and output to the TPU `snn_compact` (flat idx/dhalf
    with trailing trash slot, -1/+BIG in unwritten slots).  Internally it
    first recomputes per-(block, query) counts, prefixes them over the block
    axis into per-cell write bases, then scatters in a fully parallel grid —
    one extra count pass in exchange for no sequential dimension.
    """
    m, d = q.shape
    n = xs.shape[0]
    ke = 0 if pq is None else pq.shape[0]
    per_block = _partial_counts(q, aq, r, thresh, xs, alphas, half_norms,
                                pq, px, tq, bn, interpret, False)
    bases = offsets[None, :].astype(jnp.int32) \
        + (jnp.cumsum(per_block, axis=0) - per_block)        # (n//bn, m)
    grid, in_specs = _grid_specs(m, n, d, tq, bn, ke)
    in_specs = in_specs[:4] + [pl.BlockSpec((1, tq), lambda qi, bi: (bi, qi))] \
        + in_specs[4:]
    # prefilled outputs ride in as aliased inputs: a parallel grid has no
    # "first cell", so -1/+BIG backgrounds must exist before any cell runs
    in_specs += [pl.BlockSpec((1, nnz), lambda qi, bi: (0, 0)),
                 pl.BlockSpec((1, nnz), lambda qi, bi: (0, 0))]
    args = (q, aq[None, :], r[None, :], thresh[None, :], bases, xs,
            alphas[None, :], half_norms[None, :])
    if ke:
        args += (pq, px)
    n_in = len(args)
    args += (jnp.full((1, nnz), -1, jnp.int32),
             jnp.full((1, nnz), BIG, jnp.float32))
    out_idx, out_dh = pl.pallas_call(
        _scatter_kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, nnz), lambda qi, bi: (0, 0)),
                   pl.BlockSpec((1, nnz), lambda qi, bi: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((1, nnz), jnp.int32),
                   jax.ShapeDtypeStruct((1, nnz), jnp.float32)],
        input_output_aliases={n_in: 0, n_in + 1: 1},
        interpret=interpret,
    )(*args)
    # every cell dumped its pruned pairs into the trash slot; restore sentinel
    out_idx = out_idx.at[0, nnz - 1].set(-1)
    out_dh = out_dh.at[0, nnz - 1].set(BIG)
    return out_idx[0], out_dh[0]


@functools.partial(jax.jit, static_argnames=("nnz", "tq", "bn", "interpret"))
def snn_compact_stacked(q, aq, r, thresh, offsets, xs, alphas, half_norms,
                        pq=None, px=None, *,
                        nnz: int, tq: int = 128, bn: int = 512,
                        interpret: bool = True):
    """Stacked pass-2 compaction (pack-flat cols), parallel-grid edition.

    ``offsets`` is (S, m) as in the TPU lane; per-cell bases add the
    exclusive block prefix WITHIN each segment (the segment-axis prefix is
    already inside ``offsets``).
    """
    m, d = q.shape
    n_seg, n, _ = xs.shape
    ke = 0 if pq is None else pq.shape[0]
    per_block = _partial_counts_stacked(q, aq, r, thresh, xs, alphas,
                                        half_norms, pq, px, tq, bn,
                                        interpret, False)        # (S, nb, m)
    bases = offsets[:, None, :].astype(jnp.int32) \
        + (jnp.cumsum(per_block, axis=1) - per_block)
    grid, in_specs = _stacked_grid_specs(n_seg, m, n, d, tq, bn, ke)
    in_specs = in_specs[:4] \
        + [pl.BlockSpec((1, 1, tq), lambda s, qi, bi: (s, bi, qi))] \
        + in_specs[4:]
    in_specs += [pl.BlockSpec((1, nnz), lambda s, qi, bi: (0, 0)),
                 pl.BlockSpec((1, nnz), lambda s, qi, bi: (0, 0))]
    args = (q, aq[None, :], r[None, :], thresh[None, :], bases, xs,
            alphas[:, None, :], half_norms[:, None, :])
    if ke:
        args += (pq, px)
    n_in = len(args)
    args += (jnp.full((1, nnz), -1, jnp.int32),
             jnp.full((1, nnz), BIG, jnp.float32))
    out_idx, out_dh = pl.pallas_call(
        _scatter_stacked_kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, nnz), lambda s, qi, bi: (0, 0)),
                   pl.BlockSpec((1, nnz), lambda s, qi, bi: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((1, nnz), jnp.int32),
                   jax.ShapeDtypeStruct((1, nnz), jnp.float32)],
        input_output_aliases={n_in: 0, n_in + 1: 1},
        interpret=interpret,
    )(*args)
    out_idx = out_idx.at[0, nnz - 1].set(-1)
    out_dh = out_dh.at[0, nnz - 1].set(BIG)
    return out_idx[0], out_dh[0]
