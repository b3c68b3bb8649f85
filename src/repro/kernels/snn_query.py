"""Pallas TPU kernel for the SNN query hot loop (paper Alg. 2, step 5).

TPU adaptation of the paper's dynamic-window BLAS GEMV/GEMM:

* the sorted database is tiled into row blocks of ``bn`` rows; queries into
  tiles of ``tq``;
* grid = (num_query_tiles, num_db_blocks); for each cell the kernel first tests
  whether ANY query window in the tile can intersect the block's alpha range
  (``alpha`` is globally sorted, so the block range is just [first, last]);
* pruned cells skip the MXU matmul entirely (``pl.when``) — this is the
  sorting-based exclusion criterion executed at tile granularity;
* surviving cells compute ``dhalf = half_norm - X_block @ q`` on the MXU and
  apply the half-norm radius test  ``dhalf <= (r_q^2 - q.q)/2``  (paper eq. (4)).

The radius is PER QUERY throughout: every kernel takes an ``r`` tile of one
radius per query row (and the matching per-query ``thresh``), never a shared
scalar — the window test ``|alpha - alpha_q| <= r_q`` and the half-norm test
are both row-local, so a mixed-radius tile costs exactly what a uniform one
does.  Callers broadcasting one radius do so at the query-prep layer
(`core.metrics.broadcast_radius`), not here.

Two optional, exactness-preserving accelerations (PR 6; shared formulas live
in `kernels.ref`, the single source of truth for both dispatch paths):

* ``pq``/``px`` extra projection components add the k-dim Cauchy–Schwarz box
  test to every candidate BEFORE its result is kept — any unit-or-shorter
  direction yields a valid bound, so the box only ever removes pairs the
  distance predicate would reject;
* ``mixed=True`` (count kernels only) runs the count dot products in bf16
  under the margin certificate: candidates within ``MIX_EPS * ||x|| ||q||``
  of the threshold are re-verified with the exact f32 predicate (skipped per
  tile when the band is empty), so mixed counts EQUAL f32 counts.

Five entry kernels share the body:
  * ``filter`` : emits masked halved sq. distances (m, n), +BIG where pruned;
  * ``count``  : emits per-query neighbor counts (m,), accumulated over blocks;
  * ``compact``: pass 2 of the two-pass CSR engine — re-runs the block-pruned
    filter and scatters surviving (sorted-row index, dhalf) pairs directly into
    flat CSR arrays at caller-provided per-query offsets.  No (m, n)
    intermediate is ever materialized.  Each query row's write base lives
    in SMEM; a cell with survivors copies its per-row counts into SMEM once
    and places only the rows that have any (`_scatter_cell`), so a row
    without survivors costs one scalar test.
  * ``count_stacked`` / ``compact_stacked``: the same two passes over a whole
    *stack* of segments at once (`core.engine.SegmentPack`) — the grid grows a
    leading segment axis, so one launch covers every live segment of a
    multi-segment index instead of one launch (plus host sync) per segment.
    Each (segment, query tile) also gets its live block range
    (`block_ranges`), scalar-prefetched: the row-block index maps clamp
    into it, so a pruned cell outside the range asks for the block the
    step before it held and the pipeline fetches nothing — it skips its
    fetch as well as its matmul, and costs the grid step alone.

Layout notes (TPU): 1-D per-row arrays (alpha, half-norm, per-query scalars)
are carried as (1, n)/(1, m) so the last dim is the 128-lane axis; stacked
per-row arrays ride as (S, 1, n) with (1, 1, bn) blocks, because a block's
last two dims must be (8, 128)-divisible or span the array.  ``d`` is
zero-padded to a multiple of 128 for the MXU (zero features change nothing).
``pq`` rides as (ke, tq) tiles and ``px`` as (ke, bn) — ke is tiny (default
2 extra components), so the box adds O(ke) VPU compares per candidate against
the O(d) MXU work it saves.  The f32 distance contraction runs at HIGHEST
precision: a default-precision f32 matmul may take bf16 passes on the MXU,
which would move the radius boundary.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from .ref import MIX_EPS, box_mask, norm_scales

BIG = float(jnp.finfo(jnp.float32).max / 8)

# Pass-2 flat outputs are (tiles, 8, 128) arrays: one (8, 128) tile holds
# _TILE consecutive CSR slots, and a row's run inside one db block (at most
# bn <= _TILE survivors) always lies inside two consecutive tiles.
_TILE_BITS = 10
_TILE = 1 << _TILE_BITS  # 8 * 128

# Largest flat capacity one compaction call takes: both flat outputs stay
# resident in VMEM (8 bytes per slot).  2**23 slots (64 MiB) is the largest
# power of two that compiles for a v5e core (128 MiB of VMEM); 2**24 is
# refused for exceeding it.
MAX_NNZ = 1 << 23


def _window_hit(aq, r, a_lo, a_hi):
    """Does any query window [aq-r, aq+r] in the tile intersect [a_lo, a_hi]?"""
    return jnp.any((aq + r >= a_lo) & (aq - r <= a_hi))


def _dot_f32(q, x):
    """q @ x.T in full f32 (HIGHEST: no bf16 passes on the MXU)."""
    return jax.lax.dot_general(
        q, x,
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _tile_body(q, aq, r, th, x, al, hn, pq=None, px=None):
    """Shared compute for one (query tile, db block) cell -> (keep, dhalf).

    Takes plain arrays (not refs) so the looped 2-D kernels and the stacked
    3-D kernels run the exact same instruction sequence on the same block
    shapes — the pass-1/pass-2 and looped/stacked bit-identity both lean on
    this body being the single compiled predicate pipeline.  ``pq`` (ke, tq)
    / ``px`` (ke, bn) add the k-dim box test (`ref.box_mask`); the box is a
    superset of the distance predicate, so ``dhalf`` at kept positions is
    unchanged by it.
    """
    s = _dot_f32(q, x)  # (tq, bn)
    dhalf = hn - s  # (1, bn) broadcast over (tq, bn)
    aqc = aq[0, :][:, None]          # (tq, 1)
    rc = r[0, :][:, None]
    inwin = jnp.abs(al - aqc) <= rc
    keep = inwin & (dhalf <= th[0, :][:, None])
    if pq is not None:
        keep = keep & box_mask(pq, px, r[0, :], th[0, :], hn[0, :])
    return keep, dhalf


def _count_tile(q, aq, r, th, x, al, hn, pq, px, mix):
    """Per-query survivor counts (tq,) int32 for one cell.

    ``mix`` (static) switches the dot products to bf16 under the margin
    certificate: definitely-in candidates are counted from the bf16 pass,
    and the in-band ones re-verified with the exact f32 predicate — but only
    when the band is non-empty (`lax.cond`), so clear-cut tiles never touch
    the f32 matmul.  The result provably equals the f32 count.
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
    cnt = jnp.sum(definite.astype(jnp.int32), axis=1)

    def verify(_):
        # the exact f32 predicate, same expression as `_tile_body`
        return jnp.sum((band & ((hn - _dot_f32(q, x)) <= thc))
                       .astype(jnp.int32), axis=1)

    return cnt + jax.lax.cond(jnp.any(band), verify,
                              lambda _: jnp.zeros_like(cnt), 0)


def _split_rest(rest, n_out):
    """(pq, px, *outputs) or just outputs: kernels take optional projection
    operands ahead of their outputs, discriminated by arity."""
    if len(rest) == n_out + 2:
        return rest[0], rest[1], rest[2:]
    return None, None, rest


def _filter_kernel(q_ref, aq_ref, r_ref, th_ref, x_ref, al_ref, hn_ref, *rest):
    pq_ref, px_ref, (out_ref,) = _split_rest(rest, 1)
    a_lo = al_ref[0, 0]
    a_hi = al_ref[0, al_ref.shape[1] - 1]
    hit = _window_hit(aq_ref[0, :], r_ref[0, :], a_lo, a_hi)

    @pl.when(hit)
    def _():
        keep, dhalf = _tile_body(
            q_ref[...], aq_ref[...], r_ref[...], th_ref[...], x_ref[...],
            al_ref[...], hn_ref[...],
            None if pq_ref is None else pq_ref[...],
            None if px_ref is None else px_ref[...])
        out_ref[...] = jnp.where(keep, dhalf, BIG)

    @pl.when(jnp.logical_not(hit))
    def _():
        out_ref[...] = jnp.full_like(out_ref, BIG)


def _count_kernel(mix, q_ref, aq_ref, r_ref, th_ref, x_ref, al_ref, hn_ref,
                  *rest):
    pq_ref, px_ref, (out_ref,) = _split_rest(rest, 1)
    bi = pl.program_id(1)

    @pl.when(bi == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    a_lo = al_ref[0, 0]
    a_hi = al_ref[0, al_ref.shape[1] - 1]
    hit = _window_hit(aq_ref[0, :], r_ref[0, :], a_lo, a_hi)

    @pl.when(hit)
    def _():
        cnt = _count_tile(
            q_ref[...], aq_ref[...], r_ref[...], th_ref[...], x_ref[...],
            al_ref[...], hn_ref[...],
            None if pq_ref is None else pq_ref[...],
            None if px_ref is None else px_ref[...], mix)
        out_ref[...] += cnt[None, :]


def _stacked_hit(rng_ref, aq_ref, r_ref, al_ref, body):
    """Run ``body`` on a stacked cell that `_window_hit` finds hit.

    A cell outside its tile's block range (`block_ranges`, prefetched into
    SMEM as ``rng_ref``) holds the clamped block's alphas, which may hit,
    so the range test comes first: it is the whole cost of such a cell.
    Inside the range the test is `_window_hit` as in the 2-D kernels, so
    the cells that run ``body`` are those the window test finds hit.
    """
    si, qi, bi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    k = 2 * (si * pl.num_programs(1) + qi)

    @pl.when((rng_ref[k] <= bi) & (bi <= rng_ref[k + 1]))
    def _():
        a_lo = al_ref[0, 0, 0]
        a_hi = al_ref[0, 0, al_ref.shape[2] - 1]
        pl.when(_window_hit(aq_ref[0, :], r_ref[0, :], a_lo, a_hi))(body)


def _count_stacked_kernel(mix, rng_ref, q_ref, aq_ref, r_ref, th_ref, x_ref,
                          al_ref, hn_ref, *rest):
    """`_count_kernel` with a leading segment grid axis over stacked tensors."""
    pq_ref, px_ref, (out_ref,) = _split_rest(rest, 1)

    @pl.when(pl.program_id(2) == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    def body():
        cnt = _count_tile(
            q_ref[...], aq_ref[...], r_ref[...], th_ref[...], x_ref[0],
            al_ref[0], hn_ref[0],
            None if pq_ref is None else pq_ref[...],
            None if px_ref is None else px_ref[0], mix)
        out_ref[0] += cnt[None, :]

    _stacked_hit(rng_ref, aq_ref, r_ref, al_ref, body)


def _grid_specs(m, n, d, tq, bn, ke=0):
    grid = (m // tq, n // bn)
    in_specs = [
        pl.BlockSpec((tq, d), lambda qi, bi: (qi, 0)),    # q
        pl.BlockSpec((1, tq), lambda qi, bi: (0, qi)),    # aq
        pl.BlockSpec((1, tq), lambda qi, bi: (0, qi)),    # r
        pl.BlockSpec((1, tq), lambda qi, bi: (0, qi)),    # thresh
        pl.BlockSpec((bn, d), lambda qi, bi: (bi, 0)),    # x
        pl.BlockSpec((1, bn), lambda qi, bi: (0, bi)),    # alpha
        pl.BlockSpec((1, bn), lambda qi, bi: (0, bi)),    # half_norms
    ]
    if ke:
        in_specs += [
            pl.BlockSpec((ke, tq), lambda qi, bi: (0, qi)),   # pq (extras)
            pl.BlockSpec((ke, bn), lambda qi, bi: (0, bi)),   # px (extras)
        ]
    return grid, in_specs


def _compiler_params():
    # block dim 0 (query tiles) is parallel; dim 1 revisits the count output.
    return pltpu.CompilerParams(
        dimension_semantics=(pltpu.PARALLEL, pltpu.ARBITRARY))


@functools.partial(jax.jit, static_argnames=("tq", "bn", "interpret"))
def snn_filter(q, aq, r, thresh, xs, alphas, half_norms, pq=None, px=None, *,
               tq: int = 128, bn: int = 512, interpret: bool = True):
    """Masked halved sq. distances (m, n); +BIG outside window/radius.

    Callers are expected to pre-pad: m % tq == 0, n % bn == 0, d % 128 == 0,
    with padding DB rows carrying +BIG alpha/half-norm (see ops.pad_database).
    ``pq`` (ke, m) / ``px`` (ke, n) extra projections (padded to the same m/n)
    enable the k-dim box prune; finite outputs are identical either way.
    """
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
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(*args)


@functools.partial(jax.jit, static_argnames=("tq", "bn", "interpret", "mixed"))
def snn_count(q, aq, r, thresh, xs, alphas, half_norms, pq=None, px=None, *,
              tq: int = 128, bn: int = 512, interpret: bool = True,
              mixed: bool = False):
    """Per-query neighbor counts (m,) int32 (same padding contract as filter).

    ``mixed=True`` runs the bf16 count pass under the margin certificate —
    counts are still exactly the f32 counts (module docstring).
    """
    m, d = q.shape
    n = xs.shape[0]
    ke = 0 if pq is None else pq.shape[0]
    grid, in_specs = _grid_specs(m, n, d, tq, bn, ke)
    args = (q, aq[None, :], r[None, :], thresh[None, :], xs,
            alphas[None, :], half_norms[None, :])
    if ke:
        args += (pq, px)
    out = pl.pallas_call(
        functools.partial(_count_kernel, mixed),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, tq), lambda qi, bi: (0, qi)),
        out_shape=jax.ShapeDtypeStruct((1, m), jnp.int32),
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(*args)
    return out[0]


# --------------------------------------------------------------------------- #
# Pass-2 CSR compaction                                                        #
# --------------------------------------------------------------------------- #
def _exclusive_prefix(keep):
    """(tq, bn) int32: survivors before each column within its row.

    A matmul with a strictly-upper triangle of ones: 0/1 operands are exact
    in bf16 and the f32 accumulation counts exactly (bn < 2^24).
    """
    bn = keep.shape[1]
    tri = (jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 0)
           < jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 1))
    return jax.lax.dot_general(
        jnp.where(keep, 1.0, 0.0).astype(jnp.bfloat16),
        jnp.where(tri, 1.0, 0.0).astype(jnp.bfloat16),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(jnp.int32)


def _place_run(dest, bits, t_off):
    """One row's survivors moved to their slots in a two-tile window.

    ``dest`` (1, bn) is each survivor's rank in its run (-1 for pruned
    columns), ``bits`` (1, bn) its dhalf bit pattern, ``t_off`` the run's
    first slot in the window.  Returns (local column, dhalf bits) as
    (16, 128) int32 windows.  The move is a one-hot matmul over byte planes:
    every operand is an integer < 256 (exact in bf16) and every output sums
    exactly one nonzero product, so all 32 bits arrive unchanged.
    """
    bn = dest.shape[1]
    slot = jnp.where(dest >= 0, dest + t_off, -1)
    row_of = slot >> 7  # -1 stays -1: pruned columns match no row
    lane_of = slot & 127
    local = jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1)
    planes = [local & 255, local >> 8] + [(bits >> s) & 255
                                          for s in (0, 8, 16, 24)]
    in_row = jax.lax.broadcasted_iota(jnp.int32, (16, bn), 0) == row_of
    a = jnp.concatenate([jnp.where(in_row, p, 0) for p in planes], axis=0)
    onehot = jax.lax.broadcasted_iota(jnp.int32, (128, bn), 0) == lane_of
    w = jax.lax.dot_general(
        a.astype(jnp.float32).astype(jnp.bfloat16),
        jnp.where(onehot, 1.0, 0.0).astype(jnp.bfloat16),
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32).astype(jnp.int32)  # (96, 128)
    local_w = w[0:16] | (w[16:32] << 8)
    bits_w = (w[32:48] | (w[48:64] << 8) | (w[64:80] << 16)
              | (w[80:96] << 24))
    return local_w, bits_w


def _scatter_cell(keep, dhalf, col0, base_smem, idx_ref, dh_ref, dest_scr,
                  bits_scr, cnt_scr, cnt_smem):
    """Write one cell's survivors into the flat CSR outputs.

    Survivor j of query row k goes to ``base_smem[k]`` + (survivors before
    j in this block) — ascending sorted order, so each CSR row is written
    left to right exactly once across the block loop.  A cell without
    survivors skips the row loop.  Otherwise the (1, tq) per-row counts
    take one local copy into SMEM, and the loop reads each row's count and
    write base there as scalars: a row without survivors in this block
    costs one scalar test, a row with survivors one `_place_run` and two
    window writes, then its base moves past the run.
    """
    tq, bn = keep.shape
    dest = jnp.where(keep, _exclusive_prefix(keep), -1)
    bits = jax.lax.bitcast_convert_type(dhalf, jnp.int32)
    for g in range(tq // 8):  # rows addressable through the leading axis
        dest_scr[g] = dest[g * 8:(g + 1) * 8]
        bits_scr[g] = bits[g * 8:(g + 1) * 8]
    cnt = jnp.sum(keep.astype(jnp.int32), axis=1)[None, :]
    sub = jax.lax.broadcasted_iota(jnp.int32, (8, bn), 0)
    slots = (jax.lax.broadcasted_iota(jnp.int32, (16, 128), 0) * 128
             + jax.lax.broadcasted_iota(jnp.int32, (16, 128), 1))

    def row(k, carry):
        n_k = cnt_smem[0, k]

        @pl.when(n_k > 0)
        def _():
            b_k = base_smem[0, k]
            t0 = b_k >> _TILE_BITS
            t_off = b_k & (_TILE - 1)
            pick = sub == (k & 7)
            d_k = jnp.sum(jnp.where(pick, dest_scr[k >> 3], 0), axis=0,
                          keepdims=True)
            bits_k = jnp.sum(jnp.where(pick, bits_scr[k >> 3], 0), axis=0,
                             keepdims=True)
            local_w, bits_w = _place_run(d_k, bits_k, t_off)
            run = (slots >= t_off) & (slots < t_off + n_k)
            win = pl.ds(t0, 2)
            old_i = idx_ref[win].reshape(16, 128)
            idx_ref[win] = jnp.where(run, local_w + col0,
                                     old_i).reshape(2, 8, 128)
            old_d = dh_ref[win].reshape(16, 128)
            dh_ref[win] = jnp.where(
                run, jax.lax.bitcast_convert_type(bits_w, jnp.float32),
                old_d).reshape(2, 8, 128)
            base_smem[0, k] = b_k + n_k

        return carry

    @pl.when(jnp.sum(cnt) > 0)
    def _():
        cnt_scr[...] = cnt
        pltpu.sync_copy(cnt_scr, cnt_smem)
        jax.lax.fori_loop(0, tq, row, 0)


def _compact_outputs(nnz: int, tq: int, bn: int):
    """(out_shape, scratch_shapes, vmem limit) shared by both compactions.

    The flat outputs are whole-array VMEM blocks of ``nnz`` slots rounded up
    to (8, 128) tiles plus one spare tile, so a run's two-tile window never
    leaves the array.
    """
    if bn > _TILE:
        raise ValueError(f"bn={bn} exceeds the {_TILE}-slot output tile")
    if nnz > MAX_NNZ:
        raise ValueError(f"flat CSR capacity {nnz} exceeds MAX_NNZ="
                         f"{MAX_NNZ}; split the query batch")
    n_tiles = -(-nnz // _TILE) + 1
    out_shape = [jax.ShapeDtypeStruct((n_tiles, 8, 128), jnp.int32),
                 jax.ShapeDtypeStruct((n_tiles, 8, 128), jnp.float32)]
    scratch = [pltpu.SMEM((1, tq), jnp.int32),             # write bases
               pltpu.VMEM((tq // 8, 8, bn), jnp.int32),    # run ranks
               pltpu.VMEM((tq // 8, 8, bn), jnp.int32),    # dhalf bits
               pltpu.VMEM((1, tq), jnp.int32),             # cell counts
               pltpu.SMEM((1, tq), jnp.int32)]             # ... for scalar reads
    # the two resident outputs plus room for the tile operands
    vmem = 2 * n_tiles * _TILE * 4 + (32 << 20)
    return out_shape, scratch, vmem


def _flat(out_idx, out_dh, nnz: int):
    return out_idx.reshape(-1)[:nnz], out_dh.reshape(-1)[:nnz]


def _compact_kernel(q_ref, aq_ref, r_ref, th_ref, off_ref,
                    x_ref, al_ref, hn_ref, *rest):
    pq_ref, px_ref, (idx_ref, dh_ref, base_smem, *scr) = _split_rest(rest, 7)
    qi = pl.program_id(0)
    bi = pl.program_id(1)
    bn = x_ref.shape[0]

    @pl.when((qi == 0) & (bi == 0))
    def _():
        idx_ref[...] = jnp.full_like(idx_ref, -1)
        dh_ref[...] = jnp.full_like(dh_ref, BIG)

    @pl.when(bi == 0)
    def _():
        pltpu.sync_copy(off_ref, base_smem)

    a_lo = al_ref[0, 0]
    a_hi = al_ref[0, al_ref.shape[1] - 1]
    hit = _window_hit(aq_ref[0, :], r_ref[0, :], a_lo, a_hi)

    @pl.when(hit)
    def _():
        keep, dhalf = _tile_body(
            q_ref[...], aq_ref[...], r_ref[...], th_ref[...], x_ref[...],
            al_ref[...], hn_ref[...],
            None if pq_ref is None else pq_ref[...],
            None if px_ref is None else px_ref[...])
        _scatter_cell(keep, dhalf, bi * bn, base_smem, idx_ref, dh_ref, *scr)


@functools.partial(jax.jit, static_argnames=("nnz", "tq", "bn", "interpret"))
def snn_compact(q, aq, r, thresh, offsets, xs, alphas, half_norms,
                pq=None, px=None, *,
                nnz: int, tq: int = 128, bn: int = 512, interpret: bool = True):
    """Scatter surviving (sorted-row index, dhalf) pairs into flat CSR arrays.

    ``offsets[k]`` is the first flat slot of query k's CSR row (from the pass-1
    count prefix sum); ``nnz`` is the flat capacity INCLUDING one trailing
    slot that stays -1 (callers pass >= total_neighbors + 1; bucketing it,
    e.g. to the next power of two, bounds recompilation).  Returns (idx (nnz,) int32 sorted-row
    positions with -1 in unwritten slots, dhalf (nnz,) f32).  Same padding
    contract as filter/count; padding queries must carry offsets < nnz.
    ``pq``/``px`` must match pass 1's — both passes then evaluate the same
    box-tightened predicate, preserving the count/compact agreement.

    Both grid dims are sequential: every cell scatters into the same flat
    output block, and an SMEM write base per query row, set from
    ``offsets`` at the tile's first block, carries the row's running write
    position across db blocks.

    Memory: the flat outputs live in one VMEM block (8 bytes per slot), so
    one call holds at most `MAX_NNZ` slots and raises beyond it; callers
    with larger result sets split the query batch (serving's dispatcher
    batches naturally).  Lifting this via HBM-resident outputs + manual DMA
    is future work.
    """
    m, d = q.shape
    n = xs.shape[0]
    ke = 0 if pq is None else pq.shape[0]
    grid, in_specs = _grid_specs(m, n, d, tq, bn, ke)
    in_specs = in_specs[:4] + [pl.BlockSpec((1, tq), lambda qi, bi: (0, qi))] \
        + in_specs[4:]
    args = (q, aq[None, :], r[None, :], thresh[None, :], offsets[None, :], xs,
            alphas[None, :], half_norms[None, :])
    if ke:
        args += (pq, px)
    out_shape, scratch, vmem = _compact_outputs(nnz, tq, bn)
    whole = pl.BlockSpec(out_shape[0].shape, lambda qi, bi: (0, 0, 0))
    out_idx, out_dh = pl.pallas_call(
        _compact_kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[whole, whole],
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.ARBITRARY, pltpu.ARBITRARY),
            vmem_limit_bytes=vmem),
        interpret=interpret,
    )(*args)
    return _flat(out_idx, out_dh, nnz)


# --------------------------------------------------------------------------- #
# Stacked-grid variants (one launch over a whole SegmentPack)                  #
# --------------------------------------------------------------------------- #
def _stacked_grid_specs(n_seg, m, n, d, tq, bn, ke=0, block=None):
    """Grid + input specs over a stack; per-row stacks arrive as (S, 1, n).

    ``block(s, qi, bi, *prefetch)`` picks the db block a cell's row-block
    operands (xs, alphas, half-norms, px) come from; by default ``bi``.
    Every index map passes scalar-prefetch refs through, if there are any.
    """
    if block is None:
        block = lambda s, qi, bi, *_: bi  # noqa: E731
    grid = (n_seg, m // tq, n // bn)
    in_specs = [
        pl.BlockSpec((tq, d), lambda s, qi, bi, *_: (qi, 0)),      # q
        pl.BlockSpec((1, tq), lambda s, qi, bi, *_: (0, qi)),      # aq
        pl.BlockSpec((1, tq), lambda s, qi, bi, *_: (0, qi)),      # r
        pl.BlockSpec((1, tq), lambda s, qi, bi, *_: (0, qi)),      # thresh
        pl.BlockSpec((1, bn, d),                                   # xs stack
                     lambda s, qi, bi, *p: (s, block(s, qi, bi, *p), 0)),
        pl.BlockSpec((1, 1, bn),                                   # alphas
                     lambda s, qi, bi, *p: (s, 0, block(s, qi, bi, *p))),
        pl.BlockSpec((1, 1, bn),                                   # half-norms
                     lambda s, qi, bi, *p: (s, 0, block(s, qi, bi, *p))),
    ]
    if ke:
        in_specs += [
            pl.BlockSpec((ke, tq), lambda s, qi, bi, *_: (0, qi)),     # pq
            pl.BlockSpec((1, ke, bn),                                  # px stack
                         lambda s, qi, bi, *p: (s, 0, block(s, qi, bi, *p))),
        ]
    return grid, in_specs


def block_ranges(aq, r, alphas, *, tq: int, bn: int):
    """Each (segment, query tile)'s live db block range of a stacked grid.

    Returns (lo, hi), both (S, m // tq) int32: the first and the last block
    that some row of the tile hits under `_window_hit`, in the same float32
    expressions (``aq + r >= a_lo``, ``aq - r <= a_hi`` for the block's
    first and last alpha).  Alphas ascend within a segment, so a row hits
    the one run of blocks [first, last): ``first`` counts the blocks whose
    last alpha lies below ``aq - r``, ``last`` those whose first alpha is
    at most ``aq + r`` (binary searches, O(S m log nb)).  A tile's range
    spans its rows' runs, so it holds every cell the kernels find hit, and
    both its ends are hit.  A tile whose rows hit nothing (padding rows,
    r = -BIG, never do) gets the empty range (nb, nb - 1), whose clamped
    index maps fetch one block.
    """
    n_seg, n = alphas.shape
    nb = n // bn
    # strided slices read the block ends alone (no relayout of the stack)
    a_lo = jax.lax.slice(alphas, (0, 0), (n_seg, n), (1, bn))
    a_hi = jax.lax.slice(alphas, (0, bn - 1), (n_seg, n), (1, bn))
    search = jax.vmap(jnp.searchsorted, in_axes=(0, None, None))
    first = search(a_hi, aq - r, "left").astype(jnp.int32)
    last = search(a_lo, aq + r, "right").astype(jnp.int32)
    run = first < last
    tiles = (n_seg, aq.shape[0] // tq, tq)
    lo = jnp.min(jnp.where(run, first, nb).reshape(tiles), axis=2)
    hi = jnp.max(jnp.where(run, last - 1, -1).reshape(tiles), axis=2)
    return lo, jnp.where(lo > hi, nb - 1, hi)


def _ranged_grid(n_seg, m, n, d, tq, bn, ke, aq, r, alphas):
    """(grid, in_specs, range table) of a stacked kernel whose row-block
    index maps clamp each cell's block into its tile's `block_ranges`.

    The table is the flat (lo, hi) pairs of every (segment, tile), the
    kernel's one scalar-prefetch operand.  Consecutive cells outside the
    range map to the same block, so the pipeline issues no DMA for them.
    """
    n_q = m // tq

    def block(s, qi, bi, rng):
        k = 2 * (s * n_q + qi)
        return jnp.minimum(jnp.maximum(bi, rng[k]), rng[k + 1])

    grid, in_specs = _stacked_grid_specs(n_seg, m, n, d, tq, bn, ke, block)
    lo, hi = block_ranges(aq, r, alphas, tq=tq, bn=bn)
    return grid, in_specs, jnp.stack([lo, hi], axis=-1).reshape(-1)


@functools.partial(jax.jit, static_argnames=("tq", "bn", "interpret", "mixed"))
def snn_count_stacked(q, aq, r, thresh, xs, alphas, half_norms,
                      pq=None, px=None, *,
                      tq: int = 128, bn: int = 512, interpret: bool = True,
                      mixed: bool = False):
    """Per-(segment, query) survivor counts (S, m) int32 in ONE launch.

    ``xs`` is a (S, n_pad, d) stack of padded segments (`core.engine.
    SegmentPack`); ``alphas``/``half_norms`` are the matching (S, n_pad)
    stacks, each segment's alphas ascending, and ``px`` the (S, ke, n_pad)
    projection stack.  Per-cell block pruning is unchanged — a segment
    whose alpha range misses every query window in the tile skips its MXU
    work — so stacking costs no extra predicate evaluations, only the
    per-launch dispatch that the looped engine paid S times.  Cells outside
    their tile's `block_ranges` fetch no block either.
    """
    m, d = q.shape
    n_seg, n, _ = xs.shape
    ke = 0 if pq is None else pq.shape[0]
    grid, in_specs, ranges = _ranged_grid(n_seg, m, n, d, tq, bn, ke,
                                          aq, r, alphas)
    args = (q, aq[None, :], r[None, :], thresh[None, :], xs,
            alphas[:, None, :], half_norms[:, None, :])
    if ke:
        args += (pq, px)
    out = pl.pallas_call(
        functools.partial(_count_stacked_kernel, mixed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, tq),
                                   lambda s, qi, bi, _: (s, 0, qi))),
        out_shape=jax.ShapeDtypeStruct((n_seg, 1, m), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL, pltpu.PARALLEL,
                                 pltpu.ARBITRARY)),
        interpret=interpret,
    )(ranges, *args)
    return out[:, 0, :]


def _compact_stacked_kernel(rng_ref, q_ref, aq_ref, r_ref, th_ref, off_ref,
                            x_ref, al_ref, hn_ref, *rest):
    """`_compact_kernel` with a leading segment grid axis.

    Emitted flat indices are *pack-flat*: segment s's local row j becomes
    ``s * n_pad + j`` (callers map through the pack's padded id table).
    Offsets are per (segment, query) — the global CSR base plus the
    segment-axis exclusive prefix, both computed on device.
    """
    pq_ref, px_ref, (idx_ref, dh_ref, base_smem, *scr) = _split_rest(rest, 7)
    si = pl.program_id(0)
    qi = pl.program_id(1)
    bi = pl.program_id(2)
    bn = x_ref.shape[1]
    n_pad = pl.num_programs(2) * bn

    @pl.when((si == 0) & (qi == 0) & (bi == 0))
    def _():
        idx_ref[...] = jnp.full_like(idx_ref, -1)
        dh_ref[...] = jnp.full_like(dh_ref, BIG)

    @pl.when(bi == 0)
    def _():
        pltpu.sync_copy(off_ref.at[0], base_smem)

    def body():
        keep, dhalf = _tile_body(
            q_ref[...], aq_ref[...], r_ref[...], th_ref[...], x_ref[0],
            al_ref[0], hn_ref[0],
            None if pq_ref is None else pq_ref[...],
            None if px_ref is None else px_ref[0])
        _scatter_cell(keep, dhalf, si * n_pad + bi * bn, base_smem, idx_ref,
                      dh_ref, *scr)

    _stacked_hit(rng_ref, aq_ref, r_ref, al_ref, body)


@functools.partial(jax.jit, static_argnames=("nnz", "tq", "bn", "interpret"))
def snn_compact_stacked(q, aq, r, thresh, offsets, xs, alphas, half_norms,
                        pq=None, px=None, *,
                        nnz: int, tq: int = 128, bn: int = 512,
                        interpret: bool = True):
    """Pass-2 compaction over a (S, n_pad, d) segment stack in ONE launch.

    ``offsets`` is (S, m): flat slot of segment s's first survivor for query
    k (global CSR base + segment-axis exclusive prefix).  Returns flat
    (idx (nnz,) int32 PACK-FLAT positions ``s * n_pad + local_row``,
    dhalf (nnz,) f32); same capacity and -1/+BIG conventions as
    `snn_compact`.
    All three grid dims are sequential: every cell scatters into the same
    flat output block, with the SMEM write bases carrying each query's
    running write position across a segment's db blocks.  As in
    `snn_count_stacked`, cells outside their tile's `block_ranges` fetch
    no block.
    """
    m, d = q.shape
    n_seg, n, _ = xs.shape
    ke = 0 if pq is None else pq.shape[0]
    grid, in_specs, ranges = _ranged_grid(n_seg, m, n, d, tq, bn, ke,
                                          aq, r, alphas)
    in_specs = in_specs[:4] \
        + [pl.BlockSpec((1, 1, tq), lambda s, qi, bi, _: (s, 0, qi))] \
        + in_specs[4:]
    args = (q, aq[None, :], r[None, :], thresh[None, :], offsets[:, None, :],
            xs, alphas[:, None, :], half_norms[:, None, :])
    if ke:
        args += (pq, px)
    out_shape, scratch, vmem = _compact_outputs(nnz, tq, bn)
    whole = pl.BlockSpec(out_shape[0].shape, lambda s, qi, bi, _: (0, 0, 0))
    out_idx, out_dh = pl.pallas_call(
        _compact_stacked_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=[whole, whole],
            scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.ARBITRARY, pltpu.ARBITRARY,
                                 pltpu.ARBITRARY),
            vmem_limit_bytes=vmem),
        interpret=interpret,
    )(ranges, *args)
    return _flat(out_idx, out_dh, nnz)
