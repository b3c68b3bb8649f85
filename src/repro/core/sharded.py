"""Distributed SNN: the sorted index sharded contiguously across a mesh axis.

Layout: device k of the ``data`` axis holds sorted rows ``[k*n/D, (k+1)*n/D)``.
Because the global sort order is preserved *within and across* shards, every
device can run the same alpha-window pruning locally; a query's window touches
at most a contiguous run of devices, and devices outside it prune everything at
block level (zero matmuls on a real TPU via the Pallas kernel skip).

Device-resident sharding covers fixed-shape outputs only (`shard_index` +
the shard_map count / per-shard top-k functions).  The exact CSR entry
(`query_radius_csr_sharded`) uses the mesh only to split the index into
per-shard segments: its packed plan and every kernel launch live on one
device.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import snn as _snn


def _axis_size(mesh: Mesh, axis) -> int:
    return int(np.prod([mesh.shape[a]
                        for a in (axis if isinstance(axis, tuple) else (axis,))]))


def _pad_for_shards(index: _snn.SNNIndex, nshards: int, block: int = 512):
    """Host-side shard padding: rows to a (nshards * block) multiple.

    Returns (xs, alphas, half_norms, order, projs, rows_per_shard); padding
    rows carry +BIG alpha / half-norm (and +BIG extra projections, when the
    index has them) so they never match.
    """
    from ..kernels.snn_query import BIG
    from .engine import _index_extra_projs

    unit = nshards * block
    n, d = index.xs.shape
    npad = max((n + unit - 1) // unit, 1) * unit
    big = np.float32(BIG)  # the one +BIG sentinel (kernels.snn_query.BIG)
    xs = np.concatenate([index.xs, np.zeros((npad - n, d), index.xs.dtype)], 0)
    al = np.concatenate([index.alphas, np.full(npad - n, big, np.float32)], 0)
    hn = np.concatenate([index.half_norms, np.full(npad - n, big, np.float32)], 0)
    od = np.concatenate([index.order, np.full(npad - n, -1, np.int64)], 0)
    ep = _index_extra_projs(index)
    pj = None if ep is None else np.concatenate(
        [ep.astype(np.float32), np.full((ep.shape[0], npad - n), big,
                                        np.float32)], 1)
    return xs, al, hn, od, pj, npad // nshards


def shard_index(index: _snn.SNNIndex, mesh: Mesh, axis: str = "data", block: int = 512):
    """Pad and place the sorted database, alpha scores and half-norms on a mesh.

    Returns (xs, alphas, half_norms, order) device arrays sharded P(axis) on
    rows.  Padding rows carry +BIG alpha / half-norm so they never match.
    """
    xs, al, hn, od, _, _ = _pad_for_shards(index, _axis_size(mesh, axis), block)
    s2 = NamedSharding(mesh, P(axis, None))
    s1 = NamedSharding(mesh, P(axis))
    return (jax.device_put(xs, s2), jax.device_put(al, s1),
            jax.device_put(hn, s1), jax.device_put(od, s1))


def _local_filter(xs, alphas, half_norms, xq, aq, r, thresh):
    """Per-shard masked halved distances (m, n_local); +BIG where pruned.

    The contraction runs at HIGHEST precision: a default-precision f32
    matmul may take bf16 passes on a TPU, which moves the radius boundary.
    """
    dhalf = half_norms[None, :] - jnp.matmul(
        xq, xs.T, precision=jax.lax.Precision.HIGHEST)
    inwin = jnp.abs(alphas[None, :] - aq[:, None]) <= r[:, None]
    keep = inwin & (dhalf <= thresh[:, None])
    big = jnp.asarray(jnp.finfo(dhalf.dtype).max / 8, dhalf.dtype)
    return jnp.where(keep, dhalf, big)


def make_sharded_count_fn(mesh: Mesh, axis: str = "data"):
    """Returns count(xs, alphas, hn, xq, aq, r, thresh) -> (m,) int32, jitted.

    Queries replicated; DB sharded along rows; psum over the shard axis.
    """
    from jax.experimental.shard_map import shard_map

    def body(xs, alphas, hn, xq, aq, r, thresh):
        big = jnp.finfo(jnp.float32).max / 8
        dh = _local_filter(xs, alphas, hn, xq, aq, r, thresh)
        local = jnp.sum(dh < big, axis=1).astype(jnp.int32)
        return jax.lax.psum(local, axis)

    sm = shard_map(
        body, mesh=mesh,
        in_specs=(P(axis, None), P(axis), P(axis), P(None, None), P(None), P(None), P(None)),
        check_rep=False,
        out_specs=P(None),
    )
    return jax.jit(sm)


def make_sharded_topk_fn(mesh: Mesh, k_per_shard: int, axis: str = "data"):
    """Returns topk(xs, alphas, hn, order, xq, aq, r, thresh) ->
    (idx (m, D*k), dhalf (m, D*k)) gathering each shard's k best candidates.

    Exact as long as no single shard holds more than k_per_shard true neighbors
    of a query (callers check via the count fn and re-query with larger k).
    """
    from jax.experimental.shard_map import shard_map

    def body(xs, alphas, hn, order, xq, aq, r, thresh):
        dh = _local_filter(xs, alphas, hn, xq, aq, r, thresh)
        vals, loc = jax.lax.top_k(-dh, k_per_shard)  # smallest dhalf
        gidx = jnp.where(vals > -jnp.finfo(jnp.float32).max / 8, order[loc], -1)
        out_i = jax.lax.all_gather(gidx, axis, axis=1, tiled=True)
        out_d = jax.lax.all_gather(-vals, axis, axis=1, tiled=True)
        return out_i, out_d

    sm = shard_map(
        body, mesh=mesh,
        in_specs=(P(axis, None), P(axis), P(axis), P(axis),
                  P(None, None), P(None), P(None), P(None)),
        check_rep=False,
        out_specs=(P(None, None), P(None, None)),
    )
    return jax.jit(sm)


def make_sharded_percount_fn(mesh: Mesh, axis: str = "data"):
    """Returns percount(xs, alphas, hn, xq, aq, r, thresh) -> (D, m) int32.

    Pass 1 of the sharded CSR engine: each device counts its own survivors; the
    (shard, query) matrix lets the host compute both the global CSR offsets and
    each shard's write base (exclusive prefix over the shard axis).
    """
    from jax.experimental.shard_map import shard_map

    def body(xs, alphas, hn, xq, aq, r, thresh):
        big = jnp.finfo(jnp.float32).max / 8
        dh = _local_filter(xs, alphas, hn, xq, aq, r, thresh)
        return jnp.sum(dh < big, axis=1).astype(jnp.int32)[None, :]

    sm = shard_map(
        body, mesh=mesh,
        in_specs=(P(axis, None), P(axis), P(axis), P(None, None), P(None), P(None), P(None)),
        check_rep=False,
        out_specs=P(axis, None),
    )
    return jax.jit(sm)


def query_radius_csr_sharded(
    index: _snn.SNNIndex,
    mesh: Mesh,
    q: np.ndarray,
    radius,
    return_distance: bool = True,
    axis: str = "data",
    block: int = 512,
    query_tile: int = 128,
    use_pallas: bool | None = None,
    native: bool = True,
    packed: bool = True,
    pack=None,
) -> _snn.CSRNeighbors:
    """Exact variable-length CSR results with the database sharded over a mesh.

    ``radius`` is a scalar or a per-query (m,) vector in the native metric —
    identical contract to `snn.query_radius_csr` (the per-shard window prune
    and both kernel passes are per-query throughout).

    Because the sort order is contiguous across shards, shard k's survivors of
    query i occupy the CSR slots starting at ``indptr[i] + sum(counts[:k, i])``
    — so pass 2 runs the compaction kernel once per shard with those offsets,
    every shard scattering into disjoint slots of the same flat arrays, and
    the merged result is bit-identical to the single-device
    `query_radius_csr`.

    Each shard's padded slice becomes one `core.engine.Segment`; the engine
    runs the ONE count → prefix-sum → compact orchestration (per-segment
    `kernels.snn_count`, host prefix sums for the global `indptr` and the
    per-shard write bases, per-segment `kernels.snn_compact` into disjoint
    slots).  Both passes share the same compiled predicate pipeline, which is
    load-bearing: a ULP-level disagreement between differently-compiled
    float32 filters would corrupt the scatter layout.
    `make_sharded_percount_fn` (one shard_map over the mesh) remains
    available for device-native counting, but its `_local_filter` is a
    different XLA program, so it must not source scatter offsets.
    ``packed=True`` (default) stacks the shard segments into one
    `engine.SegmentPack` plan and runs each pass as a single stacked launch;
    callers issuing repeated batches against a static index should build the
    plan once with `mesh_pack` and pass it as ``pack`` so its device
    representations amortize (this one-shot entry otherwise rebuilds it per
    call).  ``packed=False`` keeps the one-launch-per-shard looped executor.
    The mesh fixes the shard decomposition either way (device placement of
    each launch is a deployment concern).
    """
    from . import engine as _engine

    if packed:
        if pack is None:
            pack = mesh_pack(index, mesh, axis=axis, block=block)
        return _engine.query_csr_packed(index, pack, q, radius,
                                        return_distance,
                                        query_tile=query_tile,
                                        use_pallas=use_pallas, native=native)
    segments = mesh_segments(index, mesh, axis=axis, block=block)
    return _engine.query_csr(index, segments, q, radius, return_distance,
                             query_tile=query_tile, use_pallas=use_pallas,
                             native=native)


def mesh_segments(index: _snn.SNNIndex, mesh: Mesh, axis: str = "data",
                  block: int = 512) -> list:
    """One engine `Segment` per device of ``axis`` (the shard decomposition
    used by `query_radius_csr_sharded` and `core.graph`'s sharded self-join).

    Per-shard padded slices of the contiguously sharded sort order: row
    padding inside a shard is a no-op (rows-per-shard is a block multiple);
    `make_segment` pads d to the 128-lane multiple to match padded queries.
    """
    from . import engine as _engine

    nshards = _axis_size(mesh, axis)
    xs_h, al_h, hn_h, od_h, pj_h, n_per = _pad_for_shards(index, nshards,
                                                          block)
    return [_engine.make_segment(xs_h[k * n_per:(k + 1) * n_per],
                                 al_h[k * n_per:(k + 1) * n_per],
                                 hn_h[k * n_per:(k + 1) * n_per],
                                 od_h[k * n_per:(k + 1) * n_per],
                                 block=block,
                                 projs=None if pj_h is None
                                 else pj_h[:, k * n_per:(k + 1) * n_per])
            for k in range(nshards)]


def mesh_pack(index: _snn.SNNIndex, mesh: Mesh, axis: str = "data",
              block: int = 512, epoch: int = 0):
    """The mesh's shard decomposition as one `engine.SegmentPack` plan.

    Shards are equal-size slices of the padded sort order, so the pack needs
    no re-padding: it is exactly `mesh_segments` stacked.  Long-lived owners
    build it once per index epoch and pass it to `engine.query_csr_packed`
    / `engine.run_csr_packed` for every batch.
    """
    from . import engine as _engine

    return _engine.SegmentPack.build(
        mesh_segments(index, mesh, axis=axis, block=block), epoch=epoch)


def prepare_query_arrays(index: _snn.SNNIndex, q: np.ndarray, radius):
    """Host-side prep shared by the sharded entry points (see
    `snn.prepare_query_predicates` — the single source of the float32
    predicate inputs)."""
    xq, aq, r, thresh, _ = _snn.prepare_query_predicates(index, q, radius)
    return (jnp.asarray(xq), jnp.asarray(aq), jnp.asarray(r),
            jnp.asarray(thresh))
