"""Pallas TPU kernel: embedding-bag (gather + segment-sum) for recsys tables.

JAX has no native EmbeddingBag; the hot path of every recsys arch here is a
multi-hot gather-reduce over huge tables.  TPU-idiomatic formulation: the grid
iterates (sample, bag_slot) and the *table row to fetch is chosen by the
BlockSpec index_map reading scalar-prefetched ids* — the same indirection
pattern used by paged-attention/MaxText embedding kernels.  The output block
(one row per sample) is revisited across the F bag slots and accumulated.

Padding contract: ids < 0 are padding; their contribution is masked in-kernel
(the index_map clamps them to row 0, the body multiplies by 0).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _bag_kernel(ids_ref, table_row_ref, out_ref):
    i = pl.program_id(0)
    f = pl.program_id(1)
    nf = pl.num_programs(1)

    @pl.when(f == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    raw = ids_ref[i * nf + f]
    w = jnp.where(raw >= 0, 1.0, 0.0).astype(out_ref.dtype)
    out_ref[...] += w * table_row_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def embedding_bag(ids: jax.Array, table: jax.Array, *, interpret: bool = True):
    """sum_f table[ids[b, f]] with ids==-1 masked; returns (B, D).

    ids: (B, F) int32; table: (V, D) with D a multiple of 128 on real TPUs.
    """
    b, f = ids.shape
    _, d = table.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, f),
        in_specs=[
            pl.BlockSpec((1, d), lambda i, fi, ids_ref: (jnp.maximum(ids_ref[i * f + fi], 0), 0)),
        ],
        out_specs=pl.BlockSpec((1, d), lambda i, fi, ids_ref: (i, 0)),
    )
    return pl.pallas_call(
        _bag_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, d), table.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL, pltpu.ARBITRARY)),
        interpret=interpret,
    )(ids.reshape(-1), table)
