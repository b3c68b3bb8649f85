"""Compute/communication overlap: ring collective matmul (shard_map).

``ring_allgather_matmul(x, w)`` computes ``allgather(x, 'model') @ w_local``
without ever materializing the full gathered x: each of the G steps multiplies
the locally-held x chunk while ``ppermute`` forwards it around the ring, so
the ICI transfer of step i overlaps the MXU work of step i-1 (XLA schedules
the independent ppermute/dot pair concurrently).

This is the standard TP overlap trick (Wang et al., "Overlap communication
with dependent computation", and the GSPMD collective-matmul pass); exposed
here as an explicit building block the hillclimb can swap in.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.shard_map import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def _ring_body(x_local, w_local, axis: str):
    n = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    perm = [(i, (i + 1) % n) for i in range(n)]
    rows = x_local.shape[-2] if x_local.ndim > 1 else x_local.shape[0]

    def step(i, carry):
        chunk, acc = carry
        # which global shard does `chunk` currently hold?
        src = (idx - i) % n
        part = chunk @ w_local
        acc = jax.lax.dynamic_update_slice_in_dim(
            acc, part, src * rows, axis=0)
        chunk = jax.lax.ppermute(chunk, axis, perm)
        return chunk, acc

    acc = jnp.zeros((rows * n, w_local.shape[-1]), x_local.dtype)
    # mark the accumulator as device-varying over the ring axis (shard_map
    # VMA typing: the carry must match the loop body's varying type)
    acc = jax.lax.pcast(acc, (axis,), to="varying")
    chunk, acc = jax.lax.fori_loop(0, n, lambda i, c: step(i, c),
                                   (x_local, acc))
    return acc


def ring_allgather_matmul(x, w, mesh: Mesh, axis: str = "model"):
    """x: (M, K) sharded P(axis, None); w: (K, N) replicated over axis.

    Returns (M, N) replicated: equal to ``x_full @ w`` with the all-gather
    pipelined against the matmul.
    """
    fn = shard_map(
        functools.partial(_ring_body, axis=axis), mesh=mesh,
        in_specs=(P(axis, None), P(None, None)),
        check_rep=False,
        out_specs=P(None, None))
    return fn(x, w)


def reference_allgather_matmul(x, w):
    return x @ w
