"""The work the sorted-window search needs, counted by the benchmark itself.

The paper's window: a base row x can be a neighbor of a query q at radius r
only if ``|alpha_x - alpha_q| <= r``, where alpha is the projection on the
data's first principal component.  Whatever kernel does the work, it must
at least compare each (query, row) pair inside that window, and read each
row that falls in any window once.  So over a traced window:

* ``flops = 2 d W``, with W the number of (query row, base row) pairs inside
  the windows;
* ``bytes = 4 d (distinct base rows in any window + query rows)
  + 8 (result pairs)`` (float32 rows; an int32 id and a float32 distance per
  result);
* the least time is the larger of flops over the bf16 peak and bytes over
  the HBM peak.

alpha here is the benchmark's own: the top eigenvector of the covariance of
the index-space rows, from `numpy.linalg.eigh`, not the program's power
iteration.  The bound is a lower bound, so a kernel can reach it only by
skipping pairs inside the window, which an exact kernel cannot do.  It uses
the bf16 peak although the program's distance matmul runs float32 at
HIGHEST precision, so it reads low by that factor.
"""
from __future__ import annotations

import numpy as np

from .reference import index_space
from .tracing import union_length


class Window:
    """Sorted projections of the base rows on their first principal axis."""

    def __init__(self, base: np.ndarray, metric: str):
        import jax
        import jax.numpy as jnp

        x = jnp.asarray(base, jnp.float32)
        if metric == "angular":
            x = x / jnp.maximum(jnp.linalg.norm(x, axis=1, keepdims=True),
                                1e-30)
        mu = jnp.mean(x, axis=0)
        xc = x - mu
        cov = np.asarray(jnp.matmul(xc.T, xc,
                                    precision=jax.lax.Precision.HIGHEST),
                         np.float64)
        _, vecs = np.linalg.eigh(cov)
        self.v = vecs[:, -1]
        self.mu = np.asarray(mu, np.float64)
        self.metric = metric
        self.alphas = np.sort(np.asarray(
            jnp.matmul(xc, jnp.asarray(self.v, jnp.float32),
                       precision=jax.lax.Precision.HIGHEST), np.float64))

    def project(self, q: np.ndarray) -> np.ndarray:
        return (index_space(q, self.metric) - self.mu) @ self.v

    def bounds(self, aq: np.ndarray, r: np.ndarray):
        """[lo, hi) positions in the sorted alphas of each query's window."""
        lo = np.searchsorted(self.alphas, aq - r, side="left")
        hi = np.searchsorted(self.alphas, aq + r, side="right")
        return lo, hi


def window_pairs(lo: np.ndarray, hi: np.ndarray) -> int:
    """W: (query, row) pairs inside the windows."""
    return int(np.sum(hi - lo))


def distinct_rows(lo: np.ndarray, hi: np.ndarray) -> int:
    """Base rows that fall in at least one window (union of [lo, hi))."""
    return int(union_length((s, e) for s, e in zip(lo.tolist(), hi.tolist())
                            if e > s))


def least_seconds(d: int, pairs_in_window: int, rows_read: int,
                  query_rows: int, result_pairs: int, peak: dict) -> dict:
    """The roofline's lower bound on device time, and which side bounds it."""
    flops = 2.0 * d * pairs_in_window
    nbytes = 4.0 * d * (rows_read + query_rows) + 8.0 * result_pairs
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return {"flops": flops, "bytes": nbytes, "seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory"}
