"""Seeded data: a Gaussian mixture made on the device in one jitted call.

The public data sets the configurations name (SIFT1M, GloVe-100) cannot be
downloaded here, so their shape is kept and their values are synthetic:
clusters with heavy-tailed sizes (Pareto weights) and varied spreads, the
same family `chip_smoke.py` uses, drawn with `jax.random` from ``--seed``.
One call makes the base rows and a fresh pool of query rows from the same
mixture.

The mixture itself (centers, spreads, weights) is part of the configuration
and comes from its own fixed ``mixture_seed``; ``--seed`` draws the points.
So every seed serves the same distribution, and the work a request needs
varies from seed to seed only as much as a sample of it does.

A configuration that names a ``points_seed`` as well fixes the points, and
``--seed`` draws the order of the base rows instead, so the ids that every
answer carries differ from seed to seed while each query row of the pool has
the same neighbors at the same distances, and needs the same work, under
every seed.
"""
from __future__ import annotations

import functools

import numpy as np


def prng_key(seed: int):
    """A JAX key from any whole number (seeds may exceed 32 bits)."""
    import jax

    entropy = int(seed) if seed >= 0 else [1, -int(seed)]
    word = np.random.SeedSequence(entropy).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


@functools.lru_cache(maxsize=None)
def _mixture_fn(n: int, pool: int, d: int, clusters: int, shuffle: bool):
    import jax
    import jax.numpy as jnp

    def make(mixture_key, key, order_key):
        kc, ks, kw = jax.random.split(mixture_key, 3)
        ka, kx = jax.random.split(key, 2)
        centers = 2.0 * jax.random.normal(kc, (clusters, d), jnp.float32)
        scales = jax.random.uniform(ks, (clusters,), jnp.float32, 0.6, 1.4)
        w = jax.random.pareto(kw, 2.0, (clusters,), jnp.float32)
        cdf = jnp.cumsum(w) / jnp.sum(w)
        u = jax.random.uniform(ka, (n + pool,), jnp.float32)
        c = jnp.minimum(jnp.searchsorted(cdf, u), clusters - 1)
        x = jax.random.normal(kx, (n + pool, d), jnp.float32)
        x = x * scales[c][:, None] + centers[c]
        if shuffle:
            order = jax.random.permutation(order_key, n)
            x = jnp.concatenate([x[:n][order], x[n:]])
        return x

    return jax.jit(make)


def make_rows(seed: int, n: int, pool: int, d: int, clusters: int,
              mixture_seed: int, points_seed: int | None = None):
    """(base (n, d), pool (pool, d)) float32 host arrays from ``seed``: its
    points, or with ``points_seed`` the fixed points, the base rows in the
    seed's order."""
    shuffle = points_seed is not None
    fn = _mixture_fn(n, pool, d, clusters, shuffle)
    key = prng_key(points_seed if shuffle else seed)
    rows = np.asarray(fn(prng_key(mixture_seed), key, prng_key(seed)))
    return rows[:n], rows[n:]


def config_rows(cfg: dict, seed: int):
    """`make_rows` for a configuration file's sizes and ``assumed`` seeds."""
    a = cfg["assumed"]
    return make_rows(seed, int(cfg["n"]), int(cfg["query_pool"]),
                     int(cfg["d"]), int(a["clusters"]),
                     int(a["mixture_seed"]), a.get("points_seed"))
