"""Seeded Gaussian-mixture vectors, made on the device in fixed-size batches.

The mixture is the one the SIFT1M smoke run used (16 components with
centres drawn from N(0, 1) and per-row noise of std 0.3), made here with
``jax.random`` so that any row can be made again from ``(seed, serial)``:
row ``r`` of stream ``s`` is row ``r % rows`` of batch ``r // rows`` of that
stream. Stream 0 holds the rows that are ingested (base set first, then the
rows a churn writer adds), stream 1 the queries.

The benchmark's reference makes its rows again from here; it never reads
vectors back from the system under test.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

ROWS = 0
QUERIES = 1


def seed_key(seed: int) -> jax.Array:
    """A key for any whole-number seed, also one past 32 bits."""
    s = int(seed) & (2 ** 64 - 1)
    return jax.random.fold_in(jax.random.key(np.uint32(s & 0xFFFFFFFF)),
                              np.uint32(s >> 32))


@partial(jax.jit, static_argnames=("dim", "components"))
def centres(key: jax.Array, dim: int, components: int) -> jax.Array:
    return jax.random.normal(jax.random.fold_in(key, 7), (components, dim),
                             jnp.float32)


@partial(jax.jit, static_argnames=("rows", "stream"))
def batch(key: jax.Array, cents: jax.Array, index, rows: int, stream: int,
          std: float) -> jax.Array:
    """Batch ``index`` of ``stream``: ``[rows, dim]`` float32."""
    k = jax.random.fold_in(jax.random.fold_in(key, 100 + stream), index)
    kc, kn = jax.random.split(k)
    comp = jax.random.randint(kc, (rows,), 0, cents.shape[0])
    noise = jax.random.normal(kn, (rows, cents.shape[1]), jnp.float32)
    return cents[comp] + jnp.float32(std) * noise


class Mixture:
    """The rows and queries of one run, addressed by serial number."""

    def __init__(self, seed: int, dim: int, components: int, std: float,
                 rows: int):
        self.key = seed_key(seed)
        self.cents = centres(self.key, dim, components)
        self.rows = int(rows)
        self.std = float(std)
        self.dim = int(dim)

    def batch(self, index: int, stream: int = ROWS) -> jax.Array:
        return batch(self.key, self.cents, np.int32(index), self.rows,
                     stream, self.std)

    def take(self, lo: int, n: int, stream: int = ROWS) -> jax.Array:
        """Rows ``[lo, lo + n)`` of a stream, ``n <= rows``, on the device;
        one executable for every ``lo``."""
        if not 0 < n <= self.rows:
            raise ValueError(f"take of {n} rows; 1 to {self.rows} allowed")
        return _take(self.key, self.cents, np.int32(lo // self.rows),
                     np.int32(lo % self.rows), self.rows, n, stream, self.std)


@partial(jax.jit, static_argnames=("rows", "n", "stream"))
def _take(key, cents, first, off, rows: int, n: int, stream: int, std: float):
    two = jnp.concatenate([batch(key, cents, first, rows, stream, std),
                           batch(key, cents, first + 1, rows, stream, std)])
    return jax.lax.dynamic_slice_in_dim(two, off, n)
