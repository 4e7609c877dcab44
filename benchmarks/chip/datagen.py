"""Seeded Gaussian-mixture vectors, made on the device in fixed-size batches.

The mixture is the one the SIFT1M smoke run used (16 components with
centres drawn from N(0, 1) and per-row noise of std 0.3), made here with
``jax.random`` so that any row can be made again from ``(seed, serial)``:
row ``r`` of stream ``s`` is row ``r % rows`` of batch ``r // rows`` of that
stream. Stream 0 holds the rows that are ingested (base set first, then the
rows a churn writer adds), stream 1 the queries.

A configuration's ``generator`` may set ``noise_rank`` (``r``, ``0 < r <=
dim``). The noise then lies in a subspace of ``r`` dimensions, the same for
every row of the run: an orthonormal basis ``B`` (``[r, dim]``, the QR of a
Gaussian drawn from the run's key under a tag of its own), and a row is
``centre + std * sqrt(dim / r) * (z @ B)`` with ``z ~ N(0, I_r)``. The
factor ``sqrt(dim / r)`` keeps the isotropic noise's energy, ``std**2 *
dim`` per row. Without the key every row is ``centre + std * n`` with ``n ~
N(0, I_dim)``.

A configuration's ``generator`` may also set ``dataset_seed``. The rows
(stream 0), the centres and the noise basis then come from that seed in
every run, as a published dataset is one set of vectors, and the run's own
seed draws only the queries (stream 1) and, elsewhere, the order of the
traffic. So every seed does the same work in another order. Without the key
the run's seed draws both.

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


@partial(jax.jit, static_argnames=("dim", "rank"))
def basis(key: jax.Array, dim: int, rank: int) -> jax.Array:
    """The run's noise subspace: an orthonormal ``[rank, dim]`` basis."""
    g = jax.random.normal(jax.random.fold_in(key, 8), (dim, rank), jnp.float32)
    with jax.default_matmul_precision("highest"):
        q, _ = jnp.linalg.qr(g)
    return q.T


@partial(jax.jit, static_argnames=("rows", "stream"))
def batch(key: jax.Array, cents: jax.Array, index, rows: int, stream: int,
          std: float, basis: jax.Array | None = None) -> jax.Array:
    """Batch ``index`` of ``stream``: ``[rows, dim]`` float32; the noise in
    the span of ``basis`` where one is given."""
    k = jax.random.fold_in(jax.random.fold_in(key, 100 + stream), index)
    kc, kn = jax.random.split(k)
    comp = jax.random.randint(kc, (rows,), 0, cents.shape[0])
    if basis is None:
        noise = jax.random.normal(kn, (rows, cents.shape[1]), jnp.float32)
    else:
        rank, dim = basis.shape
        z = jax.random.normal(kn, (rows, rank), jnp.float32)
        noise = jnp.float32(np.sqrt(dim / rank)) * jnp.matmul(
            z, basis, precision=jax.lax.Precision.HIGHEST)
    return cents[comp] + jnp.float32(std) * noise


class Mixture:
    """The rows and queries of one run, addressed by serial number. The
    rows come from ``seed``, the queries from ``query_seed`` where it is
    given, else from ``seed`` too."""

    def __init__(self, seed: int, dim: int, components: int, std: float,
                 rows: int, noise_rank: int | None = None,
                 query_seed: int | None = None):
        self.key = seed_key(seed)
        self.query_key = self.key if query_seed is None \
            else seed_key(query_seed)
        self.cents = centres(self.key, dim, components)
        self.rows = int(rows)
        self.std = float(std)
        self.dim = int(dim)
        if noise_rank is None:
            self.basis = None
        elif 0 < int(noise_rank) <= self.dim:
            self.basis = basis(self.key, self.dim, int(noise_rank))
        else:
            raise ValueError(f"noise_rank {noise_rank}: 1 to {dim} allowed")

    @classmethod
    def of(cls, conf: dict, seed: int) -> "Mixture":
        """The mixture a configuration's ``generator`` describes, for a run
        with ``seed``: its rows from ``dataset_seed`` where that is set."""
        gen = conf["generator"]
        data = gen.get("dataset_seed")
        return cls(seed if data is None else data, conf["dim"],
                   gen["components"], gen["std"], gen["batch_rows"],
                   gen.get("noise_rank"), query_seed=seed)

    def _key(self, stream: int) -> jax.Array:
        return self.key if stream == ROWS else self.query_key

    def batch(self, index: int, stream: int = ROWS) -> jax.Array:
        return batch(self._key(stream), self.cents, np.int32(index),
                     self.rows, stream, self.std, self.basis)

    def take(self, lo: int, n: int, stream: int = ROWS) -> jax.Array:
        """Rows ``[lo, lo + n)`` of a stream, ``n <= rows``, on the device;
        one executable for every ``lo``."""
        if not 0 < n <= self.rows:
            raise ValueError(f"take of {n} rows; 1 to {self.rows} allowed")
        return _take(self._key(stream), self.cents, np.int32(lo // self.rows),
                     np.int32(lo % self.rows), self.rows, n, stream, self.std,
                     self.basis)


@partial(jax.jit, static_argnames=("rows", "n", "stream"))
def _take(key, cents, first, off, rows: int, n: int, stream: int, std: float,
          basis=None):
    two = jnp.concatenate([batch(key, cents, first, rows, stream, std, basis),
                           batch(key, cents, first + 1, rows, stream, std,
                                 basis)])
    return jax.lax.dynamic_slice_in_dim(two, off, n)
