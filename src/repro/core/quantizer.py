"""Coarse quantizer: k-means over the training sample (Faiss-IVF style).

The quantizer is *static* in SIVF (as in the paper: lists are fixed after
training; only their contents stream). ``assign`` routes vectors to lists,
``probe`` returns the top-nprobe lists for queries.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.utils import HIGHEST, l2_sq


@partial(jax.jit, static_argnames=("n_lists", "iters"))
def train_kmeans(key: jax.Array, xs: jax.Array, n_lists: int, iters: int = 10
                 ) -> jax.Array:
    """Lloyd's k-means. xs [N, D] -> centroids [n_lists, D]."""
    n = xs.shape[0]
    idx = jax.random.choice(key, n, (n_lists,), replace=n < n_lists)
    cents = xs[idx]

    def step(cents, _):
        assign = jnp.argmin(l2_sq(xs, cents), axis=1)              # [N]
        onehot = jax.nn.one_hot(assign, n_lists, dtype=xs.dtype)   # [N, L]
        sums = jnp.matmul(onehot.T, xs, precision=HIGHEST)          # [L, D]
        counts = jnp.sum(onehot, axis=0)[:, None]                   # [L, 1]
        new = jnp.where(counts > 0, sums / jnp.maximum(counts, 1), cents)
        return new, None

    cents, _ = jax.lax.scan(step, cents, None, length=iters)
    return cents


def assign(centroids: jax.Array, xs: jax.Array, metric: str = "l2") -> jax.Array:
    """Route vectors to their IVF list. xs [B, D] -> [B] int32."""
    if metric == "ip":
        scores = jnp.matmul(xs, centroids.T, precision=HIGHEST)
        return jnp.argmax(scores, axis=1).astype(jnp.int32)
    return jnp.argmin(l2_sq(xs, centroids), axis=1).astype(jnp.int32)


def probe(centroids: jax.Array, qs: jax.Array, nprobe: int, metric: str = "l2"
          ) -> jax.Array:
    """Top-nprobe coarse lists per query. qs [Q, D] -> [Q, nprobe] int32."""
    if metric == "ip":
        scores = jnp.matmul(qs, centroids.T, precision=HIGHEST)
    else:
        scores = -l2_sq(qs, centroids)
    _, lists = jax.lax.top_k(scores, nprobe)
    return lists.astype(jnp.int32)
