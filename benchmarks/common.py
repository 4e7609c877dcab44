"""Benchmark utilities: timing, scaled datasets, index builders.

Scale note: these benchmarks reproduce the paper's 1M-10B vector
experiments at 10^4-10^5 vectors with the same methodology, so they run
on any JAX backend in minutes. Their times are those of the backend they
ran on (a CPU run says nothing about a TPU), and what carries over between
platforms is their ratios against the baselines (O(1) vs O(N), constant vs
linear scaling) and their recall. ``chip_smoke.py`` at the repository
root drives the served path on a TPU at SIFT1M scale.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import core
from repro.data.pipeline import VectorStream, VectorStreamConfig


def timeit(fn, *args, warmup: int = 1, iters: int = 3, **kw):
    """Median wall seconds over ``iters`` runs (jit warm)."""
    for _ in range(warmup):
        r = fn(*args, **kw)
        jax.block_until_ready(r)
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        r = fn(*args, **kw)
        jax.block_until_ready(r)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)), r


def dataset(dim: int, n: int, seed: int = 0, zipf: float = 0.0,
            n_clusters: int = 64):
    """SIFT/GIST-like Gaussian-mixture vectors."""
    vs = VectorStream(VectorStreamConfig(seed=seed, dim=dim,
                                         n_clusters=n_clusters, zipf_a=zipf))
    return vs.batch(0, n)


def build_sivf(dim: int, n_lists: int, n_max: int, capacity: int = 64,
               slab_factor: float = 1.5, max_chain: int | None = None,
               metric: str = "l2", train_vecs=None, seed: int = 0):
    n_slabs = int(slab_factor * n_max / capacity) + n_lists
    if max_chain is None:
        max_chain = n_slabs            # bounded only by the pool itself
    cfg = core.SIVFConfig(dim=dim, n_lists=n_lists, n_slabs=n_slabs,
                          capacity=capacity, n_max=max(n_max * 2, 1024),
                          metric=metric, max_chain=max_chain)
    if train_vecs is None:
        train_vecs = dataset(dim, max(16 * n_lists, 2048), seed=seed + 7)
    cents = core.train_kmeans(jax.random.key(seed), jnp.asarray(train_vecs),
                              n_lists)
    return cfg, core.init_state(cfg, cents), np.asarray(cents)


def recall_at_k(pred_labels: np.ndarray, true_labels: np.ndarray) -> float:
    k = true_labels.shape[1]
    hits = [len(set(pred_labels[i].tolist())
                & set(true_labels[i].tolist()))
            for i in range(len(pred_labels))]
    return float(np.mean(hits) / k)


def exact_topk(vecs: np.ndarray, qs: np.ndarray, k: int) -> np.ndarray:
    from repro.utils import l2_sq
    d = np.asarray(l2_sq(jnp.asarray(qs), jnp.asarray(vecs)))
    return np.argsort(d, axis=1, kind="stable")[:, :k]


class Row:
    """One CSV row: name, us_per_call, derived metric string."""

    def __init__(self, name: str, seconds: float, derived: str = ""):
        self.name = name
        self.us = seconds * 1e6
        self.derived = derived

    def csv(self) -> str:
        return f"{self.name},{self.us:.1f},{self.derived}"
