"""Figs 13-14: multi-shard scaling (scatter-gather over a device mesh).

Runs in the calling process over the devices JAX already has: one point
per power-of-two shard count up to ``len(jax.devices())`` (1, 2, 4, ...).
A one-chip host therefore gives the one-shard point only; a four-chip host
gives 1, 2 and 4. On a CPU host, start the process with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` for eight virtual
devices (their times are CPU times, not chip times). Every row names the
platform, device kind and device count it ran on.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import core
from repro.core import distributed as dist
from repro.data.pipeline import VectorStream, VectorStreamConfig


def run(dim: int = 64) -> list[dict]:
    devices = jax.devices()
    d0 = devices[0]
    n_lists, n, b = 16, 6000, 1000
    vs = VectorStream(VectorStreamConfig(seed=0, dim=dim, n_clusters=n_lists))
    cents = core.train_kmeans(jax.random.key(0), jnp.asarray(vs.batch(0, 1024)),
                              n_lists)
    vecs = vs.batch(1, n)
    ids = np.arange(n, dtype=np.int32)
    qs = jnp.asarray(vs.batch(2, 64))
    out = []
    shards = 1
    while shards <= len(devices):
        cfg = core.SIVFConfig(dim=dim, n_lists=n_lists,
                              n_slabs=2 * n // 32 + n_lists, capacity=32,
                              n_max=4 * n, max_chain=64)
        mesh = jax.make_mesh((shards,), ("data",),
                             devices=devices[:shards])
        state = dist.init_sharded_state(cfg, cents, mesh)
        # warm + ingest
        state = dist.dist_insert(cfg, mesh, state, jnp.asarray(vecs[:b]),
                                 jnp.asarray(ids[:b]))
        t0 = time.perf_counter()
        for lo in range(b, n, b):
            state = dist.dist_insert(cfg, mesh, state,
                                     jnp.asarray(vecs[lo:lo + b]),
                                     jnp.asarray(ids[lo:lo + b]))
        jax.block_until_ready(state.n_live)
        t_ins = time.perf_counter() - t0

        d, _ = dist.dist_search(cfg, mesh, state, qs, 10, 8)     # warm
        jax.block_until_ready(d)
        t0 = time.perf_counter()
        for _ in range(3):
            d, _ = dist.dist_search(cfg, mesh, state, qs, 10, 8)
        jax.block_until_ready(d)
        t_q = (time.perf_counter() - t0) / 3

        t0 = time.perf_counter()
        state = dist.dist_delete(cfg, mesh, state, jnp.asarray(ids[:b]))
        jax.block_until_ready(state.n_live)
        t_del = time.perf_counter() - t0
        out.append({"shards": shards, "ingest_vps": (n - b) / t_ins,
                    "search_qps": 64 / t_q, "delete_vps": b / t_del,
                    "platform": d0.platform, "device_kind": d0.device_kind,
                    "device_count": len(devices)})
        shards *= 2
    return out
