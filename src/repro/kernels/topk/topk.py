"""Iterative top-k selection — Pallas TPU kernel (paper Alg. 3 merge phase).

The paper's warp merge (32 per-lane register lists -> one top-k) becomes a
VMEM-resident iterative selection: each grid step owns a [bq, L] tile of
candidate distances and extracts the k smallest by k rounds of
(min, argmin-via-one-hot, mask-to-inf). k is small (<= a few hundred) so
k passes over a VMEM tile beat a full sort.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


_NEG = -(2 ** 31) + 1  # python literal; jnp scalars would be captured consts


def _kernel(dist_ref, lab_ref, outd_ref, outl_ref, *, k: int):
    bq, nl = dist_ref.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (bq, nl), 1)

    def body(j, cur):
        m = jnp.min(cur, axis=1, keepdims=True)                  # [bq, 1]
        # first index achieving the min (match lax.top_k tie-breaking)
        ix = jnp.min(jnp.where(cur == m, col, nl), axis=1, keepdims=True)
        oh = col == ix                                           # [bq, L]
        lab = jnp.max(jnp.where(oh, lab_ref[...], _NEG), axis=1)
        outd_ref[:, pl.ds(j, 1)] = m
        outl_ref[:, pl.ds(j, 1)] = lab[:, None]
        return jnp.where(oh, jnp.inf, cur)

    jax.lax.fori_loop(0, k, body, dist_ref[...])


def topk_pallas(dists: jax.Array, labels: jax.Array, k: int,
                block_q: int = 8, interpret: bool = False
                ) -> tuple[jax.Array, jax.Array]:
    """dists/labels [Q, L] -> smallest-k (dists [Q,k], labels [Q,k])."""
    qn, nl = dists.shape
    if qn % block_q != 0:
        block_q = 1
    grid = (qn // block_q,)
    return pl.pallas_call(
        functools.partial(_kernel, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, nl), lambda i: (i, 0)),
            pl.BlockSpec((block_q, nl), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, k), lambda i: (i, 0)),
            pl.BlockSpec((block_q, k), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((qn, k), dists.dtype),
            jax.ShapeDtypeStruct((qn, k), labels.dtype),
        ],
        interpret=interpret,
    )(dists, labels)
