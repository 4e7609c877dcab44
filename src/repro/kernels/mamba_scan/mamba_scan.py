"""Mamba selective scan — Pallas TPU kernel.

Grid (B, di/bd): each step owns a [bd] channel tile of one sequence; the
[bd, n] SSM state sits in VMEM scratch and the T-loop runs in-kernel. The
channel tile is the TPU parallelism axis (the CUDA kernel parallelizes the
same way over threadblocks).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, h_ref):
    t_len = u_ref.shape[1]
    h_ref[...] = jnp.zeros_like(h_ref)
    a = a_ref[...]                                             # [bd, n]
    d = d_ref[...]                                             # [1, bd]

    def body(t, _):
        row = (pl.ds(0, 1), pl.ds(t, 1), slice(None))
        u_t = u_ref[row].reshape(-1)                           # [bd]
        dt_t = dt_ref[row].reshape(-1)
        b_t = b_ref[row].reshape(-1)                           # [n]
        c_t = c_ref[row].reshape(-1)
        da = jnp.exp(dt_t.reshape(-1, 1) * a)                  # [bd, n]
        h = da * h_ref[...] + (dt_t * u_t).reshape(-1, 1) * b_t.reshape(1, -1)
        h_ref[...] = h
        y = jax.lax.dot_general(h, c_t.reshape(-1, 1),
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [bd, 1]
        y = y.reshape(1, -1) + d * u_t.reshape(1, -1)
        y_ref[row] = y.reshape(1, 1, -1)
        return 0

    jax.lax.fori_loop(0, t_len, body, 0)


def mamba_scan_pallas(u, delta, a, b, c, d, block_d: int = 128,
                      interpret: bool = False):
    """u, delta [B,T,di]; a [di,n]; b,c [B,T,n]; d [di] -> y [B,T,di] f32."""
    bsz, t, di = u.shape
    n = a.shape[1]
    bd = min(block_d, di)
    assert di % bd == 0
    grid = (bsz, di // bd)

    def x_ix(bi, ci):
        return (bi, 0, ci)

    def bc_ix(bi, ci):
        return (bi, 0, 0)

    def a_ix(bi, ci):
        return (ci, 0)

    def d_ix(bi, ci):
        return (0, ci)

    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, t, bd), x_ix),       # u
            pl.BlockSpec((1, t, bd), x_ix),       # delta
            pl.BlockSpec((bd, n), a_ix),          # a
            pl.BlockSpec((1, t, n), bc_ix),       # b
            pl.BlockSpec((1, t, n), bc_ix),       # c
            pl.BlockSpec((1, bd), d_ix),          # d
        ],
        out_specs=pl.BlockSpec((1, t, bd), x_ix),
        out_shape=jax.ShapeDtypeStruct((bsz, t, di), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bd, n), jnp.float32)],
        interpret=interpret,
    )(u.astype(jnp.float32), delta.astype(jnp.float32),
      a.astype(jnp.float32), b.astype(jnp.float32), c.astype(jnp.float32),
      d.astype(jnp.float32).reshape(1, -1))
