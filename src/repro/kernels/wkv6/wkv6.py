"""RWKV6 WKV recurrence — Pallas TPU kernel.

Grid (B, H): each step owns one (batch, head) pair; the [dk, dv] recurrent
state lives in VMEM scratch and the T-loop runs inside the kernel (the
recurrence is inherently sequential in T; parallelism comes from the B*H
grid, which is how the official CUDA kernel is launched too).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, s_ref):
    t_len = r_ref.shape[1]
    s_ref[...] = jnp.zeros_like(s_ref)
    u = u_ref[...]                                           # [1, dk]

    def body(t, _):
        row = (pl.ds(0, 1), pl.ds(t, 1), pl.ds(0, 1), slice(None))
        r_t = r_ref[row].reshape(1, -1)
        k_t = k_ref[row].reshape(1, -1)
        v_t = v_ref[row].reshape(1, -1)
        w_t = w_ref[row].reshape(1, -1)
        kv = k_t.reshape(-1, 1) * v_t                        # [dk, dv]
        s = s_ref[...]
        y = jax.lax.dot_general(                              # [1, dv]
            r_t, s + u.reshape(-1, 1) * kv,
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        y_ref[row] = y.reshape(1, 1, 1, -1)
        s_ref[...] = w_t.reshape(-1, 1) * s + kv
        return 0

    jax.lax.fori_loop(0, t_len, body, 0)


def wkv6_pallas(r, k, v, w, u, interpret: bool = False):
    """r,k,w [B,T,H,dk]; v [B,T,H,dv]; u [H,dk] -> y [B,T,H,dv] f32."""
    b, t, h, dk = r.shape
    dv = v.shape[-1]
    grid = (b, h)

    def x_ix(bi, hi):
        return (bi, 0, hi, 0)

    def u_ix(bi, hi):
        return (hi, 0)

    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, t, 1, dk), x_ix),
            pl.BlockSpec((1, t, 1, dk), x_ix),
            pl.BlockSpec((1, t, 1, dv), x_ix),
            pl.BlockSpec((1, t, 1, dk), x_ix),
            pl.BlockSpec((1, dk), u_ix),
        ],
        out_specs=pl.BlockSpec((1, t, 1, dv), x_ix),
        out_shape=jax.ShapeDtypeStruct((b, t, h, dv), jnp.float32),
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        interpret=interpret,
    )(r.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
      w.astype(jnp.float32), u.astype(jnp.float32))
