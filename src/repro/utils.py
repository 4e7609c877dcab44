"""Small shared utilities."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# full-f32 matmuls on a TPU (its default precision rounds operands to bf16)
HIGHEST = jax.lax.Precision.HIGHEST


def ceil_div(a, b):
    """Ceiling division for ints or int arrays."""
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    """Round ``a`` up to the next multiple of ``b``."""
    return int(ceil_div(a, b) * b)


def exclusive_cumsum(x: jax.Array, axis: int = 0) -> jax.Array:
    """Exclusive prefix sum along ``axis``."""
    inc = jnp.cumsum(x, axis=axis)
    return inc - x


def tree_num_params(tree) -> int:
    """Total number of array elements in a pytree."""
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))


def tree_num_bytes(tree) -> int:
    """Total number of bytes in a pytree."""
    return sum(
        int(np.prod(x.shape)) * x.dtype.itemsize
        for x in jax.tree_util.tree_leaves(tree)
    )


def l2_sq(a: jax.Array, b: jax.Array) -> jax.Array:
    """Pairwise squared L2 distance, ``a [N,D]`` x ``b [M,D]`` -> ``[N,M]``.

    Uses the ||a||^2 - 2 a.b + ||b||^2 expansion so the inner term hits the
    MXU as a single matmul, at ``HIGHEST`` precision: a TPU's default f32
    matmul rounds operands to bf16, whose error is a fixed bias per row of
    ``b`` large enough to move which centroid is nearest.
    """
    aa = jnp.sum(a * a, axis=-1, keepdims=True)       # [N,1]
    bb = jnp.sum(b * b, axis=-1, keepdims=True).T     # [1,M]
    ab = jnp.matmul(a, b.T, precision=HIGHEST)         # [N,M]
    return aa - 2.0 * ab + bb
