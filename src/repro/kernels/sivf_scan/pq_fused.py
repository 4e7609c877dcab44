"""Fused ADC scan -> top-k over PQ-compressed slabs — Pallas TPU kernel.

The raw fused kernel (``fused.py``) is bandwidth-bound on slab payload DMA:
every (query, slab) step moves a ``[C, D]`` fp32 tile from HBM. With
product quantization (``core/pq.py``) the same step only moves the
``[C, m]`` uint8 code tile — an ``4*D/m``-fold cut in scanned bytes (~32x
at D=64, m=8) — and scores candidates by *asymmetric distance*: per-query
lookup tables ``adc[s, j] = d(q_s, codebook[s, j])`` are staged once per
query tile in VMEM and a candidate's distance is the sum of its ``m``
table entries.

Same shape as ``fused.py`` otherwise:

  * the slab-id table is scalar-prefetched to SMEM and drives the code /
    id / bitmap ``BlockSpec`` index maps, so non-contiguous compressed
    slabs DMA as if contiguous;
  * the grid walks ``(q_tile, q_within_tile, slab)``, the ``[bq, k]``
    output block is revisited across the inner two axes and flushed once
    per tile;
  * deleted slots mask through the validity bitmap, empty chains (-1 slab
    ids) score +inf / label -1.

TPU has no fast VMEM gather, so each subspace's lookup is a one-hot
matmul: ``sel[C, ksub] @ adc_s[ksub]`` on the MXU. Exactly one product per
row is the (finite) table entry and the rest are 0.0, so each term equals
the gathered entry *bit-for-bit*; terms accumulate in ascending-subspace
order, matching ``core.index.scan_slabs_topk_pq``'s left-to-right adds.
The shared ``fold_topk`` then keeps selection/tie-breaking identical, so
the whole kernel is bit-exact against the XLA ADC reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sivf_scan.fused import (
    _unpack_bitmap,
    fold_topk,
    meta_row,
    predicate_mask,
    slab_index_maps,
)


def _pq_kernel(table_ref, *refs, capacity: int, k: int, m: int,
               ksub: int, fstruct: tuple | None = None):
    if fstruct is None:
        (adc_ref, codes_ref, ids_ref, bitmap_ref,
         outd_ref, outl_ref) = refs
        consts_ref = attrs_ref = None
    else:
        (consts_ref, adc_ref, codes_ref, ids_ref, attrs_ref, bitmap_ref,
         outd_ref, outl_ref) = refs
    qj = pl.program_id(1)                               # query within tile
    ti = pl.program_id(2)                               # slab within chain
    bq = pl.num_programs(1)
    t = pl.num_programs(2)
    qi = pl.program_id(0) * bq + qj                     # global query row
    slab = table_ref[qi * t + ti]                       # scalar, may be -1
    row = meta_row(slab, ids_ref.shape[0])

    @pl.when((qj == 0) & (ti == 0))
    def _init():
        outd_ref[...] = jnp.full((bq, k), jnp.inf, jnp.float32)
        outl_ref[...] = jnp.full((bq, k), -1, jnp.int32)

    # -- ADC-score one (query, slab) pair ----------------------------------
    codes = codes_ref[0].astype(jnp.int32)              # [C, m]
    kcol = jax.lax.broadcasted_iota(jnp.int32, (capacity, ksub), 1)
    d = None
    for s in range(m):                                  # ascending subspaces
        sel = (kcol == codes[:, s][:, None]).astype(jnp.float32)  # [C, K]
        adc_s = adc_ref[pl.ds(qj, 1), pl.ds(s * ksub, ksub)]      # [1, K]
        # HIGHEST precision: the default MXU pass truncates f32 operands
        # to bf16, which would round the looked-up table entry and break
        # bit-exactness on real TPUs (interpret mode hides this)
        term = jax.lax.dot_general(
            adc_s, sel, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)         # [1, C]
        d = term if d is None else d + term

    valid = _unpack_bitmap(bitmap_ref[row, :], capacity) & (slab >= 0)
    if fstruct is not None:
        # filtered-out slots fail exactly like deleted slots (+inf / -1)
        valid &= predicate_mask(attrs_ref, consts_ref, fstruct)
    d = jnp.where(valid, d, jnp.inf)
    lab = jnp.where(valid, ids_ref[row, :], -1)

    fold_topk(outd_ref, outl_ref, qj, d, lab, capacity=capacity, k=k)


def sivf_pq_fused_search_pallas(adc: jax.Array, table: jax.Array,
                                codes: jax.Array, ids: jax.Array,
                                bitmap: jax.Array, k: int, block_q: int = 8,
                                interpret: bool = False,
                                attrs: jax.Array | None = None,
                                fstruct: tuple | None = None,
                                fconsts: jax.Array | None = None
                                ) -> tuple[jax.Array, jax.Array]:
    """adc [Q, m, ksub], table [Q, T] -> (dists [Q, k], labels [Q, k]).

    ``adc`` comes from ``core.pq.adc_tables`` (already metric-shaped, so
    the kernel itself is metric-agnostic); ragged Q pads to a ``block_q``
    multiple with -1 slab rows (masked to +inf) and zero ADC rows.

    ``attrs``/``fstruct``/``fconsts`` add the compiled-predicate mask
    exactly as in ``fused.sivf_fused_search_pallas``: attributes become a
    slab-indexed ``[1, A, C]`` operand, constants a second scalar-prefetch
    SMEM vector, and filtered-out slots mask before the top-k fold.
    """
    qn, m, ksub = adc.shape
    t = table.shape[1]
    ns, c = ids.shape
    w = bitmap.shape[1]
    filtered = fstruct is not None
    adc = adc.reshape(qn, m * ksub)                     # row-major [s, j]

    bq = max(1, min(block_q, qn))
    pad = (-qn) % bq
    if pad:
        adc = jnp.concatenate(
            [adc, jnp.zeros((pad, m * ksub), adc.dtype)])
        table = jnp.concatenate(
            [table, jnp.full((pad, t), -1, table.dtype)])
    qp = qn + pad

    grid = (qp // bq, bq, t)
    rows, slab_ix, meta_ix = slab_index_maps(bq, t, ns)

    def q_ix(qt, qj, ti, *_):
        return (qt, 0)

    in_specs = [
        pl.BlockSpec((bq, m * ksub), q_ix),
        pl.BlockSpec((1, c, m), slab_ix),                        # codes
        pl.BlockSpec((rows, c), meta_ix),                        # ids
    ]
    operands = [adc, codes, ids]
    if filtered:
        a = attrs.shape[-1]
        in_specs.append(pl.BlockSpec((1, a, c), slab_ix))        # attrs
        operands.append(attrs.swapaxes(1, 2))     # [n_slabs, A, C]
    in_specs.append(pl.BlockSpec((rows, w), meta_ix))            # bitmap
    operands.append(bitmap)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2 if filtered else 1,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bq, k), q_ix),
            pl.BlockSpec((bq, k), q_ix),
        ],
    )
    kernel = functools.partial(_pq_kernel, capacity=c, k=k, m=m, ksub=ksub,
                               fstruct=fstruct)
    prefetch = [table.reshape(-1)]
    if filtered:
        prefetch.append(fconsts.astype(jnp.int32))
    dists, labels = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((qp, k), jnp.float32),
            jax.ShapeDtypeStruct((qp, k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(*prefetch, *operands)
    return dists[:qn], labels[:qn]
