"""Fused slab-scan -> top-k search — Pallas TPU kernel (paper Alg. 3, whole).

The unfused pipeline (``sivf_scan`` kernel -> ``topk`` kernel) materializes
the full ``[Q, T*C]`` candidate distance/label matrices in HBM between the
two kernels, which caps the query batch size and spends HBM bandwidth on
intermediates the paper's Alg. 3 never writes: the CUDA design keeps a
per-lane *register* top-k while scanning slabs and only ever emits ``[Q, k]``.

This kernel is the TPU analogue of that register top-k:

  * the slab-id table (one row per query, ``T = nprobe * max_chain``
    entries) is scalar-prefetched to SMEM and drives the ``BlockSpec``
    index_map, so each non-contiguous slab tile is DMA'd into VMEM as if it
    were a contiguous operand (§3.3 "coalesced search on non-contiguous
    memory");
  * queries are blocked into ``[bq, D]`` tiles; the grid walks
    ``(q_tile, q_within_tile, slab)`` with the slab axis innermost, and the
    ``[bq, k]`` output block is *revisited* across the inner two axes — it
    lives in VMEM for the whole scan of a query tile and is flushed to HBM
    exactly once per tile;
  * each grid step scores one ``(query, slab)`` pair on the MXU, masks dead
    slots via the validity bitmap, and folds the ``[1, C]`` candidates into
    the running ``[1, k]`` row by k rounds of min-extraction (k is small, so
    k passes over a VMEM-resident ``[1, k+C]`` row beat a sort).

Peak memory is ``O(Q*k + bq*D + C*D)`` instead of the unfused
``O(Q*T*C)`` — the ``T*C`` candidate matrix is never built.

Tie-breaking matches the XLA reference ``core.index.scan_slabs_topk``
exactly: the running buffer occupies the low indices of the merge row and
``lax.top_k`` (reference) / first-index-argmin (here) both prefer lower
indices. Both score in full f32 (the MXU at ``Precision.HIGHEST`` here,
elementwise products there), so distances agree within f32 rounding of
the dot product and labels agree wherever distances are not tied.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

WORD_BITS = 32
_NEG = -(2 ** 31) + 1  # python literal; jnp scalars would be captured consts
META_ROWS = 8          # sublane tile: [n_slabs, X] metadata planes are
#                        fetched as 8-slab blocks (a 1-row block breaks the
#                        TPU's (8, 128) block-shape rule)


def slab_index_maps(bq: int, t: int, n_slabs: int):
    """``BlockSpec`` index maps driven by the scalar-prefetched slab table.

    Returns ``(rows, payload_ix, meta_ix)``: ``payload_ix`` picks a slab's
    ``[1, C, X]`` payload block; ``meta_ix`` picks the ``[rows, X]`` block
    of a ``[n_slabs, X]`` metadata plane (ids, norms, bitmap) that holds
    the slab, whose row inside it is ``slab % rows`` (:func:`meta_row`).
    Empty table entries (-1) fetch slab 0 and are masked in the kernel.
    """
    rows = min(META_ROWS, n_slabs)

    def slab(qt, qj, ti, tab):
        return jnp.maximum(tab[(qt * bq + qj) * t + ti], 0)

    def payload_ix(qt, qj, ti, tab, *_):
        return (slab(qt, qj, ti, tab), 0, 0)

    def meta_ix(qt, qj, ti, tab, *_):
        return (slab(qt, qj, ti, tab) // rows, 0)

    return rows, payload_ix, meta_ix


def meta_row(slab, rows: int):
    """Row of ``slab`` inside the metadata block ``slab_index_maps`` chose."""
    return pl.ds(jnp.maximum(slab, 0) % rows, 1)


def _unpack_bitmap(words: jax.Array, capacity: int) -> jax.Array:
    """[1, W] u32 validity words -> [1, C] bool, slot-ordered."""
    w = capacity // WORD_BITS
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, capacity), 1)
    word_ix = slot // WORD_BITS
    bit_ix = (slot % WORD_BITS).astype(jnp.uint32)
    # gather word per slot via broadcast-compare (W is tiny)
    wsel = jnp.zeros((1, capacity), jnp.uint32)
    for wi in range(w):
        wsel = jnp.where(word_ix == wi, words[0, wi], wsel)
    return (jnp.right_shift(wsel, bit_ix) & jnp.uint32(1)) != 0


def fold_topk(outd_ref, outl_ref, qj, d, lab, *, capacity: int, k: int
              ) -> None:
    """Fold a ``[1, C]`` candidate row into the running ``[1, k]`` top-k.

    Merge row layout = [running k | C candidates]; identical to the
    reference's concatenate order, so first-index tie-breaking matches.
    Shared by the raw fused kernel (here) and the PQ ADC kernel
    (``pq_fused.py``) — candidates that score bit-identically therefore
    select bit-identically. The k extracted minima build up in the loop
    carry and the output row is stored once: Mosaic cannot store a single
    element at a dynamic lane offset.
    """
    run_d = outd_ref[pl.ds(qj, 1), :]                   # [1, k]
    run_l = outl_ref[pl.ds(qj, 1), :]
    cd = jnp.concatenate([run_d, d], axis=1)            # [1, k+C]
    cl = jnp.concatenate([run_l, lab], axis=1)
    m = k + capacity
    col = jax.lax.broadcasted_iota(jnp.int32, (1, m), 1)
    kcol = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)

    def body(j, carry):
        cur, rd, rl = carry
        lo = jnp.min(cur, axis=1, keepdims=True)        # [1, 1]
        ix = jnp.min(jnp.where(cur == lo, col, m), axis=1, keepdims=True)
        oh = col == ix
        lj = jnp.max(jnp.where(oh, cl, _NEG), axis=1, keepdims=True)
        # masking an extracted slot to +inf makes it re-selectable once the
        # true min is +inf; every genuinely-inf slot carries label -1
        # (dead / pad / init), so force -1 there instead of the stale label
        lj = jnp.where(jnp.isinf(lo), -1, lj)
        rd = jnp.where(kcol == j, lo, rd)
        rl = jnp.where(kcol == j, lj, rl)
        return jnp.where(oh, jnp.inf, cur), rd, rl

    _, rd, rl = jax.lax.fori_loop(0, k, body, (cd, run_d, run_l))
    outd_ref[pl.ds(qj, 1), :] = rd
    outl_ref[pl.ds(qj, 1), :] = rl


def predicate_mask(attrs_ref, consts_ref, fstruct: tuple) -> jax.Array:
    """Evaluate a compiled filter over one slab's attribute tile.

    ``attrs_ref`` holds the slab's attributes *pre-transposed* to
    ``[1, A, C]`` so each attribute row is a native lane-major ``[1, C]``
    vector (no in-kernel relayout); the filter constants live in SMEM via
    the second scalar-prefetch operand. Same ``filters.eval_structure``
    recursion as the XLA references and the host oracle -> identical masks.
    """
    from repro.core.filters import eval_structure
    at = attrs_ref[0]                                   # [A, C] int32
    return eval_structure(
        fstruct,
        lambda j: at[j:j + 1, :],                       # [1, C]
        lambda i: consts_ref[i])


def _kernel(table_ref, *refs, capacity: int, k: int, metric: str,
            fstruct: tuple | None = None):
    if fstruct is None:
        (q_ref, data_ref, ids_ref, norms_ref, bitmap_ref,
         outd_ref, outl_ref) = refs
        consts_ref = attrs_ref = None
    else:
        (consts_ref, q_ref, data_ref, ids_ref, norms_ref, attrs_ref,
         bitmap_ref, outd_ref, outl_ref) = refs
    qj = pl.program_id(1)                               # query within tile
    ti = pl.program_id(2)                               # slab within chain
    bq = pl.num_programs(1)
    t = pl.num_programs(2)
    qi = pl.program_id(0) * bq + qj                     # global query row
    slab = table_ref[qi * t + ti]                       # scalar, may be -1
    row = meta_row(slab, ids_ref.shape[0])

    # first touch of this output block: reset the running top-k
    @pl.when((qj == 0) & (ti == 0))
    def _init():
        outd_ref[...] = jnp.full((bq, k), jnp.inf, jnp.float32)
        outl_ref[...] = jnp.full((bq, k), -1, jnp.int32)

    # -- score one (query, slab) pair on the MXU ---------------------------
    q = q_ref[pl.ds(qj, 1), :]                          # [1, D]
    x = data_ref[0]                                     # [C, D]
    # HIGHEST: the MXU's default pass rounds f32 operands to bf16
    dot = jax.lax.dot_general(
        q.astype(jnp.float32), x.astype(jnp.float32),
        (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)             # [1, C]
    if metric == "l2":
        qq = jnp.sum(q.astype(jnp.float32) ** 2)
        d = qq - 2.0 * dot + norms_ref[row, :]
    else:
        d = -dot

    valid = _unpack_bitmap(bitmap_ref[row, :], capacity) & (slab >= 0)
    if fstruct is not None:
        # filtered-out slots fail exactly like deleted slots (+inf / -1):
        # they can never displace a passing candidate from the top-k
        valid &= predicate_mask(attrs_ref, consts_ref, fstruct)
    d = jnp.where(valid, d, jnp.inf)
    lab = jnp.where(valid, ids_ref[row, :], -1)

    # -- fold candidates into the running [1, k] row -----------------------
    fold_topk(outd_ref, outl_ref, qj, d, lab, capacity=capacity, k=k)


def sivf_fused_search_pallas(queries: jax.Array, table: jax.Array,
                             data: jax.Array, ids: jax.Array,
                             norms: jax.Array, bitmap: jax.Array, k: int,
                             metric: str = "l2", block_q: int = 8,
                             interpret: bool = False,
                             attrs: jax.Array | None = None,
                             fstruct: tuple | None = None,
                             fconsts: jax.Array | None = None
                             ) -> tuple[jax.Array, jax.Array]:
    """queries [Q,D], table [Q,T] -> (dists [Q,k], labels [Q,k]).

    Never materializes the [Q, T*C] candidate matrix; ragged Q is handled
    by padding to a block_q multiple with -1 slab rows (masked to +inf).

    With ``fstruct`` set (a compiled predicate structure from
    ``core.filters``), ``attrs`` ``[n_slabs, C, A]`` rides as one more
    slab-indexed operand (transposed here to ``[n_slabs, A, C]`` so the
    kernel reads lane-major attribute rows) and ``fconsts`` becomes a
    *second* scalar-prefetch operand — filter constants are data in SMEM,
    so every predicate of the same structure shares this one kernel.
    """
    qn, d_dim = queries.shape
    t = table.shape[1]
    ns, c = ids.shape
    w = bitmap.shape[1]
    filtered = fstruct is not None

    bq = max(1, min(block_q, qn))
    pad = (-qn) % bq
    if pad:
        queries = jnp.concatenate(
            [queries, jnp.zeros((pad, d_dim), queries.dtype)])
        table = jnp.concatenate(
            [table, jnp.full((pad, t), -1, table.dtype)])
    qp = qn + pad

    grid = (qp // bq, bq, t)
    rows, slab_ix, meta_ix = slab_index_maps(bq, t, ns)

    def q_ix(qt, qj, ti, *_):
        return (qt, 0)

    in_specs = [
        pl.BlockSpec((bq, d_dim), q_ix),                             # q
        pl.BlockSpec((1, c, d_dim), slab_ix),                        # data
        pl.BlockSpec((rows, c), meta_ix),                            # ids
        pl.BlockSpec((rows, c), meta_ix),                            # norms
    ]
    operands = [queries, data, ids, norms]
    if filtered:
        a = attrs.shape[-1]
        in_specs.append(pl.BlockSpec((1, a, c), slab_ix))            # attrs
        operands.append(attrs.swapaxes(1, 2))         # [n_slabs, A, C]
    in_specs.append(pl.BlockSpec((rows, w), meta_ix))                # bitmap
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
    kernel = functools.partial(_kernel, capacity=c, k=k, metric=metric,
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
