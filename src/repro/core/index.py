"""SIVF index operations: batched insert / delete / search (paper §3).

CUDA -> TPU adaptation (DESIGN.md §2): the paper's per-thread lock-free
protocols (Algorithms 1, 2, 4) become *bulk-synchronous batched plans*:

  insert  — sort-by-list + segmented prefix sums produce a conflict-free
            (slab, slot) coordinate for every element of the batch, then
            scatters apply payloads, bitmap bits, ATT entries and chain
            links in one shot. O(B log B) per batch of B, independent of
            index size N (the paper's O(1)-per-element claim). The batch is
            *all-or-nothing*: overwrite-deletes are staged and commit only
            after the allocation plan succeeds, so a POOL_EXHAUSTED /
            CHAIN_OVERFLOW batch leaves the index byte-identical (error
            bits aside) — previously-live ids keep their old payloads.
  delete  — ATT lookup + vectorized bitmap clear (the paper's atomicAnd
            linearization point becomes the functional state swap), then a
            bounded sequential pass reclaims slabs that dropped to zero
            occupancy (unlink + push to free stack; Alg. 4 lines 15-19).
  search  — coarse probe + slab-chain traversal + fused validity-masked
            distance scan + streaming top-k (Alg. 3). Two table sources
            (the paper-faithful pointer walk over ``nxt`` and the
            beyond-paper dense list->slab gather) feed one scan->top-k
            dispatch; no backend materializes the [Q, T*C] candidates.
            With ``cfg.pq`` set, every backend scores PQ-compressed slabs
            by ADC instead (``scan_slabs_topk_pq`` /
            kernels/sivf_scan/pq_fused.py): one per-query-batch table of
            per-subspace partial distances feeds table-lookup sums over
            the uint8 code plane, bit-exact between the XLA reference and
            the fused Pallas kernel.

All ops are jit-compiled with state donation: the returned state reuses the
input buffers (XLA in-place), mirroring "in-place mutation in VRAM".

This module is the *functional* surface (explicit cfg/state threading). The
preferred client entry point is the stateful session handle
``sivf.Index`` (``core/api.py``), which owns the state, buckets ragged
batches, turns the sticky ``state.error`` bits into per-batch
``MutationReport``s (eager, or deferred futures resolved in one packed
transfer at ``Index.flush``), persists/reshards the state across device
topologies, and delegates to the same kernels here. Design notes with the
memory-layout and commit-pipeline diagrams: docs/architecture.md.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import bitmap as bm
from repro.core import filters as flt
from repro.core import pq as pqmod
from repro.core import quantizer
from repro.core.state import (
    ERR_CHAIN_OVERFLOW,
    ERR_ID_RANGE,
    ERR_POOL_EXHAUSTED,
    SIVFConfig,
    SlabPoolState,
)
from repro.utils import ceil_div, exclusive_cumsum

_I32_MAX = jnp.iinfo(jnp.int32).max


# ---------------------------------------------------------------------------
# Insert (paper Alg. 1 Insert / Alg. 2)
# ---------------------------------------------------------------------------

def _dedupe_keep_last(ext_ids: jax.Array, valid: jax.Array) -> jax.Array:
    """Within-batch duplicate ids: keep only the last occurrence.

    Implements the paper's delete-then-insert overwrite semantics at batch
    granularity (the batch is one linearization epoch; last write wins).
    """
    b = ext_ids.shape[0]
    key = jnp.where(valid, ext_ids, _I32_MAX)
    order = jnp.argsort(key, stable=True)        # same ids: ascending position
    ks = key[order]
    keep_sorted = jnp.concatenate(
        [ks[:-1] != ks[1:], jnp.array([True])])   # last of each run
    keep = jnp.zeros((b,), bool).at[order].set(keep_sorted)
    return valid & keep


def _insert_impl(cfg: SIVFConfig, state: SlabPoolState, vecs: jax.Array,
                 ext_ids: jax.Array, lists: jax.Array,
                 codes: jax.Array | None = None,
                 attrs: jax.Array | None = None,
                 want_plan: bool = False):
    """All-or-nothing batched insert.

    With ``want_plan=True`` (the tiered host store, ``core/tiered.py``)
    the return value is ``(state, plan)`` where ``plan`` maps every *input*
    row to the coordinates the commit gave it: ``plan["slab"]`` /
    ``plan["slot"]`` ``[B]`` int32 (-1 for padding rows, out-of-range ids,
    rows superseded by a later in-batch duplicate, and — because the batch
    is atomic — *every* row of an aborted batch), plus ``plan["codes"]``
    ``[B, code_m]`` uint8, the device-encoded PQ codewords in input order
    (zero-width without PQ). The host store replays exactly the payload
    writes the device committed, so the two tiers stay bit-identical
    without ever transferring the payload planes themselves.

    With ``cfg.pq`` set, ``codes`` ``[B, m]`` may carry pre-encoded
    codewords (elastic resharding re-routes *stored* codes, so the code
    planes survive byte-for-byte by construction instead of round-tripping
    through decode/encode); omitted, the batch encodes on ingest.

    With ``cfg.attributes`` set, ``attrs`` ``[B, n_attrs]`` int32 stamps
    each row's filter attributes (core/filters.py); omitted at this
    functional layer the batch stamps zeros — the session handle
    (``Index.add``) is the strict surface that *requires* attributes, so
    tenant rows can never default their way out of a mandatory filter.

    Overwrites keep the paper's delete-then-insert linearization, but the
    whole batch is *staged*: the overwrite-deletes run on a functional copy
    (``staged``) of the pre-batch state while the pristine input value stays
    live, and the allocation plan — computed exactly, on the post-delete
    pool — picks which value survives the single ``lax.cond`` commit point.
    A batch that hits ``POOL_EXHAUSTED`` / ``CHAIN_OVERFLOW`` therefore
    returns the input state untouched except for its error bits: every
    previously-live id stays searchable with its old payload. The payload
    planes (``data`` / ``ids`` / ``norms``) pass through the staged delete
    unmodified, so keeping both values alive until the commit point costs
    one transient copy of the small metadata arrays only, never of the
    vector pool itself.
    """
    b = vecs.shape[0]
    c = cfg.capacity
    ns, nl, nm = cfg.n_slabs, cfg.n_lists, cfg.n_max

    # -- sanitize ids ------------------------------------------------------
    in_range = (ext_ids >= 0) & (ext_ids < nm)
    err_range = jnp.any((~in_range) & (ext_ids != -1))
    valid0 = in_range
    valid0 = _dedupe_keep_last(ext_ids, valid0)

    # -- stage delete-then-insert for already-present ids (§3 Data Model) --
    eid0 = jnp.where(valid0, ext_ids, 0)
    present = valid0 & (state.att_slab[eid0] >= 0)
    staged = _delete_impl(cfg, state, jnp.where(present, ext_ids, -1))

    # -- sort batch by target list; rank within list -----------------------
    lists_key = jnp.where(valid0, lists.astype(jnp.int32), nl)
    order = jnp.argsort(lists_key, stable=True)
    sl = lists_key[order]                                     # [B] sorted
    sv = vecs[order]
    sids = ext_ids[order]
    svalid = sl < nl
    first_ix = jnp.searchsorted(sl, sl, side="left")
    rank = (jnp.arange(b) - first_ix).astype(jnp.int32)
    counts = jnp.bincount(lists_key, length=nl + 1)[:nl].astype(jnp.int32)

    # -- per-list capacity plan (segmented prefix sums) --------------------
    # Exact: planned on the staged post-delete pool, so slabs drained by
    # this batch's own overwrites are already back on the free stack (a
    # full-pool overwrite of a full index still commits).
    heads = staged.heads
    cur_l = jnp.where(heads >= 0, staged.cursor[jnp.clip(heads, 0)], c)
    space_l = (c - cur_l).astype(jnp.int32)                   # head free slots
    overflow_l = jnp.maximum(counts - space_l, 0)
    n_new_l = ceil_div(overflow_l, c).astype(jnp.int32)       # new slabs/list
    offs_l = exclusive_cumsum(n_new_l).astype(jnp.int32)
    total_new = jnp.sum(n_new_l)

    pool_ok = total_new <= staged.free_top                    # fail-fast (§3.2)
    chain_ok = jnp.all(staged.table_len + n_new_l <= cfg.max_chain)
    ok = pool_ok & chain_ok

    # -- per-item coordinates ----------------------------------------------
    sl_c = jnp.clip(sl, 0, nl - 1)
    h_item = jnp.where(svalid, heads[sl_c], -1)
    space_item = space_l[sl_c]
    in_head = svalid & (rank < space_item) & (h_item >= 0)
    over = rank - space_item
    new_ord = jnp.where(svalid & ~in_head, over // c, 0)
    new_slot = jnp.where(svalid & ~in_head, over % c, 0)
    alloc_idx = offs_l[sl_c] + new_ord                        # global new-slab ordinal
    stack_pos = staged.free_top - 1 - alloc_idx
    new_slab_for_item = staged.free_stack[jnp.clip(stack_pos, 0, ns - 1)]
    item_slab = jnp.where(in_head, h_item, new_slab_for_item)
    item_slot = jnp.where(in_head, c - space_item + rank, new_slot)

    # -- per-new-slab metadata (g = global allocation ordinal) -------------
    g = jnp.arange(b, dtype=jnp.int32)
    gmask = g < total_new
    slab_of_g = staged.free_stack[jnp.clip(staged.free_top - 1 - g, 0, ns - 1)]
    slab_prev_g = staged.free_stack[jnp.clip(staged.free_top - g, 0, ns - 1)]
    slab_next_g = staged.free_stack[jnp.clip(staged.free_top - 2 - g, 0,
                                             ns - 1)]
    # ordinal/list of each new slab, scattered from the slot-0 item
    first_of_slab = svalid & (~in_head) & (new_slot == 0)
    g_tgt = jnp.where(first_of_slab, alloc_idx, b)
    list_of_g = jnp.full((b,), 0, jnp.int32).at[g_tgt].set(sl, mode="drop")
    ord_of_g = jnp.zeros((b,), jnp.int32).at[g_tgt].set(new_ord, mode="drop")
    # chain links: new slab j links next -> (j==0 ? old head : slab j-1);
    # the *last* new slab of each list becomes the new head (Alg. 2).
    nxt_of_g = jnp.where(ord_of_g == 0, heads[jnp.clip(list_of_g, 0, nl - 1)],
                         slab_prev_g)
    is_last_of_list = ord_of_g == (n_new_l[jnp.clip(list_of_g, 0, nl - 1)] - 1)
    prv_of_g = jnp.where(is_last_of_list, -1, slab_next_g)

    # PQ ingest path: encode once per batch (the codebooks are identical in
    # the staged and pristine values; an aborted batch discards the codes
    # with the rest of the staged scatter, so atomicity is untouched)
    if cfg.pq is not None:
        if codes is None:
            new_codes = pqmod.encode(state.pq_codebooks,
                                     sv.astype(jnp.float32))
        else:
            new_codes = codes[order].astype(jnp.uint8)   # same batch sort
    # attribute stamps ride the same sort and the same staged commit
    if cfg.n_attrs:
        if attrs is None:
            sattrs = jnp.zeros((b, cfg.n_attrs), jnp.int32)
        else:
            sattrs = attrs[order].astype(jnp.int32)

    def apply(operand) -> SlabPoolState:
        staged, _ = operand                          # commit the staged batch
        drop_g = jnp.where(gmask, slab_of_g, ns)
        nxt = staged.nxt.at[drop_g].set(nxt_of_g, mode="drop")
        prv = staged.prv.at[drop_g].set(prv_of_g, mode="drop")
        owner = staged.owner.at[drop_g].set(list_of_g, mode="drop")
        cursor = staged.cursor.at[drop_g].set(0, mode="drop")
        live = staged.live.at[drop_g].set(0, mode="drop")
        bitmap = staged.bitmap.at[drop_g].set(jnp.uint32(0), mode="drop")
        # per-list head relink
        has_new = n_new_l > 0
        first_new_l = slab_of_g[jnp.clip(offs_l, 0, b - 1)]
        last_new_l = slab_of_g[jnp.clip(offs_l + n_new_l - 1, 0, b - 1)]
        old_head_tgt = jnp.where(has_new & (heads >= 0), heads, ns)
        prv = prv.at[old_head_tgt].set(first_new_l, mode="drop")
        new_heads = jnp.where(has_new, last_new_l, heads)
        # dense chain tables (beyond-paper; maintained incrementally)
        tl_g = staged.table_len[jnp.clip(list_of_g, 0, nl - 1)]
        tab_l = jnp.where(gmask, list_of_g, nl)
        tables = staged.tables.at[tab_l, jnp.clip(tl_g + ord_of_g, 0,
                                                  cfg.max_chain - 1)
                                  ].set(slab_of_g, mode="drop")
        table_pos = staged.table_pos.at[drop_g].set(tl_g + ord_of_g,
                                                    mode="drop")
        table_len = staged.table_len + n_new_l
        # payload writes + publication (bitmap bits are distinct per word, so
        # a scatter-add is an OR; see DESIGN.md §2 on the fence analogue)
        drop_i = jnp.where(svalid, item_slab, ns)
        data = staged.data.at[drop_i, item_slot].set(
            sv[:, :cfg.payload_dim].astype(cfg.dtype), mode="drop")
        if cfg.pq is not None:
            codes = staged.codes.at[drop_i, item_slot].set(
                new_codes, mode="drop")
        else:
            codes = staged.codes
        if cfg.n_attrs:
            attrs_plane = staged.attrs.at[drop_i, item_slot].set(
                sattrs, mode="drop")
        else:
            attrs_plane = staged.attrs
        ids = staged.ids.at[drop_i, item_slot].set(sids, mode="drop")
        norms = staged.norms.at[drop_i, item_slot].set(
            jnp.sum(sv.astype(jnp.float32) ** 2, axis=-1), mode="drop")
        word, bit = bm.slot_word_bit(item_slot)
        bitmap = bitmap.at[drop_i, word].add(bit, mode="drop")
        cursor = cursor.at[drop_i].add(1, mode="drop")
        live = live.at[drop_i].add(1, mode="drop")
        att_tgt = jnp.where(svalid, sids, nm)
        att_slab = staged.att_slab.at[att_tgt].set(item_slab, mode="drop")
        att_slot = staged.att_slot.at[att_tgt].set(item_slot, mode="drop")
        return SlabPoolState(
            data=data, ids=ids, norms=norms, bitmap=bitmap, nxt=nxt, prv=prv,
            owner=owner, cursor=cursor, live=live, heads=new_heads,
            free_stack=staged.free_stack, free_top=staged.free_top - total_new,
            att_slab=att_slab, att_slot=att_slot,
            n_live=staged.n_live + jnp.sum(svalid),
            error=staged.error | jnp.where(err_range, ERR_ID_RANGE, 0),
            centroids=staged.centroids, tables=tables, table_len=table_len,
            table_pos=table_pos, codes=codes,
            pq_codebooks=staged.pq_codebooks, attrs=attrs_plane)

    def fail(operand) -> SlabPoolState:
        _, pristine = operand                 # drop the staged deletes whole
        err = jnp.where(~pool_ok, ERR_POOL_EXHAUSTED, 0) \
            | jnp.where(~chain_ok, ERR_CHAIN_OVERFLOW, 0) \
            | jnp.where(err_range, ERR_ID_RANGE, 0)
        return SlabPoolState(
            **{f.name: getattr(pristine, f.name)
               for f in pristine.__dataclass_fields__.values()
               if f.name != "error"},
            error=pristine.error | err)

    out = jax.lax.cond(ok, apply, fail, (staged, state))
    if not want_plan:
        return out
    # commit plan in *input* order: scatter the batch-sorted coordinates
    # back through `order`; -1 marks rows the commit never wrote (padding /
    # out-of-range / superseded duplicates / the whole batch on abort)
    inv_slab = jnp.full((b,), -1, jnp.int32).at[order].set(
        jnp.where(svalid, item_slab, -1))
    inv_slot = jnp.zeros((b,), jnp.int32).at[order].set(item_slot)
    plan_slab = jnp.where(ok, inv_slab, -1)
    if cfg.pq is not None:
        plan_codes = jnp.zeros((b, cfg.code_m), jnp.uint8
                               ).at[order].set(new_codes)
    else:
        plan_codes = jnp.zeros((b, 0), jnp.uint8)
    return out, {"slab": plan_slab, "slot": inv_slot, "codes": plan_codes}


@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(1,))
def insert(cfg: SIVFConfig, state: SlabPoolState, vecs: jax.Array,
           ext_ids: jax.Array, lists: jax.Array | None = None,
           codes: jax.Array | None = None,
           attrs: jax.Array | None = None) -> SlabPoolState:
    """Batched ingest. ``vecs`` [B, D], ``ext_ids`` [B] (-1 rows = padding).

    ``lists`` may pre-route vectors (distributed ingestion reuses the
    router's assignment); otherwise the coarse quantizer assigns. With
    ``cfg.pq``, ``codes`` may carry pre-encoded codewords (resharding);
    otherwise the batch encodes on ingest. With ``cfg.attributes``,
    ``attrs`` [B, n_attrs] stamps filter attributes (zeros when omitted).
    """
    if lists is None:
        lists = quantizer.assign(state.centroids, vecs.astype(cfg.dtype),
                                 cfg.metric)
    return _insert_impl(cfg, state, vecs, ext_ids, lists, codes, attrs)


# ---------------------------------------------------------------------------
# Delete (paper Alg. 1 Delete / Alg. 4)
# ---------------------------------------------------------------------------

def _delete_impl(cfg: SIVFConfig, state: SlabPoolState, ext_ids: jax.Array
                 ) -> SlabPoolState:
    b = ext_ids.shape[0]
    ns, nl, nm = cfg.n_slabs, cfg.n_lists, cfg.n_max

    valid = (ext_ids >= 0) & (ext_ids < nm)
    # dedupe (paper: repeated deletes are idempotent, Thm 3.3)
    key = jnp.where(valid, ext_ids, _I32_MAX)
    order = jnp.argsort(key, stable=True)
    ke = key[order]
    first = jnp.concatenate([jnp.array([True]), ke[1:] != ke[:-1]])
    act0 = first & (ke != _I32_MAX)
    ke_c = jnp.where(act0, ke, 0)
    s = state.att_slab[ke_c]                                  # [B]
    o = state.att_slot[ke_c]
    act = act0 & (s >= 0)                                     # live entries only

    # -- logical deletion: clear validity bits (linearization point) -------
    word, bit = bm.slot_word_bit(o)
    drop_s = jnp.where(act, s, ns)
    clear = jnp.zeros_like(state.bitmap).at[drop_s, word].add(bit, mode="drop")
    bitmap = state.bitmap & ~clear
    live = state.live.at[drop_s].add(-1, mode="drop")
    att_slab = state.att_slab.at[jnp.where(act, ke_c, nm)].set(-1, mode="drop")
    n_live = state.n_live - jnp.sum(act)

    # -- slab-wise reclamation (Alg. 4 lines 15-19) -------------------------
    # Bounded sequential pass: only slabs that dropped to zero occupancy are
    # unlinked (doubly-linked chains; DESIGN.md §2) and pushed to the stack.
    def body(i, carry):
        (nxt, prv, owner, heads, free_stack, free_top, cursor, live2,
         tables, table_len, table_pos) = carry
        si = jnp.clip(s[i], 0)
        do = act[i] & (live2[si] == 0) & (owner[si] >= 0)
        li = jnp.clip(owner[si], 0)
        p, n = prv[si], nxt[si]
        # unlink
        heads = heads.at[jnp.where(do & (p < 0), li, nl)].set(n, mode="drop")
        nxt = nxt.at[jnp.where(do & (p >= 0), jnp.clip(p, 0), ns)].set(
            n, mode="drop")
        prv = prv.at[jnp.where(do & (n >= 0), jnp.clip(n, 0), ns)].set(
            p, mode="drop")
        # dense-table removal: swap-with-last
        pos = jnp.clip(table_pos[si], 0)
        last = jnp.clip(table_len[li] - 1, 0)
        moved = tables[li, last]
        li_d = jnp.where(do, li, nl)
        tables = tables.at[li_d, pos].set(moved, mode="drop")
        tables = tables.at[li_d, last].set(-1, mode="drop")
        table_pos = table_pos.at[
            jnp.where(do & (moved >= 0), jnp.clip(moved, 0), ns)].set(
            pos, mode="drop")
        table_pos = table_pos.at[jnp.where(do, si, ns)].set(-1, mode="drop")
        table_len = table_len.at[li_d].add(-1, mode="drop")
        # recycle (instant reuse; paper §3.1 "immediate reclamation")
        free_stack = free_stack.at[jnp.where(do, free_top, ns)].set(
            si, mode="drop")
        free_top = free_top + do.astype(jnp.int32)
        owner = owner.at[jnp.where(do, si, ns)].set(-1, mode="drop")
        cursor = cursor.at[jnp.where(do, si, ns)].set(0, mode="drop")
        nxt = nxt.at[jnp.where(do, si, ns)].set(-1, mode="drop")
        prv = prv.at[jnp.where(do, si, ns)].set(-1, mode="drop")
        return (nxt, prv, owner, heads, free_stack, free_top, cursor, live2,
                tables, table_len, table_pos)

    carry = (state.nxt, state.prv, state.owner, state.heads,
             state.free_stack, state.free_top, state.cursor, live,
             state.tables, state.table_len, state.table_pos)
    (nxt, prv, owner, heads, free_stack, free_top, cursor, live, tables,
     table_len, table_pos) = jax.lax.fori_loop(0, b, body, carry)

    return SlabPoolState(
        data=state.data, ids=state.ids, norms=state.norms, bitmap=bitmap,
        nxt=nxt, prv=prv, owner=owner, cursor=cursor, live=live, heads=heads,
        free_stack=free_stack, free_top=free_top, att_slab=att_slab,
        att_slot=state.att_slot, n_live=n_live, error=state.error,
        centroids=state.centroids, tables=tables, table_len=table_len,
        table_pos=table_pos, codes=state.codes,
        pq_codebooks=state.pq_codebooks, attrs=state.attrs)


@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(1,))
def delete(cfg: SIVFConfig, state: SlabPoolState, ext_ids: jax.Array
           ) -> SlabPoolState:
    """Batched lazy eviction. ``ext_ids`` [B]; -1 entries are no-ops."""
    return _delete_impl(cfg, state, ext_ids)


# ---------------------------------------------------------------------------
# Search (paper Alg. 3)
# ---------------------------------------------------------------------------

def walk_chains(cfg: SIVFConfig, state: SlabPoolState, lists: jax.Array
                ) -> jax.Array:
    """Paper-faithful pointer walk: lists [Q, P] -> slab table [Q, P*T].

    Sequential gathers over ``nxt`` with the Alg. 3 traversal bound and
    self-loop guard. -1 pads exhausted chains.
    """
    s = jnp.where(lists >= 0, state.heads[jnp.clip(lists, 0)], -1)

    def step(s, _):
        n = jnp.where(s >= 0, state.nxt[jnp.clip(s, 0)], -1)
        n = jnp.where(n == s, -1, n)        # self-loop guard
        return n, s

    _, seq = jax.lax.scan(step, s, None, length=cfg.max_chain)  # [T, Q, P]
    q = lists.shape[0]
    return jnp.moveaxis(seq, 0, -1).reshape(q, -1)


def gather_tables(cfg: SIVFConfig, state: SlabPoolState, lists: jax.Array
                  ) -> jax.Array:
    """Beyond-paper dense-table path: one gather, no pointer chasing."""
    q = lists.shape[0]
    t = jnp.where(lists[..., None] >= 0,
                  state.tables[jnp.clip(lists, 0)], -1)       # [Q, P, T]
    return t.reshape(q, -1)


def _filter_mask(cfg: SIVFConfig, state: SlabPoolState, sc: jax.Array,
                 fstruct: tuple | None, fconsts: jax.Array | None
                 ) -> jax.Array | None:
    """Per-slot predicate mask for one gathered slab column (XLA paths).

    ``sc`` [Q] clipped slab ids -> bool [Q, C] (or None when unfiltered).
    Same ``filters.eval_structure`` recursion the Pallas kernels run; the
    structure is static (jit key), the constants are traced.
    """
    if fstruct is None:
        return None
    at = state.attrs[sc]                                      # [Q, C, A]
    return flt.eval_structure(
        fstruct, lambda j: at[..., j], lambda i: fconsts[i])


def scan_slabs_topk(cfg: SIVFConfig, state: SlabPoolState, queries: jax.Array,
                    table: jax.Array, k: int,
                    fstruct: tuple | None = None,
                    fconsts: jax.Array | None = None
                    ) -> tuple[jax.Array, jax.Array]:
    """Validity-masked distance scan + streaming top-k (XLA path).

    Memory-bounded: scans the slab table column-by-column keeping a running
    [Q, k] result, the jnp analogue of Alg. 3's per-lane register top-k.
    The fused Pallas kernel (kernels/sivf_scan/fused.py) is the TPU
    analogue: same selection and tie-breaking, distances within f32
    rounding of the dot product.
    ``fstruct``/``fconsts`` (core/filters.py) AND a per-slot predicate mask
    into the validity mask *before* the fold — filtered-out candidates
    score +inf / label -1, exactly like deleted slots, so they can never
    displace passing rows from the top-k.
    """
    qn = queries.shape[0]
    qf = queries.astype(jnp.float32)
    qq = jnp.sum(qf * qf, axis=-1)                            # [Q]

    def step(carry, slab_col):                                # slab_col [Q]
        bd, bl = carry
        sc = jnp.clip(slab_col, 0)
        x = state.data[sc].astype(jnp.float32)                # [Q, C, D]
        vb = bm.unpack_batch(state.bitmap[sc], cfg.capacity)  # [Q, C]
        ok = vb & (slab_col >= 0)[:, None]
        pm = _filter_mask(cfg, state, sc, fstruct, fconsts)
        if pm is not None:
            ok = ok & pm
        # elementwise f32 products reduced like the cached norms (a TPU's
        # default f32 matmul would round operands to bf16), so a stored
        # vector scores itself at exactly 0
        dot = jnp.sum(qf[:, None, :] * x, axis=-1)
        if cfg.metric == "l2":
            d = qq[:, None] - 2.0 * dot + state.norms[sc]
        else:
            d = -dot
        d = jnp.where(ok, d, jnp.inf)
        lab = jnp.where(ok, state.ids[sc], -1)
        alld = jnp.concatenate([bd, d], axis=1)               # [Q, k+C]
        alll = jnp.concatenate([bl, lab], axis=1)
        nd, idx = jax.lax.top_k(-alld, k)
        nl = jnp.take_along_axis(alll, idx, axis=1)
        return (-nd, nl), None

    init = (jnp.full((qn, k), jnp.inf, jnp.float32),
            jnp.full((qn, k), -1, jnp.int32))
    (d, lab), _ = jax.lax.scan(step, init, table.T)
    return d, lab


def scan_slabs_topk_pq(cfg: SIVFConfig, state: SlabPoolState,
                       queries: jax.Array, table: jax.Array, k: int,
                       adc: jax.Array | None = None,
                       fstruct: tuple | None = None,
                       fconsts: jax.Array | None = None
                       ) -> tuple[jax.Array, jax.Array]:
    """ADC scan + streaming top-k over PQ-compressed slabs (XLA path).

    Mirrors :func:`scan_slabs_topk` column-by-column, but scores candidates
    by summing per-subspace ADC table lookups instead of touching fp32
    payloads — only the uint8 code plane is gathered per slab. The ``m``
    partial distances accumulate in ascending-subspace order; the fused
    Pallas kernel (kernels/sivf_scan/pq_fused.py) uses the same summation
    order and — fed the *same materialized* ``adc`` array, as
    ``_scan_dispatch`` does (the table is built once per query batch and
    shared across backends; compiler fusion of the table build itself may
    differ at the ULP level otherwise) — matches this reference
    bit-for-bit, ties included.
    """
    qn = queries.shape[0]
    m = cfg.pq.m
    if adc is None:
        adc = pqmod.adc_tables(state.pq_codebooks,
                               queries.astype(jnp.float32),
                               cfg.metric)                    # [Q, m, K]

    def step(carry, slab_col):                                # slab_col [Q]
        bd, bl = carry
        sc = jnp.clip(slab_col, 0)
        codes = state.codes[sc]                               # [Q, C, m] u8
        # per-subspace table gathers, accumulated left-to-right: the peak
        # live set stays O(Q*C) per column (vs O(Q*C*m) for a fused
        # [..., m] gather) and the fixed add order is what the Pallas
        # kernel reproduces for bit-exact parity
        d = None
        for s in range(m):
            t_s = jnp.take_along_axis(
                adc[:, s, :], codes[..., s].astype(jnp.int32), axis=1)
            d = t_s if d is None else d + t_s                 # [Q, C]
        vb = bm.unpack_batch(state.bitmap[sc], cfg.capacity)  # [Q, C]
        ok = vb & (slab_col >= 0)[:, None]
        pm = _filter_mask(cfg, state, sc, fstruct, fconsts)
        if pm is not None:
            ok = ok & pm
        d = jnp.where(ok, d, jnp.inf)
        lab = jnp.where(ok, state.ids[sc], -1)
        alld = jnp.concatenate([bd, d], axis=1)               # [Q, k+C]
        alll = jnp.concatenate([bl, lab], axis=1)
        nd, idx = jax.lax.top_k(-alld, k)
        nl = jnp.take_along_axis(alll, idx, axis=1)
        return (-nd, nl), None

    init = (jnp.full((qn, k), jnp.inf, jnp.float32),
            jnp.full((qn, k), -1, jnp.int32))
    (d, lab), _ = jax.lax.scan(step, init, table.T)
    return d, lab


SEARCH_IMPLS = ("xla", "pallas", "pallas_interpret")
# Scalar-prefetch budget: each Pallas scan call stages its whole [Q, T]
# int32 slab table in SMEM, which holds 1 MiB on a TPU v5e. Half of it is
# left for Mosaic's own scalars and the filter constants.
SMEM_TABLE_BYTES = 512 * 1024


def resolve_impl(impl: str | None) -> str:
    """The scan backend ``impl`` names; ``None`` picks it from the platform.

    ``None`` resolves to the fused Pallas kernel ("pallas") when JAX's
    default backend is a TPU and to the XLA column scan ("xla") anywhere
    else. The Pallas interpreter ("pallas_interpret") runs only when a
    caller names it.
    """
    if impl is None:
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl not in SEARCH_IMPLS:
        raise ValueError(
            f"unknown impl {impl!r}; expected one of {SEARCH_IMPLS} or None")
    return impl


def smem_rows(t: int, block_q: int) -> int:
    """Query rows per Pallas call whose ``[rows, t]`` slab table fits
    :data:`SMEM_TABLE_BYTES` (a multiple of ``block_q`` when it can be)."""
    rows = SMEM_TABLE_BYTES // (4 * t)
    if rows < 1:
        raise ValueError(
            f"one query's slab table has {t} entries ({4 * t} B), more than "
            f"the {SMEM_TABLE_BYTES} B SMEM budget of the Pallas scan "
            f"kernels (SMEM_TABLE_BYTES); lower nprobe or max_chain")
    return rows - rows % block_q if rows >= block_q else rows


def _split_queries(kernel, per_query: tuple, table: jax.Array, rows: int
                   ) -> tuple[jax.Array, jax.Array]:
    """``kernel(*per_query, table)`` over query chunks of ``rows`` rows.

    One ``lax.map`` step per chunk, so each Pallas call prefetches at most
    ``rows`` table rows into SMEM; ragged tails pad with -1 table rows
    (masked to +inf in the kernel) and are cut off again.
    """
    qn, _ = table.shape
    if qn <= rows:
        return kernel(*per_query, table)
    n = -(-qn // rows)
    pad = n * rows - qn

    def chunks(x, fill):
        if pad:
            x = jnp.concatenate(
                [x, jnp.full((pad,) + x.shape[1:], fill, x.dtype)])
        return x.reshape((n, rows) + x.shape[1:])

    xs = tuple(chunks(x, 0) for x in per_query) + (chunks(table, -1),)
    d, lab = jax.lax.map(lambda a: kernel(*a), xs)
    return d.reshape(n * rows, -1)[:qn], lab.reshape(n * rows, -1)[:qn]


def _scan_dispatch(cfg: SIVFConfig, state: SlabPoolState, queries: jax.Array,
                   table: jax.Array, k: int, impl: str | None, block_q: int,
                   fstruct: tuple | None = None,
                   fconsts: jax.Array | None = None
                   ) -> tuple[jax.Array, jax.Array]:
    """Route a gathered slab table through one scan->top-k backend.

    Every backend streams: none materializes the [Q, T*C] candidate matrix.
      "xla"              — jnp column scan (the reference; CPU default);
      "pallas"           — the fused TPU kernel (kernels/sivf_scan/fused.py;
                           TPU default);
      "pallas_interpret" — same kernel, Pallas interpreter (CPU emulation).
    ``impl=None`` picks the platform's backend (:func:`resolve_impl`). The
    Pallas backends split the query batch so that each call's slab table
    fits the SMEM budget (:func:`smem_rows`).

    With ``cfg.pq`` set every backend scores compressed slabs by ADC
    (``scan_slabs_topk_pq`` / kernels/sivf_scan/pq_fused.py): the uint8
    code plane replaces the fp32 payload DMA and distances are table-lookup
    sums against per-query ADC tables held in VMEM.

    ``fstruct``/``fconsts`` (a compiled predicate, core/filters.py) thread
    the same per-slot mask into every backend: the XLA references AND it
    into their validity mask, the Pallas kernels read the constants from a
    second scalar-prefetch operand in SMEM and mask before the top-k fold.
    """
    impl = resolve_impl(impl)
    if fstruct is not None and cfg.n_attrs == 0:
        raise ValueError("filtered search needs SIVFConfig(attributes=...)")
    interpret = impl == "pallas_interpret"
    attrs = state.attrs if fstruct is not None else None
    if cfg.pq is not None:
        # one ADC table build serves whichever backend scores with it
        adc = pqmod.adc_tables(state.pq_codebooks,
                               queries.astype(jnp.float32), cfg.metric)
        if impl == "xla":
            return scan_slabs_topk_pq(cfg, state, queries, table, k, adc=adc,
                                      fstruct=fstruct, fconsts=fconsts)
        from repro.kernels.sivf_scan.pq_fused import (
            sivf_pq_fused_search_pallas,
        )

        def kernel(adc, table):
            return sivf_pq_fused_search_pallas(
                adc, table, state.codes, state.ids, state.bitmap, k,
                block_q=block_q, interpret=interpret, attrs=attrs,
                fstruct=fstruct, fconsts=fconsts)
        per_query = (adc,)
    else:
        if impl == "xla":
            return scan_slabs_topk(cfg, state, queries, table, k,
                                   fstruct=fstruct, fconsts=fconsts)
        from repro.kernels.sivf_scan.fused import sivf_fused_search_pallas

        def kernel(queries, table):
            return sivf_fused_search_pallas(
                queries, table, state.data, state.ids, state.norms,
                state.bitmap, k, metric=cfg.metric, block_q=block_q,
                interpret=interpret, attrs=attrs, fstruct=fstruct,
                fconsts=fconsts)
        per_query = (queries.astype(jnp.float32),)
    return _split_queries(kernel, per_query, table,
                          smem_rows(table.shape[1], block_q))


def _search_impl(cfg: SIVFConfig, state: SlabPoolState, queries: jax.Array,
                 k: int, nprobe: int, use_tables: bool | None,
                 impl: str | None,
                 block_q: int, fstruct: tuple | None = None,
                 fconsts: jax.Array | None = None
                 ) -> tuple[jax.Array, jax.Array]:
    """Un-jitted search body, shared by `search` and distributed shards."""
    ut = cfg.track_tables if use_tables is None else use_tables
    lists = quantizer.probe(state.centroids, queries.astype(cfg.dtype),
                            nprobe, cfg.metric)
    table = (gather_tables if ut else walk_chains)(cfg, state, lists)
    return _scan_dispatch(cfg, state, queries, table, k, impl, block_q,
                          fstruct=fstruct, fconsts=fconsts)


@partial(jax.jit, static_argnames=("cfg", "k", "nprobe", "use_tables",
                                   "impl", "block_q", "fstruct"))
def search(cfg: SIVFConfig, state: SlabPoolState, queries: jax.Array,
           k: int, nprobe: int, use_tables: bool | None = None,
           impl: str | None = None, block_q: int = 8,
           fstruct: tuple | None = None,
           fconsts: jax.Array | None = None
           ) -> tuple[jax.Array, jax.Array]:
    """Top-k search. queries [Q, D] -> (distances [Q, k], labels [Q, k]).

    ``use_tables`` selects the beyond-paper dense-table slab lookup (default
    from config); both the dense-table and pointer-walk tables feed the same
    fused scan->top-k dispatch. ``impl``: "xla" (the jnp reference),
    "pallas" (fused TPU kernel), "pallas_interpret" (the fused kernel under
    the Pallas interpreter), or ``None`` (the default: "pallas" on a TPU,
    "xla" elsewhere; :func:`resolve_impl`). ``block_q`` is the fused
    kernel's query-tile height.

    ``fstruct``/``fconsts`` come from ``filters.compile_filter``: the
    structure is a *static* argument (one executable per filter shape), the
    constants are traced (changing ``Eq("tenant", 3)`` to ``..., 7`` hits
    the same executable).
    """
    return _search_impl(cfg, state, queries, k, nprobe, use_tables, impl,
                        block_q, fstruct=fstruct, fconsts=fconsts)


# ---------------------------------------------------------------------------
# Introspection
# ---------------------------------------------------------------------------

def _memory_stats(cfg: SIVFConfig, n_shards: int = 1) -> dict:
    """Pool memory footprint, aggregated across shards like ``total_live``.

    Delegates the byte math to ``state.memory_report`` (one source of
    truth) and scales the per-pool planes by the shard count;
    ``compression_ratio`` (shard-count invariant) surfaces only when PQ is
    enabled.
    """
    from repro.core.state import memory_report
    mr = memory_report(cfg)
    out = {"payload_bytes": mr["payload_bytes"] * n_shards,
           "code_bytes": mr["code_bytes"] * n_shards,
           "attr_bytes": mr["attr_bytes"] * n_shards,
           # tiered host/device split (one source of truth: memory_report)
           "host_bytes": mr["host_bytes"] * n_shards,
           "device_bytes": mr["device_bytes"] * n_shards,
           "device_cache_bytes": mr["device_cache_bytes"] * n_shards}
    if cfg.pq is not None:
        out["compression_ratio"] = mr["compression_ratio"]
    return out


def stats(cfg: SIVFConfig, state: SlabPoolState) -> dict:
    """Occupancy / fragmentation report (paper §5.6.2).

    Handles both a single-device ``SlabPoolState`` and the stacked
    per-shard state produced by ``distributed.init_sharded_state`` (leaves
    carry a leading shard axis): shard occupancy is aggregated, the live
    count folds ``distributed.total_live``, and error bits are OR-reduced.
    Includes the pool memory footprint (``_memory_stats``) so sessions can
    observe the PQ compression ratio.
    """
    import numpy as np
    free_top = np.asarray(state.free_top)
    occ = _list_occupancy(cfg, state)
    skew = {"list_occupancy": occ.tolist(),
            "list_skew": float(occ.max() / occ.mean()) if occ.any() else 0.0}
    if free_top.ndim:                      # stacked per-shard state
        from repro.core.distributed import total_live
        used_per = (cfg.n_slabs - free_top).astype(int)
        used = int(used_per.sum())
        live = total_live(state)
        alloc_slots = used * cfg.capacity
        table_len = np.asarray(state.table_len)          # [S, n_lists]
        err = int(np.bitwise_or.reduce(np.asarray(state.error).ravel()))
        return {
            "n_live": live,
            "slabs_used": used,
            "free_slabs": int(free_top.sum()),
            "alloc_slots": alloc_slots,
            "fill_frac": live / max(alloc_slots, 1),
            "error": err,
            "max_chain_len": int(table_len.max()),
            "mean_chain_len": float(table_len.mean()),
            "n_shards": int(free_top.shape[0]),
            "per_shard_live": np.asarray(state.n_live).astype(int).tolist(),
            "per_shard_slabs_used": used_per.tolist(),
            **skew,
            **_memory_stats(cfg, int(free_top.shape[0])),
        }
    used = int(cfg.n_slabs - state.free_top)
    live = int(state.n_live)
    alloc_slots = used * cfg.capacity
    return {
        "n_live": live,
        "slabs_used": used,
        "free_slabs": int(state.free_top),
        "alloc_slots": alloc_slots,
        "fill_frac": live / max(alloc_slots, 1),
        "error": int(state.error),
        "max_chain_len": int(jnp.max(state.table_len)),
        "mean_chain_len": float(jnp.mean(state.table_len)),
        **skew,
        **_memory_stats(cfg),
    }


def _list_occupancy(cfg: SIVFConfig, state: SlabPoolState) -> "np.ndarray":
    """Exact per-list live-row counts (drift-policy input).

    Recounted from the validity bitmaps and slab ownership rather than
    the incremental ``live`` counters: the bitmap is the plane searches
    mask by, so this tally is correct by construction under any
    overwrite/delete interleaving, single or stacked state.
    """
    import numpy as np

    from repro.core.state import host_live_mask
    owner = np.asarray(state.owner)
    per_slab = host_live_mask(cfg, np.asarray(state.bitmap)).sum(-1)
    owner, per_slab = owner.reshape(-1), per_slab.reshape(-1)
    occ = np.zeros((cfg.n_lists,), np.int64)
    sel = owner >= 0
    np.add.at(occ, owner[sel], per_slab[sel])
    return occ
