"""Distributed SIVF: shared-nothing data sharding + scatter-gather (paper §4.2).

The paper's 12-GPU MPI architecture maps 1:1 onto ``jax.shard_map`` over a
mesh axis:

  * **Data sharding** — each shard owns a disjoint id range via deterministic
    ``id % n_shards`` routing (the paper's round-robin/hash routing). Every
    shard keeps its *own* SlabPoolState; the global state is the stack of
    per-shard states along a leading axis sharded on ``axis_name``.
  * **Ingestion** — the batch is broadcast; each shard masks to its owned
    ids and ingests locally (no cross-shard sync, hence the paper's linear
    ingestion scaling).
  * **Search (scatter-gather)** — queries are broadcast; each shard searches
    its local shard; partial top-k are all-gathered and merged (the paper's
    MPI_Gather / tree reduction).
  * **Deletion** — broadcast; ids live on exactly one shard, others no-op
    (paper: "the target ID exists on at most one worker").
  * **Per-shard atomicity** — each shard runs the all-or-nothing insert of
    ``core.index``: a shard that hits POOL_EXHAUSTED / CHAIN_OVERFLOW
    keeps its previously-live ids (old payloads included) and raises only
    its own ``error`` bits, while sibling shards commit normally. The
    stacked ``state.error`` vector is therefore the per-shard truth that
    ``sivf.Index`` surfaces as ``MutationReport.shard_errors`` — eagerly
    or deferred, the accounting never has to guess which rows survived.

  * **Elastic resharding** — :func:`reshard_state` remaps an index saved
    on S shards onto S' shards (grow, shrink, mesh<->single) *without a
    rebuild from raw data*: the per-shard slab pools flatten to one
    canonical id-sorted table of live rows, rows re-route by the same
    ``id % n_shards'`` rule ``sharded_insert`` uses (so post-reshard
    inserts land on the owning shard), and each target shard's chains /
    bitmaps / ATT / centroid replicas are rebuilt through the existing
    ``init_state`` + insert path. Searches before vs. after resharding
    return identical ids and distances (docs/architecture.md §Resharding).

The ``sharded_*`` builders return the raw shard-mapped callables; they are
the single code path behind both the legacy ``dist_*`` free functions and
the ``sivf.Index`` mesh backend (``core/api.py``), which wraps them in jit
with buffer donation, shape-bucketed batches, and (in deferred mode)
device-resident report aux that only syncs at ``Index.flush()``.
"""
from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import index as ix
from repro.core import pq as pqmod
from repro.core.state import (
    SIVFConfig,
    SlabPoolState,
    host_live_mask,
    init_state,
)


def shard_of(ids: jax.Array, n_shards: int) -> jax.Array:
    """Deterministic owner shard for each external id."""
    return jnp.where(ids >= 0, ids % n_shards, -1)


def stack_sharding(x, mesh: Mesh, axis: str = "data") -> NamedSharding:
    """Placement of one leaf of a stacked per-shard state: the leading axis
    on ``axis``, except zero-size planes, which XLA returns replicated —
    placing them so keeps jitted ops from compiling again for shardings
    that changed between calls."""
    return NamedSharding(mesh, P() if x.size == 0 else P(axis))


def build_stacked(init, mesh: Mesh, axis: str, *args):
    """``init(*args)`` repeated once per shard on a new leading axis, placed
    with :func:`stack_sharding`. Built under ``jit`` so that each device
    materializes only its own shard: broadcasting on one device first
    would hold every shard's planes there at once."""
    n = mesh.shape[axis]

    def stacked(*a):
        return jax.tree.map(lambda x: jnp.broadcast_to(x, (n,) + x.shape),
                            init(*a))

    args = jax.device_put(args, NamedSharding(mesh, P()))
    out = jax.tree.map(lambda x: stack_sharding(x, mesh, axis),
                       jax.eval_shape(stacked, *args))
    return jax.jit(stacked, out_shardings=out)(*args)


def init_sharded_state(cfg: SIVFConfig, centroids: jax.Array, mesh: Mesh,
                       axis: str = "data",
                       pq_codebooks: jax.Array | None = None
                       ) -> SlabPoolState:
    """Per-shard empty states stacked on a leading sharded axis.

    ``pq_codebooks`` (when ``cfg.pq`` is set) replicates to every shard,
    like the coarse centroids — shards encode and ADC-score locally.
    """
    return build_stacked(lambda c, cb: init_state(cfg, c, cb), mesh, axis,
                         centroids, pq_codebooks)


def _spec_tree(state: SlabPoolState, axis: str):
    return jax.tree.map(lambda _: P(axis), state)


# ---------------------------------------------------------------------------
# Shard-mapped op builders (one code path for dist_* and sivf.Index)
# ---------------------------------------------------------------------------

def sharded_insert(cfg: SIVFConfig, mesh: Mesh, axis: str = "data",
                   want_plan: bool = False):
    """Broadcast-ingest op: each shard ingests the ids it owns.

    Returns ``run(state, vecs, ext_ids) -> state``. Building the shard_map
    wrapper happens at trace time, so callers that jit ``run`` pay it once
    per shape bucket. Failure is per-shard atomic: an exhausted shard's
    slice of the stacked output equals its input (plus error bits), so a
    partially-failing batch never drops payloads anywhere.

    ``want_plan=True`` (the tiered slab pool, ``core/tiered.py``) makes
    ``run`` return ``(state, plan)`` where ``plan`` is the stacked [S, B]
    commit plan of ``ix._insert_impl(want_plan=True)`` — rows a shard did
    not own (or an aborted shard's whole batch) are -1, so the host-store
    replay applies exactly the device commits, per shard.
    """
    n = mesh.shape[axis]

    def run(state: SlabPoolState, vecs: jax.Array, ext_ids: jax.Array,
            attrs: jax.Array | None = None):
        def local(st, v, i, *a):
            st = jax.tree.map(lambda x: x[0], st)
            me = jax.lax.axis_index(axis)
            mine = shard_of(i, n) == me
            from repro.core.quantizer import assign
            lists = assign(st.centroids, v.astype(cfg.dtype), cfg.metric)
            out = ix._insert_impl(cfg, st, v, jnp.where(mine, i, -1), lists,
                                  attrs=a[0] if a else None,
                                  want_plan=want_plan)
            if want_plan:
                st, plan = out
                return (jax.tree.map(lambda x: x[None], st),
                        jax.tree.map(lambda x: x[None], plan))
            return jax.tree.map(lambda x: x[None], out)

        extra = () if attrs is None else (attrs,)
        state_spec = _spec_tree(state, axis)
        out_specs = state_spec if not want_plan else (
            state_spec, {"slab": P(axis), "slot": P(axis),
                         "codes": P(axis)})
        f = jax.shard_map(
            local, mesh=mesh, check_vma=False,
            in_specs=(state_spec, P(), P())
            + tuple(P() for _ in extra),
            out_specs=out_specs)
        return f(state, vecs, ext_ids, *extra)

    return run


def sharded_delete(cfg: SIVFConfig, mesh: Mesh, axis: str = "data"):
    """Broadcast-delete op: non-owners see ATT misses and no-op.

    Returns ``run(state, ext_ids) -> state``.
    """

    def run(state: SlabPoolState, ext_ids: jax.Array) -> SlabPoolState:
        def local(st, i):
            st = jax.tree.map(lambda x: x[0], st)
            st = ix._delete_impl(cfg, st, i)
            return jax.tree.map(lambda x: x[None], st)

        f = jax.shard_map(
            local, mesh=mesh, check_vma=False,
            in_specs=(_spec_tree(state, axis), P()),
            out_specs=_spec_tree(state, axis))
        return f(state, ext_ids)

    return run


def sharded_maintain(cfg: SIVFConfig, mesh: Mesh, axis: str = "data",
                     want_plan: bool = False):
    """Atomic maintenance commit across shards (``core/maintenance.py``).

    The host-planned batch (new centroid plane + the affected lists' live
    rows, id-sorted and -1-padded) is broadcast exactly like
    ``sharded_insert``: each shard stages the new centroids, re-inserts
    only the rows it owns, and the shards then *agree* on the outcome —
    if any shard aborts (pool exhausted / chain overflow), every shard
    reverts to its pre-op state via a ``pmax`` vote, so a search never
    observes shard A under the new layout and shard B under the old one.

    Returns ``run(state, new_cents, vecs, ext_ids, lists, codes?, attrs?)
    -> (state, errors [S])`` (plus the stacked ``[S, B]`` commit plan with
    ``want_plan=True`` — voided to -1 everywhere on an aborted vote, so
    the tiered host-store replay applies exactly what the devices kept).
    """
    import dataclasses as dc

    from repro.core.maintenance import ABORT_BITS
    from repro.core.state import clear_error
    n = mesh.shape[axis]

    def run(state: SlabPoolState, new_cents: jax.Array, vecs: jax.Array,
            ext_ids: jax.Array, lists: jax.Array,
            codes: jax.Array | None = None, attrs: jax.Array | None = None):
        def local(st, nc, v, i, li, *rest):
            st = jax.tree.map(lambda x: x[0], st)
            me = jax.lax.axis_index(axis)
            mine = shard_of(i, n) == me
            st0 = clear_error(st)
            staged = dc.replace(st0, centroids=nc)
            k = 0
            kw = {}
            if cfg.pq is not None:
                kw["codes"] = rest[k]
                k += 1
            if cfg.n_attrs:
                kw["attrs"] = rest[k]
            out = ix._insert_impl(cfg, staged, v, jnp.where(mine, i, -1),
                                  li, want_plan=want_plan, **kw)
            st1, plan = out if want_plan else (out, None)
            errs = st1.error
            any_ab = jax.lax.pmax(
                ((errs & ABORT_BITS) != 0).astype(jnp.int32), axis) > 0
            st1 = jax.tree.map(
                lambda old, new: jnp.where(any_ab, old, new), st0, st1)
            st1 = clear_error(st1)
            outs = (jax.tree.map(lambda x: x[None], st1), errs[None])
            if want_plan:
                plan = {"slab": jnp.where(any_ab, -1, plan["slab"]),
                        "slot": plan["slot"], "codes": plan["codes"]}
                outs += (jax.tree.map(lambda x: x[None], plan),)
            return outs

        extra = tuple(x for x in (codes, attrs) if x is not None)
        state_spec = _spec_tree(state, axis)
        out_specs = (state_spec, P(axis))
        if want_plan:
            out_specs += ({"slab": P(axis), "slot": P(axis),
                           "codes": P(axis)},)
        f = jax.shard_map(
            local, mesh=mesh, check_vma=False,
            in_specs=(state_spec, P(), P(), P(), P())
            + tuple(P() for _ in extra),
            out_specs=out_specs)
        return f(state, new_cents, vecs, ext_ids, lists, *extra)

    return run


def sharded_search(cfg: SIVFConfig, mesh: Mesh, axis: str = "data",
                   impl: str | None = None, block_q: int = 8,
                   use_tables: bool | None = None):
    """Scatter-gather search op: fused local top-k, all-gather, global merge.

    Returns ``run(state, queries, k, nprobe) -> (dists, labels)`` where
    ``k``/``nprobe`` must be trace-time constants. Each shard runs the same
    unified scan->top-k dispatch as ``core.search`` (``impl`` selects
    xla / pallas / pallas_interpret; None picks the platform's, see
    ``core.index.resolve_impl``), so only the fused [Q, k] partials ever
    cross the interconnect — never per-slab candidates.
    """

    def run(state: SlabPoolState, queries: jax.Array, k: int, nprobe: int,
            fstruct: tuple | None = None, fconsts: jax.Array | None = None
            ) -> tuple[jax.Array, jax.Array]:
        def local(st, q, *fc):
            st = jax.tree.map(lambda x: x[0], st)
            d, lab = ix._search_impl(cfg, st, q, k, nprobe, use_tables, impl,
                                     block_q, fstruct=fstruct,
                                     fconsts=fc[0] if fc else None)
            # gather fused [Q, k] partials from all shards (paper MPI_Gather)
            dg = jax.lax.all_gather(d, axis)                   # [S, Q, k]
            lg = jax.lax.all_gather(lab, axis)
            s, qn, _ = dg.shape
            dg = jnp.moveaxis(dg, 0, 1).reshape(qn, s * k)
            lg = jnp.moveaxis(lg, 0, 1).reshape(qn, s * k)
            nd, idx = jax.lax.top_k(-dg, k)                    # global merge
            return -nd, jnp.take_along_axis(lg, idx, axis=1)

        extra = () if fconsts is None else (fconsts,)
        f = jax.shard_map(
            local, mesh=mesh, check_vma=False,
            in_specs=(_spec_tree(state, axis), P())
            + tuple(P() for _ in extra),
            out_specs=(P(), P()))
        return f(state, queries, *extra)

    return run


# ---------------------------------------------------------------------------
# Elastic resharding (pure host-side; Index.load / Index.reshard wrap this)
# ---------------------------------------------------------------------------

def _leading_shards(state: SlabPoolState) -> int:
    """Shard count of a state value: leading-axis length when stacked, 1
    for a plain single-device state (``ids`` is [n_slabs, C] vs [S, n_slabs, C])."""
    ids = np.asarray(state.ids)
    return int(ids.shape[0]) if ids.ndim == 3 else 1


def flatten_live_rows(cfg: SIVFConfig, state: SlabPoolState) -> dict:
    """Flatten slab pools to the canonical host-side table of live rows.

    Works on a single-device state or the stacked per-shard state (leaves
    may be device arrays or the numpy leaves of a host-restored
    checkpoint). Rows are **id-sorted**, which makes the table canonical:
    two states hold the same logical index iff their tables are equal,
    regardless of shard count, slab layout, or deletion history. This is
    the exchange format of :func:`reshard_state` and the byte-accounting
    basis of the ``reshard_sweep`` benchmark.

    Returns a dict of numpy arrays over the N live rows:
      ``ids``     [N] int32 external ids (ascending, globally unique);
      ``lists``   [N] int32 owning IVF list (from the slab's ``owner``);
      ``data``    [N, payload_dim] stored fp payloads (width 0 when PQ
                  codes replace them);
      ``codes``   [N, code_m] uint8 PQ codewords (width 0 without PQ);
      ``attrs``   [N, n_attrs] int32 filter attributes (width 0 without
                  ``cfg.attributes``);
    plus the replicated leaves ``centroids`` [n_lists, D] and
    ``pq_codebooks`` (shard 0's copy when stacked).
    """
    c = cfg.capacity
    ids = np.asarray(state.ids).reshape(-1, c)                # [S*ns, C]
    bitmap = np.asarray(state.bitmap).reshape(-1, cfg.words)
    owner = np.asarray(state.owner).reshape(-1)               # [S*ns]
    mask = host_live_mask(cfg, bitmap).reshape(-1)            # [S*ns*C]
    idx = np.flatnonzero(mask)
    slots = mask.shape[0]            # explicit row count: the payload /
    #                                  code planes may be zero-width, where
    #                                  a -1 reshape is ambiguous
    live_ids = ids.reshape(-1)[idx]
    live_lists = np.broadcast_to(owner[:, None], (owner.shape[0], c)
                                 ).reshape(-1)[idx]
    data = np.asarray(state.data).reshape(slots, cfg.payload_dim)[idx]
    codes = np.asarray(state.codes).reshape(slots, cfg.code_m)[idx]
    attrs = np.asarray(state.attrs).reshape(slots, cfg.n_attrs)[idx]
    n_live = int(np.asarray(state.n_live).sum())
    if len(live_ids) != n_live:
        raise ValueError(
            f"corrupt state: bitmap says {len(live_ids)} live rows but "
            f"n_live says {n_live}")
    order = np.argsort(live_ids, kind="stable")               # canonical
    cents = np.asarray(state.centroids)
    cb = np.asarray(state.pq_codebooks)
    stacked = np.asarray(state.ids).ndim == 3
    return {
        "ids": live_ids[order].astype(np.int32),
        "lists": live_lists[order].astype(np.int32),
        "data": data[order],
        "codes": codes[order],
        "attrs": attrs[order].astype(np.int32),
        "centroids": cents[0] if stacked else cents,
        "pq_codebooks": cb[0] if stacked else cb,
    }


def _check_reshard_fit(cfg: SIVFConfig, ids: np.ndarray, lists: np.ndarray,
                       n_to: int) -> None:
    """Host-side feasibility: every target shard's rows must fit its pool.

    Shrinking concentrates rows, so a state that fit S shards can overflow
    the (per-shard, static) ``n_slabs`` pool or a list's ``max_chain``
    bound on S' < S shards. Failing *before* any device work gives a
    message that names the limit to raise, instead of a POOL_EXHAUSTED
    error bit halfway through the rebuild.
    """
    shard = ids % n_to
    key = shard.astype(np.int64) * cfg.n_lists + lists
    per_list = np.bincount(key, minlength=n_to * cfg.n_lists
                           ).reshape(n_to, cfg.n_lists)
    chains = -(-per_list // cfg.capacity)                     # ceil div
    slabs_needed = chains.sum(axis=1)
    if (bad := np.flatnonzero(slabs_needed > cfg.n_slabs)).size:
        s = int(bad[0])
        raise ValueError(
            f"reshard to {n_to} shards needs {int(slabs_needed[s])} slabs "
            f"on shard {s} but cfg.n_slabs={cfg.n_slabs}; raise n_slabs or "
            f"keep more shards")
    if (bad := np.argwhere(chains > cfg.max_chain)).size:
        s, li = (int(x) for x in bad[0])
        raise ValueError(
            f"reshard to {n_to} shards needs a {int(chains[s, li])}-slab "
            f"chain for list {li} on shard {s} but cfg.max_chain="
            f"{cfg.max_chain}; raise max_chain or keep more shards")


def _build_shard(cfg: SIVFConfig, centroids: np.ndarray, cb: np.ndarray,
                 vecs: np.ndarray, ids: np.ndarray, lists: np.ndarray,
                 codes: np.ndarray | None,
                 attrs: np.ndarray | None = None) -> SlabPoolState:
    """One target shard: fresh ``init_state`` + a single pre-routed insert.

    The batch pads to a power-of-two bucket (floor 64) so a sweep over
    shard counts compiles a bounded number of insert executables, same as
    the session handle's bucketing. With PQ, the *stored* codes ride
    along and are scattered as-is, so code planes survive byte-for-byte
    by construction — and the same holds for the int32 attribute stamps.
    """
    pq_cb = None if cfg.pq is None else jnp.asarray(cb)
    st = init_state(cfg, jnp.asarray(centroids), pq_cb)
    n = len(ids)
    if n == 0:
        return st
    b = max(64, 1 << (n - 1).bit_length())
    vp = np.zeros((b, cfg.dim), np.float32)
    vp[:n] = vecs
    ip = np.full((b,), -1, np.int32)
    ip[:n] = ids
    lp = np.zeros((b,), np.int32)
    lp[:n] = lists
    cp = None
    if codes is not None:
        cp = np.zeros((b, cfg.code_m), np.uint8)
        cp[:n] = codes
        cp = jnp.asarray(cp)
    ap = None
    if attrs is not None and cfg.n_attrs:
        ap = np.zeros((b, cfg.n_attrs), np.int32)
        ap[:n] = attrs
        ap = jnp.asarray(ap)
    st = ix.insert(cfg, st, jnp.asarray(vp), jnp.asarray(ip),
                   jnp.asarray(lp), cp, ap)
    if int(st.error):
        raise ValueError(
            f"reshard rebuild failed with error bits {int(st.error)} "
            f"(n={n} rows; pool n_slabs={cfg.n_slabs} max_chain="
            f"{cfg.max_chain})")                 # pragma: no cover - guarded
    return st


def reshard_state(cfg: SIVFConfig, state: SlabPoolState, n_from: int,
                  n_to: int, stack: bool | None = None) -> SlabPoolState:
    """Remap an S-shard index state onto S' shards. Pure; host-driven.

    ``state`` is a single-device state (``n_from == 1``) or the stacked
    per-shard state; leaves may live on device or host. The result is a
    plain single-device state when ``n_to == 1``, else a stacked state on
    the default device — :func:`place_sharded` places it onto a mesh.
    ``stack=True`` forces the stacked form even for ``n_to == 1`` (a
    one-shard *mesh* target still wants the leading shard axis).

    Semantics (the resharding contract, docs/checkpoint-format.md):
      * rows re-route by ``id % n_to`` — the same rule ``sharded_insert``
        applies, so inserts after the reshard land on the owning shard;
      * PQ codebooks and coarse centroids replicate to every target shard;
      * the rebuilt index is search-identical: same live ids, same
        distances — stored payloads AND stored PQ codes carry over
        byte-for-byte by construction (the codes are re-scattered as-is,
        never round-tripped through decode/encode);
      * slab layout is NOT preserved — each target shard re-packs its rows
        densely (a reshard is also a compaction), so only logical state
        (the :func:`flatten_live_rows` table) round-trips.

    Raises ``ValueError`` when the rows cannot fit ``n_to`` shards under
    the static per-shard pool geometry (see :func:`_check_reshard_fit`).
    """
    if n_to < 1:
        raise ValueError(f"n_to must be >= 1, got {n_to}")
    from repro import obs
    tel = obs.default()
    actual = _leading_shards(state)
    if n_from != actual:
        raise ValueError(
            f"state has {actual} shard(s) but n_from={n_from}")
    with tel.span("reshard.flatten"):
        rows = flatten_live_rows(cfg, state)
    ids, lists = rows["ids"], rows["lists"]
    _check_reshard_fit(cfg, ids, lists, n_to)
    codes = rows["codes"] if cfg.pq is not None else None
    if cfg.pq is not None and not cfg.pq.store_raw:
        # codes are the only payload; the rebuild scatters them verbatim.
        # Decoded codewords stand in for the raw vectors only where the
        # insert needs *some* fp rows (the zero-width data plane ignores
        # them; the cached norms they produce are unused by ADC scoring).
        vecs = np.asarray(pqmod.decode(jnp.asarray(rows["pq_codebooks"]),
                                       jnp.asarray(rows["codes"])))
    else:
        vecs = np.asarray(rows["data"], np.float32)
    if tel.enabled:
        # the bytes that cross the host on this flatten-and-rebuild path
        # (ROADMAP's device-side all-to-all would make this counter ~0)
        moved = sum(rows[k].nbytes for k in ("ids", "lists", "data",
                                             "codes", "attrs"))
        tel.counter("sivf_transfer_bytes_total",
                    "explicit host<->device transfer bytes by direction "
                    "and stage", ("direction", "stage")
                    ).inc(moved, direction="d2h", stage="reshard")
        tel.counter("sivf_reshard_rows_total",
                    "live rows re-routed by reshard_state"
                    ).inc(int(ids.shape[0]))
    shard = ids % n_to
    shards = []
    for t in range(n_to):
        sel = shard == t
        with tel.span("reshard.build_shard", shard=t):
            shards.append(_build_shard(cfg, rows["centroids"],
                                       rows["pq_codebooks"], vecs[sel],
                                       ids[sel], lists[sel],
                                       None if codes is None else codes[sel],
                                       rows["attrs"][sel] if cfg.n_attrs
                                       else None))
    if n_to == 1 and not stack:
        return shards[0]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *shards)


def search_stacked(cfg: SIVFConfig, state: SlabPoolState, queries, k: int,
                   nprobe: int, impl: str | None = None, block_q: int = 8
                   ) -> tuple[np.ndarray, np.ndarray]:
    """Search a stacked per-shard state *without* a mesh (host-side merge).

    Runs the ordinary single-device search on each shard's slice and
    merges with the same rule ``sharded_search`` applies on device
    (concatenate per-shard [Q, k] partials in shard order, stable-sort by
    distance, keep k) — so results match a real mesh search exactly, ties
    included. Intended for inspecting host-restored or freshly-resharded
    stacked states; tests and ``reshard_sweep`` assert parity through it.
    """
    q = jnp.asarray(queries)
    host = jax.tree.map(np.asarray, state)       # ONE device->host snapshot
    if host.ids.ndim == 2:                       # plain single state
        d, lab = ix.search(cfg, jax.tree.map(jnp.asarray, host), q, k,
                         nprobe, impl=impl, block_q=block_q)
        return np.asarray(d), np.asarray(lab)
    ds, ls = [], []
    for s in range(_leading_shards(host)):
        sub = jax.tree.map(lambda x: jnp.asarray(x[s]), host)
        d, lab = ix.search(cfg, sub, q, k, nprobe, impl=impl, block_q=block_q)
        ds.append(np.asarray(d))
        ls.append(np.asarray(lab))
    dg, lg = np.concatenate(ds, axis=1), np.concatenate(ls, axis=1)
    order = np.argsort(dg, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(dg, order, 1), np.take_along_axis(lg, order, 1)


def place_sharded(state: SlabPoolState, mesh: Mesh, axis: str = "data"
                  ) -> SlabPoolState:
    """Place a stacked per-shard state onto a mesh (leading axis sharded).

    Shard ``s`` of the stack lands on device ``s`` of the mesh axis, which
    is the same order ``jax.lax.axis_index`` sees inside the shard-mapped
    ops — so the ``id % n_shards`` ownership encoded in the stack matches
    the routing the ops will apply.
    """
    n = mesh.shape[axis]
    if _leading_shards(state) != n:
        raise ValueError(
            f"state has {_leading_shards(state)} shards but mesh axis "
            f"{axis!r} has {n}")
    return jax.tree.map(lambda x: jax.device_put(
        jnp.asarray(x), stack_sharding(x, mesh, axis)), state)


# ---------------------------------------------------------------------------
# Legacy free-function surface (thin delegation; prefer sivf.Index)
# ---------------------------------------------------------------------------

@lru_cache(maxsize=None)
def _jitted(builder, cfg: SIVFConfig, mesh: Mesh, axis: str, *args,
            static_argnums=()):
    """One jitted executable set per (op, cfg, mesh, ...): a shard-mapped
    op called outside ``jit`` trips JAX's sharding check on zero-width
    state planes, and a fresh ``jit`` per call would retrace every time."""
    return jax.jit(builder(cfg, mesh, axis, *args),
                   static_argnums=static_argnums)


def dist_insert(cfg: SIVFConfig, mesh: Mesh, state: SlabPoolState,
                vecs: jax.Array, ext_ids: jax.Array, axis: str = "data"
                ) -> SlabPoolState:
    """Broadcast batch; each shard ingests the ids it owns."""
    return _jitted(sharded_insert, cfg, mesh, axis)(state, vecs, ext_ids)


def dist_delete(cfg: SIVFConfig, mesh: Mesh, state: SlabPoolState,
                ext_ids: jax.Array, axis: str = "data") -> SlabPoolState:
    """Broadcast deletes; non-owners see ATT misses and no-op."""
    return _jitted(sharded_delete, cfg, mesh, axis)(state, ext_ids)


def dist_search(cfg: SIVFConfig, mesh: Mesh, state: SlabPoolState,
                queries: jax.Array, k: int, nprobe: int, axis: str = "data",
                impl: str | None = None, block_q: int = 8
                ) -> tuple[jax.Array, jax.Array]:
    """Scatter-gather search across the mesh (see ``sharded_search``)."""
    return _jitted(sharded_search, cfg, mesh, axis, impl, block_q,
                   static_argnums=(2, 3))(state, queries, k, nprobe)


def total_live(state: SlabPoolState) -> int:
    """Aggregate live count across shards."""
    return int(jnp.sum(state.n_live))
