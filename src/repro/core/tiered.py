"""Tiered slab pool: host-resident cold store + on-device hot slab cache.

The single-tier pool (``core/state.py``) caps index capacity at accelerator
memory. This module splits the storage layer in two, the SVFusion/Fantasy
co-processing layout:

  * **Host store** (:class:`HostStore`) — the *canonical* payload planes
    (``data`` / ``codes`` / ``attrs``) as numpy arrays sized by the full
    ``cfg.n_slabs`` pool, bounded by host RAM. All slab *metadata* (ids,
    norms, bitmaps, chains, ATT, tables) stays device-resident: at
    dim=128 the metadata is ~64x smaller than the payloads, and keeping
    it on device means deletes, occupancy and chain bookkeeping never
    need host mirroring or cache invalidation.
  * **Device cache** (:class:`SlabCacheDev`) — ``cfg.device_slabs`` cache
    *frames* of the same per-slab payload width, plus the residency map
    ``frame_of`` (pool slab id -> frame, -1 = not resident) and its
    inverse ``slab_of_frame``. Host-side twins of both (plus per-frame
    LRU ticks and a dirty set) drive the replacement policy without any
    device round trip.

**Search** becomes a three-stage pipeline (:class:`TieredRuntime`):

  1. *plan* (jitted) — coarse probe + slab-table gather, exactly the
     prefix of the all-resident search, producing the pool-slab-id table
     ``[Q, T]``;
  2. *prefetch* (host) — one explicit ``device_get`` of the table, a
     ``np.unique`` dedupe (slab ids shared by several probed lists are
     fetched once — the ROADMAP's query-tile DMA dedupe), LRU eviction of
     victim frames, and one packed ``device_put`` + donated scatter that
     uploads only the *missing* (or dirty-resident) slabs' payload rows
     into their frames. A warm cache uploads nothing and touches the
     device zero times;
  3. *scan* (jitted) — rewrite the table into frame coordinates
     (``kernels.sivf_scan.ops.translate_table``), gather fresh per-frame
     metadata views from the full device metadata planes, and feed the
     *unmodified* fused/PQ/filtered scan->top-k dispatch. The kernels see
     a smaller pool and a translated table; their math is untouched, so
     results are bit-identical (ids AND distances) to the all-resident
     pool whenever the probed set fits the cache.

**Inserts** stay atomic across both tiers: the device commit
(``core.index._insert_impl(want_plan=True)``) emits a *plan* — per input
row the (slab, slot) the commit wrote, -1 everywhere the commit did not
(including the whole batch on an atomic abort), plus the device-encoded
PQ codes. The host store replays exactly those writes (deferred-friendly:
plans queue as device arrays and drain in one ``device_get`` at the next
prefetch/save/reshard), and every touched slab is marked *dirty* so a
resident frame re-uploads before the next scan reads it. **Deletes** are
metadata-only (bitmap punch) and need no host action at all — the scan's
per-frame metadata gather observes them immediately, which is how a
delete "punches both tiers" for free. Recycled slabs are covered by the
insert plan of the batch that reuses them.

Residency is **runtime-only** state: checkpoints always store the
assembled full-pool planes (:func:`assemble_full`), so the on-disk format
is unchanged (format 3) and any checkpoint loads tiered or untiered.

See docs/architecture.md (tiered memory section) for the dataflow
diagram.
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import index as ix
from repro.core import quantizer
from repro.core.state import SIVFConfig, SlabPoolState
from repro.kernels.sivf_scan.ops import translate_table
from repro.obs.metrics import WindowedCounter


# ---------------------------------------------------------------------------
# Tier state containers
# ---------------------------------------------------------------------------

@partial(
    jax.tree_util.register_dataclass,
    data_fields=["data", "codes", "attrs", "frame_of", "slab_of_frame"],
    meta_fields=[],
)
@dataclasses.dataclass
class SlabCacheDev:
    """Device-resident hot-cache planes + residency map.

    Single backend shapes below; the mesh backend stacks a leading shard
    axis on every leaf (one independent cache per shard).
    """

    data: jax.Array           # [F, C, payload_dim] cached fp payload rows
    codes: jax.Array          # [F, C, code_m] uint8 cached PQ codes
    attrs: jax.Array          # [F, C, n_attrs] int32 cached attribute stamps
    frame_of: jax.Array       # [n_slabs] int32 slab -> frame (-1 = cold)
    slab_of_frame: jax.Array  # [F] int32 frame -> slab (-1 = empty frame)


def init_cache(cfg: SIVFConfig) -> SlabCacheDev:
    """Empty cache: every frame free, every slab cold."""
    f, c = cfg.device_slabs, cfg.capacity
    return SlabCacheDev(
        data=jnp.zeros((f, c, cfg.payload_dim), cfg.dtype),
        codes=jnp.zeros((f, c, cfg.code_m), jnp.uint8),
        attrs=jnp.zeros((f, c, cfg.n_attrs), jnp.int32),
        frame_of=jnp.full((cfg.n_slabs,), -1, jnp.int32),
        slab_of_frame=jnp.full((f,), -1, jnp.int32))


class HostStore:
    """One shard's canonical host-side payload planes (numpy)."""

    __slots__ = ("data", "codes", "attrs")

    def __init__(self, data: np.ndarray, codes: np.ndarray,
                 attrs: np.ndarray):
        self.data = data        # [n_slabs, C, payload_dim]
        self.codes = codes      # [n_slabs, C, code_m] uint8
        self.attrs = attrs      # [n_slabs, C, n_attrs] int32

    @classmethod
    def empty(cls, cfg: SIVFConfig) -> "HostStore":
        ns, c = cfg.n_slabs, cfg.capacity
        return cls(np.zeros((ns, c, cfg.payload_dim), np.dtype(cfg.dtype)),
                   np.zeros((ns, c, cfg.code_m), np.uint8),
                   np.zeros((ns, c, cfg.n_attrs), np.int32))

    def rows(self, slabs: np.ndarray):
        """Gather upload rows for a (padded) slab-id vector."""
        s = np.clip(slabs, 0, self.data.shape[0] - 1)
        return self.data[s], self.codes[s], self.attrs[s]


class _Residency:
    """One shard's host-side residency bookkeeping (LRU + dirty set)."""

    def __init__(self, cfg: SIVFConfig):
        self.frame_of = np.full((cfg.n_slabs,), -1, np.int32)
        self.slab_of_frame = np.full((cfg.device_slabs,), -1, np.int32)
        self.tick = np.zeros((cfg.device_slabs,), np.int64)
        self.clock = 0
        self.dirty: set[int] = set()

    @property
    def resident_slabs(self) -> int:
        return int((self.slab_of_frame >= 0).sum())


@dataclasses.dataclass(frozen=True)
class PrefetchTicket:
    """Proof that a query batch's probed slabs are resident.

    Returned by :meth:`TieredRuntime.prefetch`; pass it back to the scan
    stage to skip re-planning/re-prefetching. Valid only while nothing
    else has prefetched (``seq``) or mutated the index (``epoch``) since —
    the serve engine uses this to overlap the *next* tile's prefetch with
    the *current* tile's kernel execution, and a stale ticket silently
    falls back to the full three-stage path.
    """

    table: jax.Array          # [Q, T] (mesh: [S, Q, T]) pool-slab-id table
    nprobe: int
    padded_q: int             # query bucket the table was planned for
    seq: int                  # runtime prefetch sequence number at issue
    epoch: int                # Index.epoch at issue


# ---------------------------------------------------------------------------
# Jitted stage factories (lru_cached so equal configs share executables,
# mirroring core/api.py's _single_ops/_mesh_ops)
# ---------------------------------------------------------------------------

def cache_view(cfg: SIVFConfig, state: SlabPoolState, cache: SlabCacheDev
               ) -> SlabPoolState:
    """Frame-indexed view of the pool for the unmodified scan dispatch.

    Payload planes come from the cache frames; per-frame metadata (ids,
    norms, validity bitmaps) is gathered *fresh* from the full device
    metadata planes via ``slab_of_frame`` — so deletes and overwrites are
    visible to the very next scan with zero invalidation tracking. Empty
    frames mask to dead (bitmap 0 / ids -1); they are never referenced by
    a translated table anyway.
    """
    sof = jnp.clip(cache.slab_of_frame, 0)
    has = cache.slab_of_frame >= 0
    return dataclasses.replace(
        state,
        data=cache.data, codes=cache.codes, attrs=cache.attrs,
        ids=jnp.where(has[:, None], state.ids[sof], -1),
        norms=state.norms[sof],
        bitmap=jnp.where(has[:, None], state.bitmap[sof], jnp.uint32(0)))


@lru_cache(maxsize=None)
def _plan_ops(cfg: SIVFConfig, use_tables: bool | None):
    """Stage 1: probe + slab-table gather — the all-resident search prefix."""
    ut = cfg.track_tables if use_tables is None else use_tables

    @partial(jax.jit, static_argnames=("nprobe",))
    def plan(state, queries, nprobe):
        lists = quantizer.probe(state.centroids, queries.astype(cfg.dtype),
                                nprobe, cfg.metric)
        return (ix.gather_tables if ut else ix.walk_chains)(cfg, state, lists)

    return plan


@lru_cache(maxsize=None)
def _scan_ops(cfg: SIVFConfig, impl: str | None, block_q: int):
    """Stage 3: frame-translate the table and run the unmodified dispatch."""

    @partial(jax.jit, static_argnames=("k", "fstruct"))
    def scan(state, cache, queries, table, k, fstruct, fconsts):
        ftable = translate_table(table, cache.frame_of)
        view = cache_view(cfg, state, cache)
        return ix._scan_dispatch(cfg, view, queries, ftable, k, impl,
                                 block_q, fstruct=fstruct, fconsts=fconsts)

    return scan


@lru_cache(maxsize=None)
def _upload_ops(cfg: SIVFConfig):
    """Stage 2 device half: donated scatter of upload rows into frames.

    ``frames`` rows of -1 are padding (scatter drops them). Updates the
    device residency map for the uploaded slabs only — entries of evicted
    slabs go stale on device but are never read before a prefetch
    re-uploads them (a slab enters a table only via prefetch).
    """
    f_oob, ns = cfg.device_slabs, cfg.n_slabs

    @partial(jax.jit, donate_argnums=(0,))
    def upload(cache, frames, slabs, drows, crows, arows):
        f = jnp.where(frames >= 0, frames, f_oob)
        data = cache.data.at[f].set(drows, mode="drop")
        codes = cache.codes.at[f].set(crows, mode="drop")
        attrs = cache.attrs.at[f].set(arows, mode="drop")
        sof = cache.slab_of_frame.at[f].set(slabs, mode="drop")
        fof = cache.frame_of.at[jnp.where(frames >= 0, slabs, ns)].set(
            frames, mode="drop")
        return SlabCacheDev(data, codes, attrs, fof, sof)

    return upload


@lru_cache(maxsize=None)
def _upload_ops_mesh(cfg: SIVFConfig, n_shards: int):
    """Per-shard stacked variant of :func:`_upload_ops`."""
    f_oob, ns = cfg.device_slabs, cfg.n_slabs
    s_ix = np.arange(n_shards)[:, None]

    @partial(jax.jit, donate_argnums=(0,))
    def upload(cache, frames, slabs, drows, crows, arows):   # frames [S, U]
        f = jnp.where(frames >= 0, frames, f_oob)
        data = cache.data.at[s_ix, f].set(drows, mode="drop")
        codes = cache.codes.at[s_ix, f].set(crows, mode="drop")
        attrs = cache.attrs.at[s_ix, f].set(arows, mode="drop")
        sof = cache.slab_of_frame.at[s_ix, f].set(slabs, mode="drop")
        fof = cache.frame_of.at[
            s_ix, jnp.where(frames >= 0, slabs, ns)].set(frames, mode="drop")
        return SlabCacheDev(data, codes, attrs, fof, sof)

    return upload


# ---------------------------------------------------------------------------
# Full-state split / assemble (checkpoint + reshard interop)
# ---------------------------------------------------------------------------

def split_full(cfg: SIVFConfig, full: SlabPoolState
               ) -> tuple[SlabPoolState, list[HostStore]]:
    """Full-pool state (any backend, any leaf placement) -> (meta state
    with zero-width device payload planes, per-shard host stores)."""
    data = np.asarray(full.data)
    stacked = data.ndim == 4
    n_sh = data.shape[0] if stacked else 1
    codes = np.asarray(full.codes)
    attrs = np.asarray(full.attrs)
    stores = []
    for s in range(n_sh):
        stores.append(HostStore(
            np.ascontiguousarray(data[s] if stacked else data),
            np.ascontiguousarray(codes[s] if stacked else codes),
            np.ascontiguousarray(attrs[s] if stacked else attrs)))
    c = cfg.capacity
    shp = ((n_sh, 0) if stacked else (0,))
    meta = dataclasses.replace(
        full,
        data=np.zeros(shp + (c, cfg.payload_dim), data.dtype),
        codes=np.zeros(shp + (c, cfg.code_m), np.uint8),
        attrs=np.zeros(shp + (c, cfg.n_attrs), np.int32))
    return meta, stores


def assemble_full(cfg: SIVFConfig, meta: SlabPoolState,
                  stores: list[HostStore]) -> SlabPoolState:
    """(meta state, host stores) -> full-pool *host* state whose payload
    planes are the canonical host bytes — the value checkpoints store and
    ``flatten_live_rows`` / ``reshard_state`` consume. Byte-identical to
    what an all-resident pool would hold."""
    host = jax.tree.map(np.asarray, meta)
    stacked = host.ids.ndim == 3
    if stacked:
        return dataclasses.replace(
            host,
            data=np.stack([st.data for st in stores]),
            codes=np.stack([st.codes for st in stores]),
            attrs=np.stack([st.attrs for st in stores]))
    return dataclasses.replace(host, data=stores[0].data,
                               codes=stores[0].codes, attrs=stores[0].attrs)


def is_full_state(cfg: SIVFConfig, state: SlabPoolState) -> bool:
    """True when ``state`` carries full-width payload planes (vs the
    zero-width planes of a tiered meta state)."""
    return state.data.shape[-3] == cfg.n_slabs


def _pow2(n: int, floor: int = 8) -> int:
    b = floor
    while b < n:
        b *= 2
    return b


# ---------------------------------------------------------------------------
# The runtime
# ---------------------------------------------------------------------------

class TieredRuntime:
    """Per-handle orchestration of the host store + device cache.

    Owned by ``sivf.Index`` when ``cfg.device_slabs`` is set; runtime-only
    (never checkpointed). One instance covers both backends: the mesh
    backend keeps one :class:`HostStore` + residency per shard and stacked
    cache planes sharded with the state.
    """

    _COUNTERS = ("hits", "misses", "refs", "unique_refs", "uploads",
                 "evictions")

    def __init__(self, cfg: SIVFConfig, backend_kind: str, mesh=None,
                 axis: str = "data", impl: str | None = None,
                 block_q: int = 8,
                 use_tables: bool | None = None, n_shards: int = 1,
                 stores: list[HostStore] | None = None, telemetry=None):
        if not cfg.tiered:
            raise ValueError("TieredRuntime needs SIVFConfig(device_slabs=)")
        self.cfg = cfg
        self.backend_kind = backend_kind
        self.mesh = mesh
        self.axis = axis
        self.impl = impl
        self.block_q = block_q
        self.use_tables = use_tables
        self.n_shards = n_shards
        if stores is not None and len(stores) != n_shards:
            raise ValueError(
                f"{len(stores)} host stores for {n_shards} shards")
        self.stores = stores or [HostStore.empty(cfg)
                                 for _ in range(n_shards)]
        self.res = [_Residency(cfg) for _ in range(n_shards)]
        self.cache = self._init_cache_dev()
        self._plans: list[dict] = []     # queued insert plans (device refs)
        self.seq = 0                     # prefetch sequence number
        # counters (aggregated over shards; Index.stats surfaces them) —
        # WindowedCounters: cumulative totals + a delta window so stats()
        # can report both; roll_window()/carry_from() manage the lifecycle
        self.hits = WindowedCounter()        # resident probed slabs
        self.misses = WindowedCounter()      # uploaded-on-demand probed slabs
        self.refs = WindowedCounter()        # raw table refs (pre-dedupe)
        self.unique_refs = WindowedCounter() # post-dedupe references
        self.uploads = WindowedCounter()     # slabs uploaded (miss + dirty)
        self.evictions = WindowedCounter()   # occupied frames recycled
        self.last_prefetch: dict = {}
        if telemetry is None:
            from repro import obs
            telemetry = obs.default()
        self.tel = telemetry
        t = telemetry
        self._m_cache = t.counter(
            "sivf_tiered_cache_events_total",
            "tiered-cache events: hit/miss/eviction/upload/dirty_refresh/"
            "dedup_saved (probed-slab granularity)", ("event",))
        self._m_bytes = t.counter(
            "sivf_transfer_bytes_total",
            "explicit host<->device transfer bytes by direction and stage",
            ("direction", "stage"))

    # -- construction helpers ----------------------------------------------

    def _init_cache_dev(self) -> SlabCacheDev:
        if self.backend_kind != "mesh":
            return init_cache(self.cfg)
        from repro.core.distributed import build_stacked
        return build_stacked(lambda: init_cache(self.cfg), self.mesh,
                             self.axis)

    # -- insert-plan pipeline ----------------------------------------------

    def queue_plan(self, plan: dict, vecs, attrs) -> None:
        """Queue one committed batch's host-store writes.

        ``plan`` is the device dict from ``_insert_impl(want_plan=True)``
        (mesh: stacked [S, B] leaves). ``vecs`` / ``attrs`` are the batch
        rows in the same input order — numpy when the caller had host
        data (no transfer needed at drain), device arrays otherwise.
        Deferred-friendly: nothing syncs here.
        """
        self._plans.append({
            "slab": plan["slab"], "slot": plan["slot"],
            "codes": plan["codes"],
            "vecs": None if self.cfg.payload_dim == 0 else vecs,
            "attrs": attrs if self.cfg.n_attrs else None})

    def drain_plans(self) -> None:
        """Apply every queued plan to the host store (one ``device_get``)."""
        if not self._plans:
            return
        plans, self._plans = self._plans, []
        dev_leaves = [[p[k] for k in ("slab", "slot", "codes", "vecs",
                                      "attrs")
                       if isinstance(p[k], jax.Array)] for p in plans]
        host_flat = jax.device_get([x for sub in dev_leaves for x in sub])
        it = iter(host_flat)
        for p in plans:
            vals = {k: (next(it) if isinstance(p[k], jax.Array) else p[k])
                    for k in ("slab", "slot", "codes", "vecs", "attrs")}
            self._apply_plan(vals)

    def _apply_plan(self, p: dict) -> None:
        slab = np.asarray(p["slab"])
        slot = np.asarray(p["slot"])
        codes = np.asarray(p["codes"])
        stacked = slab.ndim == 2
        for s in range(self.n_shards):
            ps = slab[s] if stacked else slab
            po = slot[s] if stacked else slot
            rows = np.flatnonzero(ps >= 0)
            if rows.size == 0:
                continue
            tgt_s, tgt_o = ps[rows], po[rows]
            store = self.stores[s]
            if self.cfg.payload_dim:
                v = np.asarray(p["vecs"])
                store.data[tgt_s, tgt_o] = v[rows, :self.cfg.payload_dim
                                             ].astype(store.data.dtype)
            if self.cfg.code_m:
                pc = codes[s] if stacked else codes
                store.codes[tgt_s, tgt_o] = pc[rows]
            if self.cfg.n_attrs:
                a = np.asarray(p["attrs"])
                store.attrs[tgt_s, tgt_o] = a[rows]
            self.res[s].dirty.update(int(x) for x in np.unique(tgt_s))

    # -- the three search stages -------------------------------------------

    def plan(self, state: SlabPoolState, queries: jax.Array, nprobe: int
             ) -> jax.Array:
        """Stage 1 (jitted): probe lists -> pool slab-id table."""
        if self.backend_kind == "mesh":
            fn = _plan_ops_mesh(self.cfg, self.mesh, self.axis,
                                self.use_tables)
        else:
            fn = _plan_ops(self.cfg, self.use_tables)
        with self.tel.span("plan"):      # dispatch time; sync lands in
            return fn(state, queries, nprobe=nprobe)   # prefetch's get

    def prefetch(self, table: jax.Array, nprobe: int, epoch: int
                 ) -> PrefetchTicket:
        """Stage 2 (host): make every probed slab resident.

        One explicit ``device_get`` of the table; dedupe, evict, and — only
        when there are misses or dirty residents — one packed explicit
        ``device_put`` plus one donated scatter call. A fully warm cache
        performs **zero** transfers and zero device work here.
        """
        with self.tel.span("prefetch"):
            self.drain_plans()
            tbl = np.asarray(jax.device_get(table))
            per_shard = tbl if tbl.ndim == 3 else tbl[None]
            up_frames, up_slabs, total_up = [], [], 0
            stats = {"refs": 0, "unique": 0, "hits": 0, "misses": 0,
                     "dirty_refresh": 0, "uploaded": 0, "evicted": 0}
            for s in range(self.n_shards):
                f_s, s_s = self._prefetch_shard(s, per_shard[s], stats)
                up_frames.append(f_s)
                up_slabs.append(s_s)
                total_up += len(f_s)
            stats["dedup_saved"] = stats["refs"] - stats["unique"]
            self.last_prefetch = stats
            self.seq += 1
            if total_up:
                self._upload(up_frames, up_slabs)
            if self.tel.enabled:
                m = self._m_cache
                m.inc(stats["hits"], event="hit")
                m.inc(stats["misses"], event="miss")
                m.inc(stats["evicted"], event="eviction")
                m.inc(stats["uploaded"], event="upload")
                m.inc(stats["dirty_refresh"], event="dirty_refresh")
                m.inc(stats["dedup_saved"], event="dedup_saved")
                self._m_bytes.inc(tbl.nbytes, direction="d2h",
                                  stage="prefetch")
            return PrefetchTicket(table=table, nprobe=nprobe,
                                  padded_q=int(per_shard.shape[-2]),
                                  seq=self.seq, epoch=epoch)

    def _prefetch_shard(self, s: int, tbl: np.ndarray, stats: dict
                        ) -> tuple[list[int], list[int]]:
        """LRU bookkeeping for one shard -> (upload frames, upload slabs)."""
        res = self.res[s]
        flat = tbl[tbl >= 0]
        uniq = np.unique(flat)
        stats["refs"] += int(flat.size)
        stats["unique"] += int(uniq.size)
        self.refs.add(int(flat.size))
        self.unique_refs.add(int(uniq.size))
        f_cap = self.cfg.device_slabs
        if uniq.size > f_cap:
            raise ValueError(
                f"query batch probes {uniq.size} distinct slabs on shard "
                f"{s} but device_slabs={f_cap}: the hot cache cannot hold "
                f"one batch's working set — raise device_slabs, lower "
                f"nprobe, or shrink the query batch")
        frame = res.frame_of[uniq]
        hit_slabs = uniq[frame >= 0]
        miss_slabs = uniq[frame < 0]
        dirty_hits = np.array(
            [sl for sl in hit_slabs if int(sl) in res.dirty], np.int32)
        stats["hits"] += int(hit_slabs.size)
        stats["misses"] += int(miss_slabs.size)
        stats["dirty_refresh"] += int(dirty_hits.size)
        self.hits.add(int(hit_slabs.size))
        self.misses.add(int(miss_slabs.size))
        res.clock += 1
        res.tick[res.frame_of[hit_slabs]] = res.clock
        up_frames: list[int] = []
        up_slabs: list[int] = []
        if miss_slabs.size:
            needed = np.zeros((self.cfg.n_slabs,), bool)
            needed[uniq] = True
            free = np.flatnonzero(res.slab_of_frame < 0)
            occ = np.flatnonzero(res.slab_of_frame >= 0)
            evictable = occ[~needed[res.slab_of_frame[occ]]]
            evictable = evictable[np.argsort(res.tick[evictable],
                                             kind="stable")]
            victims = np.concatenate([free, evictable])[:miss_slabs.size]
            for fr, sl in zip(victims, miss_slabs):
                old = int(res.slab_of_frame[fr])
                if old >= 0:
                    res.frame_of[old] = -1
                    res.dirty.discard(old)
                    self.evictions.add(1)
                    stats["evicted"] += 1
                res.slab_of_frame[fr] = sl
                res.frame_of[sl] = fr
                res.tick[fr] = res.clock
                res.dirty.discard(int(sl))
                up_frames.append(int(fr))
                up_slabs.append(int(sl))
        for sl in dirty_hits:                  # refresh in place, same frame
            res.dirty.discard(int(sl))
            up_frames.append(int(res.frame_of[sl]))
            up_slabs.append(int(sl))
        self.uploads.add(len(up_frames))
        stats["uploaded"] += len(up_frames)
        return up_frames, up_slabs

    def _upload(self, up_frames: list[list[int]], up_slabs: list[list[int]]
                ) -> None:
        """Pack per-shard upload sets and run the donated cache scatter."""
        u = _pow2(max(max((len(f) for f in up_frames), default=0), 1))
        n = self.n_shards
        frames = np.full((n, u), -1, np.int32)
        slabs = np.zeros((n, u), np.int32)
        drows = np.zeros((n, u) + self.stores[0].data.shape[1:],
                         self.stores[0].data.dtype)
        crows = np.zeros((n, u) + self.stores[0].codes.shape[1:], np.uint8)
        arows = np.zeros((n, u) + self.stores[0].attrs.shape[1:], np.int32)
        for s in range(n):
            m = len(up_frames[s])
            if not m:
                continue
            frames[s, :m] = up_frames[s]
            slabs[s, :m] = up_slabs[s]
            d, c, a = self.stores[s].rows(slabs[s, :m])
            drows[s, :m], crows[s, :m], arows[s, :m] = d, c, a
        if self.backend_kind == "mesh":
            args = jax.device_put((frames, slabs, drows, crows, arows))
            self.cache = _upload_ops_mesh(self.cfg, n)(self.cache, *args)
            up_bytes = sum(a.nbytes for a in
                           (frames, slabs, drows, crows, arows))
        else:
            # ONE explicit host->device transfer per prefetch-with-misses:
            # the packed tuple is the only transfer site in steady state
            args = jax.device_put((frames[0], slabs[0], drows[0], crows[0],
                                   arows[0]))
            self.cache = _upload_ops(self.cfg)(self.cache, *args)
            up_bytes = sum(a.nbytes for a in args)
        if self.tel.enabled:
            self._m_bytes.inc(up_bytes, direction="h2d", stage="prefetch")

    def scan(self, state: SlabPoolState, queries: jax.Array,
             table: jax.Array, k: int, fstruct, fconsts
             ) -> tuple[jax.Array, jax.Array]:
        """Stage 3 (jitted): frame-translated scan -> top-k."""
        if self.backend_kind == "mesh":
            fn = _scan_ops_mesh(self.cfg, self.mesh, self.axis, self.impl,
                                self.block_q)
        else:
            fn = _scan_ops(self.cfg, self.impl, self.block_q)
        with self.tel.span("scan"):      # dispatch time; the caller's
            return fn(state, self.cache, queries, table, k=k,   # sync point
                      fstruct=fstruct, fconsts=fconsts)  # absorbs exec time

    def search(self, state: SlabPoolState, queries: jax.Array, k: int,
               nprobe: int, fstruct=None, fconsts=None, epoch: int = 0,
               ticket: PrefetchTicket | None = None
               ) -> tuple[jax.Array, jax.Array]:
        """The full three-stage tiered search.

        A valid ``ticket`` (same runtime ``seq`` — nothing prefetched
        since — same ``epoch``, ``nprobe`` and query bucket) skips stages
        1-2; anything stale falls back to the full path.
        """
        if not (ticket is not None and ticket.seq == self.seq
                and ticket.epoch == epoch and ticket.nprobe == nprobe
                and ticket.padded_q == int(queries.shape[0])):
            table = self.plan(state, queries, nprobe)
            ticket = self.prefetch(table, nprobe, epoch)
        return self.scan(state, queries, ticket.table, k, fstruct, fconsts)

    # -- introspection ------------------------------------------------------

    def compile_stats(self) -> dict:
        def size(f):
            try:
                return int(f._cache_size())
            except Exception:               # pragma: no cover - private API
                return -1
        if self.backend_kind == "mesh":
            plan = _plan_ops_mesh(self.cfg, self.mesh, self.axis,
                                  self.use_tables)
            scan = _scan_ops_mesh(self.cfg, self.mesh, self.axis, self.impl,
                                  self.block_q)
        else:
            plan = _plan_ops(self.cfg, self.use_tables)
            scan = _scan_ops(self.cfg, self.impl, self.block_q)
        return {"tiered_plan": size(plan), "tiered_scan": size(scan)}

    def roll_window(self) -> None:
        """Start a new stats window: the ``*_window`` reads in
        :meth:`stats` reset to 0 (cumulative totals are untouched)."""
        for name in self._COUNTERS:
            getattr(self, name).mark()

    def carry_from(self, other: "TieredRuntime") -> "TieredRuntime":
        """Adopt another runtime's cumulative counters (and their window
        marks). ``Index.reshard`` rebuilds the runtime and calls this so a
        reshard no longer silently zeroes the cache statistics."""
        for name in self._COUNTERS:
            getattr(self, name).carry(getattr(other, name))
        return self

    def stats(self) -> dict:
        probed = self.hits.total + self.misses.total
        probed_w = self.hits.window + self.misses.window
        return {
            "tiered": True,
            "device_slabs": self.cfg.device_slabs,
            "resident_slabs": sum(r.resident_slabs for r in self.res),
            "per_shard_resident": [r.resident_slabs for r in self.res],
            # labeled explicitly: hit_rate is CUMULATIVE (handle lifetime,
            # carried across reshard); hit_rate_window covers only the
            # probes since the last roll_window()
            "hit_rate": (self.hits.total / probed) if probed else 1.0,
            "hit_rate_kind": "cumulative",
            "hit_rate_window": (self.hits.window / probed_w)
            if probed_w else 1.0,
            "cache_hits": self.hits.total,
            "cache_misses": self.misses.total,
            "cache_uploads": self.uploads.total,
            "cache_evictions": self.evictions.total,
            "cache_hits_window": self.hits.window,
            "cache_misses_window": self.misses.window,
            "dedup_refs": self.refs.total,
            "dedup_unique_refs": self.unique_refs.total,
            "dedup_saved_fetches": self.refs.total - self.unique_refs.total,
            "dirty_slabs": sum(len(r.dirty) for r in self.res),
            "pending_plans": len(self._plans),
        }


# ---------------------------------------------------------------------------
# Mesh stage factories (shard_map bodies mirroring core/distributed.py)
# ---------------------------------------------------------------------------

@lru_cache(maxsize=None)
def _plan_ops_mesh(cfg: SIVFConfig, mesh, axis: str,
                   use_tables: bool | None):
    from jax.sharding import PartitionSpec as P

    ut = cfg.track_tables if use_tables is None else use_tables

    @partial(jax.jit, static_argnames=("nprobe",))
    def plan(state, queries, nprobe):
        def local(st, q):
            st = jax.tree.map(lambda x: x[0], st)
            lists = quantizer.probe(st.centroids, q.astype(cfg.dtype),
                                    nprobe, cfg.metric)
            tab = (ix.gather_tables if ut else ix.walk_chains)(cfg, st,
                                                               lists)
            return tab[None]

        f = jax.shard_map(
            local, mesh=mesh, check_vma=False,
            in_specs=(jax.tree.map(lambda _: P(axis), state), P()),
            out_specs=P(axis))
        return f(state, queries)                       # [S, Q, T]

    return plan


@lru_cache(maxsize=None)
def _scan_ops_mesh(cfg: SIVFConfig, mesh, axis: str, impl: str | None,
                   block_q: int):
    from jax.sharding import PartitionSpec as P

    @partial(jax.jit, static_argnames=("k", "fstruct"))
    def scan(state, cache, queries, table, k, fstruct, fconsts):
        def local(st, ca, q, tab, *fc):
            st = jax.tree.map(lambda x: x[0], st)
            ca = jax.tree.map(lambda x: x[0], ca)
            ftable = translate_table(tab[0], ca.frame_of)
            view = cache_view(cfg, st, ca)
            d, lab = ix._scan_dispatch(
                cfg, view, q, ftable, k, impl, block_q, fstruct=fstruct,
                fconsts=fc[0] if fc else None)
            # identical scatter-gather merge to distributed.sharded_search
            dg = jax.lax.all_gather(d, axis)           # [S, Q, k]
            lg = jax.lax.all_gather(lab, axis)
            s, qn, _ = dg.shape
            dg = jnp.moveaxis(dg, 0, 1).reshape(qn, s * k)
            lg = jnp.moveaxis(lg, 0, 1).reshape(qn, s * k)
            nd, idx = jax.lax.top_k(-dg, k)
            return -nd, jnp.take_along_axis(lg, idx, axis=1)

        extra = () if fconsts is None else (fconsts,)
        f = jax.shard_map(
            local, mesh=mesh, check_vma=False,
            in_specs=(jax.tree.map(lambda _: P(axis), state),
                      jax.tree.map(lambda _: P(axis), cache), P(), P(axis))
            + tuple(P() for _ in extra),
            out_specs=(P(), P()))
        return f(state, cache, queries, table, *extra)

    return scan
