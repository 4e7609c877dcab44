"""Streaming serve engine: search-during-ingest front door for ``sivf.Index``.

The paper's headline claim is that SIVF keeps serving millisecond searches
*while* mutations stream in. Until now every consumer drove the index
synchronously from one thread; this engine is the concurrent front door:

    index = sivf.Index(cfg, centroids, deferred=True)
    with ServeEngine(index) as eng:
        writer = eng.session("ingest")
        reader = eng.session("app")
        writer.add(vecs, ids)                       # non-blocking submit
        res = reader.search(qs, k=10).result()      # ServeSearchResult

Architecture (cribbed from the seed LLM engine's admit/step split — one
scheduler owns the device, clients only touch queues and futures):

  * **One dispatch thread.** Client threads validate + enqueue under the
    engine lock; a single scheduler thread drains the queue and is the
    only thread that touches the index. JAX device work executes in
    dispatch order, so the scheduler's ordering decisions *are* the
    consistency story.
  * **Coalesced query batching.** Queued searches sharing
    ``(k, nprobe, filter)`` concatenate into one tile (capped at
    ``max_coalesce`` rows) and ride one fused-kernel call;
    ``Index.search`` pads the tile to the PR 2 power-of-two query
    buckets, so executable counts stay bounded by ``#buckets x
    #(k, nprobe, filter-structure) groups`` — filter constants never
    mint an executable — and :meth:`assert_bounded_compiles` checks the
    observed jit cache against that bound.
  * **Mandatory tenant filters.** ``tenant_filters={tenant: predicate}``
    AND-s the predicate into every search the tenant submits and
    force-stamps its ``Eq``-pinned attributes onto the tenant's ingested
    rows — isolation holds on the read *and* write paths (see
    docs/filtering.md).
  * **Epoch-consistent mutation interleaving.** Mutations are admitted
    through the ``deferred=True`` pipeline (fire-and-forget submits, one
    packed sync per flush). Each dispatched batch bumps ``Index.epoch``;
    a search dispatched at epoch ``e`` observes exactly the first ``e``
    batches — never a half-applied one, because each batch commits
    atomically on device (PR 3) and the scheduler serializes dispatch.
    Searches dispatch *before* the mutations drained in the same cycle,
    so queries never stall behind ingest.
  * **Typed backpressure.** Per-tenant quotas (in-flight search cap,
    mutation-rate token bucket) and the global queue bound reject at
    submit time with :class:`repro.serve.quota.Backpressure` — the queue
    cannot grow without bound.

``close()`` (or context exit) drains: queued requests are processed, the
deferred queue is flushed, every future resolves. See docs/serving.md.
"""
from __future__ import annotations

import threading
import time
from collections import deque

import jax
import numpy as np

from repro.core import filters as flt
from repro.core.api import Index
from repro.obs.trace import profiling
from repro.serve.quota import (
    Backpressure,
    BackpressureKind,
    TenantQuota,
    TenantState,
)
from repro.serve.session import (
    ClientSession,
    MaintenanceRequest,
    MutationRequest,
    SearchRequest,
    ServeFuture,
    ServeMaintenanceResult,
    ServeMutationResult,
    ServeSearchResult,
)


class ServeEngine:
    """Concurrent serve front door over a ``deferred=True`` ``sivf.Index``.

    Parameters
    ----------
    index:        the :class:`sivf.Index` to serve. Must be constructed
                  with ``deferred=True`` (the engine sequences flushes)
                  and ``strict=False`` (admission errors surface on the
                  per-request :class:`ServeMutationResult`, never as a
                  mid-flush raise).
    default_k:    ``k`` used when a search request does not name one.
    default_nprobe: likewise for ``nprobe`` (``None`` probes every list).
    quota:        engine-wide default :class:`TenantQuota`.
    quotas:       per-tenant overrides, ``{tenant: TenantQuota}``.
    max_queue:    global bound on queued requests; beyond it submits are
                  rejected with ``QUEUE_FULL``.
    max_coalesce: cap on live query rows coalesced into one search tile
                  (the tile then pads to the next pow2 bucket).
    flush_every:  flush the deferred mutation queue once this many
                  batches are pending (the queue also flushes whenever
                  the engine goes idle, and at drain).
    tenant_filters: ``{tenant: predicate}`` *mandatory* filters
                  (``repro.core.filters``). Every search from a listed
                  tenant is AND-ed with its predicate — a client filter
                  can narrow but never escape it — and every attribute
                  the predicate pins with ``Eq`` (e.g. a tenant id) is
                  force-stamped onto that tenant's ingested rows, so a
                  listed tenant can neither read nor write outside its
                  slice. (``remove`` stays id-addressed; partition the id
                  space per tenant if eviction isolation matters too.)
                  Requires ``SIVFConfig(attributes=...)``.
    telemetry:    a ``repro.obs.Telemetry`` to record into. Defaults to
                  the served index's instance so engine tile spans and
                  the index's plan/prefetch/scan stage spans land in one
                  registry (see docs/observability.md).
    clock:        injectable monotonic clock (tests drive quota refill
                  deterministically).
    """

    def __init__(self, index: Index, *, default_k: int = 10,
                 default_nprobe: int | None = None,
                 quota: TenantQuota | None = None,
                 quotas: "dict[str, TenantQuota] | None" = None,
                 max_queue: int = 1024, max_coalesce: int = 256,
                 flush_every: int = 8,
                 tenant_filters: "dict | None" = None,
                 telemetry=None, clock=time.monotonic):
        if not isinstance(index, Index):
            raise TypeError(f"index must be a sivf.Index, got {index!r}")
        if not index.deferred:
            raise ValueError(
                "ServeEngine requires Index(deferred=True): the engine "
                "sequences flushes, eager per-batch syncs would stall the "
                "dispatch thread")
        if index.strict:
            raise ValueError(
                "ServeEngine requires strict=False: admission errors are "
                "reported on each ServeMutationResult, a strict flush "
                "raise would tear down the whole queue")
        if max_coalesce < 1:
            raise ValueError("max_coalesce must be >= 1")
        self._index = index
        self._default_k = int(default_k)
        self._default_nprobe = default_nprobe
        self._default_quota = quota or TenantQuota()
        self._quota_overrides = dict(quotas or {})
        self._max_queue = int(max_queue)
        self._max_coalesce = int(max_coalesce)
        self._flush_every = int(flush_every)
        self._clock = clock
        # mandatory per-tenant filters: compile eagerly so a bad predicate
        # (unknown attribute, no attributes configured) fails construction,
        # not some later search; Eq-pinned values become ingest overrides
        self._tenant_filters = dict(tenant_filters or {})
        self._tenant_stamps: dict[str, dict[str, int]] = {}
        for tenant, pred in self._tenant_filters.items():
            flt.compile_filter(pred, index.cfg.attributes)
            self._tenant_stamps[tenant] = flt.eq_bindings(pred)

        self._cv = threading.Condition()
        self._queue: deque = deque()
        self._tenants: dict[str, TenantState] = {}
        self._closing = False
        self._closed = False
        self._gate = threading.Event()        # cleared = scheduler paused
        self._gate.set()
        # scheduler-thread-only state
        self._mut_inflight: deque = deque()   # (req, PendingReport, epoch)
        self._kn_groups: set = set()
        self._max_tile = 0
        self._max_mut_rows = 0
        self._n_searches = 0
        self._n_tiles = 0
        self._n_mutations = 0
        self._n_maintenance = 0
        self._coalesce_sizes: list[int] = []
        self._flush_deferred = 0              # cycles a flush was held back
        # telemetry: default to the index's instance so one registry holds
        # the whole request path (tile roots + plan/prefetch/scan stages)
        self._tel = telemetry if telemetry is not None \
            else index._telemetry
        t = self._tel
        self._m_requests = t.counter(
            "sivf_serve_requests_total",
            "admitted serve requests by tenant and op", ("tenant", "op"))
        self._m_rows = t.counter(
            "sivf_serve_rows_total",
            "query/mutation rows admitted by tenant and op",
            ("tenant", "op"))
        self._m_backpressure = t.counter(
            "sivf_serve_backpressure_total",
            "submits rejected by tenant and backpressure kind",
            ("tenant", "kind"))
        self._m_queue_depth = t.gauge(
            "sivf_serve_queue_depth", "requests waiting in the engine queue")
        self._m_epoch = t.gauge(
            "sivf_serve_epoch", "committed mutation-batch prefix length")
        self._m_coalesce = t.histogram(
            "sivf_serve_coalesce_rows",
            "query rows coalesced into one kernel tile",
            buckets=tuple(float(2 ** i) for i in range(13)))
        if index.pending_count:               # engine owns the queue from here
            index.flush()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="sivf-serve-engine")
        self._thread.start()

    # -- client surface ------------------------------------------------------

    def session(self, tenant: str = "default") -> ClientSession:
        """A tenant-scoped submit handle (cheap; any number per tenant)."""
        return ClientSession(self, tenant)

    @property
    def index(self) -> Index:
        return self._index

    @property
    def epoch(self) -> int:
        """Committed mutation-batch prefix length (``Index.epoch``)."""
        return self._index.epoch

    def _tenant_state(self, tenant: str) -> TenantState:
        st = self._tenants.get(tenant)
        if st is None:
            st = TenantState(
                self._quota_overrides.get(tenant, self._default_quota),
                clock=self._clock)
            self._tenants[tenant] = st
        return st

    def _check_open_and_capacity(self, st: TenantState, tenant: str) -> None:
        if self._closing:
            raise Backpressure(BackpressureKind.ENGINE_CLOSED, tenant,
                               "engine is closed")
        if len(self._queue) >= self._max_queue:
            st.reject(BackpressureKind.QUEUE_FULL, tenant,
                      f"engine queue at max_queue={self._max_queue}")

    def _effective_filter(self, tenant: str, filter):
        """AND the tenant's mandatory predicate (if any) with the request's
        own, compiled once at submit so bad filters raise in the client
        thread and equal filters coalesce by value downstream."""
        mandatory = self._tenant_filters.get(tenant)
        if mandatory is None:
            pred = filter
        elif filter is None:
            pred = mandatory
        else:
            pred = flt.And(mandatory, filter)
        return flt.compile_filter(pred, self._index.cfg.attributes)

    def submit_search(self, tenant: str, queries, *, k: int | None = None,
                      nprobe: int | None = None, filter=None) -> ServeFuture:
        """Validate + enqueue a search; returns a future, never blocks."""
        q = np.asarray(queries, np.float32)
        if q.ndim == 1:
            q = q[None]
        if q.ndim != 2 or q.shape[1] != self._index.cfg.dim:
            raise ValueError(
                f"queries {q.shape} != [q, dim={self._index.cfg.dim}]")
        k = self._default_k if k is None else int(k)
        nprobe = self._default_nprobe if nprobe is None else nprobe
        n_lists = self._index.cfg.n_lists
        nprobe = n_lists if nprobe is None else min(int(nprobe), n_lists)
        cfilter = self._effective_filter(tenant, filter)
        try:
            with self._cv:
                st = self._tenant_state(tenant)
                self._check_open_and_capacity(st, tenant)
                st.admit_search(tenant)
                fut = ServeFuture(on_done=lambda _f, s=st: self._release(s))
                self._queue.append(SearchRequest(
                    tenant=tenant, queries=q, k=k, nprobe=nprobe,
                    future=fut, t_submit=self._clock(), cfilter=cfilter))
                depth = len(self._queue)
                self._cv.notify()
        except Backpressure as e:
            self._note_backpressure(tenant, e)
            raise
        if self._tel.enabled:
            self._m_requests.inc(tenant=tenant, op="search")
            self._m_rows.inc(int(q.shape[0]), tenant=tenant, op="search")
            self._m_queue_depth.set(depth)
        return fut

    def _release(self, st: TenantState) -> None:
        with self._cv:
            st.release_search()

    def _submit_mutation(self, tenant: str, op: str, vecs, ids,
                         attrs=None) -> ServeFuture:
        ids_a = np.asarray(ids, np.int32).reshape(-1)
        vecs_a = attrs_a = None
        if op == "add":
            vecs_a = np.asarray(vecs, np.float32)
            if vecs_a.ndim != 2 or vecs_a.shape[1] != self._index.cfg.dim:
                raise ValueError(
                    f"vecs {vecs_a.shape} != [B, dim={self._index.cfg.dim}]")
            if vecs_a.shape[0] != ids_a.shape[0]:
                raise ValueError(
                    f"vecs {vecs_a.shape} / ids {ids_a.shape} mismatch")
            if self._index.cfg.n_attrs:
                # normalize in the client thread (errors raise at submit);
                # Eq-pinned tenant attributes override whatever the client
                # sent — a row can never escape its mandatory filter
                attrs_a = flt.normalize_attrs(
                    self._index.cfg.attributes, attrs,
                    int(ids_a.shape[0]),
                    overrides=self._tenant_stamps.get(tenant))
            elif attrs is not None:
                raise ValueError(
                    "attrs= given but the served index has no "
                    "SIVFConfig(attributes=...)")
        try:
            with self._cv:
                st = self._tenant_state(tenant)
                self._check_open_and_capacity(st, tenant)
                st.admit_mutation(tenant, int(ids_a.shape[0]))
                fut = ServeFuture()
                self._queue.append(MutationRequest(
                    tenant=tenant, op=op, vecs=vecs_a, ids=ids_a,
                    future=fut, t_submit=self._clock(), attrs=attrs_a))
                depth = len(self._queue)
                self._cv.notify()
        except Backpressure as e:
            self._note_backpressure(tenant, e)
            raise
        if self._tel.enabled:
            self._m_requests.inc(tenant=tenant, op=op)
            self._m_rows.inc(int(ids_a.shape[0]), tenant=tenant, op=op)
            self._m_queue_depth.set(depth)
        return fut

    def _note_backpressure(self, tenant: str, e: Backpressure) -> None:
        if self._tel.enabled:
            self._m_backpressure.inc(tenant=tenant, kind=e.kind.value)

    def submit_add(self, tenant: str, vecs, ids, attrs=None) -> ServeFuture:
        """Enqueue an ingest batch through the deferred pipeline."""
        return self._submit_mutation(tenant, "add", vecs, ids, attrs=attrs)

    def submit_remove(self, tenant: str, ids) -> ServeFuture:
        """Enqueue an eviction batch through the deferred pipeline."""
        return self._submit_mutation(tenant, "remove", None, ids)

    def submit_maintenance(self, tenant: str, ops=None,
                           max_ops: int = 2) -> ServeFuture:
        """Enqueue a maintenance pass (``core/maintenance.py``).

        Operator-plane: exempt from per-tenant mutation quotas (it moves
        no client rows) but still bounded by the global queue. The
        scheduler interleaves it epoch-consistently — searches drained in
        the same cycle dispatch first, against the pre-maintenance
        prefix; each committed op then bumps the epoch like any other
        atomic batch, so later searches observe the whole new layout.
        """
        if ops is not None:
            from repro.core.maintenance import MaintOp
            ops = list(ops)
            for op in ops:
                if not isinstance(op, MaintOp):
                    raise TypeError(f"ops must be MaintOp, got {op!r}")
        try:
            with self._cv:
                st = self._tenant_state(tenant)
                self._check_open_and_capacity(st, tenant)
                fut = ServeFuture()
                self._queue.append(MaintenanceRequest(
                    tenant=tenant, ops=ops, max_ops=int(max_ops),
                    future=fut, t_submit=self._clock()))
                depth = len(self._queue)
                self._cv.notify()
        except Backpressure as e:
            self._note_backpressure(tenant, e)
            raise
        if self._tel.enabled:
            self._m_requests.inc(tenant=tenant, op="maintain")
            self._m_queue_depth.set(depth)
        return fut

    # -- scheduler -----------------------------------------------------------

    def _loop(self) -> None:
        while True:
            with self._cv:
                while True:
                    if self._closing and not self._queue \
                            and not self._mut_inflight:
                        return
                    if self._gate.is_set() and (
                            self._queue or self._closing
                            or self._mut_inflight):
                        break
                    with self._tel.span("serve.wait"):
                        self._cv.wait(timeout=0.1)
                batch = list(self._queue)
                self._queue.clear()
            searches = [r for r in batch if isinstance(r, SearchRequest)]
            muts = [r for r in batch if isinstance(r, MutationRequest)]
            maint = [r for r in batch if isinstance(r, MaintenanceRequest)]
            with self._tel.span("serve.dispatch") as sp:
                dispatched = self._dispatch_searches(searches)
                self._dispatch_mutations(muts)
                self._dispatch_maintenance(maint)
                sp.set(tiles=len(dispatched), rows=sum(
                    r.queries.shape[0] for d in dispatched for r in d[0]))
            self._maybe_flush()
            self._resolve_searches(dispatched)

    def _dispatch_searches(self, searches: list) -> list:
        """Coalesce by (k, nprobe, compiled filter), dispatch each tile
        async, at the *current* committed epoch — before this cycle's
        mutations. Equal filters (same structure AND constants) share a
        tile; the jit cache additionally collapses same-structure tiles
        onto one executable.

        On a tiered index (``SIVFConfig(device_slabs=...)``) the tiles are
        software-pipelined: after dispatching tile ``i``'s scan (async),
        the scheduler immediately prefetches tile ``i+1``'s probed slabs —
        the host->device uploads overlap the in-flight kernel, and tile
        ``i+1``'s search then skips its plan/prefetch stages via the
        returned ticket. Dispatch-order device execution makes this safe:
        tile ``i``'s scan is ordered before tile ``i+1``'s cache scatter,
        so eviction can never clobber a frame a running scan still reads.
        """
        groups: dict = {}
        for r in searches:
            groups.setdefault((r.k, r.nprobe, r.cfilter), []).append(r)
        tiles: list = []
        for (k, nprobe, cfilter), reqs in sorted(groups.items(), key=repr):
            chunk: list = []
            rows = 0
            for r in reqs + [None]:                # None terminates
                nq = 0 if r is None else r.queries.shape[0]
                if chunk and (r is None or rows + nq > self._max_coalesce):
                    qmat = chunk[0].queries if len(chunk) == 1 else \
                        np.concatenate([c.queries for c in chunk])
                    tiles.append((chunk, qmat, k, nprobe, cfilter))
                    chunk, rows = [], 0
                if r is not None:
                    chunk.append(r)
                    rows += nq
        dispatched: list = []
        epoch = self._index.epoch
        ticket = self._prefetch_tile(tiles[0]) if tiles else None
        for i, tile in enumerate(tiles):
            self._dispatch_tile(tile, epoch, dispatched, ticket)
            ticket = self._prefetch_tile(tiles[i + 1]) \
                if i + 1 < len(tiles) else None
        return dispatched

    def _prefetch_tile(self, tile):
        """Stage a tile's probed slabs ahead of its dispatch (tiered only;
        ``Index.prefetch`` is a no-op ``None`` on an all-resident index).
        Prefetch errors are swallowed — the tile's own search will hit the
        same condition and report it on the right futures."""
        _, qmat, _, nprobe, _ = tile
        try:
            return self._index.prefetch(qmat, nprobe)
        except Exception:
            return None

    def _dispatch_tile(self, tile, epoch: int, dispatched: list,
                       ticket=None) -> None:
        chunk, qmat, k, nprobe, cfilter = tile
        # the tile root span lives from dispatch to result readiness (set
        # at _resolve_searches); its scope exits right after dispatch so
        # the NEXT tile's pipelined prefetch doesn't nest into it
        span = self._tel.open_span(
            "serve.tile", root=True, epoch=epoch,
            tenant=",".join(sorted({r.tenant for r in chunk})),
            filter=None if cfilter is None else str(cfilter.structure),
            rows=int(qmat.shape[0]))
        t0 = self._clock()
        try:
            res = self._index.search(qmat, k, nprobe, filter=cfilter,
                                     _prefetched=ticket)  # async dispatch
        except Exception as e:
            self._tel.exit_scope(span)
            self._tel.finish_span(span)
            for r in chunk:
                r.future.set_exception(e)
            return
        self._tel.exit_scope(span)
        self._n_tiles += 1
        self._n_searches += len(chunk)
        self._coalesce_sizes.append(int(qmat.shape[0]))
        self._max_tile = max(self._max_tile, res.padded_to)
        if self._tel.enabled:
            self._m_coalesce.observe(int(qmat.shape[0]))
        # executables are per filter STRUCTURE, not per constant set
        self._kn_groups.add((k, res.nprobe,
                             None if cfilter is None else cfilter.structure))
        dispatched.append((chunk, res, epoch, t0, span))

    def _dispatch_mutations(self, muts: list) -> None:
        for r in muts:
            try:
                if r.op == "add":
                    pending = self._index.add(r.vecs, r.ids, attrs=r.attrs)
                else:
                    pending = self._index.remove(r.ids)
            except Exception as e:
                r.future.set_exception(e)
                continue
            self._n_mutations += 1
            self._max_mut_rows = max(self._max_mut_rows,
                                     int(r.ids.shape[0]))
            self._mut_inflight.append((r, pending, self._index.epoch))

    def _dispatch_maintenance(self, maint: list) -> None:
        """Run queued maintenance passes, after this cycle's searches
        dispatched (they observe the pre-maintenance prefix) and after
        its mutations (the pass sees their committed device state).
        ``Index.maintain`` syncs per op — acceptable for a background
        operator action; client searches already left the queue."""
        for r in maint:
            try:
                reports = self._index.maintain(ops=r.ops,
                                               max_ops=r.max_ops,
                                               strict=False)
            except Exception as e:
                r.future.set_exception(e)
                continue
            self._n_maintenance += 1
            if self._tel.enabled:
                self._m_epoch.set(self._index.epoch)
            r.future.set_result(ServeMaintenanceResult(
                reports=tuple(reports), epoch=self._index.epoch,
                queue_s=self._clock() - r.t_submit))

    def _maybe_flush(self) -> None:
        """Flush when the deferred queue is deep, the engine is idle, or
        a drain is in progress — one packed sync resolves every batch."""
        if not self._mut_inflight:
            return
        if self._index.pending_count >= self._flush_every:
            reason = "depth"
        elif self._closing:
            reason = "closing"
        else:
            with self._cv:
                if self._queue:        # more work queued: keep deferring
                    self._flush_deferred += 1
                    return
            reason = "idle"
        with self._tel.span("serve.flush", batches=len(self._mut_inflight),
                            reason=reason,
                            deferred=self._flush_deferred) as sp:
            self._flush_deferred = 0
            try:
                self._index.flush()
            except Exception as e:
                while self._mut_inflight:
                    req, _, _ = self._mut_inflight.popleft()
                    req.future.set_exception(e)
                return
            now = self._clock()
            if self._tel.enabled:
                self._m_epoch.set(self._index.epoch)
            wait_s = 0.0
            while self._mut_inflight:
                req, pending, epoch = self._mut_inflight.popleft()
                wait_s += now - req.t_submit
                if self._tel.enabled:
                    self._tel.record_duration(
                        "serve.mutation_queue", now - req.t_submit,
                        attach=False)
                req.future.set_result(ServeMutationResult(
                    report=pending.result(), epoch=epoch,
                    queue_s=now - req.t_submit))
            sp.set(wait_ms=wait_s * 1e3)

    def _resolve_searches(self, dispatched: list) -> None:
        for chunk, res, epoch, t0, span in dispatched:
            total = sum(r.queries.shape[0] for r in chunk)
            counted = res.live_entries is not None
            with self._tel.span(
                    "serve.resolve", rows=total, padded_to=res.padded_to,
                    grid_steps=res.grid_steps if counted else None) as sp:
                try:
                    with self._tel.span("serve.resolve.wait"):
                        jax.block_until_ready(res.distances)
                    with self._tel.span("serve.resolve.fetch"):
                        d = np.asarray(res.distances)
                        labels = np.asarray(res.labels)
                        if counted and profiling():
                            sp.set(live_steps=self._live_steps(res, total))
                except Exception as e:
                    self._tel.finish_span(span)
                    for r in chunk:
                        r.future.set_exception(e)
                    continue
                t1 = self._clock()
                self._tel.finish_span(span)  # tile wall time ~= service_s
                off = 0
                for r in chunk:
                    nq = r.queries.shape[0]
                    if self._tel.enabled:
                        self._tel.record_duration(
                            "serve.queue", t0 - r.t_submit, attach=False)
                    r.future.set_result(ServeSearchResult(
                        distances=d[off:off + nq],
                        labels=labels[off:off + nq],
                        k=res.k, nprobe=res.nprobe, epoch=epoch,
                        coalesced=total, padded_to=res.padded_to,
                        queue_s=t0 - r.t_submit, service_s=t1 - t0))
                    off += nq

    @staticmethod
    def _live_steps(res, rows: int) -> int:
        """Grid steps of the tile's scan that do real work: the non-empty
        slab-table entries of its ``rows`` live query rows (a device ->
        host copy, so only while a profiler records)."""
        return int(np.asarray(res.live_entries)[:rows].sum())

    # -- lifecycle -----------------------------------------------------------

    def pause(self) -> None:
        """Hold the scheduler after its current cycle: submits keep
        queueing (and hitting quota/queue bounds) but nothing dispatches
        until :meth:`resume`. Admission-control behavior under a stalled
        device becomes deterministic — that is what the backpressure
        tests (and a maintenance window) need."""
        self._gate.clear()

    def resume(self) -> None:
        with self._cv:
            self._gate.set()
            self._cv.notify_all()

    def close(self, drain: bool = True) -> None:
        """Stop the engine. ``drain=True`` (default) processes every queued
        request and flushes the deferred queue before returning — no
        future is left unresolved. ``drain=False`` fails queued requests
        with ``ENGINE_CLOSED`` (already-dispatched work still resolves)."""
        with self._cv:
            if self._closed:
                return
            self._closing = True
            dropped = []
            if not drain:
                dropped = list(self._queue)
                self._queue.clear()
            self._gate.set()                  # a paused engine still drains
            self._cv.notify_all()
        for r in dropped:
            r.future.set_exception(Backpressure(
                BackpressureKind.ENGINE_CLOSED, r.tenant,
                "engine closed before dispatch"))
        self._thread.join(timeout=120)
        if self._thread.is_alive():            # pragma: no cover - defensive
            raise RuntimeError("serve scheduler failed to drain")
        if self._index.pending_count:          # pragma: no cover - defensive
            self._index.flush()
        self._closed = True

    def __enter__(self) -> "ServeEngine":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.close(drain=exc_type is None)
        return False

    # -- introspection -------------------------------------------------------

    def compile_bound(self) -> int:
        """Upper bound on search executables for the traffic served so far:
        ``#pow2 query buckets up to the largest tile x #(k, nprobe,
        filter-structure)`` groups — filter *constants* never mint an
        executable, only distinct predicate shapes do."""
        max_tile = max(self._max_tile, self._index.min_bucket)
        buckets = len(self._index.bucket_shapes(max_tile))
        return buckets * max(1, len(self._kn_groups))

    def assert_bounded_compiles(self) -> tuple[int, int]:
        """Assert observed search executables <= :meth:`compile_bound`;
        returns ``(observed, bound)``. Shared jit caches mean handles with
        an equal (cfg, backend, impl, ...) tuple pool executables — use a
        fresh ``SIVFConfig`` to measure an engine in isolation."""
        observed = self._index.compile_stats()["search"]
        bound = self.compile_bound()
        if observed > bound:
            raise AssertionError(
                f"search executables {observed} exceed the coalescing bound "
                f"{bound} ({len(self._kn_groups)} (k, nprobe, filter) groups, max "
                f"tile {self._max_tile})")
        return observed, bound

    def telemetry(self) -> dict:
        """JSON-able telemetry snapshot (metrics + slow-query log) of the
        registry this engine records into — by default the served index's,
        so one snapshot covers tile roots, plan/prefetch/scan stages,
        cache/transfer counters and compile events."""
        self._index._note_compiles()
        return self._tel.snapshot()

    def render_prometheus(self) -> str:
        """Prometheus text exposition of the same registry."""
        self._index._note_compiles()
        return self._tel.render_prometheus()

    def stats(self) -> dict:
        """Serve-side counters + the index's own compile stats."""
        with self._cv:
            rejections = {
                tenant: {kind.value: n for kind, n in st.rejections.items()
                         if n}
                for tenant, st in self._tenants.items()}
            inflight = {tenant: st.inflight_searches
                        for tenant, st in self._tenants.items()}
            queued = len(self._queue)
        sizes = self._coalesce_sizes
        return {
            "epoch": self.epoch,
            "queued": queued,
            "searches": self._n_searches,
            "search_tiles": self._n_tiles,
            "coalesce_mean": round(float(np.mean(sizes)), 2) if sizes else 0,
            "coalesce_max": max(sizes, default=0),
            "mutations": self._n_mutations,
            "maintenance_passes": self._n_maintenance,
            "pending_mutations": self._index.pending_count,
            "inflight_searches": inflight,
            "rejections": rejections,
            "kn_groups": sorted(self._kn_groups, key=repr),
            "compiles": self._index.compile_stats(),
            "compile_bound": self.compile_bound(),
        }
