"""``sivf.Index`` — the unified streaming-session facade over SIVF backends.

The paper ships SIVF behind one mutable Faiss-style handle; this module is
that handle for the JAX reproduction. It folds the three parallel surfaces
(``core.index`` free functions, ``core.distributed.dist_*``, and the
baselines' ad-hoc signatures) into a single stateful session object:

    cfg = SIVFConfig(dim=64, n_lists=32, n_slabs=512)
    index = Index(cfg, centroids)                  # or backend=mesh
    report = index.add(vecs, ids)                  # -> MutationReport
    result = index.search(queries, k=10, nprobe=8) # -> SearchResult
    report = index.remove(ids)
    index.save(path); index = Index.load(path)

    with Index(cfg, centroids, deferred=True) as index:
        futs = [index.add(v, i) for v, i in stream]    # -> PendingReport
        reports = index.flush()                        # one sync, N reports

Design points (ISSUE 2, atomicity + deferral reworked in ISSUE 3):

  * **One code path over backends.** ``backend="single"`` wraps the
    batched kernels of ``core.index``; ``backend=<jax Mesh>`` wraps the
    shard-mapped builders of ``core.distributed``. The handle logic —
    batch bucketing, error decoding, report accounting — is identical for
    both; only the raw jitted op differs.
  * **Structured error reporting.** The core kernels accumulate sticky
    int error bits in ``state.error``; the handle converts them into a
    per-batch :class:`MutationReport` with a typed :class:`ErrorCode` and
    disjoint ``accepted`` / ``overwritten`` / ``rejected`` counts, then
    clears the handled bits so each report describes exactly one batch.
    ``strict=True`` (per handle or per call) raises
    :class:`MutationRejected` instead. Failed insert batches are
    *atomic*: ``POOL_EXHAUSTED`` / ``CHAIN_OVERFLOW`` leaves every
    previously-live id searchable with its old payload (the mesh backend
    applies this per shard, and the counts stay truthful under partial
    per-shard failure).
  * **Deferred reports.** ``Index(..., deferred=True)`` turns ``add`` /
    ``remove`` into fire-and-forget submits returning
    :class:`PendingReport` futures backed by on-device aux scalars; no
    host sync happens until :meth:`Index.flush` (or context-manager
    exit, or touching a future), so the device queue stays full between
    syncs. Resolution is one *packed* transfer per queue — every batch's
    scalars (and per-shard error vectors) concatenate into a single
    int32 array crossing in one ``jax.device_get`` — never one sync per
    future. Eager and deferred modes run the *same* jitted executables —
    deferral adds zero compilations.
  * **Serve-engine hooks** (ISSUE 6). :attr:`Index.epoch` counts
    mutation batches *dispatched* (each is an atomic on-device commit,
    so it is also the committed prefix a later search observes) and
    :attr:`Index.pending_count` exposes the deferred-queue depth;
    together with :meth:`flush` resolving futures oldest-first they are
    the contract ``repro.serve.sivf_engine.ServeEngine`` builds its
    coalescing scheduler and epoch-consistency guarantee on.
  * **Device-side padding.** Batches that arrive as ``jax.Array``s are
    padded to their bucket with ``jnp`` ops on the device; only host
    (numpy / list) inputs take the numpy padding path. Device-resident
    streams therefore never pay a device->host->device round trip per op.
  * **Bounded jit compilations under ragged streaming.** Live clients send
    arbitrary batch sizes; every batch is padded to the next power-of-two
    bucket (floor ``min_bucket``), so a stream whose batches span sizes
    ``[1, S]`` compiles at most ``log2(S / min_bucket) + 1`` add / remove /
    search executables. This is *measured*, not assumed:
    :meth:`Index.compile_stats` exposes the jit cache sizes and the tests
    assert the bound over 8+ distinct ragged sizes.
  * **Persistence** goes through ``checkpoint/manager.py`` (atomic,
    checksummed) plus a JSON sidecar holding the config, backend
    topology, and shard-routing rule, so :meth:`Index.load` can rebuild
    the handle.
  * **Elastic resharding** (ISSUE 5). A checkpoint saved on S shards
    loads onto *any* backend — S' shards or ``"single"`` — via
    ``core.distributed.reshard_state`` (rows re-route by
    ``id % n_shards'``; search results stay bit-identical), and
    :meth:`Index.reshard` does the same to a live handle in place. See
    docs/architecture.md and docs/checkpoint-format.md.
  * :class:`IndexProtocol` is the structural interface the baselines
    (``baselines/contiguous_ivf.py``, ``baselines/lsh.py``, ...) also
    implement, so benchmarks and examples drive every engine identically.

The old functional API (``core.insert/delete/search``, ``dist_*``) remains
importable and delegates to the same kernels; see README for the migration
map.
"""
from __future__ import annotations

import dataclasses
import enum
from functools import lru_cache, partial
from types import SimpleNamespace
from typing import Iterator, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.core import filters as flt
from repro.core import index as ix
from repro.core import pq as pqmod
from repro.core import quantizer
from repro.core.pq import PQConfig
from repro.core.state import (
    ERR_CHAIN_OVERFLOW,
    ERR_ID_RANGE,
    ERR_POOL_EXHAUSTED,
    SIVFConfig,
    SlabPoolState,
    clear_error as _clear_error,
    init_state,
)

_I32_MAX = jnp.iinfo(jnp.int32).max


# ---------------------------------------------------------------------------
# Report types
# ---------------------------------------------------------------------------

class ErrorCode(enum.IntFlag):
    """Typed view of the core kernels' sticky ``state.error`` bits."""

    NONE = 0
    POOL_EXHAUSTED = ERR_POOL_EXHAUSTED
    ID_RANGE = ERR_ID_RANGE
    CHAIN_OVERFLOW = ERR_CHAIN_OVERFLOW


@dataclasses.dataclass(frozen=True)
class MutationReport:
    """Per-batch admission report for :meth:`Index.add` / :meth:`Index.remove`.

    The three counts are disjoint and sum to ``requested``:

      * ``accepted``    — distinct new ids now live in the index;
      * ``overwritten`` — distinct ids that existed before the batch and
        whose payload was actually replaced (delete-then-insert
        semantics). Ids whose shard aborted are *not* counted here: a
        pool-exhausted / chain-overflow batch is atomic, so their old
        payload survives untouched;
      * ``rejected``    — everything else: rows superseded by a later
        duplicate in the same batch, ids outside ``[0, n_max)``, and all
        rows of an aborted (pool-exhausted / chain-overflow) batch —
        including ids that *would have been* overwritten, since the
        atomic abort left their old payloads live.

    All counts are measured from device state (live totals and address-
    table presence before/after), not inferred, so they stay truthful under
    partial per-shard failures on the mesh backend; ``shard_errors`` then
    carries each shard's own bits (``None`` on the single-device backend).
    """

    op: str                 # "add" | "remove"
    requested: int          # non-padding rows in the caller's batch
    accepted: int
    overwritten: int
    rejected: int
    errors: ErrorCode       # this batch's error bits (already cleared)
    n_live: int             # total live vectors after the batch
    padded_to: int          # bucket shape the batch was padded to
    shard_errors: tuple[ErrorCode, ...] | None = None  # mesh: per-shard bits

    @property
    def ok(self) -> bool:
        return self.errors == ErrorCode.NONE


class MutationRejected(RuntimeError):
    """Raised in strict mode when a batch reports any error bit.

    In deferred mode the raise happens at :meth:`Index.flush` (or context
    exit) — the whole pending queue still resolves first, so every
    :class:`PendingReport` is usable afterwards.
    """

    def __init__(self, report: MutationReport):
        super().__init__(
            f"{report.op} batch rejected: errors={report.errors!r} "
            f"accepted={report.accepted} overwritten={report.overwritten} "
            f"rejected={report.rejected} of requested={report.requested}")
        self.report = report


class MaintenanceAborted(RuntimeError):
    """Raised in strict mode when a maintenance op aborts atomically.

    The abort is clean by construction — every previously-live id stays
    searchable under the old list layout (old centroids included) — so
    catching this and retrying after evictions is always safe. Raised
    after every requested op has resolved, like :meth:`Index.flush`.
    """

    def __init__(self, report):
        super().__init__(
            f"maintenance {report.kind} on lists {report.lists} aborted: "
            f"error bits {report.errors:#x} ({report.rows} rows kept "
            f"under the old layout)")
        self.report = report


class PendingReport:
    """Future for a deferred :class:`MutationReport`.

    Returned by ``add`` / ``remove`` on a handle constructed with
    ``deferred=True``. The batch's counts live in on-device aux scalars
    until the owning :class:`Index` flushes; submitting costs no host
    sync. ``result()`` — or reading any :class:`MutationReport` attribute
    straight off the future — forces a flush of the *whole* pending queue
    (one sync resolves every outstanding future, oldest first).
    """

    __slots__ = ("_index", "_resolved")

    def __init__(self, index: "Index"):
        self._index = index
        self._resolved: MutationReport | None = None

    @property
    def done(self) -> bool:
        """True once the owning handle has flushed past this batch."""
        return self._resolved is not None

    def result(self) -> MutationReport:
        if self._resolved is None:
            self._index.flush()
        if self._resolved is None:      # pragma: no cover - defensive
            raise RuntimeError(
                "PendingReport still unresolved after flush() — its batch "
                "is no longer in the owning Index's pending queue")
        return self._resolved

    def __getattr__(self, name: str):
        # proxy MutationReport attributes (forces resolution)
        if name.startswith("_"):
            raise AttributeError(name)
        return getattr(self.result(), name)

    def __repr__(self) -> str:
        return (f"PendingReport({self._resolved!r})" if self.done
                else "PendingReport(<unresolved>)")


@dataclasses.dataclass(frozen=True)
class SearchResult:
    """Top-k result. Iterable as ``(distances, labels)`` for tuple-compat."""

    distances: jax.Array    # [Q, k] f32 (inf pads empty slots)
    labels: jax.Array       # [Q, k] int32 external ids (-1 pads)
    k: int
    nprobe: int
    padded_to: int          # query bucket the batch was padded to

    def __iter__(self) -> Iterator:
        return iter((self.distances, self.labels))


@runtime_checkable
class IndexProtocol(Protocol):
    """Structural interface every engine (SIVF + baselines) implements.

    ``benchmarks/`` and ``examples/streaming_rag.py`` drive all engines
    through this surface; engines without IVF probing accept and ignore
    ``nprobe``.
    """

    def add(self, vecs, ids) -> MutationReport: ...

    def remove(self, ids) -> MutationReport: ...

    def search(self, queries, k: int, nprobe: int | None = None
               ) -> SearchResult: ...

    def stats(self) -> dict: ...

    @property
    def n_live(self) -> int: ...


def report_from_counts(op: str, requested: int, accepted: int,
                       overwritten: int, n_live: int, padded_to: int,
                       errors: ErrorCode = ErrorCode.NONE) -> MutationReport:
    """Build a consistent report from host-side counts (baseline engines)."""
    accepted = max(int(accepted), 0)
    overwritten = max(int(overwritten), 0)
    return MutationReport(
        op=op, requested=int(requested), accepted=accepted,
        overwritten=overwritten,
        rejected=max(int(requested) - accepted - overwritten, 0),
        errors=errors, n_live=int(n_live), padded_to=int(padded_to))


# ---------------------------------------------------------------------------
# Traced accounting helpers (run inside the jitted mutation wrappers)
# ---------------------------------------------------------------------------

_ABORT_BITS = ERR_POOL_EXHAUSTED | ERR_CHAIN_OVERFLOW   # batch-atomic aborts


def _count_unique(ids: jax.Array, mask: jax.Array) -> jax.Array:
    """Number of distinct ids where ``mask`` holds (traced).

    Sorts on ``(~mask, id)`` — the mask is a second sort key, not a magic
    value — so a genuine id equal to ``INT32_MAX`` is still counted (the
    old sentinel encoding silently collapsed it into the masked-out run).
    """
    order = jnp.lexsort((ids, ~mask))       # masked-in rows first, id-sorted
    sm = mask[order]
    si = ids[order]
    first = jnp.concatenate([jnp.ones((1,), bool), si[1:] != si[:-1]])
    return jnp.sum((first & sm).astype(jnp.int32))


def _or_bits(err: jax.Array) -> jax.Array:
    """Bitwise-OR reduce error bits over any shape (per-shard arrays)."""
    acc = jnp.zeros((), jnp.int32)
    for bit in (ERR_POOL_EXHAUSTED, ERR_ID_RANGE, ERR_CHAIN_OVERFLOW):
        acc = acc | jnp.where(jnp.any((err & bit) != 0), bit, 0)
    return acc


_AUX_SCALARS = ("n_requested", "n_live_before", "errors", "n_live_after",
                "n_overwritten")


def _resolve_aux(auxes: list[dict]) -> list[dict]:
    """Sync a queue of device aux dicts in ONE device->host transfer.

    Every aux value is int32 (five scalars per batch, plus the mesh
    backend's per-shard error vector), so the whole queue packs into one
    flat device array: a single concatenate + a single explicit
    ``jax.device_get``, however long the queue. ``Index.flush`` resolving
    N deferred reports therefore costs one transfer, not 5N — and eager
    mode reuses the same path with a one-element queue.
    """
    if not auxes:
        return []
    chunks, spans, off = [], [], 0
    for a in auxes:
        se = a.get("shard_errors")
        n_se = 0 if se is None else int(se.shape[0])
        chunks.append(jnp.stack([a[k] for k in _AUX_SCALARS]))
        if se is not None:
            chunks.append(se.astype(jnp.int32).reshape(-1))
        spans.append((off, n_se))
        off += len(_AUX_SCALARS) + n_se
    flat = chunks[0] if len(chunks) == 1 else jnp.concatenate(chunks)
    host = np.asarray(jax.device_get(flat))
    out = []
    for off, n_se in spans:
        vals = host[off:off + len(_AUX_SCALARS) + n_se]
        d = dict(zip(_AUX_SCALARS, vals[:len(_AUX_SCALARS)].tolist()))
        if n_se:
            d["shard_errors"] = vals[len(_AUX_SCALARS):]
        out.append(d)
    return out


# ---------------------------------------------------------------------------
# Backend op factories (cached so handles with equal configs share jit
# caches — this is what keeps compile counts bounded across sessions)
# ---------------------------------------------------------------------------

@lru_cache(maxsize=None)
def _single_ops(cfg: SIVFConfig, impl: str, block_q: int,
                use_tables: bool | None) -> SimpleNamespace:
    """Jitted single-device insert/delete/search with report accounting.

    The aux dict returned next to the new state holds *device* scalars
    only — nothing syncs until the handle resolves a report (immediately
    in eager mode, at ``flush()`` in deferred mode).
    """

    def _presence(state, ids, valid):
        # mask before indexing: an out-of-range id must never read another
        # slot's occupancy (clipping used to alias it onto slot n_max-1,
        # misreporting it as an overwrite instead of a rejection)
        safe = jnp.where(valid, ids, 0)
        return valid & (state.att_slab[safe] >= 0)

    def _pre(state, ids):
        valid = (ids >= 0) & (ids < cfg.n_max)
        pb = _presence(state, ids, valid)
        aux = {"n_requested": jnp.sum((ids >= 0).astype(jnp.int32)),
               "n_live_before": state.n_live}
        return valid, pb, aux

    @partial(jax.jit, donate_argnums=(0,))
    def insert_fn(state, vecs, ids, attrs):
        valid, pb, aux = _pre(state, ids)
        lists = quantizer.assign(state.centroids, vecs.astype(cfg.dtype),
                                 cfg.metric)
        out = ix._insert_impl(cfg, _clear_error(state), vecs, ids, lists,
                              attrs=attrs, want_plan=cfg.tiered)
        st, plan = out if cfg.tiered else (out, None)
        aux["errors"] = _or_bits(st.error)
        aux["n_live_after"] = st.n_live
        # overwritten == present-before AND the batch committed; on an
        # atomic abort the old payload survives, so nothing is overwritten
        failed = (st.error & _ABORT_BITS) != 0
        aux["n_overwritten"] = _count_unique(ids, pb & ~failed)
        if cfg.tiered:     # commit plan rides along for the host-store replay
            return _clear_error(st), aux, plan
        return _clear_error(st), aux

    @partial(jax.jit, donate_argnums=(0,))
    def delete_fn(state, ids):
        _, _, aux = _pre(state, ids)
        st = ix._delete_impl(cfg, _clear_error(state), ids)
        aux["errors"] = _or_bits(st.error)
        aux["n_live_after"] = st.n_live
        aux["n_overwritten"] = jnp.zeros((), jnp.int32)
        return _clear_error(st), aux

    @partial(jax.jit, static_argnums=(2, 3, 4))
    def search_fn(state, queries, k, nprobe, fstruct, fconsts):
        return ix._search_impl(cfg, state, queries, k, nprobe, use_tables,
                               impl, block_q, fstruct=fstruct,
                               fconsts=fconsts)

    return SimpleNamespace(insert=insert_fn, delete=delete_fn,
                           search=search_fn, n_shards=1)


@lru_cache(maxsize=None)
def _mesh_ops(cfg: SIVFConfig, mesh: Mesh, axis: str, impl: str,
              block_q: int, use_tables: bool | None) -> SimpleNamespace:
    """Jitted shard_map insert/delete/search over a stacked sharded state.

    Same aux contract as :func:`_single_ops` (device scalars, deferred-
    friendly) plus ``shard_errors``: the per-shard error vector, so a
    report can say *which* shard aborted. Inserts are atomic per shard —
    ids owned by an aborting shard keep their old payloads and are counted
    rejected, ids on committing shards proceed normally.
    """
    from repro.core import distributed as dist
    n = mesh.shape[axis]
    raw_insert = dist.sharded_insert(cfg, mesh, axis, want_plan=cfg.tiered)
    raw_delete = dist.sharded_delete(cfg, mesh, axis)
    raw_search = dist.sharded_search(cfg, mesh, axis, impl, block_q,
                                     use_tables)

    def sharded(st):
        # keep every leaf on the shard axis: XLA would otherwise return the
        # zero-width planes and the fresh error vector replicated, and the
        # next call would compile again for the changed input shardings
        return jax.tree.map(lambda x: jax.lax.with_sharding_constraint(
            x, dist.stack_sharding(x, mesh, axis)), st)

    def replicated(x):
        # the aux scalars are replicated; a sharded [S] vector beside them
        # cannot be packed into _resolve_aux's one transfer
        return jax.sharding.reshard(x, NamedSharding(mesh, P()))

    def _presence(state, ids, valid):
        # an id lives only on its owner shard: gather that shard's ATT row
        # (mask before indexing — see the single-backend note)
        safe = jnp.where(valid, ids, 0)
        owner = jnp.where(valid, ids % n, 0)
        return valid & (state.att_slab[owner, safe] >= 0)

    def _pre(state, ids):
        valid = (ids >= 0) & (ids < cfg.n_max)
        pb = _presence(state, ids, valid)
        aux = {"n_requested": jnp.sum((ids >= 0).astype(jnp.int32)),
               "n_live_before": jnp.sum(state.n_live)}
        return valid, pb, aux

    @partial(jax.jit, donate_argnums=(0,))
    def insert_fn(state, vecs, ids, attrs):
        valid, pb, aux = _pre(state, ids)
        out = raw_insert(_clear_error(state), vecs, ids, attrs)
        st, plan = out if cfg.tiered else (out, None)
        aux["errors"] = _or_bits(st.error)
        aux["shard_errors"] = replicated(st.error)           # [S] bits
        aux["n_live_after"] = jnp.sum(st.n_live)
        # partial per-shard failure: only ids on committing shards count
        # as overwritten — an aborting shard restored its old payloads
        shard_failed = (st.error & _ABORT_BITS) != 0         # [S]
        failed = shard_failed[jnp.where(valid, ids % n, 0)]
        aux["n_overwritten"] = _count_unique(ids, pb & ~failed)
        if cfg.tiered:     # stacked [S, B] plan for the per-shard replay
            return sharded(_clear_error(st)), aux, plan
        return sharded(_clear_error(st)), aux

    @partial(jax.jit, donate_argnums=(0,))
    def delete_fn(state, ids):
        _, _, aux = _pre(state, ids)
        st = raw_delete(_clear_error(state), ids)
        aux["errors"] = _or_bits(st.error)
        aux["shard_errors"] = replicated(st.error)
        aux["n_live_after"] = jnp.sum(st.n_live)
        aux["n_overwritten"] = jnp.zeros((), jnp.int32)
        return sharded(_clear_error(st)), aux

    @partial(jax.jit, static_argnums=(2, 3, 4))
    def search_fn(state, queries, k, nprobe, fstruct, fconsts):
        return raw_search(state, queries, k, nprobe, fstruct=fstruct,
                          fconsts=fconsts)

    return SimpleNamespace(insert=insert_fn, delete=delete_fn,
                           search=search_fn, n_shards=n)


# ---------------------------------------------------------------------------
# The handle
# ---------------------------------------------------------------------------

def _resolve_backend(backend, axis: str) -> tuple[str, int, object]:
    """Validate a backend spec -> (``"single"`` | ``"mesh"``, shard count,
    backend).

    The single point of truth for what a backend argument may be
    (:class:`Index` construction, :meth:`Index.load`,
    :meth:`Index.reshard` all accept the same forms) — a mesh must carry
    the index's data axis, anything else must be the literal ``"single"``.
    A mesh comes back over the same devices with ``Auto`` axis types:
    ``jax.make_mesh`` defaults to ``Explicit`` axes, under which the
    index's gathers that mix the sharded state with replicated ids would
    each need an explicit output sharding.
    """
    if isinstance(backend, Mesh):
        if axis not in backend.shape:
            raise ValueError(
                f"target mesh has no {axis!r} axis (axes: "
                f"{tuple(backend.shape)}); pass axis= or a mesh with the "
                f"index's data axis")
        auto = Mesh(backend.devices, backend.axis_names,
                    axis_types=(AxisType.Auto,) * len(backend.axis_names))
        return "mesh", int(backend.shape[axis]), auto
    if isinstance(backend, str) and backend == "single":
        return "single", 1, backend
    raise TypeError(
        f"backend must be 'single' or a jax Mesh, got {backend!r}")


class Index:
    """Stateful SIVF session handle; see module docstring for the contract.

    Parameters
    ----------
    cfg:        static :class:`SIVFConfig` (hashable; keys the jit caches).
    centroids:  ``[n_lists, dim]`` coarse-quantizer centroids.
    backend:    ``"single"`` (default) or a ``jax.sharding.Mesh`` whose
                ``axis`` dimension data-shards the index (paper §4.2).
    impl:       scan->top-k backend. ``None`` (default) picks it from the
                platform: the fused Pallas kernel ("pallas") on a TPU, the
                XLA reference ("xla") elsewhere. Name "xla", "pallas" or
                "pallas_interpret" (the kernel in the Pallas interpreter,
                for CPU tests) to force one; see :attr:`impl`.
    block_q:    fused kernel query-tile height.
    use_tables: dense-table vs pointer-walk slab lookup (None = cfg default).
    strict:     raise :class:`MutationRejected` on any per-batch error bit
                (in deferred mode the raise happens at :meth:`flush`).
    min_bucket: smallest padded batch shape; batches are padded to
                ``max(min_bucket, next_pow2(B))`` so ragged streams trigger
                a bounded number of jit compilations.
    deferred:   make ``add`` / ``remove`` return :class:`PendingReport`
                futures instead of syncing per batch; resolve them all with
                :meth:`flush` (the handle is a context manager that flushes
                on clean exit). Uses the same jitted executables as eager
                mode — deferral never adds compilations.
    pq_codebooks: pre-trained ``[m, ksub, dim//m]`` PQ codebooks (only with
                ``cfg.pq``); otherwise call :meth:`train` before the first
                ``add``. With PQ enabled, ingest encodes batches to uint8
                codes and search runs ADC over the compressed slabs.
    """

    def __init__(self, cfg: SIVFConfig, centroids, backend="single", *,
                 axis: str = "data", impl: str | None = None,
                 block_q: int = 8,
                 use_tables: bool | None = None, strict: bool = False,
                 min_bucket: int = 64, deferred: bool = False,
                 pq_codebooks=None, telemetry=None,
                 _state: SlabPoolState | None = None,
                 _pq_trained: bool | None = None):
        if min_bucket < 1:
            raise ValueError("min_bucket must be >= 1")
        if telemetry is None:
            from repro import obs
            telemetry = obs.default()
        self._telemetry = telemetry
        if pq_codebooks is not None and cfg.pq is None:
            raise ValueError("pq_codebooks given but cfg.pq is None")
        self.cfg = cfg
        self.strict = bool(strict)
        self.min_bucket = int(min_bucket)
        self.deferred = bool(deferred)
        self._pending: list[tuple[PendingReport, str, dict, int,
                                  bool | None]] = []
        self._epoch = 0
        self._axis = axis
        self._impl = impl
        self._block_q = int(block_q)
        self._use_tables = use_tables
        if pq_codebooks is not None:
            pq_codebooks = jnp.asarray(pq_codebooks, jnp.float32)
        self._backend_kind, _, backend = _resolve_backend(backend, axis)
        if self._backend_kind == "single":
            self._mesh = None
            self._ops = _single_ops(cfg, impl, self._block_q, use_tables)
            if _state is None:
                _state = init_state(cfg, jnp.asarray(centroids),
                                    pq_codebooks)
        else:
            from repro.core import distributed as dist
            self._mesh = backend
            self._ops = _mesh_ops(cfg, backend, axis, impl, self._block_q,
                                  use_tables)
            if _state is None:
                _state = dist.init_sharded_state(
                    cfg, jnp.asarray(centroids), backend, axis,
                    pq_codebooks)
        self._tiered = None
        if cfg.tiered:
            from repro.core import tiered as trt
            stores = None
            if trt.is_full_state(cfg, _state):
                # incoming full-pool state (load / reshard): split into the
                # host canonical store + a zero-width-payload device state
                meta, stores = trt.split_full(cfg, _state)
                if self._backend_kind == "mesh":
                    from repro.core import distributed as dist
                    _state = dist.place_sharded(meta, self._mesh, axis)
                else:
                    _state = jax.tree.map(jnp.asarray, meta)
            self._tiered = trt.TieredRuntime(
                cfg, self._backend_kind, mesh=self._mesh, axis=axis,
                impl=impl, block_q=self._block_q, use_tables=use_tables,
                n_shards=self._ops.n_shards, stores=stores,
                telemetry=self._telemetry)
        self._state = _state
        if _pq_trained is None:
            _pq_trained = cfg.pq is None or pq_codebooks is not None
        self._pq_trained = bool(_pq_trained)
        # jit-compile observability: executables existing at construction
        # (lru_cached op sets are shared between same-keyed handles) are
        # the baseline; _note_compiles() turns later growth into counter
        # events so a compile storm is visible in a scrape, not just tests
        self._m_compiles = self._telemetry.counter(
            "sivf_jit_compile_events_total",
            "new jit executables observed since handle construction")
        self._m_executables = self._telemetry.gauge(
            "sivf_jit_executables",
            "current executable count across this handle's op set")
        self._m_mutations = self._telemetry.counter(
            "sivf_index_mutation_rows_total",
            "mutation rows dispatched through this handle", ("op",))
        self._m_maint = self._telemetry.counter(
            "sivf_maintenance_ops_total",
            "maintenance ops dispatched", ("kind", "outcome"))
        self._m_maint_rows = self._telemetry.counter(
            "sivf_maintenance_rows_total",
            "live rows moved by committed maintenance ops")
        self._maint_cursor = 0      # round-robin recluster position
        self._compiles_seen = self._total_compiles()
        self._compile_base = self._compiles_seen

    # -- introspection ------------------------------------------------------

    @property
    def backend(self) -> str:
        return self._backend_kind

    @property
    def n_shards(self) -> int:
        return self._ops.n_shards

    @property
    def impl(self) -> str:
        """The scan backend searches run on (``impl`` resolved for this
        platform by ``core.index.resolve_impl``)."""
        return ix.resolve_impl(self._impl)

    @property
    def state(self) -> SlabPoolState:
        """The underlying pytree (functional-API interop; treat read-only)."""
        return self._state

    @property
    def n_live(self) -> int:
        return int(jnp.sum(self._state.n_live))

    @property
    def epoch(self) -> int:
        """Mutation batches dispatched over this handle's lifetime.

        Bumps on every ``add`` / ``remove`` *dispatch* (eager or
        deferred) — device work executes in dispatch order and each
        batch commits atomically, so a search dispatched at epoch ``e``
        observes exactly the first ``e`` batches. The serve engine
        (``repro.serve.sivf_engine``) stamps results with this value to
        make search-during-ingest consistency checkable.
        """
        return self._epoch

    @property
    def pending_count(self) -> int:
        """Deferred mutation batches awaiting :meth:`flush` (0 if eager)."""
        return len(self._pending)

    def __len__(self) -> int:
        return self.n_live

    def stats(self) -> dict:
        """Occupancy/fragmentation report + handle/backend metadata."""
        s = ix.stats(self.cfg, self._state)
        s["backend"] = self._backend_kind
        s["n_shards"] = self.n_shards
        s["compiles"] = self.compile_stats()
        if self._tiered is not None:
            s.update(self._tiered.stats())
        else:
            # all-resident pool: every used slab is trivially "resident"
            s["tiered"] = False
            s["resident_slabs"] = s["slabs_used"]
            s["hit_rate"] = 1.0
            s["hit_rate_kind"] = "cumulative"
        return s

    def compile_stats(self) -> dict:
        """Observed jit-executable counts for this handle's op set.

        Counters are shared between handles constructed with an identical
        (cfg, backend, impl, block_q, use_tables) tuple — that sharing is
        deliberate (sessions over the same index config reuse executables).
        Use a fresh ``SIVFConfig`` to measure in isolation.
        """
        def size(f):
            try:
                return int(f._cache_size())
            except Exception:               # pragma: no cover - private API
                return -1
        out = {"add": size(self._ops.insert),
               "remove": size(self._ops.delete),
               "search": size(self._ops.search)}
        if self._tiered is not None:
            # tiered searches run the plan + scan executables instead of
            # self._ops.search (whose count stays 0 on a tiered handle)
            out.update(self._tiered.compile_stats())
        return out

    def _total_compiles(self) -> int:
        return sum(v for v in self.compile_stats().values() if v > 0)

    def _note_compiles(self) -> None:
        """Fold executable-count growth into the telemetry registry
        (``sivf_jit_compile_events_total`` counts *new* executables since
        construction — the compile-storm alert signal)."""
        if not self._telemetry.enabled:
            return
        now = self._total_compiles()
        if now > self._compiles_seen:
            self._m_compiles.inc(now - self._compiles_seen)
        self._compiles_seen = max(self._compiles_seen, now)
        self._m_executables.set(now)

    def compile_events(self) -> int:
        """New jit executables observed since this handle was built (the
        value ``sivf_jit_compile_events_total`` accumulates)."""
        return max(self._total_compiles(), self._compiles_seen) \
            - self._compile_base

    def telemetry(self) -> dict:
        """JSON-able snapshot of this handle's telemetry (metrics +
        slow-query log). The handle records into the process default
        unless constructed with an explicit ``telemetry=``."""
        self._note_compiles()
        return self._telemetry.snapshot()

    # -- batch bucketing ----------------------------------------------------

    def _bucket(self, n: int) -> int:
        b = self.min_bucket
        while b < n:
            b *= 2
        return b

    def bucket_shapes(self, max_size: int) -> list[int]:
        """The bounded set of padded shapes for batches up to ``max_size``."""
        out = [self.min_bucket]
        while out[-1] < max_size:
            out.append(out[-1] * 2)
        return out

    def _pad_ids(self, ids, bucket: int) -> jax.Array:
        if isinstance(ids, jax.Array):       # device fast path: jnp pad, no
            if ids.shape[0] == bucket and ids.dtype == jnp.int32:
                return ids                   # bucket-aligned: zero device ops
            return jnp.pad(ids.astype(jnp.int32),    # host round trip
                           (0, bucket - ids.shape[0]), constant_values=-1)
        out = np.full((bucket,), -1, np.int32)
        out[: len(ids)] = ids
        return jnp.asarray(out)

    def _pad_rows(self, rows, bucket: int) -> jax.Array:
        if isinstance(rows, jax.Array):
            if rows.shape[0] == bucket and rows.dtype == jnp.float32:
                return rows                  # bucket-aligned: zero device ops
            return jnp.pad(rows.astype(jnp.float32),
                           ((0, bucket - rows.shape[0]), (0, 0)))
        out = np.zeros((bucket, self.cfg.dim), np.float32)
        out[: len(rows)] = rows
        return jnp.asarray(out)

    def _pad_attrs(self, attrs: np.ndarray, bucket: int) -> jax.Array:
        # padding rows carry zeros; their ids are -1 so they never commit
        out = np.zeros((bucket, self.cfg.n_attrs), np.int32)
        out[: len(attrs)] = attrs
        return jnp.asarray(out)

    @staticmethod
    def _as_batch(x, np_dtype, flat: bool = False):
        """Host inputs -> numpy; ``jax.Array`` inputs stay on device."""
        if isinstance(x, jax.Array):
            return x.reshape(-1) if flat else x
        x = np.asarray(x, np_dtype)
        return x.reshape(-1) if flat else x

    # -- PQ training --------------------------------------------------------

    def train(self, xs, *, key=None, iters: int = 16) -> "Index":
        """Train the PQ codebooks from a sample (``cfg.pq`` required).

        Runs per-subspace k-means (``core.pq.train_pq``) and installs the
        codebooks into the device state (replicated to every shard on the
        mesh backend). Must happen on an *empty* index — stored codes
        would go stale under new codebooks — and before the first ``add``;
        alternatively pass pre-trained ``pq_codebooks=`` at construction.
        Returns ``self`` for chaining.
        """
        if self.cfg.pq is None:
            raise RuntimeError("train() needs SIVFConfig(pq=PQConfig(...))")
        if self.n_live:
            raise RuntimeError(
                "train() on a non-empty index: stored codes would go stale "
                "under new codebooks — train before the first add()")
        key = jax.random.key(0) if key is None else key
        cb = pqmod.train_pq(key, jnp.asarray(xs, jnp.float32),
                            self.cfg.pq.m, self.cfg.pq.nbits, iters=iters)
        if self._backend_kind == "mesh":
            stacked = jnp.broadcast_to(cb, (self.n_shards,) + cb.shape)
            cb = jax.device_put(
                stacked, NamedSharding(self._mesh, P(self._axis)))
        self._state = dataclasses.replace(self._state, pq_codebooks=cb)
        self._pq_trained = True
        return self

    # -- mutation -----------------------------------------------------------

    def _require_trained(self) -> None:
        if not self._pq_trained:
            raise RuntimeError(
                "PQ codebooks are untrained: call Index.train(sample) or "
                "construct with pq_codebooks= before adding vectors")

    def add(self, vecs, ids, *, attrs=None, strict: bool | None = None
            ) -> "MutationReport | PendingReport":
        """Ingest a batch. ``vecs [B, D]``, ``ids [B]`` (-1 rows skipped).

        Re-adding a live id overwrites its payload (paper delete-then-insert
        semantics); within-batch duplicate ids keep the last row. A batch
        that hits ``POOL_EXHAUSTED`` / ``CHAIN_OVERFLOW`` is atomic: it
        inserts nothing and every previously-live id keeps its old payload
        (per shard on the mesh backend). Inputs that are already
        ``jax.Array``s are padded device-side. In deferred mode this
        returns a :class:`PendingReport` without any host sync.

        With ``SIVFConfig(attributes=...)`` configured, ``attrs`` is
        **required** — either a ``{name: value_or_column}`` dict or a
        ``[B, n_attrs]`` int array in config order. Every configured
        attribute must be supplied (missing names raise): silently
        defaulting an attribute like ``tenant`` to 0 would leak rows into
        tenant 0's filtered results. Without configured attributes,
        passing ``attrs`` raises.
        """
        self._require_trained()
        vecs = self._as_batch(vecs, np.float32)
        ids_a = self._as_batch(ids, np.int32, flat=True)
        if vecs.ndim != 2 or vecs.shape[0] != ids_a.shape[0]:
            raise ValueError(
                f"vecs {vecs.shape} / ids {ids_a.shape} mismatch")
        if vecs.shape[1] != self.cfg.dim:
            raise ValueError(f"dim {vecs.shape[1]} != cfg.dim {self.cfg.dim}")
        if self.cfg.n_attrs:
            if attrs is None:
                raise ValueError(
                    f"index has attributes {self.cfg.attributes}: add() "
                    f"requires attrs= for every row (dict of per-attribute "
                    f"values or a [B, {self.cfg.n_attrs}] int array)")
            attrs_np = flt.normalize_attrs(self.cfg.attributes, attrs,
                                           int(ids_a.shape[0]))
        elif attrs is not None:
            raise ValueError(
                "attrs= given but SIVFConfig(attributes=...) is empty")
        bucket = self._bucket(ids_a.shape[0])
        with self._telemetry.span("mutation.dispatch", root="auto",
                                  op="add", epoch=self._epoch + 1):
            pv = self._pad_rows(vecs, bucket)
            pa = self._pad_attrs(attrs_np, bucket) if self.cfg.n_attrs \
                else None
            if self._tiered is not None:
                self._state, aux, plan = self._ops.insert(
                    self._state, pv, self._pad_ids(ids_a, bucket), pa)
                # queue the commit plan for the host-store replay; host
                # inputs ride along as-is (no transfer at drain), device
                # inputs as the padded device rows (fetched with the plan
                # in one device_get)
                self._tiered.queue_plan(
                    plan, vecs if isinstance(vecs, np.ndarray) else pv,
                    attrs_np if self.cfg.n_attrs else None)
            else:
                self._state, aux = self._ops.insert(
                    self._state, pv, self._pad_ids(ids_a, bucket), pa)
        if self._telemetry.enabled:
            self._m_mutations.inc(int(ids_a.shape[0]), op="add")
        return self._emit("add", aux, bucket, strict)

    def remove(self, ids, *, strict: bool | None = None
               ) -> "MutationReport | PendingReport":
        """Evict a batch of ids in O(1); absent ids count as ``rejected``."""
        ids_a = self._as_batch(ids, np.int32, flat=True)
        bucket = self._bucket(ids_a.shape[0])
        with self._telemetry.span("mutation.dispatch", root="auto",
                                  op="remove", epoch=self._epoch + 1):
            self._state, aux = self._ops.delete(
                self._state, self._pad_ids(ids_a, bucket))
        if self._telemetry.enabled:
            self._m_mutations.inc(int(ids_a.shape[0]), op="remove")
        return self._emit("remove", aux, bucket, strict)

    def _emit(self, op: str, aux: dict, bucket: int, strict: bool | None):
        self._epoch += 1          # batch dispatched: the committed prefix
        if self.deferred:         # a later search observes grows by one
            fut = PendingReport(self)
            self._pending.append((fut, op, aux, bucket, strict))
            return fut
        return self._finalize(op, _resolve_aux([aux])[0], bucket,
                              self.strict if strict is None else strict)

    def _finalize(self, op: str, aux: dict, bucket: int, strict: bool
                  ) -> MutationReport:
        """Build a report from an already-host-synced aux dict
        (``_resolve_aux`` is the only sync point)."""
        requested = int(aux["n_requested"])
        n0 = int(aux["n_live_before"])
        n1 = int(aux["n_live_after"])
        errors = ErrorCode(int(aux["errors"]))
        if op == "add":
            # overwrites are live-count-neutral and aborted shards restore
            # their state, so the net live delta is exactly the new ids
            overwritten = int(aux["n_overwritten"])
            accepted = max(n1 - n0, 0)
        else:
            overwritten = 0
            accepted = max(n0 - n1, 0)
        se = aux.get("shard_errors")
        report = MutationReport(
            op=op, requested=requested, accepted=accepted,
            overwritten=overwritten,
            rejected=max(requested - accepted - overwritten, 0),
            errors=errors, n_live=n1, padded_to=bucket,
            shard_errors=None if se is None else tuple(
                ErrorCode(int(e)) for e in np.asarray(se)))
        if strict and not report.ok:
            raise MutationRejected(report)
        return report

    def flush(self) -> list[MutationReport]:
        """Resolve every outstanding :class:`PendingReport`, oldest first.

        One host sync for the whole queue: every batch's aux scalars (and
        the mesh backend's per-shard error vectors) stack into a single
        flat int32 array and cross device->host in one ``jax.device_get``
        (``_resolve_aux``), however long the queue. In strict mode the
        first failed report raises :class:`MutationRejected` — after the
        entire queue has resolved, so no future is left dangling. No-op
        (``[]``) when nothing is pending.
        """
        pending, self._pending = self._pending, []
        with self._telemetry.span("mutation.flush", root="auto",
                                  batches=len(pending), epoch=self._epoch):
            if self._tiered is not None:  # host store catches up at the
                self._tiered.drain_plans()  # sync point reports resolve at
            reports: list[MutationReport] = []
            first_err: MutationRejected | None = None
            k = 0
            try:
                host_auxes = _resolve_aux([a for _, _, a, _, _ in pending])
                for k, (fut, op, _, bucket, strict) in enumerate(pending):
                    strict = self.strict if strict is None else strict
                    try:
                        rep = self._finalize(op, host_auxes[k], bucket,
                                             strict)
                    except MutationRejected as e:
                        rep = e.report
                        if first_err is None:
                            first_err = e
                    fut._resolved = rep
                    reports.append(rep)
            except BaseException:
                # an unexpected error (device failure, interrupt) mid-queue:
                # re-queue the unresolved tail so no future is orphaned
                self._pending = pending[k:] + self._pending
                raise
        self._note_compiles()
        if first_err is not None:
            raise first_err
        return reports

    def maintain(self, ops=None, *, max_ops: int = 2,
                 strict: bool | None = None) -> list:
        """Run background maintenance ops (``core/maintenance.py``).

        ``ops`` is a list of :class:`~repro.core.maintenance.MaintOp`
        (``split`` / ``merge`` / ``recluster``); omitted, the drift
        policy plans up to ``max_ops`` ops from the per-list occupancy
        counters in :meth:`stats`, round-robining re-clustering across
        sweeps. Each op commits atomically through the staged-insert
        path — on the mesh backend all shards revert together if any
        aborts — so a failed op leaves every live id searchable under
        the old layout and bumps no epoch. Committed ops bump
        :attr:`epoch` exactly like a mutation batch: a search dispatched
        afterwards observes the whole new layout, never a hybrid.

        Returns the per-op :class:`MaintenanceReport` list. In strict
        mode (``strict=True`` or the handle default) an aborted op
        raises :class:`MaintenanceAborted` after every op has resolved.
        """
        from repro.core import maintenance as mt
        self._require_trained()
        if self._tiered is not None:
            self._tiered.drain_plans()      # host store current pre-gather
        if ops is None:
            occ = self.stats()["list_occupancy"]
            ops, self._maint_cursor = mt.plan_ops(
                occ, self._maint_cursor, max_ops=max_ops)
        strict = self.strict if strict is None else strict
        stores = None if self._tiered is None else self._tiered.stores
        want_plan = self._tiered is not None
        reports: list[mt.MaintenanceReport] = []
        first_abort: mt.MaintenanceReport | None = None
        for op in ops:
            with self._telemetry.span("maintenance.op", root="auto",
                                      kind=op.kind, lists=list(op.lists),
                                      epoch=self._epoch + 1):
                views = mt.shard_views(self.cfg, self._state, stores)
                gathered = mt.gather_live(self.cfg, self._state, views,
                                          op.lists)
                cents = np.asarray(self._state.centroids, np.float32)
                if cents.ndim == 3:         # stacked per-shard replicas
                    cents = cents[0]
                plan = mt.plan_op(self.cfg, op, gathered, cents)
                if plan is None:            # nothing to move: host no-op
                    reports.append(mt.MaintenanceReport(
                        op.kind, op.lists, len(gathered["ids"]), True, 0,
                        self.n_live))
                    continue
                new_cents, lists = plan
                batch = mt.pad_batch(
                    self.cfg, gathered, lists,
                    mt.maint_batch_size(self.cfg, self.n_shards))
                if self._backend_kind == "mesh":
                    run = mt._commit_op_mesh(self.cfg, self._mesh,
                                             self._axis, want_plan)
                else:
                    run = mt._commit_op(self.cfg, want_plan)
                args = (self._state, jnp.asarray(new_cents),
                        jnp.asarray(batch["vecs"]),
                        jnp.asarray(batch["ids"]),
                        jnp.asarray(batch["lists"]),
                        None if batch["codes"] is None
                        else jnp.asarray(batch["codes"]),
                        None if batch["attrs"] is None
                        else jnp.asarray(batch["attrs"]))
                if want_plan:
                    self._state, aux, dev_plan = run(*args)
                else:
                    self._state, aux = run(*args)
                aux = {k: v for k, v in aux.items() if k != "shard_errors"}
                aux = jax.device_get(aux)
                committed = bool(int(aux["committed"]))
                if want_plan:
                    if committed:
                        self._tiered.queue_plan(
                            dev_plan, batch["vecs"],
                            batch["attrs"] if self.cfg.n_attrs else None)
                        self._tiered.drain_plans()
                    # centroid updates replicate into future prefetch
                    # plans automatically (they read self._state)
                rep = mt.MaintenanceReport(
                    op.kind, op.lists, batch["rows"], committed,
                    int(aux["errors"]), int(aux["n_live"]))
            if committed:
                self._epoch += 1            # a new committed prefix entry
                if self._telemetry.enabled:
                    self._m_maint_rows.inc(rep.rows)
            elif first_abort is None:
                first_abort = rep
            if self._telemetry.enabled:
                self._m_maint.inc(1, kind=op.kind,
                                  outcome="committed" if committed
                                  else "aborted")
            reports.append(rep)
        self._note_compiles()
        if strict and first_abort is not None:
            raise MaintenanceAborted(first_abort)
        return reports

    def __enter__(self) -> "Index":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc_type is None:
            self.flush()
        return False

    # -- search -------------------------------------------------------------

    def search(self, queries, k: int, nprobe: int | None = None, *,
               filter=None, _prefetched=None) -> SearchResult:
        """Top-k search; ``nprobe=None`` probes every list (exact recall).

        ``jax.Array`` queries are padded device-side (no host round trip).

        ``filter`` is a :mod:`repro.core.filters` predicate (``Eq`` /
        ``In`` / ``Range`` / ``And``) over the configured attributes — or
        an already-:func:`~repro.core.filters.compile_filter`-ed
        ``CompiledFilter`` (the serve engine pre-compiles to coalesce).
        Only rows matching it can appear in the result (non-matching slots
        mask to ``inf`` / ``-1`` *inside* the scan, before top-k, so they
        never displace passing candidates). The predicate *structure* is a
        static jit key while its constants are traced operands — searching
        ``Eq("tenant", 3)`` then ``Eq("tenant", 7)`` compiles once.
        """
        queries = self._as_batch(queries, np.float32)
        if queries.ndim == 1:
            queries = queries[None]
        if queries.shape[1] != self.cfg.dim:
            raise ValueError(
                f"dim {queries.shape[1]} != cfg.dim {self.cfg.dim}")
        fstruct = fconsts = None
        if filter is not None:
            if not self.cfg.n_attrs:
                raise ValueError(
                    "filtered search needs SIVFConfig(attributes=...)")
            cf = filter if isinstance(filter, flt.CompiledFilter) \
                else flt.compile_filter(filter, self.cfg.attributes)
            fstruct = cf.structure
            fconsts = jnp.asarray(cf.consts, jnp.int32)
        nprobe = self.cfg.n_lists if nprobe is None \
            else min(int(nprobe), self.cfg.n_lists)
        q = queries.shape[0]
        bucket = self._bucket(q)
        padded = self._pad_rows(queries, bucket)
        with self._telemetry.span("index.search", root="auto",
                                  epoch=self._epoch,
                                  filter=None if fstruct is None
                                  else str(fstruct)):
            if self._tiered is not None:
                # three-stage tiered path: plan (probe->slab table),
                # prefetch (make probed slabs cache-resident), frame-
                # translated scan. A valid ``_prefetched`` ticket
                # (Index.prefetch) skips the first two stages; a stale one
                # falls back transparently.
                d, lab = self._tiered.search(
                    self._state, padded, int(k), nprobe, fstruct, fconsts,
                    epoch=self._epoch, ticket=_prefetched)
            else:
                d, lab = self._ops.search(self._state, padded, int(k),
                                          nprobe, fstruct, fconsts)
        self._note_compiles()
        return SearchResult(distances=d[:q], labels=lab[:q], k=int(k),
                            nprobe=nprobe, padded_to=bucket)

    def prefetch(self, queries, nprobe: int | None = None):
        """Stage the slabs a coming query batch will probe (tiered only).

        Runs the plan + prefetch stages of the tiered search and returns
        an opaque ticket for ``search(..., _prefetched=ticket)``, letting
        a scheduler overlap the next tile's host->device uploads with the
        current tile's kernel execution (the serve engine does exactly
        this). The ticket is valid until the next prefetch or mutation;
        passing a stale ticket — or calling with the same queries and no
        ticket at all — is always safe, merely un-overlapped. Returns
        ``None`` on an untiered handle.
        """
        if self._tiered is None:
            return None
        queries = self._as_batch(queries, np.float32)
        if queries.ndim == 1:
            queries = queries[None]
        nprobe = self.cfg.n_lists if nprobe is None \
            else min(int(nprobe), self.cfg.n_lists)
        padded = self._pad_rows(queries, self._bucket(queries.shape[0]))
        table = self._tiered.plan(self._state, padded, nprobe)
        return self._tiered.prefetch(table, nprobe, self._epoch)

    # -- persistence --------------------------------------------------------

    _META = "index"

    def save(self, path) -> None:
        """Persist the index (atomic + checksummed via CheckpointManager)."""
        from repro.checkpoint.manager import CheckpointManager
        mgr = CheckpointManager(path, keep_last=1)
        cfg = dataclasses.asdict(self.cfg)   # nested PQConfig -> plain dict
        cfg["dtype"] = np.dtype(self.cfg.dtype).name
        mgr.save_metadata(self._META, {
            "format": 3,
            "pq_trained": self._pq_trained,
            "backend": self._backend_kind,
            "n_shards": self.n_shards,
            # self-describing shard routing: any loader (this class, or a
            # future external tool) can re-route rows onto a different
            # shard count knowing only the sidecar
            "routing": {"rule": "mod", "n_shards": self.n_shards,
                        "axis": self._axis},
            "axis": self._axis,
            "impl": self._impl,
            "block_q": self._block_q,
            "use_tables": self._use_tables,
            "strict": self.strict,
            "min_bucket": self.min_bucket,
            "deferred": self.deferred,
            "cfg": cfg,
        })
        state = self._state
        if self._tiered is not None:
            # residency is runtime-only: checkpoints always store the
            # assembled full-pool planes, so the on-disk format (3) is
            # identical to an untiered save and loads onto either mode
            from repro.core import tiered as trt
            self._tiered.drain_plans()
            state = trt.assemble_full(self.cfg, self._state,
                                      self._tiered.stores)
        mgr.save(0, state)

    @classmethod
    def load(cls, path, backend=None, **overrides) -> "Index":
        """Rebuild a handle from :meth:`save` output — onto *any* backend.

        Loading is **elastic**: a checkpoint saved on S shards loads onto
        S' shards (grow, shrink, mesh->single, single->mesh). When the
        target topology matches the checkpoint, leaves restore directly
        onto their devices; otherwise the slab pools are flattened to the
        canonical live-row table and re-routed by ``id % n_shards'``
        (``core.distributed.reshard_state``) — searches return identical
        ids and distances either way, and later inserts land on the owning
        shard.

        ``backend`` is a ``jax.sharding.Mesh`` or ``"single"``. Defaults:
        a single-device checkpoint loads as ``"single"``; a sharded
        checkpoint requires an explicit target (pass ``"single"`` to
        collapse the shards onto one device). Keyword ``overrides``
        replace any saved handle option (impl, strict, ...).
        """
        from repro.checkpoint.manager import CheckpointManager
        from repro.core import distributed as dist
        mgr = CheckpointManager(path)
        meta = mgr.load_metadata(cls._META)
        cfg_d = dict(meta["cfg"])
        cfg_d["dtype"] = jnp.dtype(cfg_d["dtype"])
        if cfg_d.get("pq") is not None:
            cfg_d["pq"] = PQConfig(**cfg_d["pq"])
        cfg = SIVFConfig(**cfg_d)
        if "device_slabs" in overrides:
            # retier on load: any checkpoint loads tiered (or back to
            # all-resident with device_slabs=None) — the stored planes are
            # the same full pool either way
            cfg = dataclasses.replace(
                cfg, device_slabs=overrides.pop("device_slabs"))
        kw = {"axis": meta["axis"], "impl": meta["impl"],
              "block_q": meta["block_q"], "use_tables": meta["use_tables"],
              "strict": meta["strict"], "min_bucket": meta["min_bucket"],
              "deferred": meta.get("deferred", False)}
        kw.update(overrides)
        src_kind = meta["backend"]
        src_shards = int(meta["n_shards"])
        # pre-routing checkpoints (PR 2-4) used the same implicit mod rule
        rule = meta.get("routing", {}).get("rule", "mod")
        if rule != "mod":
            raise ValueError(
                f"checkpoint uses unknown shard-routing rule {rule!r}; "
                f"this build can only re-route 'mod' checkpoints")
        if backend is None:
            if src_kind == "mesh":
                raise ValueError(
                    "sharded checkpoint: pass backend= — the target mesh, "
                    "or 'single' to collapse the shards onto one device")
            backend = "single"
        tgt_kind, n_to, backend = _resolve_backend(backend, kw["axis"])
        step = mgr.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoint steps under {path}")
        if cfg.tiered:
            # tiered target: the payload planes must never be device_put
            # whole, so always take the host restore path (an untiered
            # example tree — checkpoints store the full pool) and hand the
            # full host state to __init__, which splits it into the host
            # store + meta device state
            cfg_full = dataclasses.replace(cfg, device_slabs=None)
            example = jax.eval_shape(lambda: init_state(
                cfg_full, jnp.zeros((cfg.n_lists, cfg.dim), cfg.dtype)))
            if src_kind == "mesh":
                example = jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct((src_shards,) + x.shape,
                                                   x.dtype), example)
            leaves, treedef = jax.tree.flatten(example)
            n_miss = {1: 3, 2: 1}.get(int(meta.get("format", 1)), 0)
            out = mgr.restore_arrays(step)
            if n_miss:
                out = out + [np.zeros(x.shape, x.dtype)
                             for x in leaves[-n_miss:]]
            if len(out) != len(leaves):
                raise ValueError(
                    f"checkpoint stored {len(out)} leaves but the "
                    f"{src_shards}-shard state needs {len(leaves)}")
            host_state = jax.tree.unflatten(treedef, out)
            if not (tgt_kind == src_kind and n_to == src_shards):
                host_state = dist.reshard_state(cfg_full, host_state,
                                                src_shards, n_to,
                                                stack=tgt_kind == "mesh")
            return cls(cfg, None, backend=backend, _state=host_state,
                       _pq_trained=meta.get("pq_trained", True), **kw)
        # abstract example tree: restore needs only structure/shapes, so no
        # throwaway zero pool is ever allocated next to the restored one
        example = jax.eval_shape(lambda: init_state(
            cfg, jnp.zeros((cfg.n_lists, cfg.dim), cfg.dtype)))
        if src_kind == "mesh":
            example = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct((src_shards,) + x.shape,
                                               x.dtype), example)
        leaves, treedef = jax.tree.flatten(example)
        # older checkpoints predate trailing slab planes, which are by
        # design the LAST registered data fields so a legacy manifest
        # restores into the leaf prefix and the missing planes fill fresh:
        # format 1 lacks ``codes`` / ``pq_codebooks`` / ``attrs`` (all
        # zero-width: format 1 implies cfg.pq=None and no attributes),
        # format 2 lacks only ``attrs``
        n_miss = {1: 3, 2: 1}.get(int(meta.get("format", 1)), 0)
        if tgt_kind == src_kind and n_to == src_shards:
            # topology match: restore leaves straight onto their devices
            shards = None
            if tgt_kind == "mesh":
                shards = [dist.stack_sharding(x, backend, kw["axis"])
                          for x in leaves]
            want = leaves[:-n_miss] if n_miss else leaves
            out = list(mgr.restore(
                step, want,
                sharding_tree=None if shards is None
                else shards[:len(want)]))
            if n_miss:
                fill = [jnp.zeros(x.shape, x.dtype) for x in leaves[-n_miss:]]
                if shards is not None:
                    fill = [jax.device_put(f, sh) for f, sh in
                            zip(fill, shards[-n_miss:])]
                out += fill
            state = jax.tree.unflatten(treedef, out)
        else:
            # elastic reshard: manifest-described host restore, pure
            # re-route, then placement onto the target backend
            out = mgr.restore_arrays(step)
            if n_miss:
                out = out + [np.zeros(x.shape, x.dtype)
                             for x in leaves[-n_miss:]]
            if len(out) != len(leaves):
                raise ValueError(
                    f"checkpoint stored {len(out)} leaves but the "
                    f"{src_shards}-shard state needs {len(leaves)}")
            host_state = jax.tree.unflatten(treedef, out)
            state = dist.reshard_state(cfg, host_state, src_shards, n_to,
                                       stack=tgt_kind == "mesh")
            if tgt_kind == "mesh":
                state = dist.place_sharded(state, backend, kw["axis"])
        return cls(cfg, None, backend=backend, _state=state,
                   _pq_trained=meta.get("pq_trained", True), **kw)

    def reshard(self, backend="single", *, axis: str | None = None
                ) -> "Index":
        """Elastically remap this *live* handle onto a new backend in place.

        ``backend`` is a ``jax.sharding.Mesh`` (any shard count) or
        ``"single"``. Pending deferred reports are flushed first (their
        counts reference the pre-reshard shard topology), then the slab
        pools flatten to the canonical live-row table, re-route by
        ``id % n_shards'`` and rebuild on the target — the same pure
        ``core.distributed.reshard_state`` path :meth:`load` uses, so
        search results are identical before and after and subsequent
        mutations land on the owning shard. Returns ``self``.
        """
        with self._telemetry.span("reshard", root="auto",
                                  n_from=self.n_shards):
            return self._reshard_impl(backend, axis)

    def _reshard_impl(self, backend, axis):
        from repro.core import distributed as dist
        self.flush()
        axis = self._axis if axis is None else axis
        tgt_kind, n_to, backend = _resolve_backend(backend, axis)
        if self._tiered is not None:
            # assemble the canonical full pool (host planes + device
            # metadata) and reshard under the untiered twin config — the
            # reshard machinery only ever sees full-width states
            from repro.core import tiered as trt
            cfg_r = dataclasses.replace(self.cfg, device_slabs=None)
            host = trt.assemble_full(self.cfg, self._state,
                                     self._tiered.stores)
        else:
            cfg_r = self.cfg
            host = jax.tree.map(np.asarray, self._state)   # device -> host
        state = dist.reshard_state(cfg_r, host, self.n_shards, n_to,
                                   stack=tgt_kind == "mesh")
        stores = None
        if self._tiered is not None:
            meta, stores = trt.split_full(self.cfg, state)
            state = meta if tgt_kind == "mesh" \
                else jax.tree.map(jnp.asarray, meta)
        if tgt_kind == "mesh":
            state = dist.place_sharded(state, backend, axis)
            self._ops = _mesh_ops(self.cfg, backend, axis, self._impl,
                                  self._block_q, self._use_tables)
            self._mesh = backend
        else:
            self._ops = _single_ops(self.cfg, self._impl, self._block_q,
                                    self._use_tables)
            self._mesh = None
        self._backend_kind = tgt_kind
        self._axis = axis
        self._state = state
        if self._tiered is not None:
            from repro.core import tiered as trt
            # rebuild the runtime for the new topology but CARRY the
            # cumulative cache counters (and their window marks): before
            # ISSUE 9 a reshard silently zeroed hit_rate's history
            self._tiered = trt.TieredRuntime(
                self.cfg, tgt_kind, mesh=self._mesh, axis=axis,
                impl=self._impl, block_q=self._block_q,
                use_tables=self._use_tables, n_shards=n_to, stores=stores,
                telemetry=self._telemetry).carry_from(self._tiered)
        return self
