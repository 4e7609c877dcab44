"""Index maintenance under drift (ISSUE 10): split / merge / recluster.

Layers under test, matching the subsystem's structure:

  * op construction + the occupancy-driven ``plan_ops`` policy;
  * the functional core (``core.maintain``): every op commits atomically
    through the staged-insert path, never changes the live id set, and
    keeps full-probe search results layout-invariant;
  * kernel parity (shared scaffolding in tests/parity.py): search stays
    bit-identical across ``xla`` / ``pallas_interpret`` before AND after
    a maintenance pass, raw and PQ paths;
  * **atomicity acceptance**: an aborted op leaves every previously-live
    id searchable with its old payload, on the single backend, the
    1-shard mesh, and a true 2-shard mesh (subprocess) — and strict mode
    surfaces the abort as :class:`sivf.MaintenanceAborted` only after
    every op has resolved;
  * the session surface: ``stats()`` per-list occupancy/skew counters vs
    an independent host recount after overwrite-heavy churn (the
    regression satellite), tiered-store coherence, deferred handles, and
    mesh-vs-single report/search parity.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

import parity
import sivf
from repro import core
from repro.core import maintenance as mt
from repro.core import quantizer

REPO = Path(__file__).resolve().parent.parent

D, NL = 16, 4


# ---------------------------------------------------------------------------
# Op construction + policy
# ---------------------------------------------------------------------------

def test_maintop_validation():
    assert mt.split(0, 1).lists == (0, 1)
    assert mt.recluster(3).lists == (3,)
    with pytest.raises(ValueError, match="unknown maintenance kind"):
        mt.MaintOp("defrag", (0,))
    with pytest.raises(ValueError, match="takes 1 list"):
        mt.MaintOp("recluster", (0, 1))
    with pytest.raises(ValueError, match="takes 2 list"):
        mt.MaintOp("split", (0,))
    with pytest.raises(ValueError, match="distinct"):
        mt.merge(2, 2)


def test_plan_ops_split_on_skew():
    """Hot list > skew_hi*mean with a near-empty victim -> split first."""
    ops, _ = mt.plan_ops([300, 2, 2, 40], max_ops=2)
    assert ops[0] == mt.split(0, 1)


def test_plan_ops_merge_underfull():
    """Two under-full lists and no split candidate -> merge them."""
    ops, _ = mt.plan_ops([40, 2, 2, 40], max_ops=1)
    assert ops == [mt.merge(1, 2)]


def test_plan_ops_recluster_round_robin():
    """Balanced occupancy: the cursor walks every non-empty list across
    sweeps, so sustained drift recenters the whole index."""
    ops, cur = mt.plan_ops([5, 5, 5, 5], cursor=1, max_ops=2)
    assert ops == [mt.recluster(1), mt.recluster(2)] and cur == 3
    ops, cur = mt.plan_ops([5, 5, 5, 5], cursor=cur, max_ops=2)
    assert ops == [mt.recluster(3), mt.recluster(0)] and cur == 1


def test_plan_ops_empty_index_plans_nothing():
    ops, cur = mt.plan_ops([0, 0, 0, 0], cursor=2)
    assert ops == [] and cur == 2


# ---------------------------------------------------------------------------
# Functional core: live set preserved, layout-invariant full-probe search
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("op", [
    mt.recluster(0), mt.split(2, 1), mt.merge(0, 3),
], ids=["recluster", "split", "merge"])
def test_functional_op_preserves_live_set(rng, op):
    cfg, state = parity.make_state(rng)
    state, vecs, _ = parity.load_rows(cfg, state, rng, 200)
    before = int(state.n_live)
    state, rep = core.maintain(cfg, state, op)
    assert rep.committed and rep.errors == 0
    assert int(state.n_live) == before == rep.n_live
    # every id self-queries back at distance 0 (full probe)
    d, lab = core.search(cfg, state, vecs, 1, NL)
    assert (np.asarray(lab)[:, 0] == np.arange(200)).all()
    np.testing.assert_allclose(np.asarray(d)[:, 0], 0, atol=1e-4)


def test_merge_empties_the_source_list(rng):
    cfg, state = parity.make_state(rng)
    state, _, _ = parity.load_rows(cfg, state, rng, 200)
    occ0 = np.asarray(core.stats(cfg, state)["list_occupancy"])
    a, b = 0, 1
    state, rep = core.maintain(cfg, state, mt.merge(a, b))
    assert rep.committed
    occ1 = np.asarray(core.stats(cfg, state)["list_occupancy"])
    tgt = min(a, b)
    assert occ1[max(a, b)] == 0
    assert occ1[tgt] == occ0[a] + occ0[b]
    assert occ1.sum() == occ0.sum()


def test_split_rebalances_between_two_lists(rng):
    cfg, state = parity.make_state(rng)
    # pile everything into list 0 so the split has real skew to fix
    state, _, _ = parity.load_rows(cfg, state, rng, 150,
                                   lists=np.zeros((150,), np.int32))
    state, rep = core.maintain(cfg, state, mt.split(0, 1))
    assert rep.committed and rep.rows == 150
    occ = np.asarray(core.stats(cfg, state)["list_occupancy"])
    assert occ[0] > 0 and occ[1] > 0          # both halves populated
    assert occ[0] + occ[1] == 150


def test_maintenance_no_op_on_empty_lists(rng):
    """Ops over empty lists are host no-ops: committed, nothing moved,
    no device commit attempted."""
    cfg, state = parity.make_state(rng)
    state, _, _ = parity.load_rows(cfg, state, rng, 50,
                                   lists=np.zeros((50,), np.int32))
    state, rep = core.maintain(cfg, state, mt.merge(2, 3))
    assert rep.committed and rep.rows == 0 and rep.n_live == 50


# ---------------------------------------------------------------------------
# Kernel parity before vs after a maintenance pass (shared tests/parity.py)
# ---------------------------------------------------------------------------

@pytest.mark.pallas
def test_search_parity_before_and_after_maintenance(rng):
    """xla == pallas_interpret before AND after a maintenance pass, and
    the full-probe result set is identical across the pass (maintenance
    moves rows between lists; it must never change what a search
    returns)."""
    cfg, state = parity.make_state(rng)
    state, _, _ = parity.load_rows(cfg, state, rng, 200)
    qs = rng.normal(size=(5, D)).astype(np.float32)
    d0, l0 = parity.assert_search_parity(cfg, state, rng, k=8, nprobe=NL,
                                         queries=qs)
    for op in (mt.recluster(0), mt.split(1, 2), mt.merge(0, 3)):
        state, rep = core.maintain(cfg, state, op)
        assert rep.committed
    d1, l1 = parity.assert_search_parity(cfg, state, rng, k=8, nprobe=NL,
                                         queries=qs)
    assert (l0 == l1).all()
    np.testing.assert_allclose(d0, d1, rtol=1e-5, atol=1e-5)


@pytest.mark.pallas
def test_search_parity_after_maintenance_pq_bit_exact(rng):
    """Same pass on the compressed pool: moved rows' codes ride the
    re-insert verbatim, so ADC results are bit-exact across the pass AND
    across impls."""
    cfg, state = parity.make_state(rng, pq=core.PQConfig(m=4, nbits=4))
    state, _, _ = parity.load_rows(cfg, state, rng, 200)
    qs = rng.normal(size=(5, D)).astype(np.float32)
    d0, l0 = parity.assert_search_parity(cfg, state, rng, k=8, nprobe=NL,
                                         queries=qs)
    for op in (mt.split(0, 3), mt.recluster(1)):
        state, rep = core.maintain(cfg, state, op)
        assert rep.committed
    d1, l1 = parity.assert_search_parity(cfg, state, rng, k=8, nprobe=NL,
                                         queries=qs)
    assert (l0 == l1).all() and (d0 == d1).all()


@pytest.mark.pallas
def test_search_parity_after_maintenance_filtered(rng):
    """Attribute stamps ride the re-insert verbatim: filtered parity and
    the filtered result set survive a maintenance pass."""
    from repro.core import filters as flt
    cfg, state = parity.make_state(rng, attributes=("tenant", "ts"))
    state, _, _ = parity.load_rows(cfg, state, rng, 200)
    pred = flt.And(flt.Eq("tenant", 1), flt.Range("ts", 0, 60))
    qs = rng.normal(size=(4, D)).astype(np.float32)
    d0, l0 = parity.assert_search_parity(cfg, state, rng, k=7, nprobe=NL,
                                         queries=qs, pred=pred)
    state, rep = core.maintain(cfg, state, mt.split(0, 1))
    assert rep.committed
    d1, l1 = parity.assert_search_parity(cfg, state, rng, k=7, nprobe=NL,
                                         queries=qs, pred=pred)
    assert (l0 == l1).all()
    np.testing.assert_allclose(d0, d1, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Atomicity acceptance: aborted ops leave every live id searchable
# ---------------------------------------------------------------------------

_TIGHT = dict(dim=D, n_lists=NL, n_slabs=12, capacity=32, n_max=2048,
              max_chain=2)


def _tight_index(rng, backend="single"):
    """A pool whose 2-slab chain bound makes merge(0, 1) of exactly 100
    rows provably overflow: the commit must abort and revert atomically.
    Rows are drawn tightly around well-separated centroids so routing
    (and therefore the 50-rows-per-list setup) is deterministic."""
    cfg = sivf.SIVFConfig(**_TIGHT)
    cents = (rng.normal(size=(NL, D)) * 4.0).astype(np.float32)
    idx = sivf.Index(cfg, cents, backend=backend, min_bucket=8)
    vecs = (cents[np.arange(200) % NL] +
            0.1 * rng.normal(size=(200, D))).astype(np.float32)
    assert idx.add(vecs, np.arange(200, dtype=np.int32)).ok
    return idx, vecs


def _assert_all_live_searchable(idx, vecs):
    d, lab = idx.search(vecs, 1, NL)
    assert (np.asarray(lab)[:, 0] == np.arange(len(vecs))).all()
    np.testing.assert_allclose(np.asarray(d)[:, 0], 0, atol=1e-4)


@pytest.mark.parametrize("backend_name", ["single", "mesh"])
def test_aborted_op_atomic(rng, backend_name):
    """ISSUE 10 acceptance: after an aborted maintenance op every
    previously-live id is still searchable with its old payload, the
    centroids are byte-identical, and no epoch was consumed."""
    backend = "single" if backend_name == "single" \
        else jax.make_mesh((1,), ("data",))
    idx, vecs = _tight_index(rng, backend)
    cents_before = np.asarray(idx.state.centroids).copy()
    epoch_before = idx.epoch
    rep = idx.maintain(ops=[mt.merge(0, 1)], strict=False)[0]
    assert not rep.committed
    assert rep.errors & mt.ABORT_BITS
    assert (np.asarray(idx.state.centroids) == cents_before).all()
    assert idx.epoch == epoch_before
    assert idx.n_live == 200
    _assert_all_live_searchable(idx, vecs)
    # the pool still ingests after the abort (free stack fully restored)
    more = np.random.default_rng(3).normal(size=(8, D)).astype(np.float32)
    assert idx.add(more, np.arange(300, 308, dtype=np.int32)).ok


def test_strict_mode_raises_after_all_ops_resolve(rng):
    idx, vecs = _tight_index(rng)
    with pytest.raises(sivf.MaintenanceAborted) as ei:
        # the committed recluster AFTER the aborted merge must still run
        idx.maintain(ops=[mt.merge(0, 1), mt.recluster(2)], strict=True)
    assert ei.value.report.kind == "merge"
    assert not ei.value.report.committed
    _assert_all_live_searchable(idx, vecs)


_MESH2_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import json
import numpy as np, jax
import sivf
from repro.core import maintenance as mt

rng = np.random.default_rng(7)
D, NL = 16, 4
cfg = sivf.SIVFConfig(dim=D, n_lists=NL, n_slabs=12, capacity=32,
                      n_max=2048, max_chain=2)
cents = (rng.normal(size=(NL, D)) * 4.0).astype(np.float32)
mesh = jax.make_mesh((2,), ("data",))
idx = sivf.Index(cfg, cents, backend=mesh, min_bucket=8)
# lists 0/1 get 80 rows each (~40 per shard): merge(0, 1) must overflow
# the 64-row per-shard chain bound on BOTH shards however rows shard
pattern = rng.permuted(np.repeat([0, 1, 2, 3], [80, 80, 20, 20]))
vecs = (cents[pattern] + 0.1 * rng.normal(size=(200, D))).astype(np.float32)
assert idx.add(vecs, np.arange(200, dtype=np.int32)).ok
rep = idx.maintain(ops=[mt.merge(0, 1)], strict=False)[0]
d, lab = idx.search(vecs, 1, NL)
ok_ids = bool((np.asarray(lab)[:, 0] == np.arange(200)).all())
ok_d = bool(np.allclose(np.asarray(d)[:, 0], 0, atol=1e-4))
cents2 = np.asarray(idx.state.centroids)          # [2, NL, D] stacked
rep2 = idx.maintain(ops=[mt.recluster(2)], strict=False)[0]
d2, lab2 = idx.search(vecs, 1, NL)
print(json.dumps({
    "aborted": not rep.committed, "errors": rep.errors,
    "ok_ids": ok_ids, "ok_d": ok_d,
    "cents_replicated": bool((cents2[0] == cents2[1]).all()),
    "recluster_committed": rep2.committed,
    "ok_after": bool((np.asarray(lab2)[:, 0] == np.arange(200)).all()),
}))
"""


def test_aborted_op_atomic_two_shard_mesh():
    """All shards vote: one shard's overflow reverts BOTH shards (the
    pmax abort ballot), and the next committed op replicates the refined
    centroids to every shard."""
    r = subprocess.run(
        [sys.executable, "-c", _MESH2_SCRIPT], capture_output=True,
        text=True, timeout=560,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": os.environ.get("HOME", ""),
             "JAX_PLATFORMS": "cpu"},
        cwd=REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["aborted"] and out["errors"] & mt.ABORT_BITS
    assert out["ok_ids"] and out["ok_d"]
    assert out["cents_replicated"]
    assert out["recluster_committed"] and out["ok_after"]


# ---------------------------------------------------------------------------
# stats() occupancy counters vs independent recount (regression satellite)
# ---------------------------------------------------------------------------

def test_stats_occupancy_matches_recount_after_overwrite_churn(rng):
    """Per-list occupancy in ``stats()`` must agree with an independent
    recount after overwrite-heavy churn. The recount routes every live
    id's LATEST vector through the quantizer — the same truth the scan
    path uses — so stale incremental counters cannot hide."""
    cfg = sivf.SIVFConfig(dim=D, n_lists=NL, n_slabs=48, capacity=32,
                          n_max=2048, max_chain=12)
    cents = rng.normal(size=(NL, D)).astype(np.float32)
    idx = sivf.Index(cfg, cents, min_bucket=8)
    latest: dict[int, np.ndarray] = {}
    ids = np.arange(200, dtype=np.int32)
    for round_ in range(4):                      # each round re-routes ids
        vecs = rng.normal(size=(len(ids), D)).astype(np.float32)
        assert idx.add(vecs, ids).ok
        for i, v in zip(ids.tolist(), vecs):
            latest[i] = v
        drop = ids[rng.integers(0, len(ids), size=30)]
        idx.remove(drop)
        for i in set(drop.tolist()):
            latest.pop(i, None)
        ids = np.asarray(sorted(set(ids.tolist()) | set(
            range(500 + 50 * round_, 530 + 50 * round_))), np.int32)
    s = idx.stats()
    occ = np.asarray(s["list_occupancy"])
    assert occ.sum() == idx.n_live == len(latest)
    live_ids = sorted(latest)
    assigned = np.asarray(quantizer.assign(
        idx.state.centroids, np.stack([latest[i] for i in live_ids]),
        cfg.metric))
    recount = np.bincount(assigned, minlength=NL)
    assert (occ == recount).all(), (occ, recount)
    assert s["list_skew"] == pytest.approx(float(occ.max() / occ.mean()))


def test_stats_occupancy_tracks_maintenance(rng):
    """After a committed merge, the counters reflect the new layout (and
    keep summing to n_live)."""
    cfg, state = parity.make_state(rng)
    state, _, _ = parity.load_rows(cfg, state, rng, 160)
    idx = sivf.Index(cfg, np.asarray(state.centroids),
                     _state=jax.tree.map(np.asarray, state), min_bucket=8)
    occ0 = np.asarray(idx.stats()["list_occupancy"])
    rep = idx.maintain(ops=[mt.merge(1, 2)], strict=True)[0]
    assert rep.committed
    occ1 = np.asarray(idx.stats()["list_occupancy"])
    assert occ1[2] == 0 and occ1[1] == occ0[1] + occ0[2]
    assert occ1.sum() == occ0.sum() == idx.n_live


# ---------------------------------------------------------------------------
# Session surface: policy wiring, epochs, mesh parity, tiered, deferred
# ---------------------------------------------------------------------------

def _handle(rng, backend="single", **kw):
    cfg = sivf.SIVFConfig(dim=D, n_lists=NL, n_slabs=48, capacity=32,
                          n_max=2048, max_chain=12, **kw)
    cents = rng.normal(size=(NL, D)).astype(np.float32)
    return sivf.Index(cfg, cents, backend=backend, min_bucket=8)


def test_policy_planned_maintain_bumps_epoch_per_commit(rng):
    idx = _handle(rng)
    vecs = rng.normal(size=(300, D)).astype(np.float32)
    idx.add(vecs, np.arange(300, dtype=np.int32))
    e0 = idx.epoch
    reps = idx.maintain(max_ops=2)               # drift policy plans
    assert reps
    moved = sum(1 for r in reps if r.committed and r.rows > 0)
    assert idx.epoch == e0 + moved
    _assert_all_live_searchable(idx, vecs)


def test_mesh_maintain_matches_single(rng):
    mesh = jax.make_mesh((1,), ("data",))
    a = _handle(np.random.default_rng(0))
    b = _handle(np.random.default_rng(0), backend=mesh)
    vecs = np.random.default_rng(5).normal(size=(250, D)).astype(np.float32)
    qs = np.random.default_rng(6).normal(size=(6, D)).astype(np.float32)
    ops = [mt.split(0, 1), mt.merge(2, 3), mt.recluster(0)]
    for idx in (a, b):
        idx.add(vecs, np.arange(250, dtype=np.int32))
    ra = a.maintain(ops=ops, strict=True)
    rb = b.maintain(ops=ops, strict=True)
    assert [(r.kind, r.committed, r.rows) for r in ra] \
        == [(r.kind, r.committed, r.rows) for r in rb]
    parity.assert_results_same(a.search(qs, 8, NL), b.search(qs, 8, NL))


def test_tiered_maintenance_coherent(rng):
    """Tiered twin stays bit-identical to the all-resident twin through a
    maintenance pass: moved rows' payloads/attrs ride the commit plan
    into the host store, and centroid updates reach future prefetches."""
    cents = rng.normal(size=(NL, D)).astype(np.float32)
    cfg = dict(dim=D, n_lists=NL, n_slabs=48, capacity=32, n_max=4096,
               max_chain=12, attributes=("tenant",))
    it = sivf.Index(sivf.SIVFConfig(device_slabs=40, **cfg), cents)
    if_ = sivf.Index(sivf.SIVFConfig(**cfg), cents)
    vecs = rng.normal(size=(500, D)).astype(np.float32)
    ids = np.arange(500, dtype=np.int32)
    parity.twin_churn(rng, (it, if_), vecs, ids,
                      attrs={"tenant": ids % 3},
                      attrs_fn=lambda n: {"tenant": np.arange(n) % 3})
    ops = [mt.recluster(0), mt.merge(1, 2)]
    for idx in (it, if_):
        reps = idx.maintain(ops=ops, strict=True)
        assert all(r.committed for r in reps)
    qs = rng.normal(size=(5, D)).astype(np.float32)
    from repro.core import filters as flt
    for kw in ({}, {"filter": flt.Eq("tenant", 1)}):
        parity.assert_results_same(it.search(qs, 10, NL, **kw),
                                   if_.search(qs, 10, NL, **kw))
    # and the tiered pool keeps ingesting post-maintenance
    more = rng.normal(size=(16, D)).astype(np.float32)
    for idx in (it, if_):
        idx.add(more, np.arange(3000, 3016, dtype=np.int32),
                attrs={"tenant": 1})
    parity.assert_results_same(it.search(qs, 10, NL), if_.search(qs, 10, NL))


def test_deferred_handle_maintains_between_pending(rng):
    cfg = sivf.SIVFConfig(dim=D, n_lists=NL, n_slabs=48, capacity=32,
                          n_max=2048, max_chain=12)
    cents = rng.normal(size=(NL, D)).astype(np.float32)
    deferred = sivf.Index(cfg, cents, min_bucket=8, deferred=True)
    vecs = rng.normal(size=(120, D)).astype(np.float32)
    fut = deferred.add(vecs, np.arange(120, dtype=np.int32))
    reps = deferred.maintain(ops=[mt.recluster(0)], strict=False)
    assert all(isinstance(r, mt.MaintenanceReport) for r in reps)
    assert not fut.done
    deferred.flush()
    assert fut.result().ok and deferred.n_live == 120
    _assert_all_live_searchable(deferred, vecs)


def test_serve_engine_maintenance(rng):
    from repro.serve.sivf_engine import ServeEngine
    cfg = sivf.SIVFConfig(dim=D, n_lists=NL, n_slabs=48, capacity=32,
                          n_max=2048, max_chain=12)
    cents = rng.normal(size=(NL, D)).astype(np.float32)
    serve_idx = sivf.Index(cfg, cents, min_bucket=8, deferred=True)
    plain = sivf.Index(cfg, cents, min_bucket=8)
    vecs = rng.normal(size=(300, D)).astype(np.float32)
    ids = np.arange(300, dtype=np.int32)
    plain.add(vecs, ids)
    qs = rng.normal(size=(6, D)).astype(np.float32)
    with ServeEngine(serve_idx, default_nprobe=NL) as eng:
        s = eng.session("t")
        s.add(vecs, ids).result()
        res = s.maintain(max_ops=2).result()
        assert res.ok and res.epoch >= 1
        assert isinstance(res.queue_s, float)
        after = s.search(qs, k=10).result()
        assert eng.stats()["maintenance_passes"] == 1
    # maintenance must not change what the serve path returns (full probe)
    want = plain.search(qs, 10, NL)
    assert (np.asarray(after.labels) == np.asarray(want.labels)).all()
    np.testing.assert_allclose(np.asarray(after.distances),
                               np.asarray(want.distances),
                               rtol=1e-5, atol=1e-5)


def test_maintain_requires_trained(rng):
    cfg = sivf.SIVFConfig(dim=D, n_lists=NL, n_slabs=8, capacity=32,
                          pq=sivf.PQConfig(m=4, nbits=4))
    cents = rng.normal(size=(NL, D)).astype(np.float32)
    idx = sivf.Index(cfg, cents)
    with pytest.raises(RuntimeError, match="untrained"):
        idx.maintain(ops=[mt.recluster(0)])
