"""`sivf.Index` session handle: reports, bucketing, backends, persistence.

The ISSUE-2 acceptance criteria live here:
  * one handle passes the same churn test on single-device and 2+-shard
    mesh backends (the mesh case runs on 4 fake devices in a subprocess,
    because the device count must be fixed before jax initializes);
  * a stream over 8+ distinct ragged batch sizes compiles at most
    (number of bucket shapes) add/remove/search executables — asserted
    via the handle's measured jit-cache counters, not assumed.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import sivf
from repro import core
from repro.core import distributed as dist

REPO = Path(__file__).resolve().parent.parent

D, NL = 16, 8


def make(rng, *, n_slabs=64, capacity=32, n_max=4096, max_chain=16,
         min_bucket=16, **kw):
    cfg = sivf.SIVFConfig(dim=D, n_lists=NL, n_slabs=n_slabs,
                          capacity=capacity, n_max=n_max, max_chain=max_chain)
    cents = rng.normal(size=(NL, D)).astype(np.float32)
    idx = sivf.Index(cfg, cents, min_bucket=min_bucket, **kw)
    return idx, core.ReferenceIndex(cents)


def check_search(idx, ref, rng, k=5, nprobe=NL, q=6):
    qs = rng.normal(size=(q, D)).astype(np.float32)
    d, lab = idx.search(qs, k, nprobe)
    rd, rl = ref.search(qs, k, nprobe)
    np.testing.assert_allclose(np.asarray(d), rd, rtol=1e-4, atol=1e-4)
    assert (np.asarray(lab) == rl).all()


# ---------------------------------------------------------------------------
# Reports
# ---------------------------------------------------------------------------

def test_add_remove_search_matches_reference(rng):
    idx, ref = make(rng)
    vecs = rng.normal(size=(200, D)).astype(np.float32)
    rep = idx.add(vecs, np.arange(200))
    ref.insert(vecs, np.arange(200))
    assert (rep.requested, rep.accepted, rep.overwritten, rep.rejected) \
        == (200, 200, 0, 0)
    assert rep.ok and rep.n_live == idx.n_live == ref.n_live == 200
    check_search(idx, ref, rng)

    rep = idx.remove(np.arange(0, 200, 3))
    ref.delete(np.arange(0, 200, 3))
    assert rep.accepted == 67 and rep.rejected == 0
    assert idx.n_live == ref.n_live
    check_search(idx, ref, rng)


def test_report_overwrite_is_disjoint_from_accepted(rng):
    idx, ref = make(rng)
    vecs = rng.normal(size=(64, D)).astype(np.float32)
    idx.add(vecs, np.arange(64))
    ref.insert(vecs, np.arange(64))
    # 10 overwrites + 6 new in one batch
    more = rng.normal(size=(16, D)).astype(np.float32)
    ids = np.arange(54, 70, dtype=np.int32)
    rep = idx.add(more, ids)
    ref.insert(more, ids)
    assert (rep.accepted, rep.overwritten, rep.rejected) == (6, 10, 0)
    assert rep.n_live == ref.n_live == 70
    check_search(idx, ref, rng)


def test_report_within_batch_duplicates_rejected(rng):
    idx, ref = make(rng)
    vecs = rng.normal(size=(4, D)).astype(np.float32)
    ids = np.array([7, 7, 7, 8], np.int32)
    rep = idx.add(vecs, ids)
    ref.insert(vecs, ids)               # dict semantics: last row wins
    assert (rep.requested, rep.accepted, rep.rejected) == (4, 2, 2)
    assert idx.n_live == ref.n_live == 2
    check_search(idx, ref, rng, k=2)


def test_report_id_range_error_and_bit_clearing(rng):
    idx, ref = make(rng)
    vecs = rng.normal(size=(2, D)).astype(np.float32)
    rep = idx.add(vecs, np.asarray([1, idx.cfg.n_max + 5], np.int32))
    assert rep.errors == sivf.ErrorCode.ID_RANGE
    assert rep.accepted == 1 and rep.rejected == 1
    # handled bits are cleared: state is clean and the next report is too
    assert int(jnp.sum(idx.state.error)) == 0
    rep2 = idx.add(vecs, np.asarray([2, 3], np.int32))
    assert rep2.ok


def test_report_pool_exhaustion_and_strict_raise(rng):
    idx, _ = make(rng, n_slabs=8, max_chain=8)
    n = 8 * 32 + 1
    vecs = rng.normal(size=(n, D)).astype(np.float32)
    rep = idx.add(vecs, np.arange(n))
    assert rep.errors & sivf.ErrorCode.POOL_EXHAUSTED
    assert rep.accepted == 0 and rep.rejected == n
    assert idx.n_live == 0              # batch rejected atomically

    strict_idx, _ = make(rng, n_slabs=8, max_chain=8, strict=True)
    with pytest.raises(sivf.MutationRejected) as ei:
        strict_idx.add(vecs, np.arange(n))
    assert ei.value.report.errors & sivf.ErrorCode.POOL_EXHAUSTED
    # per-call override beats the handle default
    rep = strict_idx.add(vecs, np.arange(n), strict=False)
    assert not rep.ok


def test_failed_overwrite_batch_is_atomic(rng):
    """ISSUE-3 tentpole: a POOL_EXHAUSTED batch that was overwriting live
    ids must leave them searchable with their *old* payloads (the seed
    behavior dropped them)."""
    idx, _ = make(rng, n_slabs=10, max_chain=4)
    base = rng.normal(size=(30, D)).astype(np.float32)
    assert idx.add(base, np.arange(30)).ok
    n = 10 * 32 + 40
    ids = np.concatenate([np.arange(10),
                          np.arange(100, 100 + n - 10)]).astype(np.int32)
    rep = idx.add(rng.normal(size=(n, D)).astype(np.float32), ids)
    assert rep.errors & sivf.ErrorCode.POOL_EXHAUSTED
    # nothing accepted, nothing overwritten: the would-be overwrites kept
    # their old payloads, so they land in `rejected` with the rest
    assert (rep.accepted, rep.overwritten, rep.rejected) == (0, 0, n)
    assert idx.n_live == 30
    res = idx.search(base[:10], 1)
    assert (np.asarray(res.labels)[:, 0] == np.arange(10)).all()
    np.testing.assert_allclose(np.asarray(res.distances)[:, 0], 0, atol=1e-4)
    # the handle keeps streaming normally after the atomic reject
    assert idx.add(base[:5], np.arange(200, 205)).ok


def test_count_unique_counts_int32_max_id():
    """Regression (ISSUE 3): the old sentinel encoding collapsed a genuine
    id equal to INT32_MAX into the masked-out run and undercounted."""
    from repro.core.api import _count_unique
    m = np.iinfo(np.int32).max
    ids = jnp.asarray([m, 3, m, 3, -1], jnp.int32)
    mask = jnp.asarray([True, True, True, True, False])
    assert int(_count_unique(ids, mask)) == 2
    # masked-out duplicates of a live id don't double count; masked-out
    # ids alone don't count at all
    assert int(_count_unique(jnp.asarray([5, 5, 9], jnp.int32),
                             jnp.asarray([True, False, False]))) == 1
    assert int(_count_unique(jnp.asarray([m], jnp.int32),
                             jnp.asarray([False]))) == 0


def test_out_of_range_id_not_misreported_as_overwrite(rng):
    """Regression (ISSUE 3): clipping made an ID_RANGE-rejected id read
    slot n_max-1's occupancy, so it could be reported as `overwritten`
    when that boundary slot happened to be live."""
    cfg = sivf.SIVFConfig(dim=D, n_lists=NL, n_slabs=64, capacity=32,
                          n_max=64, max_chain=16)
    cents = rng.normal(size=(NL, D)).astype(np.float32)
    idx = sivf.Index(cfg, cents, min_bucket=8)
    vecs = rng.normal(size=(4, D)).astype(np.float32)
    assert idx.add(vecs[:1], np.asarray([63], np.int32)).ok  # n_max-1 live
    rep = idx.add(vecs[1:2], np.asarray([64], np.int32))     # out of range
    assert rep.errors == sivf.ErrorCode.ID_RANGE
    assert (rep.accepted, rep.overwritten, rep.rejected) == (0, 0, 1)
    # mixed batch: the real boundary id overwrites, the phantom rejects
    rep = idx.add(vecs[2:4], np.asarray([63, 64], np.int32))
    assert (rep.accepted, rep.overwritten, rep.rejected) == (0, 1, 1)
    assert idx.n_live == 1


def test_remove_missing_ids_counted_rejected(rng):
    idx, _ = make(rng)
    vecs = rng.normal(size=(10, D)).astype(np.float32)
    idx.add(vecs, np.arange(10))
    rep = idx.remove(np.asarray([0, 1, 999, 1000], np.int32))
    assert rep.accepted == 2 and rep.rejected == 2
    rep = idx.remove(np.asarray([0, 1], np.int32))   # already gone
    assert rep.accepted == 0 and rep.rejected == 2


# ---------------------------------------------------------------------------
# Bounded compilation under ragged streaming (acceptance criterion)
# ---------------------------------------------------------------------------

def test_ragged_batches_bounded_compiles(rng):
    # unique cfg so this test owns its jit-cache counters (they are shared
    # between handles with equal configs by design)
    cfg = sivf.SIVFConfig(dim=D, n_lists=NL, n_slabs=63, capacity=32,
                          n_max=4096, max_chain=17)
    cents = rng.normal(size=(NL, D)).astype(np.float32)
    idx = sivf.Index(cfg, cents, min_bucket=8)
    ref = core.ReferenceIndex(cents)

    sizes = [1, 3, 5, 9, 13, 17, 29, 33, 47, 63]      # 10 distinct raggeds
    buckets = {idx._bucket(s) for s in sizes}
    assert buckets == {8, 16, 32, 64}
    assert idx.bucket_shapes(63) == [8, 16, 32, 64]

    next_id = 0
    for s in sizes:
        vecs = rng.normal(size=(s, D)).astype(np.float32)
        ids = np.arange(next_id, next_id + s, dtype=np.int32)
        assert idx.add(vecs, ids).ok
        ref.insert(vecs, ids)
        next_id += s
    for s in sizes:
        d, lab = idx.search(rng.normal(size=(s, D)).astype(np.float32), 4, NL)
        assert d.shape == (s, 4)
    for s in (2, 6, 11, 18, 27, 34, 50, 62):
        ids = rng.integers(0, next_id, s).astype(np.int32)
        idx.remove(ids)
        ref.delete(np.unique(ids))

    compiles = idx.compile_stats()
    # >= 8 distinct ragged sizes ran; executables bounded by bucket count.
    # The lower bound of 1 guards against a broken/unavailable counter
    # (compile_stats returns -1 then) passing the bound vacuously.
    assert 1 <= compiles["add"] <= len(buckets), compiles
    assert 1 <= compiles["remove"] <= len(buckets), compiles
    assert 1 <= compiles["search"] <= len(buckets), compiles
    assert idx.n_live == ref.n_live
    check_search(idx, ref, rng)


def test_caller_centroids_buffer_survives_donation(rng):
    """Mutation kernels donate the state; the caller's centroids array must
    never be aliased into it (init_state copies), or the first add() would
    delete the caller's buffer."""
    cfg = sivf.SIVFConfig(dim=D, n_lists=NL, n_slabs=64, capacity=32,
                          n_max=4096, max_chain=16)
    cents = jnp.asarray(rng.normal(size=(NL, D)).astype(np.float32))
    idx1 = sivf.Index(cfg, cents, min_bucket=8)
    vecs = rng.normal(size=(10, D)).astype(np.float32)
    assert idx1.add(vecs, np.arange(10)).ok
    # same device array builds a second session and stays readable
    idx2 = sivf.Index(cfg, cents, min_bucket=8)
    assert idx2.add(vecs, np.arange(10)).ok
    assert np.asarray(cents).shape == (NL, D)


# ---------------------------------------------------------------------------
# Mesh backend (in-process single-shard; 4-shard case in subprocess below)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mesh1():
    return jax.make_mesh((1,), ("data",))


def test_mesh_backend_matches_reference(rng, mesh1):
    idx, ref = make(rng, backend=mesh1)
    vecs = rng.normal(size=(150, D)).astype(np.float32)
    rep = idx.add(vecs, np.arange(150))
    ref.insert(vecs, np.arange(150))
    assert rep.accepted == 150 and rep.ok
    rep = idx.add(vecs[:9], np.arange(9))
    assert rep.overwritten == 9 and rep.accepted == 0
    ref.insert(vecs[:9], np.arange(9))
    idx.remove(np.arange(0, 150, 2))
    ref.delete(np.arange(0, 150, 2))
    assert idx.n_live == ref.n_live
    check_search(idx, ref, rng)
    st = idx.stats()
    assert st["backend"] == "mesh" and st["n_shards"] == 1
    assert st["per_shard_live"] == [idx.n_live]


def test_stats_aggregates_stacked_sharded_state(rng, mesh1):
    """core.index.stats on the stacked per-shard state (used to crash)."""
    cfg = sivf.SIVFConfig(dim=D, n_lists=NL, n_slabs=32, capacity=32,
                          n_max=4096, max_chain=8)
    cents = rng.normal(size=(NL, D)).astype(np.float32)
    state = dist.init_sharded_state(cfg, jnp.asarray(cents), mesh1)
    state = dist.dist_insert(cfg, mesh1, state,
                             jnp.asarray(rng.normal(size=(40, D)), jnp.float32),
                             jnp.arange(40, dtype=jnp.int32))
    st = core.stats(cfg, state)
    assert st["n_live"] == dist.total_live(state) == 40
    assert st["n_shards"] == 1
    assert st["slabs_used"] == sum(st["per_shard_slabs_used"])
    assert st["error"] == 0


# ---------------------------------------------------------------------------
# Persistence
# ---------------------------------------------------------------------------

def test_save_load_roundtrip(tmp_path, rng):
    idx, _ = make(rng)
    vecs = rng.normal(size=(120, D)).astype(np.float32)
    idx.add(vecs, np.arange(120))
    idx.remove(np.arange(0, 120, 4))
    idx.save(tmp_path / "ckpt")

    loaded = sivf.Index.load(tmp_path / "ckpt")
    assert loaded.cfg == idx.cfg
    assert loaded.n_live == idx.n_live
    qs = rng.normal(size=(5, D)).astype(np.float32)
    d0, l0 = idx.search(qs, 5, NL)
    d1, l1 = loaded.search(qs, 5, NL)
    np.testing.assert_allclose(np.asarray(d0), np.asarray(d1))
    assert (np.asarray(l0) == np.asarray(l1)).all()
    # the restored handle keeps streaming
    assert loaded.add(vecs[:4], np.arange(200, 204)).ok


def test_load_format1_checkpoint(tmp_path, rng):
    """Pre-PQ (format-1) checkpoints lack the ``codes`` / ``pq_codebooks``
    / ``attrs`` leaves; ``Index.load`` must restore them into the leaf
    prefix and fill the (zero-width, since format 1 implies ``pq=None``
    and no attributes) planes fresh."""
    from repro.checkpoint.manager import CheckpointManager
    idx, _ = make(rng)
    vecs = rng.normal(size=(60, D)).astype(np.float32)
    idx.add(vecs, np.arange(60))
    idx.save(tmp_path / "ckpt")
    # rewrite the checkpoint as a format-1 save: drop the three trailing
    # plane leaves (last registered data fields) and the newer metadata
    mgr = CheckpointManager(tmp_path / "ckpt", keep_last=1)
    meta = mgr.load_metadata("index")
    meta["format"] = 1
    meta.pop("pq_trained")
    meta["cfg"].pop("pq")
    meta["cfg"].pop("attributes")
    mgr.save_metadata("index", meta)
    leaves, _ = jax.tree.flatten(idx.state)
    mgr.save(0, leaves[:-3])
    loaded = sivf.Index.load(tmp_path / "ckpt")
    assert loaded.n_live == 60
    qs = rng.normal(size=(4, D)).astype(np.float32)
    d0, l0 = idx.search(qs, 5, NL)
    d1, l1 = loaded.search(qs, 5, NL)
    np.testing.assert_allclose(np.asarray(d0), np.asarray(d1))
    assert (np.asarray(l0) == np.asarray(l1)).all()
    assert loaded.add(vecs[:4], np.arange(200, 204)).ok


def test_save_load_mesh_roundtrip(tmp_path, rng, mesh1):
    idx, _ = make(rng, backend=mesh1)
    idx.add(rng.normal(size=(50, D)).astype(np.float32), np.arange(50))
    idx.save(tmp_path / "ckpt")
    with pytest.raises(ValueError, match="mesh"):
        sivf.Index.load(tmp_path / "ckpt")           # mesh required
    loaded = sivf.Index.load(tmp_path / "ckpt", backend=mesh1)
    assert loaded.n_live == 50 and loaded.n_shards == 1


# ---------------------------------------------------------------------------
# Protocol conformance
# ---------------------------------------------------------------------------

def test_index_and_baselines_satisfy_protocol(rng):
    from repro.baselines import ContiguousIVF, FlatIndex, HNSWLite, LSHIndex
    idx, _ = make(rng)
    cents = rng.normal(size=(4, D)).astype(np.float32)
    engines = [idx, FlatIndex(D, 64), ContiguousIVF(cents, list_cap=32),
               LSHIndex(jax.random.key(0), D, bucket_cap=64), HNSWLite(D)]
    vecs = rng.normal(size=(20, D)).astype(np.float32)
    for eng in engines:
        assert isinstance(eng, sivf.IndexProtocol), type(eng)
        rep = eng.add(vecs, np.arange(20))
        assert rep.accepted == 20, type(eng)
        res = eng.search(vecs[:3], 4)
        d, lab = res                                   # tuple-compat unpack
        assert np.asarray(d).shape == (3, 4)
        assert eng.remove(np.arange(10)).accepted == 10
        assert eng.stats()["n_live"] == eng.n_live == 10


# ---------------------------------------------------------------------------
# 4-shard mesh churn (subprocess: device count fixed before jax init)
# ---------------------------------------------------------------------------

_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import numpy as np, jax, jax.numpy as jnp
import sivf
from repro import core

rng = np.random.default_rng(3)
D, NL = 16, 8
cfg = sivf.SIVFConfig(dim=D, n_lists=NL, n_slabs=32, capacity=32,
                      n_max=4096, max_chain=8)
cents = rng.normal(size=(NL, D)).astype(np.float32)
mesh = jax.make_mesh((4,), ("data",))
idx = sivf.Index(cfg, cents, backend=mesh, min_bucket=8)
ref = core.ReferenceIndex(cents)
assert idx.n_shards == 4

# ragged churn with overwrites and eviction against the oracle
next_id = 0
sizes = [5, 17, 9, 30, 3, 21, 14, 8]
for step, s in enumerate(sizes):
    vecs = rng.normal(size=(s, D)).astype(np.float32)
    ids = np.arange(next_id, next_id + s, dtype=np.int32)
    rep = idx.add(vecs, ids)
    assert rep.ok and rep.accepted == s, rep
    ref.insert(vecs, ids)
    next_id += s
    if step % 2:
        over = np.arange(0, next_id, 7, dtype=np.int32)[:6]
        ov = rng.normal(size=(len(over), D)).astype(np.float32)
        present = len(set(over.tolist()) & set(ref.store))
        rep = idx.add(ov, over)
        ref.insert(ov, over)
        assert rep.overwritten == present, (rep, present)
    if next_id > 60:
        evict = np.arange(next_id - 60 - s, next_id - 60, dtype=np.int32)
        idx.remove(evict)
        ref.delete(evict)
    assert idx.n_live == ref.n_live, (idx.n_live, ref.n_live)
    qs = rng.normal(size=(4, D)).astype(np.float32)
    d, l = idx.search(qs, 5, NL)
    rd, rl = ref.search(qs, 5, NL)
    np.testing.assert_allclose(np.asarray(d), rd, rtol=1e-4, atol=1e-4)
    assert (np.asarray(l) == rl).all()

# sharded-aware stats aggregation
st = idx.stats()
assert st["n_shards"] == 4
assert st["n_live"] == ref.n_live
assert sum(st["per_shard_live"]) == st["n_live"]
assert st["error"] == 0

# bounded compiles across the ragged stream (buckets of min_bucket=8);
# lower bound 1 keeps the assertion non-vacuous if the counter breaks
buckets = set(idx._bucket(s) for s in sizes + [6])
comp = idx.compile_stats()
assert 1 <= comp["add"] <= len(buckets), (comp, buckets)
assert 1 <= comp["remove"] <= len(buckets), (comp, buckets)

# ---- partial per-shard failure stays truthful under deferral (ISSUE 3) ----
# shard 0 gets overloaded past its own pool; shards 1-3 commit. The report
# must count shard-0 rows rejected (its overwrites kept old payloads) and
# the other shards' rows accepted, with per-shard bits naming the culprit.
tiny = sivf.SIVFConfig(dim=D, n_lists=NL, n_slabs=4, capacity=32,
                      n_max=4096, max_chain=2)
tidx = sivf.Index(tiny, cents, backend=mesh, min_bucket=8, deferred=True)
base_ids = np.asarray([0, 4, 8, 1, 2, 3], np.int32)      # 3 on shard 0
base = rng.normal(size=(len(base_ids), D)).astype(np.float32)
f0 = tidx.add(base, base_ids)
over = np.arange(0, 4 * 4 * 32 + 4, 4, dtype=np.int32)   # all shard 0, > pool
n0 = len(over)
others = np.asarray([5, 6, 7], np.int32)                 # shards 1-3 commit
batch_ids = np.concatenate([over, others])
bv = rng.normal(size=(len(batch_ids), D)).astype(np.float32)
f1 = tidx.add(bv, batch_ids)
reps = tidx.flush()
assert reps == [f0.result(), f1.result()]
assert f0.result().ok and f0.result().accepted == len(base_ids)
rep = f1.result()
POOL = sivf.ErrorCode.POOL_EXHAUSTED
assert rep.errors & POOL, rep
assert rep.shard_errors is not None and (rep.shard_errors[0] & POOL)
assert not any(e & POOL for e in rep.shard_errors[1:]), rep.shard_errors
assert rep.accepted == len(others), rep
assert rep.overwritten == 0, rep                          # shard 0 aborted
assert rep.rejected == n0, rep
assert tidx.n_live == len(base_ids) + len(others)
# shard 0's previously-live ids keep their *old* payloads
sq = np.stack([base[0], base[1], base[2]])                # ids 0, 4, 8
d, l = tidx.search(sq, 1, NL)
assert (np.asarray(l)[:, 0] == np.asarray([0, 4, 8])).all(), np.asarray(l)
np.testing.assert_allclose(np.asarray(d)[:, 0], 0, atol=1e-4)

print(json.dumps({"ok": True, "live": idx.n_live,
                  "per_shard": st["per_shard_live"], "compiles": comp,
                  "partial_shard_errors": [int(e) for e in rep.shard_errors]}))
"""


def _run(script, *args):
    return subprocess.run(
        [sys.executable, "-c", script, *args], capture_output=True,
        text=True, timeout=560,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": os.environ.get("HOME", ""),
             "JAX_PLATFORMS": "cpu"},
        cwd=REPO)


def test_sharded_index_handle_churn():
    """ISSUE-2 acceptance: the same handle semantics on a 4-shard mesh,
    plus the ISSUE-3 partial per-shard failure truthfulness under
    deferral (shard 0 aborts atomically, shards 1-3 commit)."""
    r = _run(_MESH_SCRIPT)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["ok"]
    assert sum(out["per_shard"]) == out["live"]
    assert out["partial_shard_errors"][0] & int(sivf.ErrorCode.POOL_EXHAUSTED)
