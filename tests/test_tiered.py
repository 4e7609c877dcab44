"""Tiered slab pool (ISSUE 8): host cold store + device hot cache.

The contract under test: an index whose payload planes live host-side
(``SIVFConfig(device_slabs=...)``) serves searches **bit-identical** —
ids AND distances, ``==`` not allclose — to the all-resident pool, at
every cache size that fits the probed set, across the raw / PQ / filtered
scan paths on both backends, including under insert/delete churn; warm
caches search with zero host->device transfers; and the probe-driven
prefetch dedupes slab ids shared by probed lists.
"""
import dataclasses
import json
import os
import subprocess
import sys
import unittest.mock as mock
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import parity
from repro.core import filters as flt
from repro.core.api import Index
from repro.core.pq import PQConfig
from repro.core.state import SIVFConfig

REPO = Path(__file__).resolve().parent.parent

D, NL = 16, 8


def make_cfg(device_slabs=None, **kw):
    base = dict(dim=D, n_lists=NL, n_slabs=64, capacity=32, n_max=4096)
    base.update(kw)
    return SIVFConfig(device_slabs=device_slabs, **base)


_assert_same = parity.assert_results_same


def _pair(rng, device_slabs, n=600, backend="single", **kw):
    """(tiered, all-resident) twin handles over the same data."""
    cents = rng.normal(size=(NL, D)).astype(np.float32)
    it = Index(make_cfg(device_slabs, **kw), cents, backend=backend)
    if_ = Index(make_cfg(None, **kw), cents, backend=backend)
    vecs = rng.normal(size=(n, D)).astype(np.float32)
    ids = np.arange(n, dtype=np.int32)
    return it, if_, vecs, ids


def _churn(rng, it, if_, vecs, ids, attrs=None):
    """The shared twin mutation schedule (tests/parity.py): bulk add,
    overwrite, delete, refill — the refill recycles reclaimed slabs, so
    dirty-frame coherence on tiered pools is exercised."""
    fn = None if attrs is None else \
        (lambda n: {"tenant": np.arange(n) % 3})
    return parity.twin_churn(rng, (it, if_), vecs, ids, attrs=attrs,
                             attrs_fn=fn)


@pytest.mark.parametrize("device_slabs", [28, 40, 64])
def test_parity_raw_under_churn(rng, device_slabs):
    """Bit-identical results at several cache sizes, through overwrite,
    delete, and slab-recycling churn."""
    it, if_, vecs, ids = _pair(rng, device_slabs)
    _churn(rng, it, if_, vecs, ids)
    qs = rng.normal(size=(5, D)).astype(np.float32)
    for nprobe in (2, 4, NL):
        _assert_same(it.search(qs, k=10, nprobe=nprobe),
                     if_.search(qs, k=10, nprobe=nprobe))
    # repeat on a warm cache: residency must not change results
    _assert_same(it.search(qs, k=10, nprobe=NL),
                 if_.search(qs, k=10, nprobe=NL))


def test_parity_pq(rng):
    cents = rng.normal(size=(NL, D)).astype(np.float32)
    vecs = rng.normal(size=(600, D)).astype(np.float32)
    ids = np.arange(600, dtype=np.int32)
    pq = PQConfig(m=4, nbits=4)
    it = Index(make_cfg(32, pq=pq), cents).train(vecs)
    if_ = Index(make_cfg(None, pq=pq), cents).train(vecs)
    _churn(rng, it, if_, vecs, ids)
    qs = rng.normal(size=(5, D)).astype(np.float32)
    _assert_same(it.search(qs, k=10, nprobe=4),
                 if_.search(qs, k=10, nprobe=4))


def test_parity_filtered(rng):
    it, if_, vecs, ids = _pair(rng, 40, attributes=("tenant",))
    _churn(rng, it, if_, vecs, ids, attrs={"tenant": ids % 3})
    qs = rng.normal(size=(5, D)).astype(np.float32)
    for pred in (flt.Eq("tenant", 1), flt.In("tenant", (0, 2))):
        _assert_same(it.search(qs, k=10, nprobe=NL, filter=pred),
                     if_.search(qs, k=10, nprobe=NL, filter=pred))


def test_parity_mesh(rng):
    mesh = jax.make_mesh((1,), ("data",))
    it, if_, vecs, ids = _pair(rng, 40, backend=mesh)
    _churn(rng, it, if_, vecs, ids)
    qs = rng.normal(size=(5, D)).astype(np.float32)
    for nprobe in (4, NL):
        _assert_same(it.search(qs, k=10, nprobe=nprobe),
                     if_.search(qs, k=10, nprobe=nprobe))
    st = it.stats()
    assert st["tiered"] and st["per_shard_resident"][0] > 0


def test_parity_rejected_rows(rng):
    """Rows the device commit rejects (out-of-range ids) must not leak
    into the host store either — the plan carries -1 for them."""
    it, if_, vecs, ids = _pair(rng, 40)
    bad = ids.copy()
    bad[::7] = 100_000                     # outside [0, n_max)
    for idx in (it, if_):
        r = idx.add(vecs, bad)
        assert r.rejected > 0
    qs = rng.normal(size=(4, D)).astype(np.float32)
    _assert_same(it.search(qs, k=10, nprobe=NL),
                 if_.search(qs, k=10, nprobe=NL))


def test_cache_too_small_raises(rng):
    it, _, vecs, ids = _pair(rng, 4)
    it.add(vecs, ids)
    qs = rng.normal(size=(8, D)).astype(np.float32)
    with pytest.raises(ValueError, match="device_slabs"):
        it.search(qs, k=5, nprobe=NL)


def test_device_slabs_validation():
    with pytest.raises(ValueError, match="device_slabs"):
        make_cfg(0)
    with pytest.raises(ValueError, match="device_slabs"):
        make_cfg(65)                       # > n_slabs


# ---------------------------------------------------------------------------
# Satellite: probe-set dedupe
# ---------------------------------------------------------------------------

def test_prefetch_dedupes_shared_slabs(rng):
    """Slab ids shared by several probed lists (and by the queries of one
    tile) are fetched once: uploads == unique ids, never raw references."""
    it, _, vecs, ids = _pair(rng, 64)
    it.add(vecs, ids)
    qs = rng.normal(size=(16, D)).astype(np.float32)
    it.search(qs, k=5, nprobe=NL)          # every query probes every list
    rt = it._tiered
    last = rt.last_prefetch
    assert last["refs"] > last["unique"]          # sharing actually occurred
    assert last["uploaded"] == last["unique"]     # cold cache: one per slab
    assert last["dedup_saved"] == last["refs"] - last["unique"]
    st = it.stats()
    assert st["dedup_saved_fetches"] == st["dedup_refs"] - \
        st["dedup_unique_refs"] > 0
    # warm repeat: same refs, zero uploads
    it.search(qs, k=5, nprobe=NL)
    assert rt.last_prefetch["uploaded"] == 0
    assert rt.last_prefetch["hits"] == last["unique"]


# ---------------------------------------------------------------------------
# Satellite: stats / memory_report split
# ---------------------------------------------------------------------------

def test_memory_report_split():
    from repro.core.state import memory_report
    ct, cf = make_cfg(16), make_cfg(None)
    mt, mf = memory_report(ct), memory_report(cf)
    assert mf["host_bytes"] == 0
    assert mf["device_cache_bytes"] == 0
    assert mf["device_bytes"] == mf["total_bytes"]
    # tiered: payloads live host-side, cache frames on device
    payload_all = mt["payload_bytes"] + mt["code_bytes"] + mt["attr_bytes"]
    assert mt["host_bytes"] == payload_all
    assert mt["device_cache_bytes"] == payload_all * 16 // ct.n_slabs
    assert mt["total_bytes"] == mt["host_bytes"] + mt["device_bytes"]
    assert mt["device_bytes"] == mt["metadata_bytes"] \
        + mt["device_cache_bytes"]


def test_stats_split_sharded(rng):
    mesh = jax.make_mesh((1,), ("data",))
    it, _, vecs, ids = _pair(rng, 40, backend=mesh)
    it.add(vecs, ids)
    it.search(rng.normal(size=(4, D)).astype(np.float32), k=5, nprobe=4)
    st = it.stats()
    for key in ("host_bytes", "device_bytes", "device_cache_bytes",
                "resident_slabs", "hit_rate", "per_shard_resident"):
        assert key in st
    assert st["resident_slabs"] == sum(st["per_shard_resident"])
    assert 0.0 <= st["hit_rate"] <= 1.0
    # untiered twin reports the all-resident view
    su = Index(make_cfg(None), rng.normal(size=(NL, D)).astype(np.float32)
               ).stats()
    assert su["tiered"] is False and su["hit_rate"] == 1.0
    assert su["host_bytes"] == 0
    assert su["resident_slabs"] == su["slabs_used"]


# ---------------------------------------------------------------------------
# Satellite: zero-copy steady state
# ---------------------------------------------------------------------------

def test_zero_copy_warm_search(rng):
    """Warm-cache repeated search does no host->device transfers at all
    (asserted under ``transfer_guard("disallow")`` with counted
    ``device_put`` calls); cache misses are the only transfer sites —
    one packed ``device_put`` per miss batch."""
    it, _, vecs, ids = _pair(rng, 64)
    it.add(vecs, ids)
    qs = jnp.asarray(rng.normal(size=(64, D)).astype(np.float32))
    puts, gets = [], []
    orig_put, orig_get = jax.device_put, jax.device_get
    with mock.patch.object(
            jax, "device_put",
            side_effect=lambda *a, **k: (puts.append(1),
                                         orig_put(*a, **k))[1]), \
         mock.patch.object(
            jax, "device_get",
            side_effect=lambda *a, **k: (gets.append(1),
                                         orig_get(*a, **k))[1]):
        cold = it.search(qs, k=10, nprobe=NL)
        # cold: one device_get drains the queued insert plan, one fetches
        # the slab table; ONE packed device_put uploads every missed slab
        assert len(puts) == 1 and len(gets) == 2
        puts.clear(), gets.clear()
        with jax.transfer_guard("disallow"):
            for _ in range(3):
                warm = it.search(qs, k=10, nprobe=NL)
        # warm: the explicit table device_get is the only transfer; the
        # cache, residency map, and payload planes are never touched
        assert len(puts) == 0
        assert len(gets) == 3
        _assert_same(cold, warm)
        # a new insert dirties its slabs -> next search re-uploads (the
        # miss/dirty path is the only transfer site)
        it.add(jnp.asarray(rng.normal(size=(64, D)).astype(np.float32)),
               jnp.arange(3000, 3064, dtype=jnp.int32))
        puts.clear(), gets.clear()
        it.search(qs, k=10, nprobe=NL)
        assert len(puts) == 1              # one packed refresh upload


# ---------------------------------------------------------------------------
# Prefetch tickets (serve-engine pipelining hook)
# ---------------------------------------------------------------------------

def test_prefetch_ticket_skips_stages(rng):
    it, _, vecs, ids = _pair(rng, 64)
    it.add(vecs, ids)
    qs = rng.normal(size=(6, D)).astype(np.float32)
    t = it.prefetch(qs, nprobe=4)
    assert t is not None and t.seq == it._tiered.seq
    seq_before = it._tiered.seq
    res = it.search(qs, k=10, nprobe=4, _prefetched=t)
    # the ticketed search ran scan-only: no new prefetch happened
    assert it._tiered.seq == seq_before
    _assert_same(res, it.search(qs, k=10, nprobe=4))
    # a mutation invalidates the ticket (epoch moved): search falls back
    t2 = it.prefetch(qs, nprobe=4)
    it.add(vecs[:8], np.arange(4000, 4008, dtype=np.int32))
    res2 = it.search(qs, k=10, nprobe=4, _prefetched=t2)
    assert it._tiered.seq == t2.seq + 1    # full path re-prefetched
    assert res2 is not None
    # untiered handles return None and ignore tickets
    if2 = Index(make_cfg(None), rng.normal(size=(NL, D)).astype(np.float32))
    assert if2.prefetch(qs) is None


# ---------------------------------------------------------------------------
# Persistence + elastic reshard (format stays 3; residency is runtime-only)
# ---------------------------------------------------------------------------

def test_save_load_roundtrips(rng, tmp_path):
    it, if_, vecs, ids = _pair(rng, 32)
    _churn(rng, it, if_, vecs, ids)
    qs = rng.normal(size=(5, D)).astype(np.float32)
    ref = if_.search(qs, k=10, nprobe=NL)
    it.save(tmp_path / "t")
    meta = json.loads((tmp_path / "t" / "index.json").read_text())
    assert meta["format"] == 3             # tiered saves keep the format
    # tiered -> tiered
    _assert_same(Index.load(tmp_path / "t").search(qs, k=10, nprobe=NL), ref)
    # tiered -> all-resident (retier on load)
    j = Index.load(tmp_path / "t", device_slabs=None)
    assert j._tiered is None
    _assert_same(j.search(qs, k=10, nprobe=NL), ref)
    # all-resident checkpoint -> tiered
    if_.save(tmp_path / "f")
    k = Index.load(tmp_path / "f", device_slabs=28)
    assert k._tiered is not None
    _assert_same(k.search(qs, k=10, nprobe=NL), ref)
    # tiered -> 1-shard mesh (elastic + tiered at once)
    mesh = jax.make_mesh((1,), ("data",))
    m = Index.load(tmp_path / "t", backend=mesh)
    assert m.backend == "mesh" and m._tiered is not None
    _assert_same(m.search(qs, k=10, nprobe=NL), ref)


def test_reshard_live_tiered(rng):
    it, if_, vecs, ids = _pair(rng, 32)
    _churn(rng, it, if_, vecs, ids)
    qs = rng.normal(size=(5, D)).astype(np.float32)
    ref = if_.search(qs, k=10, nprobe=NL)
    mesh = jax.make_mesh((1,), ("data",))
    it.reshard(mesh)
    assert it.backend == "mesh" and it._tiered is not None
    _assert_same(it.search(qs, k=10, nprobe=NL), ref)
    it.reshard("single")
    _assert_same(it.search(qs, k=10, nprobe=NL), ref)
    # the handle still mutates after two reshard round trips
    before = it.n_live
    it.add(rng.normal(size=(16, D)).astype(np.float32),
           np.arange(3500, 3516, dtype=np.int32))
    assert it.n_live == before + 16


# ---------------------------------------------------------------------------
# Serve engine: tiled prefetch pipelining
# ---------------------------------------------------------------------------

def test_serve_engine_tiered(rng):
    from repro.serve.sivf_engine import ServeEngine
    cents = rng.normal(size=(NL, D)).astype(np.float32)
    it = Index(make_cfg(48), cents, deferred=True)
    if_ = Index(make_cfg(None), cents)
    vecs = rng.normal(size=(600, D)).astype(np.float32)
    ids = np.arange(600, dtype=np.int32)
    if_.add(vecs, ids)
    qs = rng.normal(size=(9, D)).astype(np.float32)
    with ServeEngine(it, max_coalesce=3) as eng:
        s = eng.session("t")
        s.add(vecs, ids).result()
        futs = [s.search(qs[i:i + 3], k=5, nprobe=4) for i in (0, 3, 6)]
        results = [f.result() for f in futs]
        eng.assert_bounded_compiles()
    for i, r in enumerate(results):
        ref = if_.search(qs[3 * i:3 * i + 3], k=5, nprobe=4)
        assert np.array_equal(np.asarray(r.labels), np.asarray(ref.labels))
        assert np.array_equal(np.asarray(r.distances),
                              np.asarray(ref.distances))
    assert it.stats()["cache_uploads"] > 0


# ---------------------------------------------------------------------------
# Multi-shard mesh (subprocess: fake device count must precede jax init)
# ---------------------------------------------------------------------------

_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import numpy as np, jax, jax.numpy as jnp
from repro.core.api import Index
from repro.core.state import SIVFConfig

rng = np.random.default_rng(7)
D, NL = 16, 8
def cfg(ds):
    return SIVFConfig(dim=D, n_lists=NL, n_slabs=64, capacity=32,
                      n_max=4096, device_slabs=ds)
cents = rng.normal(size=(NL, D)).astype(np.float32)
mesh = jax.make_mesh((4,), ("data",))
it = Index(cfg(24), cents, backend=mesh)
if_ = Index(cfg(None), cents, backend=mesh)
vecs = rng.normal(size=(600, D)).astype(np.float32)
ids = np.arange(600, dtype=np.int32)
for idx in (it, if_):
    idx.add(vecs, ids)
    idx.remove(ids[100:250])
    idx.add(rng.normal(size=(80, D)).astype(np.float32) * 0 + vecs[:80],
            np.arange(2000, 2080, dtype=np.int32))
qs = rng.normal(size=(5, D)).astype(np.float32)
ok = True
for nprobe in (4, NL):
    a = it.search(qs, k=10, nprobe=nprobe)
    b = if_.search(qs, k=10, nprobe=nprobe)
    ok &= np.array_equal(np.asarray(a.labels), np.asarray(b.labels))
    ok &= np.array_equal(np.asarray(a.distances), np.asarray(b.distances))
st = it.stats()
print(json.dumps({"ok": bool(ok), "resident": st["resident_slabs"],
                  "per_shard": st["per_shard_resident"],
                  "hit_rate": st["hit_rate"]}))
"""


def test_tiered_four_shard_parity():
    r = subprocess.run(
        [sys.executable, "-c", _MESH_SCRIPT], capture_output=True,
        text=True, timeout=560,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": os.environ.get("HOME", ""),
             "JAX_PLATFORMS": "cpu"},
        cwd=REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["ok"]
    assert len(out["per_shard"]) == 4
    assert out["resident"] == sum(out["per_shard"]) > 0


# ---------------------------------------------------------------------------
# hit-rate accounting: windowed vs cumulative, counters survive reshard
# (ISSUE 9 regression: stats() used to report only the cumulative rate
# unlabeled, and reshard rebuilt the runtime with zeroed counters)
# ---------------------------------------------------------------------------

def test_hit_rate_windowed_and_cumulative(rng):
    it, _, vecs, ids = _pair(rng, 32)
    it.add(vecs, ids)
    qs = rng.normal(size=(5, D)).astype(np.float32)
    it.search(qs, k=10, nprobe=NL)            # cold: misses + uploads
    st = it.stats()
    assert st["hit_rate_kind"] == "cumulative"
    assert 0.0 <= st["hit_rate"] < 1.0        # cold fill missed
    assert st["hit_rate_window"] == st["hit_rate"]   # no roll yet
    assert st["cache_hits_window"] == st["cache_hits"]
    it._tiered.roll_window()                  # new observation window
    st = it.stats()
    assert st["cache_misses_window"] == 0     # window reset...
    assert st["cache_misses"] > 0             # ...cumulative untouched
    it.search(qs, k=10, nprobe=NL)            # warm: same probe set
    st = it.stats()
    assert st["hit_rate_window"] == 1.0       # all-hit window
    assert st["hit_rate"] < 1.0               # lifetime still shows the fill


def test_hit_rate_counters_carry_across_reshard(rng):
    it, _, vecs, ids = _pair(rng, 32)
    it.add(vecs, ids)
    qs = rng.normal(size=(5, D)).astype(np.float32)
    it.search(qs, k=10, nprobe=NL)
    before = {k: it.stats()[k]
              for k in ("cache_hits", "cache_misses", "cache_uploads")}
    assert before["cache_uploads"] > 0
    it.reshard(jax.make_mesh((1,), ("data",)))
    after = it.stats()
    for k, v in before.items():               # cumulative story unbroken
        assert after[k] >= v, (k, v, after[k])
    assert after["hit_rate_kind"] == "cumulative"
