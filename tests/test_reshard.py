"""Elastic resharding: load any checkpoint onto any mesh (ISSUE 5).

Two layers of coverage:

  * **Pure function** (in-process, one device): ``reshard_state`` is
    host-driven and topology-agnostic — a stacked per-shard state is just
    a pytree with a leading axis — so grow / shrink / collapse chains run
    and verify without any fake-device subprocess. Search parity on a
    stacked state uses the same merge rule as ``sharded_search`` (per-
    shard top-k, global re-sort).
  * **Acceptance** (subprocess, 4 forced host devices): a checkpoint saved
    on a real 4-shard mesh loads onto 2-shard, 3-shard, and single
    backends with bit-identical search results (ids AND distances), PQ on
    and off; a live handle reshards in place and keeps streaming; post-
    reshard inserts land on the owning shard.

Everything asserts exact equality (``==``), not allclose: resharding
re-routes stored bytes, it never recomputes distances differently.
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


def make_cfg(pq=None, **kw):
    base = dict(dim=D, n_lists=NL, n_slabs=64, capacity=32, n_max=4096,
                max_chain=16, pq=pq)
    base.update(kw)
    return sivf.SIVFConfig(**base)


def make_index(rng, cfg, **kw):
    cents = rng.normal(size=(NL, D)).astype(np.float32)
    return sivf.Index(cfg, cents, min_bucket=16, **kw), cents


def search_any(cfg, state, qs, k, nprobe=NL):
    """Search a single OR stacked host state (``dist.search_stacked`` is
    the shared mesh-free merge; its rule mirrors ``sharded_search``)."""
    return dist.search_stacked(cfg, state, qs, k, nprobe)


PQ_CASES = [None, sivf.PQConfig(m=4, nbits=6),
            sivf.PQConfig(m=4, nbits=6, store_raw=True)]


@pytest.mark.parametrize("pq", PQ_CASES,
                         ids=["raw", "pq", "pq_store_raw"])
def test_reshard_chain_is_search_identical(rng, pq):
    """Grow -> shrink -> odd -> collapse (1->4->2->3->1): every step keeps
    the canonical live-row table AND the search results bit-identical."""
    cfg = make_cfg(pq)
    idx, _ = make_index(rng, cfg)
    vecs = rng.normal(size=(300, D)).astype(np.float32)
    if pq is not None:
        idx.train(vecs, key=jax.random.key(1))
    idx.add(vecs, np.arange(300))
    idx.remove(np.arange(0, 300, 7))
    idx.add(vecs[:10], np.arange(10))              # overwrites
    qs = rng.normal(size=(6, D)).astype(np.float32)
    d0, l0 = idx.search(qs, 5, NL)
    d0, l0 = np.asarray(d0), np.asarray(l0)
    rows0 = dist.flatten_live_rows(cfg, idx.state)

    st = idx.state
    for n_from, n_to in [(1, 4), (4, 2), (2, 3), (3, 1)]:
        st = dist.reshard_state(cfg, st, n_from, n_to)
        rows = dist.flatten_live_rows(cfg, st)
        assert np.array_equal(rows["ids"], rows0["ids"])
        assert np.array_equal(rows["lists"], rows0["lists"])
        assert np.array_equal(rows["data"], rows0["data"])      # payloads
        assert np.array_equal(rows["codes"], rows0["codes"])    # PQ codes
        d, lab = search_any(cfg, st, qs, 5)
        assert np.array_equal(d, d0) and np.array_equal(lab, l0), (n_from, n_to)
        # routing invariant: every id lives on the shard id % n_to picks
        if n_to > 1:
            for s in range(n_to):
                sub = jax.tree.map(lambda x: np.asarray(x)[s], st)
                srows = dist.flatten_live_rows(cfg, sub)
                assert (srows["ids"] % n_to == s).all()

    # the collapsed state is a drop-in handle state that keeps streaming
    idx2 = sivf.Index(cfg, rows0["centroids"], _state=st, min_bucket=16,
                      _pq_trained=True)
    assert idx2.n_live == idx.n_live
    nv = rng.normal(size=(3, D)).astype(np.float32)
    assert idx2.add(nv, np.arange(2000, 2003)).ok


def test_reshard_empty_index(rng):
    cfg = make_cfg()
    idx, _ = make_index(rng, cfg)
    st = dist.reshard_state(cfg, idx.state, 1, 3)
    assert int(np.asarray(st.n_live).sum()) == 0
    assert np.asarray(st.ids).shape[0] == 3
    d, lab = search_any(cfg, st, rng.normal(size=(2, D)).astype(np.float32), 4)
    assert (lab == -1).all() and np.isinf(d).all()
    st = dist.reshard_state(cfg, st, 3, 1)
    assert int(np.asarray(st.n_live)) == 0


def test_shrink_leaves_a_shard_empty(rng):
    """All ids even -> on a 2-shard target, shard 1 owns zero live rows;
    the empty shard must still be a well-formed, searchable, growable
    state."""
    cfg = make_cfg()
    idx, _ = make_index(rng, cfg)
    vecs = rng.normal(size=(60, D)).astype(np.float32)
    idx.add(vecs, np.arange(0, 240, 4))            # ids ≡ 0 (mod 4)
    qs = rng.normal(size=(4, D)).astype(np.float32)
    d0, l0 = idx.search(qs, 5, NL)
    st4 = dist.reshard_state(cfg, idx.state, 1, 4)  # shards 1-3 empty
    per_shard = np.asarray(st4.n_live)
    assert per_shard[0] == 60 and (per_shard[1:] == 0).all()
    st2 = dist.reshard_state(cfg, st4, 4, 2)
    per_shard = np.asarray(st2.n_live)
    assert per_shard[0] == 60 and per_shard[1] == 0
    d, lab = search_any(cfg, st2, qs, 5)
    assert np.array_equal(d, np.asarray(d0))
    assert np.array_equal(lab, np.asarray(l0))
    # the empty shard accepts its first insert (id 1 routes to shard 1)
    one = jax.tree.map(lambda x: jnp.asarray(np.asarray(x)[1]), st2)
    one = core.insert(cfg, one, jnp.asarray(vecs[:1]),
                      jnp.asarray([1], jnp.int32))
    assert int(one.n_live) == 1 and int(one.error) == 0


def test_reshard_rejects_wrong_n_from(rng):
    cfg = make_cfg()
    idx, _ = make_index(rng, cfg)
    with pytest.raises(ValueError, match="n_from"):
        dist.reshard_state(cfg, idx.state, 2, 4)


def _stack_shards(states):
    return jax.tree.map(lambda *xs: np.stack([np.asarray(x) for x in xs]),
                        *states)


def test_reshard_capacity_overflow_raises(rng):
    """Shrinking concentrates rows: 4 shards' pools can together hold more
    than one shard's static ``n_slabs`` pool fits — the collapse must fail
    up front with an error naming the limit, before any rebuild work.
    (The 4-shard state is assembled by stacking independently-filled
    single states, since no single pool could ever have held it.)"""
    cfg = make_cfg(n_slabs=16, max_chain=16)
    cents = rng.normal(size=(NL, D)).astype(np.float32)
    states = []
    for s in range(4):                 # 200 rows/shard, ids ≡ s (mod 4)
        idx = sivf.Index(cfg, cents, min_bucket=16)
        ids = np.arange(s, s + 4 * 200, 4, dtype=np.int32)
        rep = idx.add(rng.normal(size=(200, D)).astype(np.float32), ids)
        assert rep.ok
        states.append(idx.state)
    st4 = _stack_shards(states)
    # 800 rows need >= ceil(800/32) = 25 slabs on the collapsed shard > 16
    with pytest.raises(ValueError, match="n_slabs"):
        dist.reshard_state(cfg, st4, 4, 1)


def test_reshard_chain_overflow_raises(rng):
    """Per-list chain bound: merging shards whose rows share one IVF list
    exceeds ``max_chain`` even though the pool itself would fit."""
    cfg = make_cfg(n_slabs=64, max_chain=1)
    cents = rng.normal(size=(NL, D)).astype(np.float32)
    one = rng.normal(size=(1, D)).astype(np.float32)
    states = []
    for s in range(4):                 # 20 rows/shard, all in one list
        idx = sivf.Index(cfg, cents, min_bucket=16)
        ids = np.arange(s, s + 4 * 20, 4, dtype=np.int32)
        rep = idx.add(np.repeat(one, 20, axis=0), ids)
        assert rep.ok
        states.append(idx.state)
    # 80 rows in a single list: ceil(80/32) = 3 chained slabs > max_chain=1
    with pytest.raises(ValueError, match="max_chain"):
        dist.reshard_state(cfg, _stack_shards(states), 4, 1)


def test_load_wrong_axis_mesh_raises(tmp_path, rng):
    """Strict-mode load onto a mesh without the checkpoint's data axis must
    raise up front, not fail inside shard_map."""
    cfg = make_cfg()
    idx, _ = make_index(rng, cfg, strict=True)
    idx.add(rng.normal(size=(20, D)).astype(np.float32), np.arange(20))
    idx.save(tmp_path / "ckpt")
    wrong = jax.make_mesh((1,), ("model",))
    with pytest.raises(ValueError, match="axis"):
        sivf.Index.load(tmp_path / "ckpt", backend=wrong, strict=True)
    with pytest.raises(TypeError, match="backend"):
        sivf.Index.load(tmp_path / "ckpt", backend=3)


def test_load_unknown_routing_rule_raises(tmp_path, rng):
    from repro.checkpoint.manager import CheckpointManager
    cfg = make_cfg()
    idx, _ = make_index(rng, cfg)
    idx.add(rng.normal(size=(8, D)).astype(np.float32), np.arange(8))
    idx.save(tmp_path / "ckpt")
    mgr = CheckpointManager(tmp_path / "ckpt", keep_last=1)
    meta = mgr.load_metadata("index")
    assert meta["routing"] == {"rule": "mod", "n_shards": 1, "axis": "data"}
    meta["routing"]["rule"] = "rendezvous"
    mgr.save_metadata("index", meta)
    with pytest.raises(ValueError, match="routing"):
        sivf.Index.load(tmp_path / "ckpt")


def test_load_single_checkpoint_onto_one_shard_mesh(tmp_path, rng):
    """Kind change without count change (single -> 1-shard mesh and back)
    goes through the reshard path and stays bit-identical."""
    cfg = make_cfg()
    idx, _ = make_index(rng, cfg)
    vecs = rng.normal(size=(100, D)).astype(np.float32)
    idx.add(vecs, np.arange(100))
    idx.remove(np.arange(0, 100, 3))
    idx.save(tmp_path / "ckpt")
    qs = rng.normal(size=(5, D)).astype(np.float32)
    d0, l0 = idx.search(qs, 5, NL)
    mesh1 = jax.make_mesh((1,), ("data",))
    m = sivf.Index.load(tmp_path / "ckpt", backend=mesh1)
    assert m.backend == "mesh" and m.n_shards == 1
    d1, l1 = m.search(qs, 5, NL)
    assert np.array_equal(np.asarray(d0), np.asarray(d1))
    assert np.array_equal(np.asarray(l0), np.asarray(l1))
    assert m.add(vecs[:2], np.arange(500, 502)).ok
    # and back down: mesh checkpoint -> "single" collapse
    m.remove(np.arange(500, 502))
    m.save(tmp_path / "ckpt2")
    s = sivf.Index.load(tmp_path / "ckpt2", backend="single")
    assert s.backend == "single"
    d2, l2 = s.search(qs, 5, NL)
    assert np.array_equal(np.asarray(d0), np.asarray(d2))
    assert np.array_equal(np.asarray(l0), np.asarray(l2))


def test_live_reshard_flushes_deferred_queue(rng):
    cfg = make_cfg()
    idx, _ = make_index(rng, cfg, deferred=True)
    vecs = rng.normal(size=(30, D)).astype(np.float32)
    fut = idx.add(vecs, np.arange(30))
    assert not fut.done
    idx.reshard(jax.make_mesh((1,), ("data",)))
    assert fut.done and fut.result().accepted == 30   # resolved pre-reshard
    assert idx.backend == "mesh" and idx.n_live == 30
    fut2 = idx.add(vecs, np.arange(100, 130))
    assert idx.flush() == [fut2.result()]


# ---------------------------------------------------------------------------
# Acceptance: real 4-shard mesh checkpoint onto 2 / 3 / single (subprocess)
# ---------------------------------------------------------------------------

_MESH_RESHARD_SCRIPT = r"""
import os, json, tempfile
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax
import sivf

rng = np.random.default_rng(11)
D, NL = 16, 8
out = {}
for tag, pq in (("raw", None), ("pq", sivf.PQConfig(m=4, nbits=6))):
    cfg = sivf.SIVFConfig(dim=D, n_lists=NL, n_slabs=32, capacity=32,
                          n_max=4096, max_chain=16, pq=pq)
    cents = rng.normal(size=(NL, D)).astype(np.float32)
    mesh4 = jax.make_mesh((4,), ("data",))
    idx = sivf.Index(cfg, cents, backend=mesh4, min_bucket=16)
    vecs = rng.normal(size=(300, D)).astype(np.float32)
    if pq is not None:
        idx.train(vecs, key=jax.random.key(3))
    idx.add(vecs, np.arange(300))
    idx.remove(np.arange(0, 300, 7))
    idx.add(vecs[:10], np.arange(10))            # overwrites survive reshard
    qs = rng.normal(size=(6, D)).astype(np.float32)
    d0, l0 = idx.search(qs, 5, NL)
    d0, l0 = np.asarray(d0), np.asarray(l0)
    nv = rng.normal(size=(4, D)).astype(np.float32) * 3.0 + 10.0

    with tempfile.TemporaryDirectory() as td:
        idx.save(td)
        for tgt, n in ((jax.make_mesh((2,), ("data",)), 2),
                       (jax.make_mesh((3,), ("data",)), 3),
                       ("single", 1)):
            m = sivf.Index.load(td, backend=tgt)
            assert m.n_shards == n and m.n_live == idx.n_live
            d, l = m.search(qs, 5, NL)
            # acceptance: bit-identical ids AND distances, PQ on and off
            assert np.array_equal(np.asarray(d), d0), (tag, n)
            assert np.array_equal(np.asarray(l), l0), (tag, n)
            # post-reshard inserts land on the owning shard and are found
            rep = m.add(nv, np.arange(2000, 2004))
            assert rep.ok and rep.accepted == 4, (tag, n, rep)
            if n > 1:
                per = np.asarray(m.state.n_live)
                live = sorted((set(range(300)) - set(range(0, 300, 7)))
                              | set(range(10)) | {2000, 2001, 2002, 2003})
                want = np.bincount(np.asarray(live) % n, minlength=n)
                assert (per == want).all(), (tag, n, per, want)
            dd, ll = m.search(nv, 1, NL)
            if pq is None:                       # exact payloads: d == 0
                assert (np.asarray(ll)[:, 0] ==
                        np.arange(2000, 2004)).all(), (tag, n)

    # live handle reshard: 4 -> 2 -> single, streaming throughout
    idx.reshard(jax.make_mesh((2,), ("data",)))
    d, l = idx.search(qs, 5, NL)
    assert np.array_equal(np.asarray(d), d0) and np.array_equal(
        np.asarray(l), l0), (tag, "live-2")
    assert idx.add(nv, np.arange(3000, 3004)).ok
    assert idx.remove(np.arange(3000, 3004)).accepted == 4
    idx.reshard("single")
    d, l = idx.search(qs, 5, NL)
    assert np.array_equal(np.asarray(d), d0) and np.array_equal(
        np.asarray(l), l0), (tag, "live-single")
    out[tag] = {"live": idx.n_live, "backend": idx.backend}

print(json.dumps({"ok": True, **out}))
"""


def _run(script):
    return subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=560,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": os.environ.get("HOME", ""),
             "JAX_PLATFORMS": "cpu"},
        cwd=REPO)


def test_mesh_checkpoint_loads_onto_any_backend():
    """ISSUE-5 acceptance: a 4-shard checkpoint loads onto 2-shard,
    3-shard, and single backends bit-identically (PQ on and off), and a
    live handle reshards in place."""
    r = _run(_MESH_RESHARD_SCRIPT)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["ok"] and out["raw"]["backend"] == "single"
