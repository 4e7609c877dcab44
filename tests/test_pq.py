"""Product-quantization subsystem (ISSUE 4).

Four layers under test:
  * the codec (``core/pq.py``): train/encode/decode/ADC-table math;
  * state + ingest: uint8 code planes replace fp32 payloads, codes stay
    consistent with ids under churn, failed batches stay atomic;
  * the fused ADC kernel (``kernels/sivf_scan/pq_fused.py``): **bit-exact**
    against the XLA reference ``core.scan_slabs_topk_pq`` — distances AND
    labels — including deleted-slot masking, empty chains, ``k > n_live``
    and ragged query blocking;
  * the session surface: recall oracle on clustered data, stats/memory
    accounting, save/load round-trips on single and sharded backends.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import parity
import sivf
from repro import core
from repro.core import pq

D, NL = 16, 4


def clustered(rng, n, dim=D, n_clusters=8, spread=0.25):
    """Gaussian-mixture vectors (PQ-friendly: codebooks have structure)."""
    centers = rng.normal(size=(n_clusters, dim)).astype(np.float32) * 2.0
    which = rng.integers(0, n_clusters, size=n)
    return (centers[which]
            + spread * rng.normal(size=(n, dim)).astype(np.float32)
            ).astype(np.float32)


def make(rng, m=4, nbits=4, capacity=32, metric="l2", n_slabs=24,
         max_chain=8, store_raw=False, n_train=512):
    """Build scaffolding lives in tests/parity.py; only the clustered
    training distribution is suite-specific."""
    return parity.make_state(
        rng, dim=D, n_lists=NL, n_slabs=n_slabs, capacity=capacity,
        metric=metric, max_chain=max_chain,
        pq=core.PQConfig(m=m, nbits=nbits, store_raw=store_raw),
        train=clustered(rng, n_train))


def load(cfg, state, rng, n, start=0):
    state, vecs, _ = parity.load_rows(cfg, state, rng, n, start=start,
                                      vecs=clustered(rng, n))
    return state, vecs


# ---------------------------------------------------------------------------
# Codec
# ---------------------------------------------------------------------------

def test_pqconfig_validation():
    with pytest.raises(ValueError, match="nbits"):
        core.PQConfig(m=4, nbits=9)
    with pytest.raises(ValueError, match="divisible"):
        core.SIVFConfig(dim=D, n_lists=NL, n_slabs=8,
                        pq=core.PQConfig(m=5))
    assert core.PQConfig(m=8).ksub == 256
    assert core.PQConfig(m=8).code_bytes() == 8


def test_encode_decode_roundtrip(rng):
    xs = clustered(rng, 400)
    cb = pq.train_pq(jax.random.key(1), jnp.asarray(xs), 4, 6, iters=10)
    assert cb.shape == (4, 64, D // 4)
    codes = pq.encode(cb, jnp.asarray(xs))
    assert codes.shape == (400, 4) and codes.dtype == jnp.uint8
    rec = pq.decode(cb, codes)
    mse = float(jnp.mean((rec - xs) ** 2))
    base = float(jnp.mean(jnp.var(jnp.asarray(xs), axis=0)))
    assert mse < 0.5 * base     # trained codebooks beat the data variance
    # encoding is the per-subspace argmin: re-encoding the decode is stable
    assert (np.asarray(pq.encode(cb, rec)) == np.asarray(codes)).all()


@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_adc_tables_match_decoded_distance(rng, metric):
    xs = clustered(rng, 256)
    qs = clustered(rng, 9)
    cb = pq.train_pq(jax.random.key(2), jnp.asarray(xs), 4, 4, iters=8)
    codes = pq.encode(cb, jnp.asarray(xs[:32]))
    rec = np.asarray(pq.decode(cb, codes))
    adc = np.asarray(pq.adc_tables(cb, jnp.asarray(qs), metric))  # [Q, m, K]
    got = adc[:, np.arange(4)[None, :], np.asarray(codes, np.int32)]
    got = got.sum(-1)                                             # [Q, 32]
    if metric == "l2":
        want = ((qs[:, None] - rec[None]) ** 2).sum(-1)
    else:
        want = -(qs[:, None] * rec[None]).sum(-1)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# State + ingest
# ---------------------------------------------------------------------------

def test_pq_state_replaces_payload_plane(rng):
    cfg, state = make(rng)
    assert state.data.shape == (cfg.n_slabs, cfg.capacity, 0)
    assert state.codes.shape == (cfg.n_slabs, cfg.capacity, 4)
    assert state.codes.dtype == jnp.uint8
    cfg_raw, state_raw = make(rng, store_raw=True)
    assert state_raw.data.shape == (cfg_raw.n_slabs, cfg_raw.capacity, D)


def test_insert_encodes_codes_consistent_with_ids(rng):
    cfg, state = make(rng)
    (state, vecs) = load(cfg, state, rng, 150)
    att_slab = np.asarray(state.att_slab)[:150]
    att_slot = np.asarray(state.att_slot)[:150]
    assert (att_slab >= 0).all()
    got = np.asarray(state.codes)[att_slab, att_slot]
    want = np.asarray(pq.encode(state.pq_codebooks, jnp.asarray(vecs)))
    assert (got == want).all()
    # overwrite re-encodes: new payloads land under the same ids
    new = clustered(rng, 30)
    state = core.insert(cfg, state, jnp.asarray(new),
                        jnp.asarray(np.arange(30), np.int32))
    att_slab = np.asarray(state.att_slab)[:30]
    att_slot = np.asarray(state.att_slot)[:30]
    got = np.asarray(state.codes)[att_slab, att_slot]
    want = np.asarray(pq.encode(state.pq_codebooks, jnp.asarray(new)))
    assert (got == want).all()


def test_failed_batch_leaves_old_codes_searchable(rng):
    """Atomicity extends to the code plane: a POOL_EXHAUSTED batch changes
    neither the ATT nor any stored code, and a full-probe search still
    returns exactly the previously-live id set."""
    cfg, state = make(rng, n_slabs=4, max_chain=2)
    (state, vecs) = load(cfg, state, rng, 40)
    codes_before = np.asarray(state.codes).copy()
    att_before = np.asarray(state.att_slab).copy()
    n = 4 * 32 + 50                              # provably > free capacity
    state = core.insert(
        cfg, state, jnp.asarray(clustered(rng, n)),
        jnp.asarray(np.arange(100, 100 + n), np.int32))
    assert int(state.error) & core.ERR_POOL_EXHAUSTED
    assert (np.asarray(state.codes) == codes_before).all()
    assert (np.asarray(state.att_slab) == att_before).all()
    qs = jnp.asarray(clustered(rng, 3))
    _, labels = core.search(cfg, state, qs, 40, NL)
    got = set(np.asarray(labels).ravel().tolist()) - {-1}
    assert got == set(range(40))


# ---------------------------------------------------------------------------
# Fused ADC kernel: bit-exact parity vs the XLA reference
# ---------------------------------------------------------------------------

pq_kernel = pytest.mark.pallas


def assert_pq_fused_matches_ref(cfg, state, rng, k, nprobe, q=5, block_q=8,
                                use_tables=True, reshape=None):
    from repro.kernels.sivf_scan.pq_fused import sivf_pq_fused_search_pallas
    qs = jnp.asarray(clustered(rng, q))
    lists = core.probe(state.centroids, qs, nprobe, cfg.metric)
    table = (core.gather_tables if use_tables else core.walk_chains)(
        cfg, state, lists)
    if reshape is not None:
        table = reshape(table)
    # one materialized ADC table feeds both backends — exactly what
    # core._scan_dispatch does — so parity is structural, not rounding luck
    adc = pq.adc_tables(state.pq_codebooks, qs, cfg.metric)
    dr, lr = core.scan_slabs_topk_pq(cfg, state, qs, table, k, adc=adc)
    df, lf = sivf_pq_fused_search_pallas(
        adc, table, state.codes, state.ids, state.bitmap, k,
        block_q=block_q, interpret=True)
    # acceptance: BIT-exact — same tables, same summation order, same fold;
    # not merely allclose
    assert (np.asarray(df) == np.asarray(dr)).all(), (df, dr)
    assert (np.asarray(lf) == np.asarray(lr)).all()
    return np.asarray(df), np.asarray(lf)


@pq_kernel
@pytest.mark.parametrize("metric", ["l2", "ip"])
@pytest.mark.parametrize("m,nbits", [(4, 4), (8, 5)])
def test_pq_fused_parity(rng, metric, m, nbits):
    cfg, state = make(rng, m=m, nbits=nbits, metric=metric)
    state, _ = load(cfg, state, rng, 200)
    assert_pq_fused_matches_ref(cfg, state, rng, k=7, nprobe=2)


@pq_kernel
@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_pq_fused_deleted_slot_masking(rng, metric):
    cfg, state = make(rng, metric=metric)
    state, _ = load(cfg, state, rng, 200)
    dels = np.arange(0, 200, 3, dtype=np.int32)
    state = core.delete(cfg, state, jnp.asarray(dels))
    _, lf = assert_pq_fused_matches_ref(cfg, state, rng, k=9, nprobe=NL)
    live = lf[lf >= 0]
    assert not np.isin(live, dels).any()


@pq_kernel
def test_pq_fused_empty_chains(rng):
    cfg, state = make(rng)
    vecs = clustered(rng, 40)
    state = core.insert(cfg, state, jnp.asarray(vecs),
                        jnp.asarray(np.arange(40), np.int32),
                        jnp.zeros((40,), jnp.int32))   # single list only
    assert_pq_fused_matches_ref(cfg, state, rng, k=5, nprobe=NL)


@pq_kernel
def test_pq_fused_fully_empty_index(rng):
    cfg, state = make(rng)
    df, lf = assert_pq_fused_matches_ref(cfg, state, rng, k=4, nprobe=NL)
    assert np.isinf(df).all() and (lf == -1).all()


@pq_kernel
def test_pq_fused_k_exceeds_n_live(rng):
    cfg, state = make(rng)
    state, _ = load(cfg, state, rng, 6)
    df, lf = assert_pq_fused_matches_ref(cfg, state, rng, k=16, nprobe=NL)
    assert np.isinf(df[:, -1]).all()
    assert (np.sort(lf, axis=1) != -1).sum(axis=1).max() <= 6


@pq_kernel
@pytest.mark.parametrize("q,block_q", [(1, 8), (5, 4), (8, 8), (13, 8)])
def test_pq_fused_ragged_query_blocking(rng, q, block_q):
    cfg, state = make(rng)
    state, _ = load(cfg, state, rng, 150)
    assert_pq_fused_matches_ref(cfg, state, rng, k=5, nprobe=2, q=q,
                                block_q=block_q)


@pq_kernel
@pytest.mark.parametrize("case", sorted(parity.TABLE_CASES))
def test_pq_fused_parity_sparse_tables(rng, case):
    """The raw kernel's skipped-step cases (test_fused_search.py), through
    the ADC kernel: bit-exact, ties included."""
    q, block_q, dead, dup = parity.TABLE_CASES[case]
    cfg, state = make(rng)
    vecs = clustered(rng, 100)
    state, _, _ = parity.load_rows(cfg, state, rng, 100, vecs=vecs)
    if dup:
        state, _, _ = parity.load_rows(cfg, state, rng, 100, start=100,
                                       vecs=vecs)
    df, lf = assert_pq_fused_matches_ref(
        cfg, state, rng, k=7, nprobe=NL, q=q, block_q=block_q,
        reshape=lambda t: parity.spread_table(t, rng, NL, dead))
    assert np.isinf(df[list(dead)]).all() and (lf[list(dead)] == -1).all()
    if dup:
        assert (np.diff(df, axis=1) == 0).any()     # ties were resolved


@pq_kernel
def test_pq_fused_pointer_walk_table(rng):
    cfg, state = make(rng)
    state, _ = load(cfg, state, rng, 150)
    state = core.delete(cfg, state,
                        jnp.asarray(np.arange(0, 150, 2), np.int32))
    assert_pq_fused_matches_ref(cfg, state, rng, k=5, nprobe=NL,
                                use_tables=False)


@pq_kernel
def test_pq_fused_randomized_churn(rng):
    cfg, state = make(rng, n_slabs=48, max_chain=12)
    rows: dict = {}
    for step in range(5):
        state, rows = parity.churn(cfg, state, rng, steps=1, rows=rows)
        assert_pq_fused_matches_ref(cfg, state, rng, k=8,
                                    nprobe=int(rng.integers(1, NL + 1)),
                                    q=int(rng.integers(1, 7)))


@pq_kernel
def test_pq_search_dispatch_parity(rng):
    """core.search impl="pallas_interpret" == impl="xla", bit-for-bit
    (exact_dist comes from cfg.pq in the shared helper)."""
    cfg, state = make(rng)
    state, _ = load(cfg, state, rng, 180)
    state = core.delete(cfg, state,
                        jnp.asarray(np.arange(0, 180, 4), np.int32))
    parity.assert_search_parity(cfg, state, rng, k=5, nprobe=3,
                                queries=clustered(rng, 6))


# ---------------------------------------------------------------------------
# Recall oracle
# ---------------------------------------------------------------------------

def test_pq_recall_oracle(rng):
    """ADC recall@10 vs exact fp32 search >= 0.8 on clustered data.

    300 planted clusters of 10 near-neighbors each (the query's true top-10
    is its cluster; spread 0.4 vs inter-cluster distances ~sqrt(2*dim)*2,
    so the ranking is non-trivial but resolvable). Full probe, so coarse
    quantization contributes no loss — the gap under test is purely the PQ
    approximation (m=8 subspaces of 4 dims, 6 bits = 8 B/vector vs 128 B
    fp32). Measured headroom: recall ~1.0 at these settings; the 0.8 floor
    is the ISSUE acceptance bar and catches codec/ADC regressions.
    """
    dim, k, ngroups, per = 32, 10, 300, 10
    gcent = rng.normal(size=(ngroups, dim)).astype(np.float32) * 2.0
    xs = (np.repeat(gcent, per, axis=0)
          + 0.4 * rng.normal(size=(ngroups * per, dim))).astype(np.float32)
    n = len(xs)
    cfg = core.SIVFConfig(dim=dim, n_lists=8, n_slabs=160, capacity=32,
                          n_max=4096, max_chain=64,
                          pq=core.PQConfig(m=8, nbits=6))
    cents = core.train_kmeans(jax.random.key(3), jnp.asarray(xs), 8)
    idx = sivf.Index(cfg, cents, min_bucket=64).train(xs[:2000], iters=25)
    assert idx.add(xs, np.arange(n)).ok
    qs = (gcent[rng.integers(0, ngroups, size=64)]
          + 0.4 * rng.normal(size=(64, dim))).astype(np.float32)
    res = idx.search(qs, k)                        # nprobe=None: full probe
    d = ((qs[:, None] - xs[None]) ** 2).sum(-1)
    true = np.argsort(d, axis=1, kind="stable")[:, :k]
    pred = np.asarray(res.labels)
    hits = [len(set(pred[i].tolist()) & set(true[i].tolist()))
            for i in range(len(qs))]
    recall = float(np.mean(hits)) / k
    assert recall >= 0.8, f"PQ recall@10 {recall:.3f} < 0.8"


# ---------------------------------------------------------------------------
# Session surface: stats, save/load (single + sharded), mesh parity
# ---------------------------------------------------------------------------

def _session(rng, backend="single", **kw):
    cfg = sivf.SIVFConfig(dim=D, n_lists=NL, n_slabs=48, capacity=32,
                          n_max=2048, max_chain=12,
                          pq=sivf.PQConfig(m=4, nbits=4))
    cents = rng.normal(size=(NL, D)).astype(np.float32)
    idx = sivf.Index(cfg, cents, backend=backend, min_bucket=8, **kw)
    idx.train(clustered(rng, 512), key=jax.random.key(7))
    return cfg, cents, idx


def test_stats_reports_compression(rng):
    cfg, _, idx = _session(rng)
    idx.add(clustered(rng, 100), np.arange(100))
    s = idx.stats()
    assert s["payload_bytes"] == 0
    assert s["code_bytes"] == cfg.n_slabs * cfg.capacity * 4
    assert s["compression_ratio"] == pytest.approx(D * 4 / 4)
    # store_raw keeps the fp32 plane: ratio < 1 (codes are pure overhead)
    mr = sivf.memory_report(dataclasses.replace(
        cfg, pq=sivf.PQConfig(m=4, nbits=4, store_raw=True)))
    assert mr["payload_bytes"] > 0 and mr["compression_ratio"] < 1.0
    # non-PQ configs don't advertise a ratio through stats
    plain = sivf.Index(dataclasses.replace(cfg, pq=None),
                       rng.normal(size=(NL, D)).astype(np.float32))
    assert "compression_ratio" not in plain.stats()
    assert plain.stats()["code_bytes"] == 0


def test_stats_sharded_aggregates(rng):
    mesh = jax.make_mesh((1,), ("data",))
    cfg, _, idx = _session(rng, backend=mesh)
    idx.add(clustered(rng, 60), np.arange(60))
    s = idx.stats()
    assert s["n_shards"] == 1
    assert s["code_bytes"] == cfg.n_slabs * cfg.capacity * 4
    assert s["compression_ratio"] == pytest.approx(16.0)


def test_pq_save_load_single(rng, tmp_path):
    _, _, idx = _session(rng)
    vecs = clustered(rng, 120)
    idx.add(vecs, np.arange(120))
    idx.remove(np.arange(0, 120, 7))
    idx.save(tmp_path)
    back = sivf.Index.load(tmp_path)
    assert back.cfg.pq == idx.cfg.pq
    assert (np.asarray(back.state.codes) == np.asarray(idx.state.codes)).all()
    qs = clustered(rng, 6)
    a, b = idx.search(qs, 5), back.search(qs, 5)
    assert (np.asarray(a.distances) == np.asarray(b.distances)).all()
    assert (np.asarray(a.labels) == np.asarray(b.labels)).all()
    # trainedness survives the round trip: ingest keeps working
    assert back.add(clustered(rng, 8), np.arange(500, 508)).ok


def test_pq_save_load_sharded(rng, tmp_path):
    mesh = jax.make_mesh((1,), ("data",))
    _, _, idx = _session(rng, backend=mesh)
    vecs = clustered(rng, 120)
    idx.add(vecs, np.arange(120))
    idx.save(tmp_path)
    back = sivf.Index.load(tmp_path, backend=mesh)
    assert back.backend == "mesh" and back.cfg.pq == idx.cfg.pq
    qs = clustered(rng, 6)
    a, b = idx.search(qs, 5), back.search(qs, 5)
    assert (np.asarray(a.distances) == np.asarray(b.distances)).all()
    assert (np.asarray(a.labels) == np.asarray(b.labels)).all()
    assert back.add(clustered(rng, 8), np.arange(500, 508)).ok


def test_pq_mesh_matches_single(rng):
    mesh = jax.make_mesh((1,), ("data",))
    _, _, single = _session(rng)
    rng2 = np.random.default_rng(0)
    _, _, sharded = _session(rng2, backend=mesh)
    vecs = clustered(np.random.default_rng(5), 200)
    for idx in (single, sharded):
        idx.add(vecs, np.arange(200))
        idx.remove(np.arange(0, 200, 3))
    qs = clustered(np.random.default_rng(6), 7)
    a, b = single.search(qs, 6), sharded.search(qs, 6)
    assert (np.asarray(a.labels) == np.asarray(b.labels)).all()
    np.testing.assert_allclose(np.asarray(a.distances),
                               np.asarray(b.distances), rtol=1e-6)


def test_train_guards(rng):
    cfg = sivf.SIVFConfig(dim=D, n_lists=NL, n_slabs=8, capacity=32,
                          pq=sivf.PQConfig(m=4, nbits=4))
    cents = rng.normal(size=(NL, D)).astype(np.float32)
    idx = sivf.Index(cfg, cents)
    with pytest.raises(RuntimeError, match="untrained"):
        idx.add(clustered(rng, 4), np.arange(4))
    idx.train(clustered(rng, 256))
    idx.add(clustered(rng, 4), np.arange(4))
    with pytest.raises(RuntimeError, match="non-empty"):
        idx.train(clustered(rng, 256))
    plain = sivf.Index(dataclasses.replace(cfg, pq=None), cents)
    with pytest.raises(RuntimeError, match="pq"):
        plain.train(clustered(rng, 256))
    with pytest.raises(ValueError, match="pq_codebooks"):
        sivf.Index(dataclasses.replace(cfg, pq=None), cents,
                   pq_codebooks=np.zeros((4, 16, 4), np.float32))
