"""Fused scan->top-k kernel vs the XLA streaming reference.

The fused Pallas kernel (kernels/sivf_scan/fused.py) must match
``core.index.scan_slabs_topk`` — the jnp register-top-k analogue — on
distances AND labels, including deleted-slot masking, empty chains,
``k > n_live`` padding, ragged query counts (block_q padding path), and
tables whose empty entries the kernel skips without a copy.
"""
import jax.numpy as jnp
import numpy as np
import pytest

import parity
from repro import core
from repro.kernels.sivf_scan import ops as scan_ops

pytestmark = pytest.mark.pallas

D, NL = 16, 4


def make(rng, capacity=32, metric="l2", n_slabs=24, max_chain=8):
    """Build/churn scaffolding lives in tests/parity.py (shared by the
    pq / filters / tiered suites)."""
    return parity.make_state(rng, dim=D, n_lists=NL, n_slabs=n_slabs,
                             capacity=capacity, metric=metric,
                             max_chain=max_chain)


def load(cfg, state, rng, n, lists=None):
    state, _, _ = parity.load_rows(cfg, state, rng, n, lists=lists)
    return state


def assert_fused_matches_ref(cfg, state, rng, k, nprobe, q=5, block_q=8,
                             use_tables=True, reshape=None):
    qs = jnp.asarray(rng.normal(size=(q, D)).astype(np.float32))
    lists = core.probe(state.centroids, qs, nprobe, cfg.metric)
    table = (core.gather_tables if use_tables else core.walk_chains)(
        cfg, state, lists)
    if reshape is not None:
        table = reshape(table)
    dr, lr = core.scan_slabs_topk(cfg, state, qs, table, k)
    df, lf = scan_ops.sivf_fused_search(
        qs, table, state.data, state.ids, state.norms, state.bitmap, k,
        metric=cfg.metric, block_q=block_q, interpret=True)
    np.testing.assert_allclose(np.asarray(df), np.asarray(dr), rtol=1e-5,
                               atol=1e-5)
    assert (np.asarray(lf) == np.asarray(lr)).all()
    return np.asarray(df), np.asarray(lf)


@pytest.mark.parametrize("metric", ["l2", "ip"])
@pytest.mark.parametrize("capacity", [32, 64])
def test_fused_parity_metrics(rng, metric, capacity):
    cfg, state = make(rng, capacity=capacity, metric=metric)
    state = load(cfg, state, rng, 200)
    assert_fused_matches_ref(cfg, state, rng, k=7, nprobe=2)


@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_fused_deleted_slot_masking(rng, metric):
    """Deleted ids must never surface: bitmap masking inside the kernel."""
    cfg, state = make(rng, metric=metric)
    state = load(cfg, state, rng, 200)
    dels = np.arange(0, 200, 3, dtype=np.int32)
    state = core.delete(cfg, state, jnp.asarray(dels))
    _, lf = assert_fused_matches_ref(cfg, state, rng, k=9, nprobe=NL)
    live = lf[lf >= 0]
    assert not np.isin(live, dels).any()


def test_fused_empty_chains(rng):
    """Probing empty lists yields -1 slab rows -> +inf / -1 results."""
    cfg, state = make(rng)
    # route everything into a single list so the other probed chains are empty
    state = load(cfg, state, rng, 40, lists=np.zeros((40,), np.int32))
    assert_fused_matches_ref(cfg, state, rng, k=5, nprobe=NL)


def test_fused_fully_empty_index(rng):
    cfg, state = make(rng)
    df, lf = assert_fused_matches_ref(cfg, state, rng, k=4, nprobe=NL)
    assert np.isinf(df).all() and (lf == -1).all()


def test_fused_k_exceeds_n_live(rng):
    """k > live candidates: the tail must pad with +inf / -1."""
    cfg, state = make(rng)
    state = load(cfg, state, rng, 6)
    df, lf = assert_fused_matches_ref(cfg, state, rng, k=16, nprobe=NL)
    assert np.isinf(df[:, -1]).all()            # not enough live vectors
    assert (np.sort(lf, axis=1) != -1).sum(axis=1).max() <= 6


@pytest.mark.parametrize("q,block_q", [(1, 8), (5, 4), (8, 8), (13, 8)])
def test_fused_ragged_query_blocking(rng, q, block_q):
    """Q not divisible by block_q exercises the padding path."""
    cfg, state = make(rng)
    state = load(cfg, state, rng, 150)
    assert_fused_matches_ref(cfg, state, rng, k=5, nprobe=2, q=q,
                             block_q=block_q)


@pytest.mark.parametrize("case", sorted(parity.TABLE_CASES))
def test_fused_parity_sparse_tables(rng, case):
    """-1 runs inside every probed chain, all -1 rows, ragged Q and tied
    duplicates: the skipped steps change no label and no distance."""
    q, block_q, dead, dup = parity.TABLE_CASES[case]
    cfg, state = make(rng)
    vecs = rng.normal(size=(100, D)).astype(np.float32)
    state, _, _ = parity.load_rows(cfg, state, rng, 100, vecs=vecs)
    if dup:
        state, _, _ = parity.load_rows(cfg, state, rng, 100, start=100,
                                       vecs=vecs)
    df, lf = assert_fused_matches_ref(
        cfg, state, rng, k=7, nprobe=NL, q=q, block_q=block_q,
        reshape=lambda t: parity.spread_table(t, rng, NL, dead))
    assert np.isinf(df[list(dead)]).all() and (lf[list(dead)] == -1).all()
    if dup:
        assert (np.diff(df, axis=1) == 0).any()     # ties were resolved


def grid_blocks(table, bq, n_slabs):
    """Walk the kernel's grid over ``table`` ([Q, T], Q a ``bq`` multiple)
    in its order, checking the blocks each step's index maps ask for;
    returns the live steps per row."""
    from repro.kernels.sivf_scan.fused import compact_table, slab_index_maps
    tab = np.asarray(table)
    q, t = tab.shape
    flat = np.asarray(compact_table(jnp.asarray(tab))).reshape(-1)
    rows, payload_ix, meta_ix = slab_index_maps(bq, t, n_slabs)
    prev = ((0, 0, 0), (0, 0))          # what the first step's fetch reads
    live = np.zeros(q, np.int32)
    for qi in range(q):
        want = tab[qi][tab[qi] >= 0]
        for ti in range(t):
            ix = divmod(qi, bq) + (ti, flat)
            got = (tuple(int(v) for v in payload_ix(*ix)),
                   tuple(int(v) for v in meta_ix(*ix)))
            if flat[qi * t + ti] >= 0:
                # a live step reads its own slab, in the row's order
                s = int(want[live[qi]])
                assert got == ((s, 0, 0), (s // rows, 0))
                live[qi] += 1
            else:
                # a dead step asks for the block in hand: no copy is issued
                assert got == prev
            prev = got
    return live


def test_compacted_index_maps_skip_dead_steps(rng):
    """The index maps over a compacted table: each live step maps to its
    own slab, each dead step to the block of the step before it, and the
    live steps are ``_search_impl``'s ``live_entries``."""
    cfg, state = make(rng, n_slabs=48, max_chain=12)
    state = load(cfg, state, rng, 300)
    qs = jnp.asarray(rng.normal(size=(16, D)).astype(np.float32))
    _, _, live = core.index._search_impl(cfg, state, qs, 5, 3, True, "xla",
                                         8)
    table = core.gather_tables(cfg, state,
                               core.probe(state.centroids, qs, 3, cfg.metric))
    assert (grid_blocks(table, 8, cfg.n_slabs) == np.asarray(live)).all()
    dead = [0, 5, 6, 15]
    want = np.asarray(live).copy()
    want[dead] = 0
    got = grid_blocks(parity.spread_table(table, rng, 3, dead), 8,
                      cfg.n_slabs)
    assert (got == want).all() and want.sum() > 0
    for density in (0.0, 0.1, 0.6, 1.0):
        tab = np.where(rng.random((8, 40)) < density,
                       rng.integers(0, cfg.n_slabs, (8, 40)), -1)
        tab[rng.integers(0, 8)] = -1
        got = grid_blocks(tab.astype(np.int32), 4, cfg.n_slabs)
        assert (got == (tab >= 0).sum(axis=1)).all()


def test_fused_pointer_walk_table(rng):
    """The paper-faithful walk_chains table feeds the same fused kernel."""
    cfg, state = make(rng)
    state = load(cfg, state, rng, 150)
    state = core.delete(cfg, state,
                        jnp.asarray(np.arange(0, 150, 2), np.int32))
    assert_fused_matches_ref(cfg, state, rng, k=5, nprobe=NL,
                             use_tables=False)


def test_fused_randomized_churn_workload(rng):
    """Acceptance: randomized insert/delete workloads, fused == reference."""
    cfg, state = make(rng, n_slabs=48, max_chain=12)
    rows: dict = {}
    for step in range(6):
        state, rows = parity.churn(cfg, state, rng, steps=1, rows=rows)
        assert_fused_matches_ref(cfg, state, rng, k=8,
                                 nprobe=int(rng.integers(1, NL + 1)),
                                 q=int(rng.integers(1, 7)))


def test_search_impl_dispatch_parity(rng):
    """core.search impl="pallas_interpret" == impl="xla" end to end."""
    cfg, state = make(rng)
    state = load(cfg, state, rng, 180)
    state = core.delete(cfg, state,
                        jnp.asarray(np.arange(0, 180, 4), np.int32))
    parity.assert_search_parity(cfg, state, rng, k=5, nprobe=3, q=6)


def test_search_impl_rejects_unknown(rng):
    cfg, state = make(rng)
    state = load(cfg, state, rng, 30)
    qs = jnp.asarray(rng.normal(size=(2, D)).astype(np.float32))
    with pytest.raises(ValueError, match="unknown impl"):
        core.search(cfg, state, qs, 3, 1, impl="cuda")


@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_pallas_splits_query_batch_for_smem(rng, monkeypatch, metric):
    """A batch whose slab table exceeds the SMEM budget runs as several
    kernel calls (``lax.map`` over query chunks) and still equals the XLA
    reference, ragged tail included."""
    cfg, state = make(rng, metric=metric)
    state = load(cfg, state, rng, 180)
    t = NL * cfg.max_chain
    # 3 rows per call: 11 queries -> chunks of 3, 3, 3, 2 (+1 pad row)
    monkeypatch.setattr(core.index, "SMEM_TABLE_BYTES", 4 * t * 3)
    assert core.index.smem_rows(t, block_q=8) == 3
    parity.assert_search_parity(cfg, state, rng, k=5, nprobe=NL, q=11)


def test_smem_rows_names_the_limit():
    """Rows are a block_q multiple when they can be; a single row over the
    budget raises an error that names the limit."""
    budget = core.index.SMEM_TABLE_BYTES
    assert core.index.smem_rows(1024, 8) == budget // 4096
    assert core.index.smem_rows(1024, 48) == budget // 4096 // 48 * 48
    assert core.index.smem_rows(budget // 4, 8) == 1
    with pytest.raises(ValueError, match="SMEM_TABLE_BYTES"):
        core.index.smem_rows(budget // 4 + 1, 8)


def test_impl_none_resolves_from_platform():
    """impl=None picks the kernel only on a TPU; the interpreter never
    runs unless named."""
    import jax
    want = "pallas" if jax.default_backend() == "tpu" else "xla"
    assert core.index.resolve_impl(None) == want
    for impl in core.index.SEARCH_IMPLS:
        assert core.index.resolve_impl(impl) == impl
    with pytest.raises(ValueError, match="unknown impl"):
        core.index.resolve_impl("cuda")
