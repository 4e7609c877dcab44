"""Fused scan->top-k kernel vs the XLA streaming reference.

The fused Pallas kernel (kernels/sivf_scan/fused.py) must match
``core.index.scan_slabs_topk`` — the jnp register-top-k analogue — on
distances AND labels, including deleted-slot masking, empty chains,
``k > n_live`` padding, and ragged query counts (block_q padding path).
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
                             use_tables=True):
    qs = jnp.asarray(rng.normal(size=(q, D)).astype(np.float32))
    lists = core.probe(state.centroids, qs, nprobe, cfg.metric)
    table = (core.gather_tables if use_tables else core.walk_chains)(
        cfg, state, lists)
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
