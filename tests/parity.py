"""Shared kernel-parity scaffolding (build → churn → compare impls).

Four suites (test_fused_search / test_pq / test_filters / test_tiered)
grew copy-pasted variants of the same skeleton: build an index, churn
it, then assert that two execution paths return the same ids AND the
same distances. This module is the single copy. The comparison contract
everywhere:

  * labels compare ``==`` exactly — never allclose;
  * distances compare ``==`` (bit-exact) on paths that share the
    summation structure (PQ/ADC: one materialized table feeds both
    impls; tiered: a pure residency layer over identical planes), and
    ``allclose(rtol=atol=1e-5)`` only where fp accumulation order
    legitimately differs (raw-payload XLA vs Pallas fold).

``assert_search_parity`` is the end-to-end form (``core.search`` with
``impl="xla"`` vs ``impl="pallas_interpret"``, optional compiled
filter); the kernel-level single-impl asserts stay in their own suites.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro import core
from repro.core import filters as flt


def make_state(rng, dim=16, n_lists=4, n_slabs=24, capacity=32, n_max=2048,
               max_chain=8, metric="l2", pq=None, attributes=None,
               train=None):
    """(cfg, fresh state) with random centroids; trains PQ if configured."""
    cfg = core.SIVFConfig(dim=dim, n_lists=n_lists, n_slabs=n_slabs,
                          capacity=capacity, n_max=n_max, metric=metric,
                          max_chain=max_chain, pq=pq,
                          attributes=attributes or ())
    cents = rng.normal(size=(n_lists, dim)).astype(np.float32)
    cb = None
    if pq is not None:
        from repro.core import pq as pq_mod
        data = train if train is not None else \
            rng.normal(size=(512, dim)).astype(np.float32)
        cb = pq_mod.train_pq(jax.random.key(0), jnp.asarray(data),
                             pq.m, pq.nbits, iters=8)
    return cfg, core.init_state(cfg, jnp.asarray(cents), cb)


def random_attrs(cfg, rng, n, n_tenants=5):
    """Attribute rows: first column tenant-like, the rest wide ints."""
    cols = [rng.integers(0, n_tenants, n)]
    cols += [rng.integers(0, 100, n) for _ in range(cfg.n_attrs - 1)]
    return np.stack(cols, axis=1).astype(np.int32)


def load_rows(cfg, state, rng, n, start=0, vecs=None, lists=None,
              n_tenants=5):
    """Insert ``n`` rows with ids ``start..start+n``; returns attrs too."""
    if vecs is None:
        vecs = rng.normal(size=(n, cfg.dim)).astype(np.float32)
    attrs = random_attrs(cfg, rng, n, n_tenants) if cfg.n_attrs else None
    state = core.insert(
        cfg, state, jnp.asarray(vecs),
        jnp.asarray(np.arange(start, start + n), np.int32),
        None if lists is None else jnp.asarray(lists, jnp.int32),
        attrs=None if attrs is None else jnp.asarray(attrs))
    return state, vecs, attrs


def churn(cfg, state, rng, steps=4, id_space=512, rows=None):
    """Randomized insert/delete churn; mirrors membership in ``rows``.

    ``rows`` (dict id -> vec) doubles as the oracle the property suites
    diff against; pass an existing dict to continue a schedule.
    """
    rows = {} if rows is None else rows
    nxt = max(rows) + 1 if rows else 0
    for _ in range(steps):
        n_ins = int(rng.integers(8, 40))
        ids = (np.arange(nxt, nxt + n_ins) % id_space).astype(np.int32)
        nxt += n_ins
        vecs = rng.normal(size=(n_ins, cfg.dim)).astype(np.float32)
        state = core.insert(cfg, state, jnp.asarray(vecs),
                            jnp.asarray(ids))
        for i, v in zip(ids.tolist(), vecs):
            rows[i] = v
        if len(rows) > 20:
            dels = rng.choice(sorted(rows), size=8, replace=False)
            state = core.delete(cfg, state, jnp.asarray(dels, np.int32))
            for i in dels.tolist():
                rows.pop(i, None)
        assert int(np.asarray(state.error).max()) == 0
    return state, rows


# Slab-table shapes whose empty entries the scan kernels skip, as parity
# cases: name -> (query rows, block_q, rows made all -1, rows loaded twice
# under new ids so that distances tie)
TABLE_CASES = {
    "interleaved": (5, 8, (), False),
    "dead_rows": (6, 4, (0, 3), False),
    "ragged": (13, 8, (12,), False),
    "duplicates": (9, 4, (), True),
}


def spread_table(table, rng, chains, dead_rows=()):
    """The same live entries in the same order, with -1 runs between them.

    Each of a row's ``chains`` segments (one per probed list) doubles in
    width and holds its live entries at random places in their order, -1
    around them; the rows in ``dead_rows`` become all -1.
    """
    t = np.asarray(table)
    q, w = t.shape
    seg = t.reshape(q, chains, w // chains)
    out = np.full((q, chains, 2 * seg.shape[2]), -1, t.dtype)
    for i in range(q):
        for j in range(chains):
            live = seg[i, j][seg[i, j] >= 0]
            at = np.sort(rng.choice(out.shape[2], live.size, replace=False))
            out[i, j, at] = live
    out = out.reshape(q, -1)
    out[list(dead_rows)] = -1
    return jnp.asarray(out)


def assert_search_parity(cfg, state, rng, k, nprobe, q=5, use_tables=True,
                         block_q=8, pred=None, exact_dist=None,
                         queries=None):
    """``core.search`` xla vs pallas_interpret on identical state.

    Labels must be identical; distances bit-exact on the ADC path (the
    default when PQ is configured), allclose on the raw-payload path.
    Returns the (xla) distances and labels for follow-on asserts.
    """
    if exact_dist is None:
        exact_dist = cfg.pq is not None
    if queries is None:
        queries = rng.normal(size=(q, cfg.dim)).astype(np.float32)
    qs = jnp.asarray(queries)
    kw = {}
    if pred is not None:
        cf = flt.compile_filter(pred, cfg.attributes)
        kw = {"fstruct": cf.structure,
              "fconsts": jnp.asarray(cf.consts, jnp.int32)}
    dx, lx = core.search(cfg, state, qs, k, nprobe, use_tables=use_tables,
                         impl="xla", block_q=block_q, **kw)
    dp, lp = core.search(cfg, state, qs, k, nprobe, use_tables=use_tables,
                         impl="pallas_interpret", block_q=block_q, **kw)
    if exact_dist:
        assert (np.asarray(dp) == np.asarray(dx)).all()
    else:
        np.testing.assert_allclose(np.asarray(dp), np.asarray(dx),
                                   rtol=1e-5, atol=1e-5)
    assert (np.asarray(lp) == np.asarray(lx)).all()
    return np.asarray(dx), np.asarray(lx)


# ---------------------------------------------------------------------------
# Index-handle twins (the tiered-vs-resident form of the same skeleton)
# ---------------------------------------------------------------------------

def assert_results_same(res_a, res_b):
    """Two ``SearchResult``s: ids AND distances ``==`` exactly."""
    assert np.array_equal(np.asarray(res_a.labels),
                          np.asarray(res_b.labels))
    assert np.array_equal(np.asarray(res_a.distances),
                          np.asarray(res_b.distances))


def twin_churn(rng, twins, vecs, ids, attrs=None, attrs_fn=None):
    """The shared mutation schedule over N twin handles: bulk add,
    overwrite, delete, refill (the refill recycles reclaimed slabs —
    dirty-frame coherence on tiered pools)."""
    dim = vecs.shape[1]
    for idx in twins:
        idx.add(vecs, ids, attrs=attrs)
    over = rng.normal(size=(100, dim)).astype(np.float32)
    oa = None if attrs_fn is None else attrs_fn(100)
    for idx in twins:
        idx.add(over, ids[:100], attrs=oa)
        idx.remove(ids[150:300])
    refill = rng.normal(size=(120, dim)).astype(np.float32)
    rid = np.arange(2000, 2120, dtype=np.int32)
    ra = None if attrs_fn is None else attrs_fn(120)
    for idx in twins:
        idx.add(refill, rid, attrs=ra)
    return twins
