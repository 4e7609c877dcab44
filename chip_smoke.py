"""Smoke run of SIVF's served path on a TPU.

    python chip_smoke.py              # one chip, SIFT1M shape
    python chip_smoke.py --chips 4    # only the four-shard mesh phase

One process drives the public API (``sivf.Index`` -> ``ServeEngine``) at
the shape of ANN-benchmarks ``sift-128-euclidean`` (1,000,000 x 128-d fp32,
L2, k=10), with a Gaussian-mixture stream generated from ``--seed``
(``repro.data.pipeline.VectorStream``). The index has 1024 lists and a
24,576-slab pool of 64-slot slabs (max_chain=64), and each row carries a
``tenant`` attribute (id % 8). Searches probe 16 lists.

One chip:
  1. k-means on a 64k sample, then the 1M rows through ``Index.add`` in
     62,500-row batches (each pads to the 64k bucket); every report must
     accept its whole batch;
  2. a few hundred single-query searches from two client threads through
     ``ServeEngine`` while one more batch lands;
  3. removal of 10% of the ids; no removed id may come back, and
     ``stats()`` must count the live rows;
  4. checks against references on the same state: the Pallas kernel's
     ``[Q, k]`` against ``impl="xla"`` (within f32 rounding, below),
     recall@10 >= 0.99 with every list probed against a brute-force oracle
     computed on the device, one filtered batch against its XLA reference,
     and one batch of a PQ (m=16) index against its XLA reference.

``--chips 4`` runs only the sharded path: 4M rows on a 4-shard mesh built
from ``jax.devices()``, then search and delete, compared with the same
stream on a one-device index; ids and distances must be bit-identical.

The script exits non-zero, printing no result, unless JAX's first device
is a TPU. Its last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
If ``JAX_COMPILATION_CACHE_DIR`` is unset, compiled programs are cached in
``.jax_cache`` beside this file.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent


class SmokeFailure(RuntimeError):
    """A check of the smoke run did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


@dataclasses.dataclass(frozen=True)
class Scale:
    """Sizes of one smoke phase; the defaults are the SIFT1M deployment."""

    rows: int = 1_000_000        # base rows ingested before serving
    batch: int = 62_500          # rows per Index.add / Index.remove call
    sample: int = 65_536         # k-means training sample
    dim: int = 128
    n_lists: int = 1024
    n_slabs: int = 24_576
    capacity: int = 64
    max_chain: int = 64
    nprobe: int = 16
    k: int = 10
    served: int = 256            # single-query searches per client thread
    check_q: int = 64            # queries of each reference comparison
    # Gaussian-mixture components of the stream. With 4096 well-separated
    # components, k-means centroids averaging several of them attract far
    # more rows than the rest (a list passed max_chain * C = 4096 rows by
    # 437k rows); with 16 the fullest of the 1024 lists holds 2.7x the
    # mean (45 of 64 slabs at 1.06M rows).
    clusters: int = 16
    tenants: int = 8             # attribute "tenant" = id % tenants
    pq_m: int = 16

    @property
    def n_max(self) -> int:
        """Dense id space: base rows plus the batch ingested while serving."""
        return 1 << (self.rows + self.batch - 1).bit_length()

    def config(self, **kw):
        import sivf
        return sivf.SIVFConfig(
            dim=self.dim, n_lists=self.n_lists, n_slabs=self.n_slabs,
            capacity=self.capacity, max_chain=self.max_chain,
            n_max=self.n_max, attributes=("tenant",), **kw)


ONE_CHIP = Scale()
# 4M rows: each of the four shards holds SIFT1M's 1M rows; the one-device
# twin holds all 4M, hence 4x the slabs and a longer chain bound
FOUR_CHIPS = dataclasses.replace(ONE_CHIP, rows=4_000_000, n_slabs=98_304,
                                 max_chain=256)


def stream(s: Scale, seed: int):
    from repro.data.pipeline import VectorStream, VectorStreamConfig
    return VectorStream(VectorStreamConfig(seed=seed, dim=s.dim,
                                           n_clusters=s.clusters))


def make_rows(s: Scale, vs, n: int, step0: int):
    """``n`` rows of the stream, made ``s.batch`` rows per stream step."""
    import numpy as np
    return np.concatenate([vs.batch(step0 + i, min(s.batch, n - lo))
                           for i, lo in enumerate(range(0, n, s.batch))])


def ingest(index, s: Scale, vecs, first_id: int) -> None:
    """Add ``vecs`` with ids from ``first_id`` in ``s.batch``-row calls;
    every report must accept its whole batch."""
    import numpy as np
    pending = []
    for lo in range(0, len(vecs), s.batch):
        v = vecs[lo:lo + s.batch]
        ids = np.arange(first_id + lo, first_id + lo + len(v), dtype=np.int32)
        pending.append((index.add(v, ids, attrs={"tenant": ids % s.tenants}),
                        len(v)))
    if index.deferred:
        index.flush()
    for rep, m in pending:
        rep = rep.result() if index.deferred else rep
        check(rep.ok and rep.accepted == m,
              f"add accepted {rep.accepted} of {m}: {rep}")


def f32_tol(queries, max_norm: float):
    """Per-query bound on the f32 rounding of ``||q||^2 - 2 q.x + ||x||^2``
    summed in any order: ``2 * D * eps * (||q||^2 + max ||x||^2)``."""
    import numpy as np
    qq = np.sum(np.asarray(queries, np.float64) ** 2, axis=1)
    d = queries.shape[1]
    return 2 * d * np.finfo(np.float32).eps * (qq + max_norm)


def compare(name: str, got, ref, tol) -> float:
    """Distances agree within ``tol`` at every rank; labels agree wherever
    the rank's distance is not tied within ``tol`` with a neighbour rank."""
    import numpy as np
    gd, gl = (np.asarray(x) for x in got)
    rd, rl = (np.asarray(x) for x in ref)
    check(gd.shape == rd.shape and gl.shape == rl.shape,
          f"{name}: shapes {gd.shape} vs {rd.shape}")
    fin = np.isfinite(rd)
    check(np.array_equal(fin, np.isfinite(gd)), f"{name}: inf pattern")
    t = tol[:, None]
    diff = np.where(fin, np.abs(gd - rd), 0.0)
    check(bool(np.all(diff <= t)),
          f"{name}: max |d - d_ref| {diff.max()} above tolerance")
    gap = np.abs(np.diff(rd, axis=1))
    tied = np.zeros_like(fin)
    tied[:, 1:] |= gap <= 2 * t
    tied[:, :-1] |= gap <= 2 * t
    bad = (gl != rl) & ~tied
    check(not bad.any(), f"{name}: {int(bad.sum())} labels differ at "
          f"untied ranks")
    return float(diff.max())


def pct(xs, q):
    import numpy as np
    return float(np.percentile(np.asarray(xs), q))


def one_chip(s: Scale, seed: int, impl=None) -> dict:
    """The served path on one device; returns the measured numbers."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import sivf
    from repro import core
    from repro.core import filters as flt
    from repro.serve.sivf_engine import ServeEngine

    out = {}
    vs = stream(s, seed)
    t = time.perf_counter()
    base = make_rows(s, vs, s.rows, 0)
    extra = vs.batch(1_000, s.batch)             # lands while serving
    extra_ids = np.arange(s.rows, s.rows + s.batch, dtype=np.int32)
    queries = vs.batch(2_000, 2 * s.served)
    out["datagen_s"] = time.perf_counter() - t
    max_norm = float(max(np.max(np.sum(base ** 2, 1)),
                         np.max(np.sum(extra ** 2, 1))))

    # -- build: k-means on a sample, then the base rows ---------------------
    t = time.perf_counter()
    cents = sivf.train_kmeans(jax.random.key(seed),
                              jnp.asarray(base[:s.sample]), s.n_lists)
    cents.block_until_ready()
    out["kmeans_s"] = time.perf_counter() - t
    cfg = s.config()
    index = sivf.Index(cfg, cents, impl=impl, deferred=True)
    out["impl"] = index.impl
    t = time.perf_counter()
    ingest(index, s, base[:s.batch], 0)          # the first batch compiles
    out["ingest_first_batch_s"] = time.perf_counter() - t
    t = time.perf_counter()
    ingest(index, s, base[s.batch:], s.batch)
    dt = time.perf_counter() - t
    out["ingest_s"] = dt
    out["ingest_rows_per_s"] = (s.rows - s.batch) / dt
    check(index.n_live == s.rows, f"n_live {index.n_live} != {s.rows}")
    # fullest list against the max_chain * capacity bound
    out["list_rows_max"] = max(index.stats()["list_occupancy"])

    # -- serve: two client threads while one more batch lands ---------------
    t = time.perf_counter()
    for q in (64, 128, 256):                     # the engine's tile buckets
        np.asarray(index.search(queries[:q], s.k, s.nprobe).labels)
    out["search_warmup_s"] = time.perf_counter() - t
    served = [None, None]
    errors = []

    def reader(i, sess):
        try:
            res = []
            rows = queries[i * s.served:(i + 1) * s.served]
            for lo in range(0, s.served, 32):     # 32 requests in flight
                futs = [sess.search(rows[j:j + 1], k=s.k, nprobe=s.nprobe)
                        for j in range(lo, min(lo + 32, s.served))]
                res += [f.result(timeout=600) for f in futs]
            served[i] = res
        except Exception as e:           # reported by the main thread
            errors.append(e)

    t = time.perf_counter()
    with ServeEngine(index, default_k=s.k, default_nprobe=s.nprobe) as eng:
        threads = [threading.Thread(target=reader, args=(i, eng.session(
            f"reader{i}"))) for i in range(2)]
        for th in threads:
            th.start()
        wfut = eng.session("writer").add(
            extra, extra_ids, attrs={"tenant": extra_ids % s.tenants})
        for th in threads:
            th.join(timeout=900)
        check(not any(th.is_alive() for th in threads), "reader hung")
        wres = wfut.result(timeout=900)
    out["serve_s"] = time.perf_counter() - t
    if errors:
        raise errors[0]
    check(wres.ok and wres.report.accepted == s.batch,
          f"served add: {wres.report}")
    res = served[0] + served[1]
    lat = [r.queue_s + r.service_s for r in res]
    out["served_searches"] = len(res)
    out["search_p50_ms"] = pct(lat, 50) * 1e3
    out["search_p99_ms"] = pct(lat, 99) * 1e3
    out["mean_coalesced"] = float(np.mean([r.coalesced for r in res]))
    for r in res:
        check(r.labels.shape == (1, s.k) and np.all(np.isfinite(
            r.distances)) and np.all(r.labels >= 0), "served result")
    n_all = s.rows + s.batch
    check(index.n_live == n_all, f"n_live {index.n_live} != {n_all}")

    # -- delete 10% of the ids ----------------------------------------------
    rng = np.random.default_rng(seed)
    gone = rng.choice(n_all, n_all // 10, replace=False).astype(np.int32)
    t = time.perf_counter()
    futs = [index.remove(gone[lo:lo + s.batch])
            for lo in range(0, len(gone), s.batch)]
    index.flush()
    out["delete_s"] = time.perf_counter() - t
    out["delete_rows_per_s"] = len(gone) / out["delete_s"]
    check(sum(f.result().accepted for f in futs) == len(gone),
          "remove accepted count")
    live = np.ones(n_all, bool)
    live[gone] = False
    n_live = int(live.sum())
    st = index.stats()
    check(st["n_live"] == n_live == index.n_live
          and sum(st["list_occupancy"]) == n_live,
          f"stats live {st['n_live']} / occupancy "
          f"{sum(st['list_occupancy'])} != {n_live}")

    # -- references on the same state ---------------------------------------
    q = queries[:s.check_q]
    tol = f32_tol(q, max_norm)
    got = index.search(q, s.k, s.nprobe)
    ref = core.search(cfg, index.state, jnp.asarray(q), s.k, s.nprobe,
                      impl="xla")
    out["kernel_vs_xla_max_abs"] = compare("kernel vs xla", got, ref, tol)

    pred = sivf.Eq("tenant", 3)
    cf = flt.compile_filter(pred, cfg.attributes)
    got_f = index.search(q, s.k, s.nprobe, filter=pred)
    ref_f = core.search(cfg, index.state, jnp.asarray(q), s.k, s.nprobe,
                        impl="xla", fstruct=cf.structure,
                        fconsts=jnp.asarray(cf.consts, jnp.int32))
    out["filtered_vs_xla_max_abs"] = compare("filtered", got_f, ref_f, tol)
    lab_f = np.asarray(got_f.labels)
    check(np.all((lab_f % s.tenants == 3) | (lab_f < 0)),
          "filtered result outside its tenant")

    t = time.perf_counter()
    full = index.search(q, s.k, None)            # every list probed
    full_l = np.asarray(full.labels)
    out["full_probe_search_s"] = time.perf_counter() - t
    xs = jnp.asarray(np.concatenate([base, extra]))
    qd = jnp.asarray(q)
    d = (jnp.sum(qd * qd, 1, keepdims=True)
         - 2 * jnp.matmul(qd, xs.T, precision=jax.lax.Precision.HIGHEST)
         + jnp.sum(xs * xs, 1)[None])
    d = jnp.where(jnp.asarray(live)[None], d, jnp.inf)
    _, oracle = jax.lax.top_k(-d, s.k)
    oracle = np.asarray(oracle)
    hits = sum(len(set(a) & set(b)) for a, b in zip(full_l, oracle))
    out["recall_at_10_full_probe"] = hits / oracle.size
    check(out["recall_at_10_full_probe"] >= 0.99,
          f"recall@10 {out['recall_at_10_full_probe']} < 0.99")
    probed = np.asarray(index.search(queries[:256], s.k, s.nprobe).labels)
    for lab in (got.labels, got_f.labels, full_l, probed):
        lab = np.asarray(lab)
        check(not np.isin(lab[lab >= 0], gone).any(),
              "a removed id was returned")
    hits = sum(len(set(a) & set(b))
               for a, b in zip(np.asarray(got.labels), oracle))
    out["recall_at_10_nprobe"] = hits / oracle.size
    del xs, d

    # -- PQ (m=16) index over the same base rows ----------------------------
    cfg_pq = s.config(pq=sivf.PQConfig(m=s.pq_m))
    pq = sivf.Index(cfg_pq, cents, impl=impl)
    t = time.perf_counter()
    pq.train(base[:s.sample])
    ingest(pq, s, base, 0)
    out["pq_build_s"] = time.perf_counter() - t
    got_p = pq.search(q, s.k, s.nprobe)
    ref_p = core.search(cfg_pq, pq.state, jnp.asarray(q), s.k, s.nprobe,
                        impl="xla")
    out["pq_vs_xla_max_abs"] = compare("pq", got_p, ref_p, tol)
    out["pq_bit_exact"] = bool(
        np.array_equal(np.asarray(got_p.distances),
                       np.asarray(ref_p[0]))
        and np.array_equal(np.asarray(got_p.labels), np.asarray(ref_p[1])))
    return out


def four_chips(s: Scale, seed: int, devices, impl=None) -> dict:
    """The same stream into a one-device index, then into a 4-shard mesh.

    The two run one after the other, so the first device never holds the
    one-device pool and its own shard at once."""
    import gc

    import jax
    import jax.numpy as jnp
    import numpy as np

    import sivf

    out = {}
    vs = stream(s, seed)
    base = make_rows(s, vs, s.rows, 0)
    queries = vs.batch(2_000, 4 * s.check_q)
    gone = np.random.default_rng(seed).choice(
        s.rows, s.rows // 10, replace=False).astype(np.int32)
    cents = sivf.train_kmeans(jax.random.key(seed),
                              jnp.asarray(base[:s.sample]), s.n_lists)
    cfg = s.config()

    def drive(name, ix):
        """Ingest, search, delete, search; the two results on the host."""
        t = time.perf_counter()
        ingest(ix, s, base, 0)
        out[f"{name}_ingest_s"] = time.perf_counter() - t
        out[f"{name}_ingest_rows_per_s"] = s.rows / out[f"{name}_ingest_s"]
        check(ix.n_live == s.rows, f"{name}: n_live {ix.n_live}")
        res = [ix.search(queries, s.k, s.nprobe)]
        q = queries[:s.check_q]
        ix.search(q, s.k, s.nprobe).labels.block_until_ready()
        t = time.perf_counter()
        ix.search(q, s.k, s.nprobe).labels.block_until_ready()
        out[f"{name}_search_{s.check_q}q_ms"] = (time.perf_counter() - t) * 1e3
        t = time.perf_counter()
        futs = [ix.remove(gone[lo:lo + s.batch])
                for lo in range(0, len(gone), s.batch)]
        ix.flush()
        out[f"{name}_delete_s"] = time.perf_counter() - t
        check(sum(f.result().accepted for f in futs) == len(gone)
              and ix.n_live == s.rows - len(gone), f"{name}: remove count")
        res.append(ix.search(queries, s.k, s.nprobe))
        return [(np.asarray(r.labels), np.asarray(r.distances)) for r in res]

    ref = drive("single", sivf.Index(cfg, cents, impl=impl, deferred=True))
    gc.collect()                      # free the one-device pool

    mesh = jax.make_mesh((4,), ("data",), devices=devices[:4])
    sharded = sivf.Index(cfg, cents, backend=mesh, impl=impl, deferred=True)
    out["impl"] = sharded.impl
    placed = sharded.state.data.sharding.device_set
    check(placed == set(devices[:4]),
          f"shards on {sorted(d.id for d in placed)}, not four devices")
    got = drive("mesh", sharded)
    per_shard = sharded.stats()["per_shard_live"]
    out["per_shard_live"] = per_shard
    check(sum(per_shard) == s.rows - len(gone) and min(per_shard) > 0,
          f"live counts {per_shard}")
    for tag, a, b in zip(("after ingest", "after delete"), ref, got):
        for x, y in zip(a, b):
            check(np.array_equal(x, y),
                  f"{tag}: mesh and one-device results differ")
    lab = got[1][0]
    check(not np.isin(lab[lab >= 0], gone).any(), "a removed id was returned")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the four-shard mesh phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no SIVF sources under {ROOT / 'src'}; run it "
              f"from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r} "
              f"({dev.device_kind})", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs that many devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}",
          flush=True)
    t = time.perf_counter()
    try:
        if args.chips == 4:
            out = four_chips(FOUR_CHIPS, args.seed, devices)
        else:
            out = one_chip(ONE_CHIP, args.seed)
        check(out["impl"] == "pallas",
              f"searches resolved impl {out['impl']!r}, not the kernel")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    out["total_s"] = time.perf_counter() - t
    for key, val in out.items():
        print(f"{key}: {val}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
