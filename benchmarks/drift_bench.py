"""``drift_sweep``: recall under distribution drift, maintained vs frozen.

The ISSUE 10 acceptance benchmark. A sliding-window stream draws vectors
from Gaussian clusters whose means random-walk every step, so the
coarse quantizer trained at t=0 goes progressively stale. Because insert
and query routing share the quantizer, staleness does not show up as a
routing error — it shows up as *pileup*: drifted clusters collide onto
the few frozen centroids nearest their new positions, the hot lists hit
the ``max_chain`` bound, and (batch admission being atomic) whole
batches start bouncing. Dropped rows are exactly the rows the client
expects to be searchable, so recall vs the brute-force oracle over the
intended window decays. Two twin indexes consume the *identical*
mutation stream:

  * **maintained** — runs ``Index.maintain`` (the occupancy-driven
    split / merge / recluster policy) after every step, and answers an
    aborted batch with a maintenance pass + retry (the serving recovery
    loop: split the hot list, re-admit);
  * **frozen** — never maintains; its centroids are the t=0 snapshot
    and an aborted batch is simply lost.

Per step we record recall@10 against the exact brute-force top-k over
the live window (the rows the *stream* says are live, not the rows the
index managed to keep). The claim under test: the maintained index
holds recall at the end of the schedule (>= 0.95, asserted in-bench, so
the harness exits non-zero on regression) while the frozen baseline visibly
decays below it — drift is the signal, maintenance is the fix.

Also recorded: search executable counts for both twins (maintenance
must not mint per-epoch executables) and per-step maintenance op
outcomes. Writes ``BENCH_drift.json`` via
``PYTHONPATH=src python -m benchmarks.run drift_sweep``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import sivf
from benchmarks.common import Row

DIM = 16
N_LISTS = 16
N_CLUSTERS = 12
WINDOW = 3072                  # live rows (sliding)
BATCH = 768                    # rows inserted (and evicted) per step
STEPS = 12
Q = 64
K = 10
NPROBE = 4                     # << N_LISTS
SIGMA = 2.0                    # per-step cluster-mean random-walk scale
SPREAD = 0.35                  # intra-cluster noise
MAX_CHAIN = 14                 # 448 rows/list: pileup hits this bound
MAINT_OPS = 6                  # policy budget per step
RETRIES = 4                    # maintain+retry attempts per aborted batch
RECALL_FLOOR = 0.95            # ISSUE acceptance bar (end of schedule)
DECAY_MARGIN = 0.05            # frozen must fall at least this far behind


def _draw(rng, means, n):
    which = rng.integers(0, len(means), size=n)
    return (means[which] + SPREAD * rng.normal(size=(n, DIM))
            ).astype(np.float32)


def _admit(idx, vecs, ids):
    """Add with the serving recovery loop: on an atomic abort, split the
    hottest list into the coldest and retry the identical batch."""
    for _ in range(RETRIES):
        if idx.add(vecs, ids).ok:
            return True
        occ = np.asarray(idx.stats()["list_occupancy"])
        idx.maintain(ops=[sivf.split(int(occ.argmax()), int(occ.argmin()))])
    return bool(idx.add(vecs, ids).ok)


def _recall(idx, qs, live_ids, live_vecs):
    d = ((qs[:, None] - live_vecs[None]) ** 2).sum(-1)
    order = np.argsort(d, axis=1, kind="stable")[:, :K]
    true = live_ids[order]                       # [Q, K] external ids
    pred = np.asarray(idx.search(qs, K, NPROBE).labels)
    hits = sum(len(set(pred[i].tolist()) & set(true[i].tolist()))
               for i in range(len(qs)))
    return hits / (len(qs) * K)


def drift_sweep_summary():
    """-> (rows, summary dict) for ``BENCH_drift.json``."""
    rng = np.random.default_rng(0)
    means = rng.normal(size=(N_CLUSTERS, DIM)).astype(np.float32) * 4.0

    cfg = sivf.SIVFConfig(dim=DIM, n_lists=N_LISTS, n_slabs=256,
                          capacity=32, n_max=1 << 14, max_chain=MAX_CHAIN)
    seed_vecs = _draw(rng, means, WINDOW)
    cents = np.asarray(sivf.train_kmeans(
        jax.random.key(0), jnp.asarray(seed_vecs), N_LISTS))

    # Bootstrap one index to a healthy layout (the seed k-means may glue
    # clusters past the chain bound; _admit splits its way out), then
    # clone the settled state into BOTH twins. The frozen baseline is a
    # *well-built* static index — it lacks only online maintenance.
    boot = sivf.Index(cfg, cents, min_bucket=Q)
    ids = np.arange(WINDOW, dtype=np.int32)
    half = WINDOW // 2
    assert _admit(boot, seed_vecs[:half], ids[:half])
    assert _admit(boot, seed_vecs[half:], ids[half:])
    boot.maintain(max_ops=MAINT_OPS)
    snap = jax.tree.map(np.asarray, boot.state)
    cents0 = np.asarray(snap.centroids)
    maintained = sivf.Index(cfg, cents0, min_bucket=Q,
                            _state=jax.tree.map(jnp.asarray, snap))
    frozen = sivf.Index(cfg, cents0, min_bucket=Q,
                        _state=jax.tree.map(jnp.asarray, snap))

    live: dict[int, np.ndarray] = {}
    live.update(zip(ids.tolist(), seed_vecs))
    next_id = WINDOW

    rows, steps, ops_log = [], [], []
    frozen_lost = 0
    for step in range(1, STEPS + 1):
        means = means + SIGMA * rng.normal(size=means.shape).astype(
            np.float32)
        vecs = _draw(rng, means, BATCH)
        ids = np.arange(next_id, next_id + BATCH, dtype=np.int32)
        next_id += BATCH
        evict = np.asarray(sorted(live)[:BATCH], np.int32)
        for idx in (maintained, frozen):
            idx.remove(evict)
        for i in evict.tolist():
            live.pop(i)
        live.update(zip(ids.tolist(), vecs))

        # frozen: an aborted batch is simply lost (nothing to retry with)
        if not frozen.add(vecs, ids).ok:
            frozen_lost += BATCH
        # maintained: abort -> split the hot list -> retry the identical
        # batch (_admit); plus one policy-planned tracking pass per step
        if not _admit(maintained, vecs, ids):
            raise AssertionError(
                f"maintained index failed admission at step {step} even "
                f"after {RETRIES} split+retry rounds")
        reps = maintained.maintain(max_ops=MAINT_OPS, strict=False)
        ops_log.append([(r.kind, r.committed, r.rows) for r in reps])

        # queries follow the *window* distribution (sampled live rows +
        # noise), not just the newest batch — rows a frozen index dropped
        # stay query targets for as long as the stream says they're live
        live_ids = np.fromiter(live.keys(), np.int32)
        live_vecs = np.stack([live[int(i)] for i in live_ids])
        pick = rng.integers(0, len(live_ids), Q)
        qs = (live_vecs[pick] +
              SPREAD * rng.normal(size=(Q, DIM))).astype(np.float32)
        rm = _recall(maintained, qs, live_ids, live_vecs)
        rf = _recall(frozen, qs, live_ids, live_vecs)
        m_occ = maintained.stats()["list_occupancy"]
        f_occ = frozen.stats()["list_occupancy"]
        steps.append({"step": step, "maintained_recall_at_10": round(rm, 4),
                      "frozen_recall_at_10": round(rf, 4),
                      "maintenance_ops": len(reps),
                      "committed_ops": sum(1 for r in reps if r.committed),
                      "frozen_rows_lost": frozen_lost,
                      "maintained_n_live": int(maintained.stats()["n_live"]),
                      "frozen_n_live": int(frozen.stats()["n_live"]),
                      "maintained_max_occ": int(max(m_occ)),
                      "frozen_max_occ": int(max(f_occ))})
        print(f"# drift step {step}: maintained={rm:.3f} frozen={rf:.3f} "
              f"max_occ m={max(m_occ)} f={max(f_occ)} lost={frozen_lost}",
              flush=True)

    final_m = steps[-1]["maintained_recall_at_10"]
    final_f = steps[-1]["frozen_recall_at_10"]
    decayed = 1.0 if final_f <= final_m - DECAY_MARGIN else 0.0
    rows.append(Row(
        "drift_sweep.final", 0.0,
        f"maintained={final_m:.3f} frozen={final_f:.3f} "
        f"steps={STEPS} nprobe={NPROBE}/{N_LISTS} "
        f"decayed={'YES' if decayed else 'NO'}"))

    # regression in either direction fails the harness run
    if final_m < RECALL_FLOOR:
        raise AssertionError(
            f"maintained recall@10 {final_m:.3f} < {RECALL_FLOOR} at end "
            f"of drift schedule — maintenance stopped tracking drift")
    if not decayed:
        raise AssertionError(
            f"frozen baseline did not decay (frozen={final_f:.3f} vs "
            f"maintained={final_m:.3f}) — the drift schedule lost its "
            f"witness and the benchmark proves nothing")

    summary = {
        "dim": DIM, "n_lists": N_LISTS, "n_clusters": N_CLUSTERS,
        "window": WINDOW, "batch": BATCH, "steps_total": STEPS,
        "k": K, "nprobe": NPROBE, "sigma": SIGMA,
        "maint_ops_per_step": MAINT_OPS,
        "steps": steps,
        "final": {
            "maintained_recall_at_10": final_m,
            "frozen_recall_at_10": final_f,
            "recall_gap": round(final_m - final_f, 4),
            "decayed": decayed,
            "frozen_rows_lost": frozen_lost,
        },
        # counters are shared across the twins (identical cfg) — one
        # number bounds both: maintenance must not mint executables
        "jit": {
            "search_executables": maintained.compile_stats()["search"],
        },
        "maintenance": {
            "total_ops": sum(len(o) for o in ops_log),
            "committed_ops": sum(c for s in steps
                                 for c in [s["committed_ops"]]),
        },
    }
    return rows, summary
