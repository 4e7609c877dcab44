"""``tiered_sweep``: host-resident cold store vs device hot cache (ISSUE 8).

Sweeps the *working set* (live slabs) across 0.25x / 0.5x / 1x / 2x of a
fixed device cache budget and measures, per ratio:

  * steady-state **hit rate** of the probe-driven prefetch (counter
    deltas over the timed region only, after a full warmup rotation);
  * search **QPS** through the tiered path, next to the all-resident
    twin's QPS on the identical query schedule;
  * **parity** — every timed batch is compared bit-for-bit (ids AND
    distances) against the all-resident twin; the recorded value is 1.0
    only if every batch matched, so the gate turns any residency bug
    into a hard CI failure.

Queries model temporal locality (the regime a tiered cache serves):
each batch targets one cluster "window", and successive batches rotate
through the windows. At <=1x the whole index becomes resident and the
timed region runs at hit rate ~1.0 with zero uploads; at 2x the rotation
forces LRU eviction and the hit rate measures how much of the working
set survives a full cycle.

Writes ``BENCH_tiered.json`` via ``benchmarks/run.py tiered_sweep``.
"""
from __future__ import annotations

import time

import jax
import numpy as np

import sivf
from benchmarks.common import Row
from repro.obs import latency_summary_ms

DIM = 32
N_LISTS = 8
CAPACITY = 64
DEVICE_SLABS = 64               # fixed hot-cache budget (slabs)
K, NPROBE = 10, 2
Q = 64                          # bucket-aligned batch (no pad rows probe)
RATIOS = {"r025": 0.25, "r05": 0.5, "r10": 1.0, "r20": 2.0}
TIMED_ROTATIONS = 6             # full window cycles in the timed region


def _build_pair(rng, n: int):
    """(tiered, all-resident) twins over the same ``n`` vectors."""
    n_slabs = 2 * int(2.0 * DEVICE_SLABS) + N_LISTS    # fits the 2x point
    cents = rng.normal(size=(N_LISTS, DIM)).astype(np.float32) * 4.0
    kw = dict(dim=DIM, n_lists=N_LISTS, n_slabs=n_slabs, capacity=CAPACITY,
              n_max=1 << 18)
    it = sivf.Index(sivf.SIVFConfig(device_slabs=DEVICE_SLABS, **kw), cents)
    if_ = sivf.Index(sivf.SIVFConfig(**kw), cents)
    # draw vectors tightly around their centroid so list occupancy is
    # uniform and a window's probes stay inside the window's chains
    owner = np.arange(n) % N_LISTS
    vecs = (cents[owner] + 0.1 * rng.normal(size=(n, DIM))).astype(
        np.float32)
    ids = np.arange(n, dtype=np.int32)
    for idx in (it, if_):
        r = idx.add(vecs, ids)
        assert r.ok, r
    return it, if_, cents


def _query_schedule(rng, cents) -> list[np.ndarray]:
    """One bucket-aligned batch per cluster window, cycling all lists."""
    return [(cents[w] + 0.1 * rng.normal(size=(Q, DIM))).astype(np.float32)
            for w in range(N_LISTS)]


def _run_point(rng, ratio_key: str, ratio: float):
    n = int(ratio * DEVICE_SLABS * CAPACITY)
    it, if_, cents = _build_pair(rng, n)
    batches = _query_schedule(rng, cents)

    def sweep(idx, lats=None):
        out = []
        for qs in batches:
            t = time.perf_counter()
            res = idx.search(qs, k=K, nprobe=NPROBE)
            out.append((np.asarray(res.labels), np.asarray(res.distances)))
            if lats is not None:        # np.asarray above forced the sync
                lats.append(time.perf_counter() - t)
        return out

    sweep(it), sweep(if_)                       # warmup: jit + cache fill
    s0 = it.stats()
    batch_lats: list[float] = []
    t0 = time.perf_counter()
    for _ in range(TIMED_ROTATIONS):
        got = sweep(it, batch_lats)
    t_tiered = time.perf_counter() - t0
    s1 = it.stats()
    t0 = time.perf_counter()
    for _ in range(TIMED_ROTATIONS):
        ref = sweep(if_)
    t_full = time.perf_counter() - t0

    parity = all(np.array_equal(g[0], r[0]) and np.array_equal(g[1], r[1])
                 for g, r in zip(got, ref))
    dh = s1["cache_hits"] - s0["cache_hits"]
    dm = s1["cache_misses"] - s0["cache_misses"]
    nq = TIMED_ROTATIONS * len(batches) * Q
    point = {
        "n_vectors": n,
        "slabs_used": int(it.stats()["slabs_used"]),
        "working_set_ratio": round(ratio, 4),
        "hit_rate": round(dh / max(dh + dm, 1), 4),
        "uploads_per_rotation": round(
            (s1["cache_uploads"] - s0["cache_uploads"]) / TIMED_ROTATIONS,
            2),
        "qps": round(nq / t_tiered, 1),
        "all_resident_qps": round(nq / t_full, 1),
        "parity": 1.0 if parity else 0.0,
    }
    point.update(latency_summary_ms(batch_lats))    # per-batch, shared math
    row = Row(
        f"tiered_sweep.{ratio_key}", t_tiered / nq,
        f"ws={ratio:g}x hit_rate={point['hit_rate']:.3f} "
        f"qps={point['qps']:.0f} full={point['all_resident_qps']:.0f}qps "
        f"batch_p99={point['p99_ms']}ms "
        f"parity={'OK' if parity else 'FAIL'}")
    return row, point


def tiered_sweep_summary():
    """-> (rows, summary dict) for ``BENCH_tiered.json``."""
    rng = np.random.default_rng(0)
    rows, ratios = [], {}
    for key, ratio in RATIOS.items():
        row, point = _run_point(rng, key, ratio)
        rows.append(row)
        ratios[key] = point
    bad = [k for k, p in ratios.items() if p["parity"] != 1.0]
    if bad:        # benchmarks/run.py turns this into a non-zero exit
        raise AssertionError(
            f"tiered search diverged from the all-resident pool at "
            f"{','.join(bad)} — residency bug")
    mem = sivf.memory_report(sivf.SIVFConfig(
        dim=DIM, n_lists=N_LISTS, n_slabs=2 * int(2.0 * DEVICE_SLABS)
        + N_LISTS, capacity=CAPACITY, n_max=1 << 18,
        device_slabs=DEVICE_SLABS))
    summary = {
        "dim": DIM, "n_lists": N_LISTS, "capacity": CAPACITY,
        "device_slabs": DEVICE_SLABS, "k": K, "nprobe": NPROBE,
        "queries_per_batch": Q,
        "host_bytes": mem["host_bytes"],
        "device_cache_bytes": mem["device_cache_bytes"],
        "ratios": ratios,
        "backend": jax.default_backend(),
    }
    return rows, summary
