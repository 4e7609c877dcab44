"""``serve_churn``: open-loop mixed workload through the serve engine.

The repo's headline number (ISSUE 6): sustained search QPS *during*
ingest at a p99 SLO. Methodology follows the scale-point/SLO-percentile
scheme of the parquet-aggregator benchmark plan (SNIPPETS.md §2):

  * **Open loop.** Search arrivals are scheduled on a fixed-rate clock
    that never waits for completions, so queueing delay is *measured*
    rather than hidden (no coordinated omission). Per-request latency =
    (submit lag behind schedule) + queue wait + service time.
  * **Scale points.** Each arrival rate runs twice — ``idle`` (no
    mutations) then ``active`` (a second tenant streams paced add/remove
    batches through the deferred pipeline) — and records p50/p99/p999
    search latency plus the sustained mutation row throughput.
  * **SLO gate.** The bench itself asserts p99(active) <= 5x p99(idle)
    at every scale point (the paper's search-during-ingest claim) and
    that jit executable counts stay within the engine's coalescing
    bound. A violation raises, which ``benchmarks/run.py`` turns into a
    non-zero exit.

Writes ``BENCH_serve.json`` via ``benchmarks/run.py serve_churn``, plus
``TELEMETRY_serve.json`` — the engine's end-of-run JSON telemetry
snapshot (per-stage histograms, per-tenant counters, slow-query log) —
which the slow CI job uploads as an artifact.
"""
from __future__ import annotations

import json
import threading
import time
from pathlib import Path

import jax
import numpy as np

import sivf
from benchmarks.common import Row
from repro.obs import Telemetry, latency_summary_ms
from sivf import Backpressure, ServeEngine, TenantQuota

DIM = 32
N_LISTS = 32
WINDOW = 16_384
K, NPROBE = 10, 8
MUT_BATCH = 64                  # rows per add (and per remove) batch
MUT_ROWS_PER_S = 1_500          # paced ingest pressure in the active phase
RATES = (50, 100, 200)          # open-loop search arrival rates (QPS)
PHASE_SECONDS = 4.0
SLO_RATIO = 5.0                 # p99 active/idle acceptance bound


def _build_engine(rng):
    n_slabs = int(2.5 * WINDOW / 64) + N_LISTS
    cfg = sivf.SIVFConfig(dim=DIM, n_lists=N_LISTS, n_slabs=n_slabs,
                          capacity=64, n_max=1 << 20)
    train = rng.normal(size=(4096, DIM)).astype(np.float32)
    cents = sivf.train_kmeans(jax.random.key(0), train, N_LISTS)
    # Telemetry stays ON for the whole measured run: the snapshot written
    # at the end (TELEMETRY_serve.json) is a CI artifact, and the bench
    # thereby exercises the <=5% overhead claim under the real SLO gate.
    tel = Telemetry(enabled=True, slow_threshold_s=0.050)
    idx = sivf.Index(cfg, cents, deferred=True, min_bucket=64,
                     telemetry=tel)
    eng = ServeEngine(
        idx, default_k=K, default_nprobe=NPROBE, max_queue=4096,
        max_coalesce=128, flush_every=8,
        quotas={"app": TenantQuota(max_inflight_searches=1024),
                "ingest": TenantQuota()})
    return idx, eng


def _prefill(eng, rng) -> int:
    """Fill the index to its steady-state window; returns next free id."""
    writer = eng.session("ingest")
    futs = []
    for base in range(0, WINDOW, MUT_BATCH):
        vecs = rng.normal(size=(MUT_BATCH, DIM)).astype(np.float32)
        ids = np.arange(base, base + MUT_BATCH, dtype=np.int32)
        futs.append(writer.add(vecs, ids))
    assert all(f.result(600).ok for f in futs)
    return WINDOW


def _warm_executables(eng, rng) -> None:
    """Compile every pow2 search tile (1..max_coalesce) and the mutation
    buckets before measurement, so scale points compare steady-state
    latency, not compile storms."""
    reader = eng.session("warmup")
    sizes = []
    b = 1
    while b <= 128:
        sizes.append(b)
        b *= 2
    futs = [reader.search(
        rng.normal(size=(s, DIM)).astype(np.float32), k=K, nprobe=NPROBE)
        for s in sizes]
    for f in futs:
        f.result(600)


class _IngestLoad:
    """Paced add/remove streamer: ``MUT_ROWS_PER_S`` rows/s in
    ``MUT_BATCH``-row batches, evicting behind a sliding window."""

    def __init__(self, eng, rng, next_id: int):
        self._sess = eng.session("ingest")
        self._rng = rng
        self.next_id = next_id
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._futs: list = []
        self.elapsed = 0.0

    def start(self) -> None:
        self._stop.clear()
        self._futs = []
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self) -> None:
        interval = 2 * MUT_BATCH / MUT_ROWS_PER_S   # add+remove per cycle
        t0 = time.perf_counter()
        cycle = 0
        while not self._stop.is_set():
            sched = t0 + cycle * interval
            now = time.perf_counter()
            if now < sched:
                time.sleep(sched - now)
            vecs = self._rng.normal(size=(MUT_BATCH, DIM)
                                    ).astype(np.float32)
            ids = np.arange(self.next_id, self.next_id + MUT_BATCH,
                            dtype=np.int32)
            evict = ids - WINDOW
            try:
                self._futs.append(self._sess.add(vecs, ids))
                self._futs.append(self._sess.remove(evict))
            except Backpressure:               # shed, keep pacing
                pass
            else:
                self.next_id += MUT_BATCH
            cycle += 1
        self.elapsed = time.perf_counter() - t0

    def stop(self) -> dict:
        self._stop.set()
        self._thread.join()
        add_rows = rm_rows = 0
        for f in self._futs:
            res = f.result(600)
            assert res.ok, res.report
            if res.report.op == "add":
                add_rows += res.report.accepted + res.report.overwritten
            else:
                rm_rows += res.report.accepted
        dt = max(self.elapsed, 1e-9)
        return {"add_rows_per_s": round(add_rows / dt, 1),
                "remove_rows_per_s": round(rm_rows / dt, 1),
                "batches": len(self._futs)}


def _open_loop_searches(eng, rng, rate: float, seconds: float) -> dict:
    """Fixed-rate open-loop search phase; latency includes schedule lag +
    queue wait + service, per request."""
    reader = eng.session("app")
    n = int(rate * seconds)
    pool = [rng.normal(size=(int(rng.integers(1, 5)), DIM)
                       ).astype(np.float32) for _ in range(64)]
    records: list = []
    rejected = 0
    t0 = time.perf_counter()
    for i in range(n):
        sched = t0 + i / rate
        now = time.perf_counter()
        if now < sched:
            time.sleep(sched - now)
            now = sched
        try:
            fut = reader.search(pool[i % len(pool)])
        except Backpressure:
            rejected += 1
            continue
        records.append((now - sched, fut))
    lats = []
    for lag, fut in records:
        res = fut.result(600)
        lats.append(lag + res.queue_s + res.service_s)
    wall = time.perf_counter() - t0
    out = {"requests": n, "rejected": rejected,
           "achieved_qps": round(len(lats) / wall, 1)}
    out.update(latency_summary_ms(lats))        # shared obs percentile math
    return out


def serve_churn_summary():
    """(rows, summary) for ``BENCH_serve.json`` — see module docstring."""
    rng = np.random.default_rng(11)
    idx, eng = _build_engine(rng)
    rows, scale_points = [], []
    try:
        next_id = _prefill(eng, rng)
        _warm_executables(eng, rng)
        for rate in RATES:
            idle = _open_loop_searches(eng, rng, rate, PHASE_SECONDS)
            load = _IngestLoad(eng, rng, next_id)
            load.start()
            active = _open_loop_searches(eng, rng, rate, PHASE_SECONDS)
            active.update(load.stop())
            next_id = load.next_id
            ratio = round(active["p99_ms"] / max(idle["p99_ms"], 1e-9), 2)
            scale_points.append({"rate_qps": rate, "idle": idle,
                                 "active": active,
                                 "p99_active_over_idle": ratio})
            for phase, d in (("idle", idle), ("active", active)):
                rows.append(Row(
                    f"serve_churn.{phase}@{rate}qps", d["p50_ms"] / 1e3,
                    f"p99={d['p99_ms']}ms p999={d['p999_ms']}ms "
                    f"qps={d['achieved_qps']}"))
            rows.append(Row(
                f"serve_churn.slo@{rate}qps", 0.0,
                f"p99_active/idle={ratio}x "
                f"add={active['add_rows_per_s']}rows/s "
                f"remove={active['remove_rows_per_s']}rows/s"))
        observed, bound = eng.assert_bounded_compiles()
        worst = max(sp["p99_active_over_idle"] for sp in scale_points)
        assert worst <= SLO_RATIO, (
            f"p99 under ingest {worst}x idle exceeds the {SLO_RATIO}x SLO "
            f"bound: {scale_points}")
        stats = eng.stats()
        snap = eng.telemetry()            # full JSON snapshot, CI artifact
    finally:
        eng.close()
    Path("TELEMETRY_serve.json").write_text(json.dumps(snap, indent=1))
    print("# wrote TELEMETRY_serve.json")
    comp = idx.compile_stats()
    rows.append(Row(
        "serve_churn.jit_executables", 0.0,
        f"search={observed} (bound {bound}) add={comp['add']} "
        f"remove={comp['remove']} coalesce_mean={stats['coalesce_mean']}"))
    summary = {
        "dim": DIM, "window": WINDOW, "k": K, "nprobe": NPROBE,
        "phase_seconds": PHASE_SECONDS,
        "mutation_rows_per_s_offered": MUT_ROWS_PER_S,
        "scale_points": scale_points,
        "max_p99_active_over_idle": worst,
        "slo_ratio_bound": SLO_RATIO,
        "coalesce_mean": stats["coalesce_mean"],
        "coalesce_max": stats["coalesce_max"],
        "jit": {"search_executables": observed, "search_bound": bound,
                "add": comp["add"], "remove": comp["remove"]},
    }
    return rows, summary
