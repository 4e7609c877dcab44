"""``obs_overhead``: telemetry-on vs telemetry-off serve latency (ISSUE 9).

The observability layer's acceptance claim is *always-on-cheap*: running
with full telemetry (spans on every tile, per-tenant counters, stage
histograms) must cost at most 5% of serve p99 versus the disabled
fast path. This bench measures that directly on ONE warmed engine by
toggling ``Telemetry.enabled`` between interleaved blocks:

  * **Interleaved blocks, alternating order.** Each cycle runs one
    small OFF block and one small ON block — even cycles off->on, odd
    cycles on->off — so slow drift (thermal, allocator, runner warmup)
    and rare hiccups land on both sides equally in expectation instead
    of systematically penalising whichever side runs second.
  * **Pooled percentiles.** All OFF samples form one distribution, all
    ON samples another; the reported overhead is pooled
    ``p99_on / p99_off``. Per-cycle p50 ratios (median over cycles) are
    recorded alongside as the low-noise per-request check.
  * **Serve-side latency.** Each sample is ``queue_s + service_s`` from
    the engine's own provenance — the exact latency composition
    ``serve_churn`` gates — which excludes the waiter-thread wakeup
    handoff, a pure OS-scheduler noise source that telemetry cannot
    influence. Closed loop (one request in flight), so queueing
    amplification cannot multiply scheduler noise into the tail.
  * **In-bench gate.** The bench asserts pooled p99 ratio <= 1.05; a
    violation raises, which ``benchmarks/run.py`` turns into a non-zero
    exit. ``scripts/check_bench.py`` additionally bands the
    recorded ratio against the committed baseline so the gate itself
    cannot be silently loosened.

Writes ``BENCH_obs.json`` via ``benchmarks/run.py obs_overhead``.
"""
from __future__ import annotations

import time

import jax
import numpy as np

import sivf
from benchmarks.common import Row
from repro.obs import Telemetry, latency_summary_ms, percentiles
from sivf import ServeEngine, TenantQuota

DIM = 32
N_LISTS = 32
WINDOW = 4096
K, NPROBE = 10, 8
BATCH = 8                       # fixed query-batch shape: one executable
BLOCK = 5                       # requests per on/off block: blocks must be
                                # much shorter than system noise bursts
                                # (~1-2 s here), or a burst lands on one
                                # side of a pair and swamps the comparison
CYCLES = 120                    # off+on block pairs (order alternates)
WARMUP = 150
OVERHEAD_BOUND = 1.05           # pooled p99_on/p99_off acceptance bound


def _build_engine(rng, tel):
    n_slabs = int(2.5 * WINDOW / 64) + N_LISTS
    cfg = sivf.SIVFConfig(dim=DIM, n_lists=N_LISTS, n_slabs=n_slabs,
                          capacity=64, n_max=1 << 18)
    train = rng.normal(size=(2048, DIM)).astype(np.float32)
    cents = sivf.train_kmeans(jax.random.key(0), train, N_LISTS)
    idx = sivf.Index(cfg, cents, deferred=True, min_bucket=64,
                     telemetry=tel)
    eng = ServeEngine(idx, default_k=K, default_nprobe=NPROBE,
                      max_queue=1024, max_coalesce=64, flush_every=8,
                      quotas={"app": TenantQuota(
                          max_inflight_searches=1024)})
    return idx, eng


def _prefill(eng, rng) -> None:
    writer = eng.session("ingest")
    futs = []
    for base in range(0, WINDOW, 64):
        vecs = rng.normal(size=(64, DIM)).astype(np.float32)
        ids = np.arange(base, base + 64, dtype=np.int32)
        futs.append(writer.add(vecs, ids))
    assert all(f.result(600).ok for f in futs)


def _block(sess, pool, n: int) -> list[float]:
    """Closed-loop: ``n`` sequential BATCH-row searches; per-request
    serve-side seconds (queue wait + tile service, engine-stamped)."""
    lats = []
    for i in range(n):
        res = sess.search(pool[i % len(pool)]).result(600)
        assert res.labels.shape == (BATCH, K)
        lats.append(res.queue_s + res.service_s)
    return lats


def obs_overhead_summary():
    """(rows, summary) for ``BENCH_obs.json`` — see module docstring."""
    rng = np.random.default_rng(7)
    tel = Telemetry(enabled=True)
    idx, eng = _build_engine(rng, tel)
    rows = []
    samples = {"off": [], "on": []}
    p50_ratios = []
    try:
        _prefill(eng, rng)
        sess = eng.session("app")
        pool = [rng.normal(size=(BATCH, DIM)).astype(np.float32)
                for _ in range(32)]
        _block(sess, pool, WARMUP)      # warm executables + both branches
        for i in range(CYCLES):
            order = ("off", "on") if i % 2 == 0 else ("on", "off")
            cycle = {}
            for mode in order:
                tel.enabled = mode == "on"
                cycle[mode] = _block(sess, pool, BLOCK)
                samples[mode] += cycle[mode]
            p50_ratios.append(
                percentiles(cycle["on"], (50.0,))[50.0]
                / max(percentiles(cycle["off"], (50.0,))[50.0], 1e-9))
        tel.enabled = True
        observed, bound = eng.assert_bounded_compiles()
    finally:
        eng.close()
    off = latency_summary_ms(samples["off"])
    on = latency_summary_ms(samples["on"])
    p99_ratio = on["p99_ms"] / max(off["p99_ms"], 1e-9)
    p50_ratio_median = float(np.median(p50_ratios))
    rows.append(Row(
        "obs_overhead.off", off["p50_ms"] / 1e3,
        f"p99={off['p99_ms']}ms over {len(samples['off'])} requests"))
    rows.append(Row(
        "obs_overhead.on", on["p50_ms"] / 1e3,
        f"p99={on['p99_ms']}ms over {len(samples['on'])} requests"))
    rows.append(Row(
        "obs_overhead.verdict", 0.0,
        f"pooled_p99_ratio={p99_ratio:.3f} "
        f"median_p50_ratio={p50_ratio_median:.3f} "
        f"(bound {OVERHEAD_BOUND}x over {CYCLES} interleaved cycles)"))
    assert p99_ratio <= OVERHEAD_BOUND, (
        f"telemetry overhead {p99_ratio:.3f}x exceeds the "
        f"{OVERHEAD_BOUND}x pooled-p99 bound (off={off}, on={on})")
    summary = {
        "dim": DIM, "window": WINDOW, "k": K, "nprobe": NPROBE,
        "batch": BATCH, "block": BLOCK, "cycles": CYCLES,
        "off": off, "on": on,
        "overhead": {
            "p99_ratio_pooled": round(p99_ratio, 4),
            "p50_ratio_median": round(p50_ratio_median, 4),
            "bound": OVERHEAD_BOUND,
        },
        "jit": {"search_executables": observed, "search_bound": bound},
    }
    return rows, summary
