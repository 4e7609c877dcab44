"""Observability layer (ISSUE 9): metrics, spans, exporters, wiring.

Three layers under test:

  * the zero-dependency metric primitives (``repro.obs.metrics``) —
    counter/gauge/histogram semantics, windowed reads, label handling;
  * span tracing (``repro.obs.trace``) — nesting, stage attribution to
    the innermost root, the slow-query log, the disabled fast path
    (all with an injected fake clock, so durations are exact);
  * the instrumented product paths — a tiered ``Index`` and a
    ``ServeEngine`` run a real mixed workload and the resulting snapshot
    must agree with the ground-truth counters the code already exposes
    (``stats()``, ``compile_events()``), and the Prometheus text render
    must round-trip the same values as the JSON snapshot.
"""
import json
import math

import numpy as np
import pytest

import sivf
from repro.obs import (BUCKETS_S, MetricsRegistry, Telemetry,
                       WindowedCounter, latency_summary_ms,
                       parse_prometheus, percentiles, render_prometheus,
                       snapshot_json)
from repro.obs.trace import _NOOP

# ---------------------------------------------------------------------------
# metric primitives
# ---------------------------------------------------------------------------


def test_counter_cumulative_and_window():
    reg = MetricsRegistry()
    c = reg.counter("req_total", "requests", ("tenant",))
    c.inc(tenant="a")
    c.inc(4, tenant="a")
    c.inc(2, tenant="b")
    assert c.get(tenant="a") == 5 and c.get(tenant="b") == 2
    assert c.get_window(tenant="a") == 5
    reg.roll_window()
    assert c.get_window(tenant="a") == 0      # window reset...
    assert c.get(tenant="a") == 5             # ...cumulative untouched
    c.inc(3, tenant="a")
    assert c.get_window(tenant="a") == 3 and c.get(tenant="a") == 8


def test_counter_rejects_negative():
    c = MetricsRegistry().counter("n")
    with pytest.raises(ValueError, match="only go up"):
        c.inc(-1)


def test_label_validation():
    c = MetricsRegistry().counter("n", labels=("tenant",))
    with pytest.raises(ValueError, match="labels"):
        c.inc(shard="0")                      # wrong label name
    with pytest.raises(ValueError, match="labels"):
        c.inc()                               # missing label


def test_reregistration_idempotent_and_mismatch():
    reg = MetricsRegistry()
    a = reg.counter("n", "h", ("x",))
    assert reg.counter("n", "h", ("x",)) is a     # same declaration: reuse
    with pytest.raises(ValueError, match="re-registered"):
        reg.counter("n", "h", ("y",))             # label mismatch
    with pytest.raises(ValueError, match="re-registered"):
        reg.gauge("n")                            # kind mismatch


def test_gauge_last_write_wins():
    g = MetricsRegistry().gauge("depth")
    g.set(3)
    g.set(7)
    assert g.get() == 7.0


def test_histogram_buckets_and_percentile_estimate():
    reg = MetricsRegistry()
    h = reg.histogram("lat", labels=("stage",))
    assert h.buckets == BUCKETS_S
    # bucket bounds are inclusive upper bounds (bisect_left: first >= v)
    h.observe(1e-6, stage="s")                # lands in bucket 0
    h.observe(3e-6, stage="s")                # first bound >= 3us is 4us
    h.observe(1e9, stage="s")                 # beyond the last bound: +inf
    d = h.get(stage="s")
    assert d["count"] == 3 and d["counts"][0] == 1 and d["counts"][-1] == 1
    assert d["counts"][2] == 1                # 1,2,4us -> index 2
    assert h.percentile(50.0, stage="s") == BUCKETS_S[2]
    assert h.percentile(99.0, stage="s") == math.inf
    assert h.percentile(50.0, stage="empty") == 0.0


def test_windowed_counter_and_carry():
    a = WindowedCounter()
    a.add(5)
    a.mark()
    a.add(2)
    assert a.total == 7 and a.window == 2
    b = WindowedCounter().carry(a)            # reshard-style adoption
    assert b.total == 7 and b.window == 2
    b.add(1)
    assert b.total == 8 and b.window == 3 and a.total == 7


def test_percentiles_and_latency_summary():
    assert percentiles([], (50.0, 99.0)) == {50.0: 0.0, 99.0: 0.0}
    p = percentiles(range(1, 101), (50.0, 99.0))
    assert p[50.0] == pytest.approx(50.5) and p[99.0] == pytest.approx(99.01)
    s = latency_summary_ms([0.001] * 10)
    assert s == {"p50_ms": 1.0, "p99_ms": 1.0, "p999_ms": 1.0}
    # the helper IS np.percentile (shared definition with the benchmarks)
    a = np.random.default_rng(0).uniform(size=97)
    assert percentiles(a, (99.0,))[99.0] == float(np.percentile(a, 99.0))


# ---------------------------------------------------------------------------
# span tracing (fake clock: exact durations)
# ---------------------------------------------------------------------------


def _fake_clock():
    t = [0.0]

    def clock():
        return t[0]
    return t, clock


def test_span_nesting_attributes_stages_to_root():
    t, clock = _fake_clock()
    tel = Telemetry(enabled=True, slow_threshold_s=0.0, clock=clock)
    with tel.span("serve.tile", root=True, tenant="a", epoch=3):
        t[0] += 0.010                         # un-attributed root time
        with tel.span("plan"):
            t[0] += 0.002
        with tel.span("scan"):
            t[0] += 0.005
    (entry,) = tel.slow_queries()             # threshold 0: every root logs
    assert entry["span"] == "serve.tile"
    assert entry["duration_ms"] == pytest.approx(17.0)
    assert entry["stages_ms"] == {"plan": 2.0, "scan": 5.0}
    assert entry["tenant"] == "a" and entry["epoch"] == 3
    hist = tel.histogram("sivf_stage_seconds", labels=("stage",))
    assert hist.get(stage="plan")["sum"] == pytest.approx(0.002)
    assert hist.get(stage="serve.tile")["count"] == 1
    assert tel.counter("sivf_slow_queries_total").get() == 1


def test_root_auto_depends_on_enclosing_root():
    t, clock = _fake_clock()
    tel = Telemetry(enabled=True, slow_threshold_s=0.0, clock=clock)
    with tel.span("index.search", root="auto"):   # no enclosing root
        t[0] += 0.001
    assert tel.slow_queries()[0]["span"] == "index.search"
    tel.clear_slow_log()
    with tel.span("serve.tile", root=True):
        with tel.span("index.search", root="auto"):   # under a tile: stage
            t[0] += 0.001
        t[0] += 0.001
    (entry,) = tel.slow_queries()
    assert entry["span"] == "serve.tile"
    assert "index.search" in entry["stages_ms"]


def test_open_span_exit_scope_finish_lifecycle():
    t, clock = _fake_clock()
    tel = Telemetry(enabled=True, slow_threshold_s=0.0, clock=clock)
    sp = tel.open_span("serve.tile", root=True, rows=4)
    with tel.span("plan"):
        t[0] += 0.002
    tel.exit_scope(sp)                        # dispatch done; tile still runs
    with tel.span("prefetch"):                # next tile's work: NOT attributed
        t[0] += 0.004
    t[0] += 0.001
    tel.finish_span(sp)                       # result resolved
    (entry,) = [e for e in tel.slow_queries() if e["span"] == "serve.tile"]
    assert entry["duration_ms"] == pytest.approx(7.0)
    assert entry["stages_ms"] == {"plan": 2.0}    # prefetch was out of scope


def test_disabled_fast_path_records_nothing():
    tel = Telemetry(enabled=False)
    assert tel.span("x", root=True) is _NOOP      # shared no-op instance
    assert tel.open_span("x") is None
    tel.exit_scope(None)
    tel.finish_span(None)                         # all None-safe
    tel.record_duration("x", 1.0)
    with tel.span("x", root=True):
        pass
    assert tel.slow_queries() == []
    assert tel.histogram("sivf_stage_seconds",
                         labels=("stage",)).items() == []


def test_slow_log_keeps_n_slowest():
    t, clock = _fake_clock()
    tel = Telemetry(enabled=True, slow_threshold_s=0.0, slow_log_size=2,
                    clock=clock)
    for ms in (5, 1, 9, 3):
        with tel.span("op", root=True):
            t[0] += ms / 1e3
    got = [e["duration_ms"] for e in tel.slow_queries()]
    assert got == [9.0, 5.0]
    tel.clear_slow_log()
    assert tel.slow_queries() == []


def test_record_duration_and_traced_decorator():
    t, clock = _fake_clock()
    tel = Telemetry(enabled=True, slow_threshold_s=0.0, clock=clock)

    with tel.span("queue_drain", root=True):
        t[0] += 0.004
        tel.record_duration("serve.queue", 0.003)

    (entry,) = tel.slow_queries()
    assert entry["stages_ms"] == {"serve.queue": 3.0}
    h = tel.histogram("sivf_stage_seconds", labels=("stage",))
    assert h.get(stage="serve.queue")["sum"] == pytest.approx(0.003)


# ---------------------------------------------------------------------------
# exporters: Prometheus <-> JSON round trip
# ---------------------------------------------------------------------------


def test_prometheus_round_trips_snapshot_values():
    tel = Telemetry(enabled=True)
    c = tel.counter("sivf_serve_requests_total", "reqs", ("tenant", "op"))
    c.inc(6, tenant="appA", op="search")
    c.inc(2, tenant="ingest", op="add")
    tel.roll_window()
    c.inc(1, tenant="appA", op="search")
    tel.gauge("sivf_serve_queue_depth", "depth").set(4)
    h = tel.histogram("sivf_stage_seconds", "stage secs", ("stage",))
    h.observe(3e-6, stage="plan")
    h.observe(5e-3, stage="plan")

    series = parse_prometheus(render_prometheus(tel))
    assert series['sivf_serve_requests_total{tenant="appA",op="search"}'] == 7
    assert series['sivf_serve_requests_total_window'
                  '{tenant="appA",op="search"}'] == 1
    assert series["sivf_serve_queue_depth"] == 4
    assert series['sivf_stage_seconds_count{stage="plan"}'] == 2
    assert series['sivf_stage_seconds_bucket{stage="plan",le="+Inf"}'] == 2
    # cumulative le buckets: monotone, ending at count
    le_keys = [k for k in series
               if k.startswith('sivf_stage_seconds_bucket{stage="plan"')]
    vals = [series[k] for k in le_keys]
    assert vals == sorted(vals)

    snap = json.loads(snapshot_json(tel))
    req = snap["metrics"]["sivf_serve_requests_total"]["series"]
    by_tenant = {(s["labels"]["tenant"], s["labels"]["op"]): s for s in req}
    assert by_tenant[("appA", "search")]["total"] == 7
    assert by_tenant[("appA", "search")]["window"] == 1
    plan = [s for s in snap["metrics"]["sivf_stage_seconds"]["series"]
            if s["labels"]["stage"] == "plan"][0]
    assert plan["count"] == 2
    assert plan["sum"] == pytest.approx(5e-3 + 3e-6)
    # every snapshot value appears identically in the text exposition
    assert series['sivf_stage_seconds_sum{stage="plan"}'] == \
        pytest.approx(plan["sum"])


# ---------------------------------------------------------------------------
# instrumented product paths (real Index / ServeEngine workloads)
# ---------------------------------------------------------------------------

D, NL = 16, 8


def _tiered_index(rng, tel, n_slabs, device_slabs=24, **kw):
    cfg = sivf.SIVFConfig(dim=D, n_lists=NL, n_slabs=n_slabs, capacity=32,
                          n_max=4096, device_slabs=device_slabs)
    cents = rng.normal(size=(NL, D)).astype(np.float32)
    return sivf.Index(cfg, cents, telemetry=tel, **kw)


def test_index_spans_cache_events_and_compile_counter(rng):
    tel = Telemetry(enabled=True, slow_threshold_s=0.0)
    idx = _tiered_index(rng, tel, n_slabs=93)
    vecs = rng.normal(size=(400, D)).astype(np.float32)
    idx.add(vecs, np.arange(400, dtype=np.int32))
    qs = rng.normal(size=(4, D)).astype(np.float32)
    idx.search(qs, k=5, nprobe=4)
    idx.search(qs, k=5, nprobe=4)             # second pass: warm hits

    snap = idx.telemetry()
    stages = {s["labels"]["stage"]
              for s in snap["metrics"]["sivf_stage_seconds"]["series"]}
    assert {"plan", "prefetch", "scan", "index.search",
            "mutation.dispatch"} <= stages

    # cache-event counters must equal the stats() ground truth
    st = idx.stats()
    ev = tel.counter("sivf_tiered_cache_events_total", labels=("event",))
    assert ev.get(event="hit") == st["cache_hits"] > 0
    assert ev.get(event="miss") == st["cache_misses"] > 0
    assert ev.get(event="upload") == st["cache_uploads"] > 0
    tb = tel.counter("sivf_transfer_bytes_total",
                     labels=("direction", "stage"))
    assert tb.get(direction="h2d", stage="prefetch") > 0

    # compile-event counter == the handle's observed executable delta
    assert idx.compile_events() > 0
    assert tel.counter("sivf_jit_compile_events_total").get() == \
        idx.compile_events()
    assert tel.counter("sivf_index_mutation_rows_total",
                       labels=("op",)).get(op="add") == 400

    # a root span (the direct index.search) landed in the slow log with
    # its stage breakdown
    entries = [e for e in tel.slow_queries() if e["span"] == "index.search"]
    assert entries and {"plan", "prefetch", "scan"} <= \
        set(entries[0]["stages_ms"])


def test_serve_engine_mixed_workload_snapshot(rng):
    from sivf import Backpressure, ServeEngine, TenantQuota
    tel = Telemetry(enabled=True, slow_threshold_s=0.0)
    idx = _tiered_index(rng, tel, n_slabs=95, deferred=True, min_bucket=16)
    eng = ServeEngine(idx, default_k=5, default_nprobe=4,
                      quotas={"appA": TenantQuota(max_inflight_searches=2),
                              "ingest": TenantQuota()})
    with eng:
        writer, reader = eng.session("ingest"), eng.session("appA")
        ids = np.arange(128, dtype=np.int32)
        writer.add(rng.normal(size=(128, D)).astype(np.float32),
                   ids).result(60)
        # sequential: the appA quota caps *concurrent* searches at 2
        for _ in range(3):
            reader.search(
                rng.normal(size=(2, D)).astype(np.float32)).result(60)
        # provoke a typed rejection so the backpressure counter moves
        eng.pause()
        held = [reader.search(rng.normal(size=(1, D)).astype(np.float32))
                for _ in range(2)]
        with pytest.raises(Backpressure):
            reader.search(rng.normal(size=(1, D)).astype(np.float32))
        eng.resume()
        for f in held:
            f.result(60)
        snap = eng.telemetry()
        prom = eng.render_prometheus()

    req = tel.counter("sivf_serve_requests_total", labels=("tenant", "op"))
    assert req.get(tenant="appA", op="search") == 5
    assert req.get(tenant="ingest", op="add") == 1
    rows = tel.counter("sivf_serve_rows_total", labels=("tenant", "op"))
    assert rows.get(tenant="ingest", op="add") == 128
    assert rows.get(tenant="appA", op="search") == 3 * 2 + 2
    bp = tel.counter("sivf_serve_backpressure_total",
                     labels=("tenant", "kind"))
    assert bp.get(tenant="appA", kind="search_inflight") == 1

    stages = {s["labels"]["stage"]
              for s in snap["metrics"]["sivf_stage_seconds"]["series"]}
    assert {"serve.tile", "serve.queue", "serve.mutation_queue",
            "index.search", "plan", "prefetch", "scan"} <= stages
    tiles = [e for e in tel.slow_queries() if e["span"] == "serve.tile"]
    assert tiles and "index.search" in tiles[0]["stages_ms"]
    assert "tenant" in tiles[0] and "epoch" in tiles[0]

    # Prometheus text agrees with the JSON snapshot series-by-series
    series = parse_prometheus(prom)
    assert series['sivf_serve_requests_total{tenant="appA",op="search"}'] \
        == 5
    assert series["sivf_serve_epoch"] == \
        snap["metrics"]["sivf_serve_epoch"]["series"][0]["value"]
    # compile-event counter equals the engine's observed executable delta
    assert tel.counter("sivf_jit_compile_events_total").get() == \
        idx.compile_events() > 0


def test_telemetry_disabled_by_default_and_module_facade(rng):
    import repro.obs as obs
    from sivf import telemetry as sivf_tel
    assert obs.default().enabled is False     # process default: off
    # an Index built without explicit telemetry records nothing
    cfg = sivf.SIVFConfig(dim=D, n_lists=NL, n_slabs=91, capacity=32,
                          n_max=4096)
    idx = sivf.Index(cfg, rng.normal(size=(NL, D)).astype(np.float32))
    idx.add(rng.normal(size=(64, D)).astype(np.float32),
            np.arange(64, dtype=np.int32))
    idx.search(rng.normal(size=(2, D)).astype(np.float32), k=5, nprobe=2)
    snap = sivf_tel.snapshot()
    hist = snap["metrics"].get("sivf_stage_seconds")
    assert hist is None or hist["series"] == []
    # the facade exports the same default instance
    assert sivf_tel.get() is obs.default()
