"""The profiler sink of ``repro.obs`` spans and the serve loop's spans.

Every lexically scoped ``Telemetry.span`` is written to a recording
``jax.profiler`` session as a host event named ``sivf.<name>``, whether or
not telemetry is enabled, with the span's attributes as the event's args.
The serve engine names its loop's phases that way (``serve.wait``,
``serve.dispatch``, ``serve.flush``, ``serve.resolve`` with its
``.wait`` / ``.fetch`` children), and each resolve carries the tile's
scan grid steps and the live ones, counted inside the search executable.
"""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import sivf
from repro.core import index as ix
from repro.core import quantizer
from repro.obs import Telemetry
from repro.obs.trace import _NOOP, profiling
from repro.serve.sivf_engine import ServeEngine

DIM = 16
SERVE_SPANS = ("serve.wait", "serve.dispatch", "serve.flush",
               "serve.resolve", "serve.resolve.wait", "serve.resolve.fetch")


def _record(tmp_path, fn):
    """Run ``fn`` under a profiler session; return its host events named
    ``sivf.*`` as ``(line, name, start_ns, end_ns, args)``."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for li, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("sivf."):
                    out.append((li, ev.name[len("sivf."):], ev.start_ns,
                                ev.start_ns + ev.duration_ns, dict(ev.stats)))
    return out


def _index(rng, n_slabs, telemetry, **kw):
    cfg = sivf.SIVFConfig(dim=DIM, n_lists=8, n_slabs=n_slabs, capacity=32,
                          n_max=4096, **kw)
    cents = sivf.train_kmeans(
        jax.random.key(0), rng.normal(size=(512, DIM)).astype(np.float32), 8)
    return sivf.Index(cfg, cents, deferred=True, min_bucket=16,
                      telemetry=telemetry)


def _live_recount(idx, qs, nprobe):
    """Non-empty slab-table entries of ``qs`` rows, recounted from the
    table the dense-table path gathers."""
    cfg = idx.cfg
    lists = quantizer.probe(idx.state.centroids,
                            jnp.asarray(qs).astype(cfg.dtype), nprobe,
                            cfg.metric)
    return int((np.asarray(ix.gather_tables(cfg, idx.state, lists)) >= 0
                ).sum())


# ---------------------------------------------------------------------------
# the span sinks
# ---------------------------------------------------------------------------

def test_span_is_shared_noop_with_profiler_and_telemetry_off():
    assert not profiling()
    tel = Telemetry(enabled=False)
    sp = tel.span("serve.flush", batches=2)
    assert sp is _NOOP
    with sp as s:
        s.set(wait_ms=1.0)              # end-time args: a no-op too


@pytest.mark.parametrize("enabled", [False, True])
def test_spans_reach_the_profiler_with_args(tmp_path, enabled):
    tel = Telemetry(enabled=enabled, slow_threshold_s=0.0)

    def body():
        assert profiling()
        with tel.span("outer", root=True, tiles=2, reason="idle",
                      skipped=None) as sp:
            with tel.span("outer.inner"):
                pass
            sp.set(wait_ms=2.5)

    ev = {name: (li, t0, t1, args)
          for li, name, t0, t1, args in _record(tmp_path, body)}
    assert set(ev) == {"outer", "outer.inner"}
    (lo, o0, o1, oargs), (li, i0, i1, iargs) = ev["outer"], ev["outer.inner"]
    assert oargs == {"tiles": 2, "reason": "idle", "wait_ms": 2.5}
    assert iargs == {}
    assert lo == li and o0 <= i0 <= i1 <= o1          # nested, one thread
    # the registry sink records behind `enabled` alone
    h = tel.histogram("sivf_stage_seconds", labels=("stage",))
    assert (h.get(stage="outer")["count"] == 1) is enabled
    assert bool(tel.slow_queries()) is enabled
    if enabled:
        assert tel.slow_queries()[0]["wait_ms"] == 2.5


def test_open_spans_stay_registry_only(tmp_path):
    tel = Telemetry(enabled=True)

    def body():
        sp = tel.open_span("serve.tile", rows=3)
        tel.exit_scope(sp)
        tel.finish_span(sp)

    assert _record(tmp_path, body) == []
    h = tel.histogram("sivf_stage_seconds", labels=("stage",))
    assert h.get(stage="serve.tile")["count"] == 1


# ---------------------------------------------------------------------------
# the live-entry counter of the search executable
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("q,t,block_q,smem,want", [
    (64, 1024, 8, None, 64 * 1024),        # the sift1m tile: one chunk
    (9, 16, 8, None, 16 * 16),             # ragged rows pad to block_q
    (3, 16, 8, None, 3 * 16),              # fewer rows than block_q
    (40, 16, 8, 16 * 4 * 16, 3 * 16 * 16),  # SMEM split: 3 chunks of 16
])
def test_scan_grid_steps_follow_the_launch(monkeypatch, q, t, block_q, smem,
                                           want):
    if smem is not None:
        monkeypatch.setattr(ix, "SMEM_TABLE_BYTES", smem)
    assert ix.scan_grid_steps(q, t, block_q) == want


@pytest.mark.parametrize("use_tables", [True, False])
def test_search_result_counts_live_entries(rng, use_tables):
    # the dense table and the pointer walk build the same table: the
    # counter reads whichever the executable built
    cfg = sivf.SIVFConfig(dim=DIM, n_lists=8, n_slabs=301 + use_tables,
                          capacity=32, n_max=4096)
    cents = sivf.train_kmeans(
        jax.random.key(0), rng.normal(size=(512, DIM)).astype(np.float32), 8)
    idx = sivf.Index(cfg, cents, use_tables=use_tables, min_bucket=16,
                     telemetry=Telemetry(enabled=False))
    n = 500
    idx.add(rng.normal(size=(n, DIM)).astype(np.float32),
            np.arange(n, dtype=np.int32))
    qs = rng.normal(size=(5, DIM)).astype(np.float32)
    res = idx.search(qs, k=5, nprobe=4)
    live = np.asarray(res.live_entries)
    assert live.shape == (res.padded_to,) and live.dtype == np.int32
    assert int(live[:5].sum()) == _live_recount(idx, qs, 4)
    assert res.grid_steps == ix.scan_grid_steps(
        res.padded_to, 4 * cfg.max_chain, 8)
    assert res.grid_steps > live[:5].sum() > 0


def test_tiered_search_reports_no_counter(rng):
    idx = _index(rng, 96, Telemetry(enabled=False), device_slabs=24)
    idx.add(rng.normal(size=(200, DIM)).astype(np.float32),
            np.arange(200, dtype=np.int32))
    idx.flush()
    res = idx.search(rng.normal(size=(2, DIM)).astype(np.float32), k=5,
                     nprobe=4)
    assert res.live_entries is None and res.grid_steps == 0
    assert np.asarray(res.labels).shape == (2, 5)


# ---------------------------------------------------------------------------
# the serve loop's spans
# ---------------------------------------------------------------------------

def test_serve_loop_spans_in_profiler_trace(rng, tmp_path):
    idx = _index(rng, 303, Telemetry(enabled=False))
    nprobe = 4
    qs = rng.normal(size=(3, DIM)).astype(np.float32)

    def body():
        with ServeEngine(idx, default_k=5, default_nprobe=nprobe) as eng:
            app = eng.session("app")
            ids = np.arange(300, dtype=np.int32)
            app.add(rng.normal(size=(300, DIM)).astype(np.float32),
                    ids).result(60)
            eng.pause()
            futs = [app.search(q) for q in qs]
            eng.resume()
            for f in futs:
                assert f.result(60).coalesced == 3

    events = _record(tmp_path, body)
    names = {name for _, name, _, _, _ in events}
    assert set(SERVE_SPANS) <= names
    serve = [e for e in events if e[1].startswith("serve.")]
    assert len({li for li, *_ in serve}) == 1          # the serve thread
    by = {n: [e for e in serve if e[1] == n] for n in SERVE_SPANS}
    # every resolve child lies inside a resolve on the same line
    for child in by["serve.resolve.wait"] + by["serve.resolve.fetch"]:
        assert any(p[2] <= child[2] and child[3] <= p[3]
                   for p in by["serve.resolve"])
    # the index's own spans reach the trace too, inside a dispatch / flush
    assert {"index.search", "mutation.dispatch", "mutation.flush"} <= names
    (search,) = [e for e in events if e[1] == "index.search"]
    assert any(d[2] <= search[2] and search[3] <= d[3]
               for d in by["serve.dispatch"])

    (res,) = [e[4] for e in by["serve.resolve"]]
    assert res["rows"] == 3 and res["padded_to"] == 16
    assert res["grid_steps"] == ix.scan_grid_steps(
        16, nprobe * idx.cfg.max_chain, 8)
    assert res["live_steps"] == _live_recount(idx, qs, nprobe)
    assert 0 < res["live_steps"] < res["grid_steps"]
    disp = [e[4] for e in by["serve.dispatch"] if e[4].get("tiles")]
    assert disp == [{"tiles": 1, "rows": 3}]
    (flush,) = [e[4] for e in by["serve.flush"]]
    assert flush["batches"] == 1 and flush["reason"] == "idle"
    assert flush["deferred"] >= 0 and flush["wait_ms"] > 0


def test_flush_reason_depth_and_closing(rng, tmp_path):
    idx = _index(rng, 304, Telemetry(enabled=False))

    def body():
        with ServeEngine(idx, default_k=5, default_nprobe=4,
                         flush_every=2) as eng:
            app = eng.session("app")
            eng.pause()
            futs = [app.add(rng.normal(size=(20, DIM)).astype(np.float32),
                            np.arange(20 * i, 20 * i + 20, dtype=np.int32))
                    for i in range(2)]
            eng.resume()
            for f in futs:
                f.result(60)
            eng.pause()
            last = app.add(rng.normal(size=(20, DIM)).astype(np.float32),
                           np.arange(40, 60, dtype=np.int32))
        last.result(60)

    flushes = [e[4] for e in _record(tmp_path, body)
               if e[1] == "serve.flush"]
    assert [f["reason"] for f in flushes] == ["depth", "closing"]
    assert [f["batches"] for f in flushes] == [2, 1]
    assert all(f["deferred"] == 0 for f in flushes)


def test_flush_deferred_counts_held_back_cycles(rng, tmp_path):
    idx = _index(rng, 306, Telemetry(enabled=False))
    q = rng.normal(size=(DIM,)).astype(np.float32)

    def body():
        with ServeEngine(idx, default_k=5, default_nprobe=4) as eng:
            app = eng.session("app")
            dispatch = eng._dispatch_mutations
            more = [2]

            def dispatch_then_submit(muts):
                # a search arrives during each of the first two cycles, so
                # the queue has work when the flush is due: it waits
                dispatch(muts)
                if more[0]:
                    more[0] -= 1
                    app.search(q)

            eng._dispatch_mutations = dispatch_then_submit
            app.add(rng.normal(size=(20, DIM)).astype(np.float32),
                    np.arange(20, dtype=np.int32)).result(60)

    flushes = [e[4] for e in _record(tmp_path, body)
               if e[1] == "serve.flush"]
    assert flushes == [dict(flushes[0], batches=1, reason="idle",
                            deferred=2)]
    assert flushes[0]["wait_ms"] > 0


def test_no_live_count_fetch_with_profiler_off(rng, monkeypatch):
    calls = []
    monkeypatch.setattr(ServeEngine, "_live_steps",
                        staticmethod(lambda res, rows: calls.append(rows)))
    idx = _index(rng, 305, Telemetry(enabled=False))
    assert not profiling()
    with ServeEngine(idx, default_k=5, default_nprobe=4) as eng:
        app = eng.session("app")
        app.add(rng.normal(size=(100, DIM)).astype(np.float32),
                np.arange(100, dtype=np.int32)).result(60)
        got = [app.search(q).result(60) for q in
               rng.normal(size=(4, DIM)).astype(np.float32)]
    assert calls == []
    assert all(g.labels.shape == (1, 5) for g in got)
