"""The program's spans in a trace (host events ``sivf.<name>``) and the
per-layer metrics that read them: on a trace built by hand, on traces
recorded here, and on the chip-recorded trace, which has none."""
from types import SimpleNamespace

import numpy as np
import pytest

import spans
from conftest import BENCH
from run import load_reader
from xplane import Events, Trace

READERS = ("idle_serve_host.search", "serve_fetch_ms", "scan_grid_live_share",
           "mutation_ack_ms.churn")


def _events(rows):
    """``Events`` from ``(name, start, end, args)`` rows."""
    return Events(np.array([r[0] for r in rows], dtype=object),
                  np.array([r[1] for r in rows], np.float64),
                  np.array([r[2] - r[1] for r in rows], np.float64))


@pytest.fixture
def trace(monkeypatch):
    """A trace of window [0, 1000) ns, the chip busy over [100, 400) and
    [600, 900), from ``(name, start, end, args)`` host rows; the args stand
    for those of the trace's file."""
    def make(host_rows):
        ops = _events([("fusion", 100, 400, {}), ("fusion", 600, 900, {})])
        found = {}
        for name, start, _, a in host_rows:
            if name.startswith(spans.PREFIX):
                found.setdefault(name, []).append((float(start), a))
        monkeypatch.setitem(spans._args_cache, 0.0, found)
        return Trace({"/device:TPU:0": ops}, {}, _events(host_rows),
                     (0.0, 1000.0))
    return make


SERVE_LOOP = [
    ("sivf.serve.wait", 0, 90, {}),
    ("sivf.serve.dispatch", 90, 110, {"tiles": 1, "rows": 3}),
    ("sivf.serve.resolve", 110, 500,
     {"rows": 3, "padded_to": 64, "grid_steps": 1000, "live_steps": 250}),
    ("sivf.serve.resolve.wait", 110, 400, {}),
    ("sivf.serve.resolve.fetch", 400, 450, {}),
    ("np.asarray_jax.Array_", 400, 460, {}),
    ("sivf.serve.flush", 450, 480,
     {"batches": 2, "reason": "idle", "deferred": 1, "wait_ms": 30.0}),
    ("sivf.serve.dispatch", 480, 610, {"tiles": 1, "rows": 5}),
    ("sivf.serve.resolve", 900, 1100,
     {"rows": 5, "padded_to": 64, "grid_steps": 1000, "live_steps": 150}),
    ("sivf.serve.resolve.fetch", 950, 980, {}),
    ("sivf.serve.flush", 990, 1005,
     {"batches": 1, "reason": "depth", "deferred": 0, "wait_ms": 12.0}),
    # starts after the window: read by no metric
    ("sivf.serve.flush", 1010, 1020,
     {"batches": 5, "reason": "idle", "deferred": 0, "wait_ms": 900.0}),
]


def _read(name, tr):
    return load_reader(name)(SimpleNamespace(trace=tr))


def test_spans_start_in_window(trace):
    tr = trace(SERVE_LOOP)
    res = spans.spans(tr, "serve.resolve")
    assert res.start.tolist() == [110.0, 900.0]
    assert res.dur.tolist() == [390.0, 200.0]
    assert len(spans.spans(tr)) == 10         # every sivf.* span in the window
    assert len(spans.spans(tr, "serve.flush")) == 2
    assert [a["live_steps"] for a in spans.args(tr, "serve.resolve")] \
        == [250, 150]
    assert len(spans.args(tr, "serve.flush")) == 2


def test_idle_under_spans(trace):
    tr = trace(SERVE_LOOP)
    # idle [0,100) [400,600) [900,1000); under dispatch/flush/resolve:
    # 10 + 200 + 100 ns; under wait: 90 ns
    assert spans.idle_under(tr, "serve.dispatch", "serve.flush",
                            "serve.resolve") == pytest.approx(310e-9)
    assert spans.idle_under(tr, "serve.wait") == pytest.approx(90e-9)
    assert spans.idle_under(tr, "serve.nothing") == 0.0


def test_readers_on_the_serve_loop(trace):
    tr = trace(SERVE_LOOP)
    assert _read("idle_serve_host.search", tr) == pytest.approx(0.31)
    assert _read("serve_fetch_ms", tr) == pytest.approx(40e-6)
    assert _read("scan_grid_live_share", tr) == pytest.approx(0.2)
    assert _read("mutation_ack_ms.churn", tr) == pytest.approx(14.0)


def test_live_share_skips_tiles_without_counter(trace):
    tr = trace([("sivf.serve.resolve", 10, 20, {"rows": 1, "padded_to": 64}),
                ("sivf.serve.resolve", 30, 40,
                 {"grid_steps": 100, "live_steps": 40})])
    assert _read("scan_grid_live_share", tr) == pytest.approx(0.4)
    tr = trace([("sivf.serve.resolve", 10, 20, {"rows": 1})])
    assert _read("scan_grid_live_share", tr) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_find_nothing_without_program_spans(trace, name):
    tr = trace([("np.asarray_jax.Array_", 400, 560, {}),
                ("PjitFunction(search_fn)", 590, 600, {})])
    assert _read(name, tr) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_find_nothing_in_recorded_chip_trace(name):
    tr = Trace.load(str(BENCH / "testdata" / "search64x2.xplane.pb"))
    assert len(spans.spans(tr)) == 0
    assert spans.idle_under(tr, "serve.resolve") == 0.0
    assert _read(name, tr) is None


def _record(path, wait_ms):
    """A trace recorded here, as ``run.py`` records one: the benchmark's
    window around one flush span of the program, telemetry off."""
    import jax
    from repro.obs import Telemetry
    tel = Telemetry(enabled=False)
    jax.profiler.start_trace(str(path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with tel.span("serve.flush", batches=2, reason="idle") as sp:
                with jax.profiler.TraceAnnotation("other", x=1):
                    pass
                sp.set(wait_ms=wait_ms)
    finally:
        jax.profiler.stop_trace()
    return Trace.load(str(path))


def test_args_read_from_the_trace_file(tmp_path, monkeypatch):
    """The args come from the file whose window is the trace's, newest
    first among the run directories; none where no file matches."""
    monkeypatch.setattr(spans, "TRACE_DIRS", str(tmp_path / "sivf-trace-*"))
    mine = _record(tmp_path / "sivf-trace-a", 7.5)
    _record(tmp_path / "sivf-trace-b", 99.0)         # another run, newer
    (a,) = spans.args(mine, "serve.flush")
    assert a == {"batches": 2, "reason": "idle", "wait_ms": 7.5}
    assert _read("mutation_ack_ms.churn", mine) == pytest.approx(3.75)
    assert spans.args(mine, "other") == []           # not a program span
    monkeypatch.setattr(spans, "TRACE_DIRS", str(tmp_path / "none-*"))
    monkeypatch.setattr(spans, "_args_cache", {})
    assert _read("mutation_ack_ms.churn", mine) is None


@pytest.mark.parametrize("writer", [False, True])
def test_trace_run_reports_program_span_metrics(run_cell, writer):
    """The serve loop's spans reach the trace: the metrics that read them
    report (here on the CPU, whose trace has no device plane)."""
    result, lines = run_cell(writer=writer, trace=1)
    assert result["correct"], lines
    m = result["metrics"]
    if writer:
        assert m["mutation_ack_ms.churn"]["value"] > 0
    else:
        assert m["serve_fetch_ms"]["value"] > 0
        assert 0 < m["scan_grid_live_share"]["value"] < 1
        assert 0 < m["idle_serve_host.search"]["value"] < 1
