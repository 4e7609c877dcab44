"""Span-based tracing + the :class:`Telemetry` facade (ISSUE 9).

A *span* is one timed region of the request path. Spans nest through a
per-thread stack: while a **root** span (a serve tile, a flush, a
reshard) is open, every nested stage span that finishes on the same
thread both records its duration into the shared
``sivf_stage_seconds{stage=...}`` histogram *and* contributes to the
root's per-stage breakdown — which is what makes a slow-query-log entry
say "23 ms total: 1 ms plan, 19 ms prefetch, 3 ms scan" instead of just
"23 ms".

:class:`Telemetry` bundles the three observability pieces one handle
needs: a :class:`~repro.obs.metrics.MetricsRegistry`, the span tracer,
and the rolling slow-query log (top-N root spans over a configurable
threshold, with stage breakdown and tenant/filter/epoch provenance).
It is **always-on-cheap**: with ``enabled=False`` (the process default)
and no profiler recording, ``span()`` returns a shared no-op context
manager and every recording method returns after a single attribute
check — instrumented code paths never pay for telemetry they did not ask
for.

Spans have two sinks. The registry sink (stage histograms, slow-query
log, Prometheus) records behind ``enabled``. The profiler sink writes
every lexically scoped span as a ``jax.profiler.TraceAnnotation`` named
``sivf.<name>`` whenever a ``jax.profiler`` session is recording, whether
or not ``enabled`` is set; the span's attributes become the event's args
and :meth:`Span.set` adds those known only at its end. The program's spans
then share the device trace's clock. ``open_span`` / ``finish_span`` spans
(a serve tile, whose life overlaps its neighbours on the serve thread) stay
registry-only. The serve-churn overhead
benchmark (``benchmarks/obs_bench.py``) gates the *enabled* cost too:
p99 with telemetry on must stay within 5% of off.

Usage::

    tel = Telemetry(enabled=True, slow_threshold_s=0.010)
    with tel.span("serve.search", root=True, tenant="app", epoch=3):
        with tel.span("plan"):
            ...
        with tel.span("scan") as sp:
            ...
            sp.set(rows=12)       # known at the end: an arg of the event
    tel.snapshot()            # JSON-able dict (metrics + slow queries)
    tel.render_prometheus()   # Prometheus text exposition
"""
from __future__ import annotations

import threading
import time

from jax.profiler import TraceAnnotation

from repro.obs.metrics import MetricsRegistry

STAGE_HISTOGRAM = "sivf_stage_seconds"
PROFILER_PREFIX = "sivf."

# True while a jax.profiler session records: the profiler's own switch
# decides whether spans reach the trace
profiling = TraceAnnotation.is_enabled


def _event_args(attrs: dict) -> dict:
    return {k: v for k, v in attrs.items() if v is not None}


def _annotation(name: str, attrs: dict) -> TraceAnnotation:
    return TraceAnnotation(PROFILER_PREFIX + name, **_event_args(attrs))


class Span:
    """One timed region; produced by :meth:`Telemetry.span` /
    :meth:`Telemetry.open_span`. ``stages`` accumulates nested spans'
    durations (root spans only, by stage name)."""

    __slots__ = ("name", "root", "attrs", "t0", "t1", "stages", "_tel",
                 "_ann")

    def __init__(self, tel: "Telemetry", name: str, root: bool,
                 attrs: dict, t0: float):
        self._tel = tel
        self.name = name
        self.root = root
        self.attrs = attrs
        self.t0 = t0
        self.t1: float | None = None
        self.stages: dict[str, float] = {}
        self._ann: TraceAnnotation | None = None

    def set(self, **attrs) -> None:
        """Attributes known only at the span's end: kept on the span and,
        while the profiler records, written to its trace event."""
        self.attrs.update(attrs)
        if self._ann is not None:
            self._ann.set_metadata(**_event_args(attrs))

    @property
    def duration_s(self) -> float:
        return (self.t1 if self.t1 is not None
                else self._tel._clock()) - self.t0

    def add_stage(self, stage: str, seconds: float) -> None:
        self.stages[stage] = self.stages.get(stage, 0.0) + seconds

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, root={self.root}, "
                f"dur={self.duration_s * 1e3:.3f}ms, stages="
                f"{sorted(self.stages)})")


class _NoopSpan:
    """Shared do-nothing context manager for the disabled fast path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def add_stage(self, stage, seconds):
        pass

    def set(self, **attrs):
        pass


_NOOP = _NoopSpan()


class _ProfilerSpan:
    """A span written to the profiler trace alone (telemetry disabled)."""

    __slots__ = ("_ann",)

    def __init__(self, name: str, attrs: dict):
        self._ann = _annotation(name, attrs)

    def __enter__(self):
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        return False

    def add_stage(self, stage, seconds):
        pass

    def set(self, **attrs):
        self._ann.set_metadata(**_event_args(attrs))


class _SpanCtx:
    """Context manager binding one live span to the thread's stack."""

    __slots__ = ("_tel", "_span")

    def __init__(self, tel: "Telemetry", span: Span):
        self._tel = tel
        self._span = span

    def __enter__(self) -> Span:
        self._tel._push(self._span)
        if self._span._ann is not None:
            self._span._ann.__enter__()
        return self._span

    def __exit__(self, *exc) -> bool:
        if self._span._ann is not None:
            self._span._ann.__exit__(*exc)
        self._tel._pop(self._span)
        self._tel.finish_span(self._span)
        return False


class Telemetry:
    """Per-process (or per-handle) observability hub.

    Parameters
    ----------
    enabled:          master switch of the registry sink. Disabled, every
                      entry point is a single-attribute-check no-op
                      (spans still reach a recording profiler); flip
                      :attr:`enabled` at runtime to start/stop recording
                      (the overhead benchmark toggles it mid-run).
    slow_threshold_s: root spans at least this long enter the slow-query
                      log (0 logs every root span — tests use that).
    slow_log_size:    the log keeps the N slowest qualifying spans seen
                      since the last :meth:`clear_slow_log`.
    clock:            injectable monotonic clock for deterministic tests.
    """

    def __init__(self, enabled: bool = True,
                 slow_threshold_s: float = 0.050,
                 slow_log_size: int = 32, clock=time.perf_counter):
        self.enabled = bool(enabled)
        self.slow_threshold_s = float(slow_threshold_s)
        self.slow_log_size = int(slow_log_size)
        self._clock = clock
        self.registry = MetricsRegistry()
        self._stage_hist = self.registry.histogram(
            STAGE_HISTOGRAM, "wall seconds per pipeline stage", ("stage",))
        self._slow_counter = self.registry.counter(
            "sivf_slow_queries_total",
            "root spans over the slow-query threshold")
        self._local = threading.local()
        self._slow_lock = threading.Lock()
        self._slow: list[dict] = []

    # -- span API ------------------------------------------------------------

    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _push(self, span: Span) -> None:
        self._stack().append(span)

    def _pop(self, span: Span) -> None:
        st = self._stack()
        if st and st[-1] is span:
            st.pop()

    def span(self, name: str, root: bool | str = False, **attrs):
        """Context manager timing one region. Non-root spans feed the
        innermost enclosing root span's stage breakdown; root spans are
        slow-query-log candidates. ``root="auto"`` makes the span a root
        only when no root is already open on this thread (a directly-used
        Index.search is a root; the same call under a serve tile is a
        stage). Written to the profiler trace while one records; the
        shared no-op when disabled and no profiler records."""
        if not self.enabled:
            return _ProfilerSpan(name, attrs) if profiling() else _NOOP
        if root == "auto":
            root = self._enclosing_root() is None
        sp = Span(self, name, bool(root), attrs, self._clock())
        if profiling():
            sp._ann = _annotation(name, attrs)
        return _SpanCtx(self, sp)

    def open_span(self, name: str, root: bool = True, **attrs
                  ) -> "Span | None":
        """Begin a span whose end is *not* lexically scoped (e.g. a serve
        tile: dispatched now, completed at result resolution). Pushes it
        on this thread's stack; call :meth:`exit_scope` when the region
        that spawns nested stages ends, then :meth:`finish_span` when the
        span's real end time arrives. Returns ``None`` when disabled."""
        if not self.enabled:
            return None
        sp = Span(self, name, root, attrs, self._clock())
        self._push(sp)
        return sp

    def exit_scope(self, span: "Span | None") -> None:
        """Remove an :meth:`open_span` from the nesting stack without
        recording it (its duration keeps running)."""
        if span is not None:
            self._pop(span)

    def finish_span(self, span: "Span | None", t1: float | None = None
                    ) -> None:
        """Record a span: stage histogram + root bookkeeping (slow log)."""
        if span is None or not self.enabled:
            return
        span.t1 = self._clock() if t1 is None else t1
        dur = span.t1 - span.t0
        self._stage_hist.observe(dur, stage=span.name)
        root = self._enclosing_root()
        if root is not None and root is not span:
            root.add_stage(span.name, dur)
        if span.root and dur >= self.slow_threshold_s:
            self._log_slow(span, dur)

    def _enclosing_root(self) -> "Span | None":
        for sp in reversed(self._stack()):
            if sp.root:
                return sp
        return None

    def record_duration(self, stage: str, seconds: float,
                        attach: bool = True) -> None:
        """Record a pre-measured duration as if a span ran (queue waits
        are measured from request timestamps, not a context manager)."""
        if not self.enabled:
            return
        self._stage_hist.observe(seconds, stage=stage)
        if attach:
            root = self._enclosing_root()
            if root is not None:
                root.add_stage(stage, seconds)

    # -- slow-query log ------------------------------------------------------

    def _log_slow(self, span: Span, dur: float) -> None:
        self._slow_counter.inc()
        entry = {
            "span": span.name,
            "duration_ms": round(dur * 1e3, 3),
            "stages_ms": {k: round(v * 1e3, 3)
                          for k, v in sorted(span.stages.items())},
            "t_wall": time.time(),
        }
        entry.update({k: v for k, v in span.attrs.items()
                      if v is not None})
        with self._slow_lock:
            self._slow.append(entry)
            if len(self._slow) > self.slow_log_size:
                self._slow.sort(key=lambda e: -e["duration_ms"])
                del self._slow[self.slow_log_size:]

    def slow_queries(self) -> list[dict]:
        """The current slow-query log, slowest first."""
        with self._slow_lock:
            return sorted(self._slow, key=lambda e: -e["duration_ms"])

    def clear_slow_log(self) -> None:
        with self._slow_lock:
            self._slow.clear()

    # -- metric passthrough --------------------------------------------------

    def counter(self, name, help="", labels=()):
        return self.registry.counter(name, help, labels)

    def gauge(self, name, help="", labels=()):
        return self.registry.gauge(name, help, labels)

    def histogram(self, name, help="", labels=(), **kw):
        return self.registry.histogram(name, help, labels, **kw)

    def roll_window(self) -> None:
        self.registry.roll_window()

    # -- exporters -----------------------------------------------------------

    def snapshot(self) -> dict:
        from repro.obs.export import snapshot
        return snapshot(self)

    def render_prometheus(self) -> str:
        from repro.obs.export import render_prometheus
        return render_prometheus(self)
