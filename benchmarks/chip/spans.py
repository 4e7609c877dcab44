"""The program's own spans in a benchmark trace: host events named
``sivf.<span>``, which ``repro.obs`` writes while a profiler records.

* the spans that start inside the window, by name, with their durations;
* the idle time of the first device that falls under a set of spans;
* the spans' args (counts and waits known only to the program).

A :class:`xplane.Trace` keeps each host event's name, start and duration,
and no args. The args are read here from the ``.xplane.pb`` file the trace
was loaded from: the newest under ``TRACE_DIRS`` (the directories
``run.py`` records into) whose ``bench.window`` event starts where the
trace's window does. Each function finds nothing where the trace holds
none of the program's spans, so a program without them leaves the metrics
that read them out.
"""
from __future__ import annotations

import glob
import os
import tempfile

import numpy as np

from xplane import HOST_PLANE, WINDOW_SPAN, Events, Trace, union

PREFIX = "sivf."
TRACE_DIRS = os.path.join(tempfile.gettempdir(), "sivf-trace-*")

_args_cache: dict = {}        # window start ns -> {span name: [(start, args)]}


def _named(trace: Trace, names: tuple) -> np.ndarray:
    """Mask of the host events ``sivf.<name>`` for each of ``names``, or of
    every program span when ``names`` is empty."""
    h = trace.host
    want = {PREFIX + n for n in names}
    return np.fromiter(((x in want) if want else str(x).startswith(PREFIX)
                        for x in h.name), bool, len(h))


def spans(trace: Trace, *names: str) -> Events:
    """The program's spans ``sivf.<name>`` (all of them when no name is
    given) that start inside the window."""
    h = trace.host
    lo, hi = trace.window
    m = _named(trace, names) & (h.start >= lo) & (h.start < hi)
    return Events(h.name[m], h.start[m], h.dur[m])


def _length(iv: np.ndarray) -> float:
    return float(np.sum(iv[:, 1] - iv[:, 0]))


def idle_under(trace: Trace, *names: str) -> float:
    """Seconds inside the window in which the first device runs no op
    while one of the program's spans ``sivf.<name>`` is open."""
    h = trace.host
    m = _named(trace, names)
    on = Events(h.name[m], h.start[m], h.dur[m]).clip(*trace.window)
    lo, hi = trace.window
    b = trace.busy()
    edges = np.concatenate([[lo], b.reshape(-1), [hi]]).reshape(-1, 2)
    idle = edges[edges[:, 1] > edges[:, 0]]
    spans_iv = union(on.start, on.end)
    both = union(np.concatenate([spans_iv[:, 0], idle[:, 0]]),
                 np.concatenate([spans_iv[:, 1], idle[:, 1]]))
    # |A ∩ B| = |A| + |B| - |A ∪ B|, each a union of disjoint intervals
    return max(0.0, _length(spans_iv) + _length(idle) - _length(both)) * 1e-9


def _file_args(path: str, window_start: float) -> dict | None:
    """``{span name: [(start ns, args), ...]}`` of the program's spans in
    one trace file, or None when its window does not start at
    ``window_start``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    found, start = {}, None
    for plane in pd.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                n = ev.name
                if n.startswith(PREFIX):
                    found.setdefault(n, []).append(
                        (float(ev.start_ns), dict(ev.stats)))
                elif n == WINDOW_SPAN and start is None:
                    start = float(ev.start_ns)
    return found if start == window_start else None


def args(trace: Trace, name: str) -> list[dict]:
    """The args of the program's spans ``sivf.<name>`` that start inside
    the window, in start order; empty where the trace holds none or its
    file is not found."""
    if not len(spans(trace, name)):
        return []
    lo, hi = trace.window
    if lo not in _args_cache:
        files = sorted(glob.glob(os.path.join(TRACE_DIRS, "**",
                                              "*.xplane.pb"), recursive=True),
                       key=os.path.getmtime, reverse=True)
        for f in files:
            found = _file_args(f, lo)
            if found is not None:
                _args_cache[lo] = found
                break
        else:
            return []
    evs = sorted(_args_cache[lo].get(PREFIX + name, []), key=lambda e: e[0])
    return [a for s, a in evs if lo <= s < hi]
