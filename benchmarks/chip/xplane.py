"""Reduction of a JAX profiler trace (``.xplane.pb``) to device time.

A trace holds one plane per TPU (``/device:TPU:<n>``) whose ``XLA Ops``
line has one event per operation run on the chip and whose ``XLA Modules``
line has one event per executable run, and one host plane
(``/host:CPU``) with a line per thread. This module turns it into:

* busy intervals per device (the union of its op events) and their total;
* device time per op name and per module name, inside a window;
* the longest idle gaps, each named by the host event that covers most of
  it (what the host was doing while the chip waited).

Per-layer metric readers (``metrics/*.py``) take their numbers from a
:class:`Trace` and hold the event names they match themselves.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "bench.window"
# host events that only wrap others: never the name of an idle gap
_GENERIC = re.compile(r"^(ThreadpoolListener|ThunkExecutor|\$|bench\.window$)")


@dataclass
class Events:
    name: np.ndarray          # object array of str (shared per distinct name)
    start: np.ndarray         # float64 ns
    dur: np.ndarray           # float64 ns

    def __len__(self) -> int:
        return len(self.name)

    @property
    def end(self) -> np.ndarray:
        return self.start + self.dur

    def clip(self, lo: float, hi: float) -> "Events":
        keep = (self.end > lo) & (self.start < hi)
        s = np.maximum(self.start[keep], lo)
        e = np.minimum(self.end[keep], hi)
        return Events(self.name[keep], s, e - s)

    def matching(self, patterns, contains: str | tuple = "") -> np.ndarray:
        """Mask of events whose name matches any of ``patterns`` (regular
        expressions) and holds ``contains``, or one of its strings where it
        is a tuple."""
        rx = [re.compile(p) for p in ([patterns] if isinstance(patterns, str)
                                      else patterns)]
        subs = (contains,) if isinstance(contains, str) else tuple(contains)
        memo: dict = {}
        out = np.zeros(len(self), bool)
        for i, n in enumerate(self.name):
            hit = memo.get(n)
            if hit is None:
                hit = memo[n] = any(s in n for s in subs) \
                    and any(r.search(n) for r in rx)
            out[i] = hit
        return out


def _events(line) -> Events:
    names, starts, durs = [], [], []
    distinct: dict = {}
    for ev in line.events:
        n = ev.name
        names.append(distinct.setdefault(n, n))
        starts.append(ev.start_ns)
        durs.append(ev.duration_ns)
    return Events(np.array(names, dtype=object), np.array(starts, np.float64),
                  np.array(durs, np.float64))


def union(start: np.ndarray, end: np.ndarray) -> np.ndarray:
    """Merged ``[k, 2]`` intervals covering the given ones."""
    if not len(start):
        return np.zeros((0, 2))
    o = np.argsort(start, kind="stable")
    s, e = start[o], end[o]
    run_end = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > run_end[:-1]
    idx = np.flatnonzero(new)
    last = np.append(idx[1:], len(s)) - 1
    return np.stack([s[idx], run_end[last]], axis=1)


@dataclass
class Trace:
    ops: dict                 # device plane name -> Events (XLA Ops)
    modules: dict             # device plane name -> Events (XLA Modules)
    host: Events              # every host thread's events, merged
    window: tuple             # (start, end) ns of the benchmark's window

    @classmethod
    def load(cls, path: str) -> "Trace":
        """Read one ``.xplane.pb`` file, or the newest under a directory."""
        from jax.profiler import ProfileData
        if os.path.isdir(path):
            found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                     recursive=True), key=os.path.getmtime)
            if not found:
                raise FileNotFoundError(f"no .xplane.pb under {path}")
            path = found[-1]
        pd = ProfileData.from_file(path)
        ops, modules, host = {}, {}, []
        for plane in pd.planes:
            if DEVICE_PLANE.match(plane.name):
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        ops[plane.name] = _events(line)
                    elif line.name == MODULES_LINE:
                        modules[plane.name] = _events(line)
            elif plane.name == HOST_PLANE:
                host.extend(_events(line) for line in plane.lines)
        if host:
            host_ev = Events(np.concatenate([h.name for h in host]),
                             np.concatenate([h.start for h in host]),
                             np.concatenate([h.dur for h in host]))
        else:
            host_ev = Events(np.array([], object), np.zeros(0), np.zeros(0))
        win = host_ev.name == WINDOW_SPAN
        if win.any():
            i = int(np.flatnonzero(win)[0])
            window = (host_ev.start[i], host_ev.end[i])
        else:
            allev = [e for e in ops.values() if len(e)]
            lo = min((e.start.min() for e in allev), default=0.0)
            hi = max((e.end.max() for e in allev), default=0.0)
            window = (lo, hi)
        return cls(ops, modules, host_ev, window)

    # -- reductions ---------------------------------------------------------

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def _ops_in_window(self):
        return {p: e.clip(*self.window) for p, e in self.ops.items()}

    def busy(self, plane: str | None = None) -> np.ndarray:
        """Merged busy intervals of one device (the first by default)."""
        ops = self._ops_in_window()
        if not ops:
            return np.zeros((0, 2))
        e = ops[plane or sorted(ops)[0]]
        return union(e.start, e.end)

    def busy_s(self) -> float:
        """Seconds some op ran, averaged over the traced devices."""
        ops = self._ops_in_window()
        if not ops:
            return 0.0
        tot = []
        for p in ops:
            b = self.busy(p)
            tot.append(float(np.sum(b[:, 1] - b[:, 0])) * 1e-9)
        return float(np.mean(tot))

    def _first(self, planes: dict):
        return planes[sorted(planes)[0]].clip(*self.window) if planes else None

    def module_runs(self, pattern: str) -> tuple[int, float]:
        """Runs and device seconds of the executables matching ``pattern``
        (first device), from the ``XLA Modules`` line."""
        e = self._first(self.modules)
        if e is None:
            return 0, 0.0
        hit = e.matching(pattern)
        return int(hit.sum()), float(np.sum(e.dur[hit])) * 1e-9

    def ops_in_module(self, module: str, patterns,
                      contains: str | tuple = "") -> float:
        """Device seconds (first device) covered by ops that match
        ``patterns`` and hold ``contains`` (one of them, where it is a
        tuple), and start inside a run of an executable matching
        ``module``. Nested matches count once."""
        mods, ops = self._first(self.modules), self._first(self.ops)
        if mods is None or ops is None:
            return 0.0
        m = mods.matching(module)
        o = np.argsort(mods.start[m])
        ms, me = mods.start[m][o], mods.end[m][o]
        hit = ops.matching(patterns, contains)
        st = ops.start[hit]
        i = np.searchsorted(ms, st, side="right") - 1
        inside = (i >= 0) & (st < me[np.clip(i, 0, None)]) if len(ms) else \
            np.zeros(len(st), bool)
        u = union(st[inside], ops.end[hit][inside])
        return float(np.sum(u[:, 1] - u[:, 0])) * 1e-9

    def top_ops(self, n: int = 10) -> list:
        """``[[name, seconds], ...]`` of the ops that took most device time
        (first device), each named ``<executable>/<op>``. An op that only
        wraps others (a loop around a kernel) gives way to what it holds."""
        mods, e = self._first(self.modules), self._first(self.ops)
        if e is None or not len(e):
            return []
        o = np.argsort(e.start, kind="stable")
        st, en, nm = e.start[o], e.end[o], e.name[o]
        leaf = np.ones(len(st), bool)
        leaf[:-1] = st[1:] >= en[:-1]
        owner = np.full(len(st), "", dtype=object)
        if mods is not None and len(mods):
            mo = np.argsort(mods.start)
            ms, me, mn = mods.start[mo], mods.end[mo], mods.name[mo]
            i = np.searchsorted(ms, st, side="right") - 1
            ok = (i >= 0) & (st < me[np.clip(i, 0, None)])
            owner[ok] = [str(x).split("(")[0] for x in mn[i[ok]]]
        tot: dict = {}
        for name, mod, d in zip(nm[leaf], owner[leaf], (en - st)[leaf]):
            key = f"{mod}/{str(name).split(' = ')[0].lstrip('%')}"
            tot[key] = tot.get(key, 0.0) + d
        best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9] for k, v in best]

    def idle_gaps(self, n: int = 10) -> list:
        """``[[name, seconds], ...]``: the longest gaps with no op on the
        first device inside the window, each named by the host event that
        overlaps it most (``idle`` when none does)."""
        b = self.busy()
        lo, hi = self.window
        edges = np.concatenate([[lo], b.reshape(-1), [hi]]).reshape(-1, 2)
        gaps = edges[edges[:, 1] > edges[:, 0]]
        order = np.argsort(gaps[:, 0] - gaps[:, 1])[:n]
        h = self.host
        keep = np.fromiter((not _GENERIC.match(str(x)) for x in h.name), bool,
                           len(h))
        hs, he, hn = h.start[keep], h.end[keep], h.name[keep]
        out = []
        for g0, g1 in gaps[order]:
            ov = np.minimum(he, g1) - np.maximum(hs, g0)
            j = int(np.argmax(ov)) if len(ov) else -1
            name = str(hn[j]) if j >= 0 and ov[j] > 0 else "idle"
            out.append([name, (g1 - g0) * 1e-9])
        return out
