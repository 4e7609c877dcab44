"""The one traffic generator: every mix is a data file, ``traffic/<mix>.json``,
that this module reads. A mix adds no code.

A mix holds:

* ``searches``: open-loop single-session readers. ``profile`` is a cycle of
  phases, each ``{"seconds": s, "load": l}``: arrivals at ``l`` times the
  configuration's ``sustained_qps`` for ``s`` seconds, then the next phase.
  One phase is a steady Poisson stream; an on and an off phase make bursts.
  ``rows`` is the multiset of query rows per request, ``tenant`` the engine
  session the requests go through.
* ``writer`` (optional): one closed-loop client (``tenant``) that keeps one
  pair in flight: it adds the next ``b`` rows and removes the ``b`` oldest,
  for each ``b`` of ``batches`` in turn. Each ``b`` is at most the
  configuration's ingest batch.
* ``query_pool``: host rows the requests draw from (each request takes
  consecutive rows from a seeded start).
* ``trace_seconds`` (optional): how much of a ``--trace 1`` window the
  profiler records; the window itself runs on.

Every seed gets the same arrival gaps, request sizes and writer batches, in
an order of its own, so seeds change the order of the work and not its
amount.
"""
from __future__ import annotations

from dataclasses import dataclass
from itertools import cycle

import numpy as np


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2 ** 63 - 1), stream])


@dataclass(frozen=True)
class Mix:
    profile: tuple          # ((seconds, load), ...), one cycle
    rows: tuple             # query rows per request, the multiset
    reader: str
    query_pool: int
    batches: tuple = ()     # writer batches, one cycle; () without a writer
    writer: str = "ingest"
    trace_seconds: float | None = None

    @classmethod
    def from_json(cls, d: dict, ingest_batch: int) -> "Mix":
        s, w = d["searches"], d.get("writer")
        mix = cls(profile=tuple((float(p["seconds"]), float(p["load"]))
                                for p in s["profile"]),
                  rows=tuple(int(r) for r in s["rows"]),
                  reader=s["tenant"], query_pool=int(d["query_pool"]),
                  batches=tuple(int(b) for b in w["batches"]) if w else (),
                  writer=w["tenant"] if w else "ingest",
                  trace_seconds=d.get("trace_seconds"))
        if not mix.profile or any(s <= 0 or ld <= 0 for s, ld in mix.profile):
            raise ValueError("every search phase needs seconds > 0 and "
                             "load > 0")
        if not mix.rows or min(mix.rows) < 1:
            raise ValueError("requests carry one query row or more")
        if w and not (mix.batches and 0 < min(mix.batches)
                      and max(mix.batches) <= ingest_batch):
            raise ValueError(f"writer batches lie in [1, {ingest_batch}], "
                             f"the ingest batch")
        return mix

    @property
    def mean_load(self) -> float:
        secs = sum(s for s, _ in self.profile)
        return sum(s * ld for s, ld in self.profile) / secs

    def arrivals(self, qps_unit: float, seconds: float, seed: int
                 ) -> np.ndarray:
        """Arrival times in ``[0, seconds)``: a Poisson process whose rate
        follows the profile, made by mapping one unit-rate process through
        the inverse of the integrated rate. The unit-rate gaps are the
        exponential's quantiles, scaled to the window's expected count and
        shuffled by the seed."""
        secs = np.array([s for s, _ in self.profile])
        rate = np.array([ld for _, ld in self.profile]) * qps_unit
        reps = int(np.ceil(seconds / secs.sum()))
        ends = np.minimum(np.cumsum(np.tile(secs, reps)), seconds)
        t = np.concatenate([[0.0], ends])
        width = np.diff(t)
        keep = width > 0
        t = np.concatenate([[0.0], ends[keep]])
        lam = np.concatenate([[0.0], np.cumsum(width[keep]
                                               * np.tile(rate, reps)[keep])])
        n = max(1, int(round(lam[-1])))
        u = (np.arange(n) + 0.5) / n
        gaps = -np.log1p(-u)
        gaps *= lam[-1] / gaps.sum()
        _rng(seed, 17).shuffle(gaps)
        return np.interp(np.concatenate([[0.0], np.cumsum(gaps)[:-1]]), lam, t)

    def requests(self, n: int, seed: int) -> tuple[np.ndarray, np.ndarray]:
        """Rows and first pool row of each of ``n`` requests."""
        sizes = np.resize(np.asarray(self.rows, np.int64), n)
        _rng(seed, 19).shuffle(sizes)
        starts = _rng(seed, 23).permutation(n) % self.query_pool
        return sizes, starts

    def writer_batches(self, seed: int):
        """The writer's batch sizes, endlessly: the cycle in the seed's
        order."""
        order = np.asarray(self.batches, np.int64)
        _rng(seed, 31).shuffle(order)
        return cycle(int(b) for b in order)

    def tile_rows(self, max_coalesce: int) -> list[int]:
        """Every row count a search tile of this mix can have: up to
        ``max_coalesce`` coalesced, or one larger request alone."""
        return sorted(set(range(1, max_coalesce + 1))
                      | {r for r in self.rows if r > max_coalesce})
