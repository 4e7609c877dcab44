"""One run of one cell: set-up, the measured window, the check.

Everything a cell needs is found by name: ``BENCHMARK.json`` names the
cell's configuration and traffic mix, ``configs/<config>.json`` holds the
deployment, ``traffic/<mix>.json`` the load that the one generator
(``generator.py``) makes, and every metric, end-to-end and per-layer, is
read by ``metrics/<metric>.py``. Nothing here branches on a cell's or a
metric's name.

The window drives the served path only: ``ServeEngine`` sessions over a
``sivf.Index(deferred=True)``. Searches go through ``session.search`` and
mutations through ``session.add`` / ``session.remove``.
"""
from __future__ import annotations

import json
import queue
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

import datagen
import reference
from generator import Mix

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent.parent
DRAIN_S = 60.0          # how long past the window's close an answer may come
SAMPLE = 512            # query rows compared with the reference per run


class CellError(RuntimeError):
    """The benchmark's files do not define the requested cell."""


@dataclass
class Cell:
    name: str
    conf: dict
    traffic: Mix
    end_to_end: list
    per_layer: list
    chips: int

    @property
    def qps_unit(self) -> float:
        """The rate a mix's ``load`` of 1 stands for."""
        return float(self.conf["sustained_qps"])


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise CellError(f"no workload {name!r} in BENCHMARK.json "
                        f"({', '.join(sorted(cells))})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    conf = json.loads((root / configs[w["config"]]["file"]).read_text())
    try:
        traffic = Mix.from_json(json.loads(
            (HERE / "traffic" / f"{w['traffic']}.json").read_text()),
            int(conf["generator"]["batch_rows"]))
    except ValueError as e:
        raise CellError(f"traffic {w['traffic']!r}: {e}") from e
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if ("workloads" in m and name in m["workloads"])
             or ("workloads" not in m and m["moves"] in reported)]
    return Cell(name, conf, traffic, e2e, layer, int(w["chips"]))


def engine_cfg(conf: dict):
    import sivf
    return sivf.SIVFConfig(
        dim=conf["dim"], n_lists=conf["n_lists"], n_slabs=conf["n_slabs"],
        capacity=conf["capacity"], n_max=conf["n_max"],
        metric=conf["metric"], max_chain=conf["max_chain"],
        attributes=tuple(conf["attributes"]))


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

class Deployment:
    """The index of one configuration, filled with its base rows."""

    def __init__(self, conf: dict, traffic: Mix, seed: int, impl=None):
        import sivf
        gen = conf["generator"]
        self.conf, self.traffic, self.seed = conf, traffic, int(seed)
        self.k, self.nprobe = int(conf["k"]), int(conf["nprobe"])
        self.batch = int(gen["batch_rows"])
        self.mix = datagen.Mixture.of(conf, seed)
        train = jnp.concatenate([self.mix.batch(b) for b in
                                 range(-(-gen["kmeans_sample"] // self.batch))
                                 ])[:gen["kmeans_sample"]]
        self.centroids = reference.kmeans(
            jax.random.fold_in(self.mix.key, 1), train, conf["n_lists"],
            gen["kmeans_iters"])
        del train
        self.cfg = engine_cfg(conf)
        eng = conf["engine"]
        self.index = sivf.Index(self.cfg, self.centroids, impl=impl,
                                deferred=True, min_bucket=eng["min_bucket"])
        self.ledger = reference.Ledger(conf["n_max"])
        self.tenants = int(conf["attributes"]["tenant"])
        n = int(conf["base_rows"])
        pending = []
        for lo in range(0, n, self.batch):
            m = min(self.batch, n - lo)
            vecs = self.mix.take(lo, self.batch)
            ids = self.ledger.ids(lo, lo + m)
            if m < self.batch:       # the last batch pads to the same bucket
                ids = np.concatenate([ids, np.full(self.batch - m, -1,
                                                   np.int32)])
            pending.append((self.index.add(vecs, ids,
                                           attrs={"tenant": ids % self.tenants}),
                            m))
            self.ledger.added(lo, lo + m, self.index.epoch)
        self.index.flush()
        bad = [(p.result(), m) for p, m in pending if p.result().accepted != m]
        if bad:
            raise RuntimeError(f"set-up ingest refused rows: {bad[0]}")
        self.next_serial = n
        self.oldest = 0
        # the query pool: host rows, as clients send them
        pool = traffic.query_pool
        self.queries = np.concatenate([
            np.asarray(self.mix.batch(b, datagen.QUERIES))
            for b in range(-(-pool // self.batch))])[:pool]

    def request_rows(self, start: int, n: int) -> np.ndarray:
        """``n`` consecutive pool rows from ``start``, wrapping."""
        return self.queries[(start + np.arange(n)) % len(self.queries)]

    def warm(self) -> None:
        """Compile and run every shape the window uses, and no other."""
        eng = self.conf["engine"]
        tiles = self.traffic.tile_rows(eng["max_coalesce"])
        for b in self.index.bucket_shapes(max(tiles)):
            np.asarray(self.index.search(self.request_rows(0, b), self.k,
                                         self.nprobe).labels)
        # a tile of q live rows comes back as its padded [bucket, k] outputs
        # sliced to q rows: one small program per tile size (the outputs
        # are not committed to a device, and neither are these)
        for q in tiles:
            b = self.index.bucket_shapes(q)[-1]
            for dt in (np.float32, np.int32):
                jnp.asarray(np.zeros((b, self.k), dt))[:q].block_until_ready()
        # each writer batch: its rows, an add of skipped rows (-1 ids) and
        # removes of ids that are not live. They compile the insert and
        # delete at the batch's bucket and the flush of one and of two
        # pending batches (the writer keeps one pair in flight), and change
        # nothing
        lo = self.next_serial + self.conf["n_max"] // 2
        for b in sorted(set(self.traffic.batches)):
            vecs = np.asarray(self.mix.take(self.next_serial, b))
            skip = np.full(b, -1, np.int32)
            self.index.add(vecs, skip, attrs={"tenant": skip % self.tenants})
            self.index.flush()
            absent = self.ledger.ids(lo, lo + b)
            for pending in (1, 2):
                for _ in range(pending):
                    self.index.remove(absent)
                self.index.flush()


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

class CompileCount:
    """Counts programs lowered while open: each is a jit cache miss, a
    compile or a load from the persistent cache."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.n += 1

    def close(self) -> int:
        jax.monitoring.unregister_event_duration_listener(self._on)
        return self.n


class Answer(NamedTuple):
    start: int              # first pool row of the request
    rows: int               # query rows it carried
    result: object          # the engine's ServeSearchResult
    t_done: float           # when the client had it


@dataclass
class Window:
    t0: float = 0.0
    late_s: list = field(default_factory=list)   # generator lateness
    lat_s: list = field(default_factory=list)    # scheduled -> result
    answers: list = field(default_factory=list)  # Answer, in completion order
    shed: int = 0
    errors: list = field(default_factory=list)
    unanswered: int = 0
    attempted: int = 0
    mut_rows: int = 0
    mut_refused: int = 0
    mut_pairs: int = 0
    mut_last_ack: float = 0.0
    compiles: int = 0
    trace_window: tuple = (0.0, 0.0)


def serve(dep: Deployment, qps_unit: float, seconds: float, seed: int,
          on_open=None, on_close=None, trace_s: float | None = None
          ) -> Window:
    """The mix's open-loop searches, with ``qps_unit`` the rate of a load
    of 1, for ``seconds``, and its closed-loop writer where it has one,
    through ``ServeEngine`` sessions.

    ``on_open`` runs as the window opens; ``on_close`` after ``trace_s``
    seconds of it, or once every answer is in when ``trace_s`` is None or
    not shorter than the window."""
    from repro.serve.quota import Backpressure, TenantQuota
    from repro.serve.sivf_engine import ServeEngine

    eng_conf = dep.conf["engine"]
    mix = dep.traffic
    engine = ServeEngine(
        dep.index, default_k=dep.k, default_nprobe=dep.nprobe,
        quota=TenantQuota(max_inflight_searches=eng_conf["max_inflight"]),
        max_queue=eng_conf["max_queue"], max_coalesce=eng_conf["max_coalesce"],
        flush_every=eng_conf["flush_every"])
    reader = engine.session(mix.reader)
    writer = engine.session(mix.writer)
    sched = mix.arrivals(qps_unit, seconds, seed)
    sizes, starts = mix.requests(len(sched), seed)
    w = Window(attempted=len(sched))
    inbox: queue.Queue = queue.Queue()

    def collect():
        while True:
            item = inbox.get()
            if item is None:
                return
            due, start, n, fut = item
            try:
                res = fut.result(timeout=max(0.0, w.t0 + seconds + DRAIN_S
                                             - time.perf_counter()))
            except TimeoutError:
                w.unanswered += 1
                continue
            except Exception as e:       # the request failed in the engine
                w.errors.append(repr(e))
                continue
            t_done = time.perf_counter()
            w.lat_s.append(t_done - due)
            w.answers.append(Answer(start, n, res, t_done))

    def write():
        t_end = w.t0 + seconds
        for b in mix.writer_batches(seed):
            if time.perf_counter() >= t_end:
                return
            lo = dep.next_serial
            vecs = np.asarray(dep.mix.take(lo, b))
            ids = dep.ledger.ids(lo, lo + b)
            gone_lo = dep.oldest
            fa = writer.add(vecs, ids, attrs={"tenant": ids % dep.tenants})
            fr = writer.remove(dep.ledger.ids(gone_lo, gone_lo + b))
            try:
                ra = fa.result(timeout=DRAIN_S + seconds)
                rr = fr.result(timeout=DRAIN_S + seconds)
            except Exception as e:
                w.errors.append(repr(e))
                return
            w.mut_last_ack = time.perf_counter()
            w.mut_pairs += 1
            w.mut_rows += 2 * b
            w.mut_refused += (b - ra.report.accepted) + (b - rr.report.accepted)
            dep.ledger.added(lo, lo + b, ra.epoch)
            dep.ledger.removed(gone_lo, gone_lo + b, rr.epoch)
            dep.next_serial += b
            dep.oldest += b

    collector = threading.Thread(target=collect, name="bench-collect")
    collector.start()
    writer_th = None
    if on_open is not None:
        on_open()
    compiles = CompileCount()
    w.t0 = time.perf_counter()
    closed = [False]

    # the traced part of the window, as a host span the trace reduction
    # finds (``xplane.WINDOW_SPAN``)
    span = jax.profiler.TraceAnnotation("bench.window")
    span.__enter__()

    def close_trace():
        if not closed[0]:
            closed[0] = True
            span.__exit__(None, None, None)
            w.trace_window = (w.t0, time.perf_counter())
            if on_close is not None:
                on_close()

    if mix.batches:
        writer_th = threading.Thread(target=write, name="bench-writer")
        writer_th.start()
    for due_rel, n, start in zip(sched, sizes, starts):
        due = w.t0 + due_rel
        if trace_s is not None and due >= w.t0 + trace_s:
            close_trace()
        q = dep.request_rows(int(start), int(n))
        wait = due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        w.late_s.append(max(0.0, time.perf_counter() - due))
        try:
            fut = reader.search(q)
        except Backpressure:
            w.shed += 1
            continue
        inbox.put((due, int(start), int(n), fut))
    inbox.put(None)
    collector.join(timeout=seconds + 2 * DRAIN_S)
    if writer_th is not None:
        writer_th.join(timeout=seconds + 2 * DRAIN_S)
    close_trace()
    engine.close()
    w.compiles = compiles.close()
    if collector.is_alive() or (writer_th is not None and writer_th.is_alive()):
        raise RuntimeError("a client thread of the benchmark did not finish")
    return w


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------

@dataclass
class Check:
    numbers: list           # (name, value, limit): value <= limit passes
    info: dict              # reported beside them, not compared
    sample: dict            # what the comparison used, for the control

    @property
    def correct(self) -> bool:
        return all(v <= lim for _, v, lim in self.numbers)


def readback(dep: Deployment) -> np.ndarray:
    """Ids the index holds live, read back once the window has closed."""
    return np.flatnonzero(np.asarray(dep.index.state.att_slab) >= 0)


def sample_answers(dep: Deployment, w: Window, seed: int) -> dict:
    """Up to ``SAMPLE`` answered query rows, drawn from the seed across the
    window: each row's query, epoch, labels and distances."""
    rng = np.random.default_rng([int(seed) & (2 ** 63 - 1), 29])
    ends = np.cumsum([a.rows for a in w.answers], dtype=np.int64)
    total = int(ends[-1]) if len(ends) else 0
    n = min(SAMPLE, total)
    pick = np.sort(rng.choice(total, n, replace=False)) if n else \
        np.zeros(0, np.int64)
    which = np.searchsorted(ends, pick, side="right")
    got = [(w.answers[i], int(p - (ends[i] - w.answers[i].rows)))
           for i, p in zip(which, pick)]
    dim, k = dep.conf["dim"], dep.k
    return {
        "queries": np.stack([dep.request_rows(a.start + j, 1)[0]
                             for a, j in got]) if got
        else np.zeros((0, dim), np.float32),
        "epoch": np.array([a.result.epoch for a, _ in got], np.int64),
        "labels": np.stack([a.result.labels[j] for a, j in got]) if got
        else np.zeros((0, k), np.int32),
        "dist": np.stack([a.result.distances[j] for a, j in got]) if got
        else np.zeros((0, k), np.float32),
    }


def final_epoch(ledger: reference.Ledger) -> int:
    """The last epoch any acknowledged mutation reported."""
    never = reference.Ledger.NEVER
    ep = np.concatenate([ledger.add[ledger.add < never],
                         ledger.rem[ledger.rem < never]])
    return int(ep.max()) if ep.size else 0


def window_work(dep: Deployment, w: Window) -> tuple[float, float]:
    """Bytes and operations the window's answered searches needed
    (``work.scan_work``), by the reference's routing and probe."""
    from work import scan_work
    live = dep.ledger.live_at(final_epoch(dep.ledger))
    counts = reference.list_rows(dep.mix, dep.centroids, live)
    lo, hi = w.trace_window
    qs = [dep.request_rows(a.start, a.rows) for a in w.answers
          if lo <= a.t_done <= hi]
    qs = np.concatenate(qs) if qs else np.zeros((0, dep.conf["dim"]),
                                                np.float32)
    picked = [np.asarray(reference.probe_sets(
        dep.centroids, jnp.asarray(qs[i:i + 4096]),
        dep.nprobe, "f32")[2]) for i in range(0, len(qs), 4096)]
    picked = np.concatenate(picked) if picked else np.zeros((0, dep.nprobe),
                                                             np.int32)
    return scan_work(counts, picked, dep.conf["dim"], dep.conf["capacity"])


def check(dep: Deployment, w: Window, live_ids: np.ndarray, sample: dict,
          limits: dict) -> Check:
    """Compare the sampled answers with the reference; the caller has
    freed the index first."""
    expect = np.unique(dep.ledger.ids(0, dep.ledger.n)[dep.ledger.live_at(
        final_epoch(dep.ledger))])
    mismatch = int(np.setxor1d(expect, live_ids).size)
    res = reference.compare(dep.mix, dep.centroids, dep.ledger,
                            jnp.asarray(sample["queries"]), sample["epoch"],
                            sample["labels"], sample["dist"], dep.k,
                            dep.nprobe) if len(sample["epoch"]) else \
        {"gap": 0.0, "stray": 0, "recall_at_k": 0.0, "bracket_ties": 0}
    numbers = [
        ("gap", res["gap"], limits["gap"]),
        ("stray", res["stray"], 0),
        ("live_mismatch", mismatch, 0),
        ("unanswered", w.unanswered + len(w.errors), 0),
        ("mutation_refused", w.mut_refused, 0),
    ]
    info = {"recall_at_10": res["recall_at_k"],
            "bracket_ties": res["bracket_ties"],
            "live_rows": int(expect.size)}
    return Check(numbers, info, sample)
