"""The comparison that decides ``correct``: a sound run passes; the
control (the reference at bfloat16) and the faults a cell can have fail.

Each case drives a whole run of a tiny cell on the CPU, past the look for a
chip, with the served path broken underneath where a fault is planted. Each
runs at two shapes: the sift1m configuration shrunk, and a deployment
shaped like GIST's (a payload width that is not a multiple of the 128
lanes, the generator's noise in a low-rank subspace)."""
import gc

import numpy as np
import pytest

SHAPES = {"dim16": {},
          "dim200_rank16": {"dim": 200, "generator": {"noise_rank": 16}}}
shapes = pytest.mark.parametrize("shape", list(SHAPES))


def _failed(result):
    return {n for n, c in result["checks"].items() if c["value"] > c["limit"]}


@shapes
@pytest.mark.parametrize("writer", [False, True])
def test_sound_run_is_correct(run_cell, writer, shape, capsys):
    result, lines = run_cell(writer=writer, **SHAPES[shape])
    assert result["correct"], lines
    assert result["failed"] == 0
    # every end-to-end metric has its reader; the CPU has no allocator
    # counter, so the memory peak finds nothing to read here
    want = {"setup_s"} | ({"churn_search_p95_ms", "mutation_rows_per_s"}
                          if writer else {"search_p95_ms"})
    assert set(result["metrics"]) == want
    # set-up warmed every program the window runs
    assert "info compiles_in_window: 0\n" in capsys.readouterr().out
    assert list(result)[-1] == "checks"


def test_sound_run_of_bursts_ragged_writes_and_multirow_requests(run_cell,
                                                               capsys):
    """What a mix's file can ask for beyond the committed cells: on/off
    bursts, requests of several rows, writer batches of several sizes. The
    warm-up covers their shapes and the check their answers."""
    result, lines = run_cell(writer=True, traffic_over={
        "searches": {"tenant": "app", "rows": [1, 3, 70],
                     "profile": [{"seconds": 0.25, "load": 2.0},
                                 {"seconds": 0.75, "load": 0.2}]},
        "writer": {"tenant": "ingest", "batches": [1024, 300]}})
    assert result["correct"], lines
    assert result["failed"] == 0
    assert "info compiles_in_window: 0\n" in capsys.readouterr().out


def test_trace_run_reports_layer_metrics(run_cell):
    result, lines = run_cell(writer=False, trace=1)
    assert result["correct"], lines
    assert {"busy_s", "window_s"} <= set(result["device"])
    assert "serve_queue_ms" in result["metrics"]
    assert "query_pad_share" in result["metrics"]
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


@shapes
def test_control_fails(shape):
    """The reference at bfloat16 in the program's place fails the gap."""
    import control
    import harness
    from conftest import tiny_cell
    cell = tiny_cell(False, **SHAPES[shape])
    dep = harness.Deployment(cell.conf, cell.traffic, seed=31)
    dep.warm()
    w = harness.serve(dep, cell.qps_unit, 1.0, 31)
    sample = harness.sample_answers(dep, w, 31)
    dep.index = None
    gc.collect()
    ctl = control.control_reading(dep, sample, dep.k, dep.nprobe)
    assert ctl["gap"] > cell.conf["limits"]["gap"] or ctl["stray"] > 0


@shapes
def test_fault_state_unchanged(run_cell, monkeypatch, shape):
    """An add that reports success but leaves the index as it was."""
    import sivf
    orig = sivf.Index.add

    def add(self, vecs, ids, **kw):
        if not getattr(self, "_bench_serving", False):
            return orig(self, vecs, ids, **kw)
        import jax
        before = jax.tree.map(lambda x: x.copy(), self._state)
        rep = orig(self, vecs, ids, **kw)
        self._state = before
        return rep

    orig_init = sivf.ServeEngine.__init__

    def init(self, index, **kw):
        index._bench_serving = True
        orig_init(self, index, **kw)

    monkeypatch.setattr(sivf.Index, "add", add)
    monkeypatch.setattr(sivf.ServeEngine, "__init__", init)
    result, _ = run_cell(writer=True, **SHAPES[shape])
    assert not result["correct"]
    assert "live_mismatch" in _failed(result)


@shapes
def test_fault_half_of_tile_left_out(run_cell, monkeypatch, shape):
    """The second half of each coalesced tile gets the first half's rows."""
    import sivf
    orig = sivf.Index.search

    def search(self, queries, *a, **kw):
        res = orig(self, queries, *a, **kw)
        q = res.labels.shape[0]
        if q < 2:
            return res
        h = q // 2
        lab = np.asarray(res.labels).copy()
        dist = np.asarray(res.distances).copy()
        lab[h:2 * h], dist[h:2 * h] = lab[:h], dist[:h]
        return type(res)(distances=dist, labels=lab, k=res.k,
                         nprobe=res.nprobe, padded_to=res.padded_to)

    monkeypatch.setattr(sivf.Index, "search", search)
    result, _ = run_cell(writer=False, **SHAPES[shape])
    assert not result["correct"]
    assert {"gap", "stray"} & _failed(result)


@shapes
def test_fault_answer_altered(run_cell, monkeypatch, shape):
    """One label of every answer replaced where the search produces it."""
    import sivf
    orig = sivf.Index.search

    def search(self, queries, *a, **kw):
        res = orig(self, queries, *a, **kw)
        lab = np.asarray(res.labels).copy()
        lab[:, 0] = (lab[:, 0] + 1) % 6000
        return type(res)(distances=res.distances, labels=lab, k=res.k,
                         nprobe=res.nprobe, padded_to=res.padded_to)

    monkeypatch.setattr(sivf.Index, "search", search)
    result, _ = run_cell(writer=False, **SHAPES[shape])
    assert not result["correct"]
    assert {"gap", "stray"} & _failed(result)
