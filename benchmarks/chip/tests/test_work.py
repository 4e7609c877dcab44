"""The scan's work counts live rows only, never the slab layout."""
import numpy as np
import pytest

from work import META_BYTES_PER_SLOT, least_time_s, scan_work


def test_formula():
    rows = np.array([0, 1, 64, 65])
    probed = np.array([[1, 2], [3, 0]])
    nbytes, flops = scan_work(rows, probed, dim=128, capacity=64)
    slots = (1 + 1 + 2 + 0) * 64
    assert nbytes == slots * (4 * 128 + META_BYTES_PER_SLOT) + 2 * 4 * 128
    assert flops == 2 * 128 * slots
    t, bound = least_time_s(nbytes, flops, {"hbm_bytes_per_s": 819e9,
                                            "bf16_flops": 197e12})
    assert bound == "bytes" and t == pytest.approx(nbytes / 819e9)


def test_same_rows_two_layouts_same_bytes():
    """Two deployments of the same rows, one with twice the slabs and a
    longer chain bound, count the same work for the same window."""
    import harness
    from conftest import tiny_cell
    works = []
    for over in ({}, {"n_slabs": 2048, "max_chain": 128}):
        cell = tiny_cell(False, **over)
        dep = harness.Deployment(cell.conf, cell.traffic, seed=5)
        w = harness.Window(trace_window=(0.0, 1.0))
        w.answers = [harness.Answer(r, 1, None, 0.5) for r in range(64)]
        works.append(harness.window_work(dep, w))
    assert works[0] == works[1]
    assert works[0][0] > 0
