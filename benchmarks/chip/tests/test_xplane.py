"""The trace reduction, on a trace recorded on a TPU v5e (two served-size
searches of 64 queries against the sift1m index, ``testdata/``) and on
intervals made by hand."""
import numpy as np
import pytest

from conftest import BENCH
from xplane import Trace, union

SEARCH = r"^jit_search_fn\("
KERNEL = (r'custom_call_target="tpu_custom_call"',
          r"^%closed_call[.\d]* = .*kind=kCustom")
PAYLOAD = "f32[24576,64,128]"


@pytest.fixture(scope="module")
def recorded():
    return Trace.load(str(BENCH / "testdata" / "search64x2.xplane.pb"))


def test_window_is_the_benchmark_span(recorded):
    # the host span bench.window bounds the reduction; both searches
    # (~271 ms each on the chip) lie inside it
    assert 0.5 < recorded.window_s < 0.6
    assert recorded.window[0] < recorded.window[1]


def test_busy_union_inside_window(recorded):
    busy = recorded.busy_s()
    assert 0.5 < busy <= recorded.window_s


def test_search_runs_and_kernel_time(recorded):
    runs, total = recorded.module_runs(SEARCH)
    assert runs == 2
    kernel = recorded.ops_in_module(SEARCH, KERNEL, PAYLOAD)
    # the kernel is nearly all of the executable, never more
    assert 0.99 * total < kernel <= total
    assert recorded.ops_in_module(SEARCH, KERNEL, "f32[1,2,3]") == 0.0
    assert recorded.module_runs(r"^jit_insert_fn\(") == (0, 0.0)


def test_breakdown(recorded):
    ops = recorded.top_ops(10)
    assert ops[0][0].startswith("jit_search_fn/search_fn")
    assert ops[0][1] == pytest.approx(
        recorded.ops_in_module(SEARCH, KERNEL, PAYLOAD))
    assert len(ops) <= 10 and all(s >= 0 for _, s in ops)
    assert len(recorded.idle_gaps(10)) <= 10
    gaps = recorded.idle_gaps(10 ** 6)
    assert sum(s for _, s in gaps) == pytest.approx(
        recorded.window_s - recorded.busy_s(), rel=1e-6)


def test_union_merges_nested_and_overlapping():
    s = np.array([0.0, 1.0, 2.0, 10.0, 11.0])
    e = np.array([5.0, 2.0, 6.0, 12.0, 11.5])
    assert union(s, e).tolist() == [[0.0, 6.0], [10.0, 12.0]]
    assert union(np.zeros(0), np.zeros(0)).shape == (0, 2)
