"""The three readers that find the scan kernel (``scan_kernel.py``): on
traces built by hand with the payload plane at ``dim`` and lane-padded, and
on the chip-recorded trace, where they read what they read before the
lane-padded width was accepted."""
import json
from types import SimpleNamespace

import numpy as np
import pytest

import scan_kernel
from conftest import BENCH
from run import load_reader
from xplane import Events, Trace

READERS = ("scan_kernel_ms", "scan_roofline", "search_other_device_ms")
PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
WORK = (2.5e8, 4.0e9)            # bytes, operations: any fixed count


def _events(rows):
    return Events(np.array([r[0] for r in rows], dtype=object),
                  np.array([r[1] for r in rows], np.float64),
                  np.array([r[2] - r[1] for r in rows], np.float64))


def _kernel_op(form, n, c, d, w):
    plane = f"f32[{n},{c},{w}]{{2,1,0:T(8,128)}} %state_data.1"
    if form == "custom_call":
        return (f"%search_fn.1 = (f32[64,10]{{1,0}}, s32[64,10]{{1,0}}) "
                f"custom-call(s32[65536]{{0}} %reshape.6, f32[64,{w}]{{1,0}} "
                f"%copy-done.2, {plane}, s32[{n},{c}]{{1,0}} %copy.7), "
                f'custom_call_target="tpu_custom_call"')
    return (f"%closed_call.3 = (f32[64,10]{{1,0}}, s32[64,10]{{1,0}}) "
            f"fusion(f32[64,{d}]{{1,0}} %q, {plane}), kind=kCustom, "
            f"calls=%fused_computation.7")


def _ctx(conf, trace):
    return SimpleNamespace(trace=trace, conf=conf, answers=[object()],
                           peak=PEAK, work=lambda: WORK)


def _trace(kernel_name):
    """Two runs of the search executable, 1,000 ns each, each holding the
    kernel for 600 ns and a sort for 100 ns; a kernel-named op outside
    the executable, which no reader counts."""
    mods = _events([("jit_search_fn(a1b2)", 0, 1000),
                    ("jit_search_fn(a1b2)", 2000, 3000)])
    ops = _events([(kernel_name, 100, 700), ("%sort.9 = s32[64] sort()",
                                             800, 900),
                   (kernel_name, 2100, 2700), ("%sort.9 = s32[64] sort()",
                                               2800, 2900),
                   (kernel_name, 4000, 4600)])
    return Trace({"/device:TPU:0": ops}, {"/device:TPU:0": mods},
                 _events([]), (0.0, 5000.0))


@pytest.mark.parametrize("form", ["custom_call", "closed_call"])
@pytest.mark.parametrize("dim,padded", [(960, False), (960, True),
                                        (200, False), (200, True),
                                        (128, False)])
def test_readers_find_the_plane_at_dim_and_lane_padded(form, dim, padded):
    conf = {"n_slabs": 24576, "capacity": 64, "dim": dim}
    w = -(-dim // 128) * 128 if padded else dim
    tr = _trace(_kernel_op(form, 24576, 64, dim, w))
    got = {n: load_reader(n)(_ctx(conf, tr)) for n in READERS}
    kernel_s = 1200e-9
    assert got["scan_kernel_ms"] == pytest.approx(kernel_s / 2 * 1e3)
    assert got["search_other_device_ms"] == pytest.approx(
        (2000e-9 - kernel_s) / 2 * 1e3)
    least = max(WORK[0] / PEAK["hbm_bytes_per_s"],
                WORK[1] / PEAK["bf16_flops"])
    assert got["scan_roofline"] == pytest.approx(100 * least / kernel_s)


@pytest.mark.parametrize("dim,width", [(960, 896), (960, 1088), (200, 128),
                                       (128, 256)])
def test_readers_leave_out_a_plane_of_another_width(dim, width):
    conf = {"n_slabs": 24576, "capacity": 64, "dim": dim}
    tr = _trace(_kernel_op("custom_call", 24576, 64, dim, width))
    for n in READERS:
        assert load_reader(n)(_ctx(conf, tr)) is None
    # another slab count or capacity is another plane too
    tr = _trace(_kernel_op("custom_call", 24575, 64, dim, dim))
    assert scan_kernel.kernel_s(tr, conf) == 0.0


def test_payload_operands():
    ops = scan_kernel.payload_operands
    assert ops({"n_slabs": 8, "capacity": 64, "dim": 960}) == (
        "f32[8,64,960]", "f32[8,64,1024]")
    assert ops({"n_slabs": 8, "capacity": 64, "dim": 128}) == (
        "f32[8,64,128]",)
    assert ops({"n_slabs": 8, "capacity": 64, "dim": 200}) == (
        "f32[8,64,200]", "f32[8,64,256]")


def test_recorded_trace_reads_as_before():
    """The values the readers gave on the chip-recorded trace before a
    lane-padded plane was accepted, with the same fixed work and peaks."""
    tr = Trace.load(str(BENCH / "testdata" / "search64x2.xplane.pb"))
    conf = json.loads((BENCH / "configs" / "sift1m.json").read_text())
    got = {n: load_reader(n)(_ctx(conf, tr)) for n in READERS}
    assert got == {"scan_kernel_ms": 271.46048850000005,
                   "scan_roofline": 0.05622370808676733,
                   "search_other_device_ms": 0.02760099999998822}
