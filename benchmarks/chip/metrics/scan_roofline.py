"""Share of the roofline the fused scan kernel reaches, in %: the least
time the chip needs for the window's searches (``work.scan_work`` over the
live rows of the lists each answered query probes, against the peaks of
``peaks.json``) over the kernel's device time. The names are those of
``scan_kernel_ms``."""
from work import least_time_s

SEARCH_MODULE = r"^jit_search_fn\("
KERNEL = (r'custom_call_target="tpu_custom_call"',
          r"^%closed_call[.\d]* = .*kind=kCustom")


def read(ctx):
    c = ctx.conf
    payload = f"f32[{c['n_slabs']},{c['capacity']},{c['dim']}]"
    k = ctx.trace.ops_in_module(SEARCH_MODULE, KERNEL, payload)
    if k <= 0 or not ctx.answers:
        return None
    nbytes, flops = ctx.work()
    least, _ = least_time_s(nbytes, flops, ctx.peak)
    return 100.0 * least / k
