"""Share of the roofline the fused scan kernel reaches, in %: the least
time the chip needs for the window's searches (``work.scan_work`` over the
live rows of the lists each answered query probes, against the peaks of
``peaks.json``) over the kernel's device time, the kernel as
``scan_kernel.py`` finds it."""
import scan_kernel
from work import least_time_s


def read(ctx):
    k = scan_kernel.kernel_s(ctx.trace, ctx.conf)
    if k <= 0 or not ctx.answers:
        return None
    nbytes, flops = ctx.work()
    least, _ = least_time_s(nbytes, flops, ctx.peak)
    return 100.0 * least / k
