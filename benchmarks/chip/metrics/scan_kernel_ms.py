"""Device time of the fused scan kernel per search call, in ms: the kernel
as ``scan_kernel.py`` finds it, over the runs of the search executable."""
import scan_kernel


def read(ctx):
    runs, _ = ctx.trace.module_runs(scan_kernel.SEARCH_MODULE)
    k = scan_kernel.kernel_s(ctx.trace, ctx.conf)
    return k / runs * 1e3 if runs and k > 0 else None
