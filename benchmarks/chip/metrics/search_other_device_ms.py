"""Device time of the search executable outside the scan kernel, per call,
in ms: probe, slab-table gather, operand copies (a relayout copy of the
payload plane among them). The executable and the kernel are found by
``scan_kernel.py``."""
import scan_kernel


def read(ctx):
    runs, total = ctx.trace.module_runs(scan_kernel.SEARCH_MODULE)
    k = scan_kernel.kernel_s(ctx.trace, ctx.conf)
    return (total - k) / runs * 1e3 if runs and k > 0 else None
