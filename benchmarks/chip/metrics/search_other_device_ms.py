"""Device time of the search executable outside the scan kernel, per call,
in ms: probe, slab-table gather, operand copies (the 960-lane payload
copy among them). The executable and kernel names are those of
``scan_kernel_ms``."""
SEARCH_MODULE = r"^jit_search_fn\("
KERNEL = (r'custom_call_target="tpu_custom_call"',
          r"^%closed_call[.\d]* = .*kind=kCustom")


def read(ctx):
    c = ctx.conf
    payload = f"f32[{c['n_slabs']},{c['capacity']},{c['dim']}]"
    runs, total = ctx.trace.module_runs(SEARCH_MODULE)
    k = ctx.trace.ops_in_module(SEARCH_MODULE, KERNEL, payload)
    return (total - k) / runs * 1e3 if runs and k > 0 else None
