"""Device time of the fused scan kernel per search call, in ms.

The search executable is ``jit_search_fn(...)`` on the trace's
``XLA Modules`` line. Inside it the kernel is the Mosaic custom call
(``tpu_custom_call``) that reads the payload plane, or, where the query
batch is split into SMEM-sized chunks, the ``closed_call`` fusion around it
in the chunk loop."""
SEARCH_MODULE = r"^jit_search_fn\("
KERNEL = (r'custom_call_target="tpu_custom_call"',
          r"^%closed_call[.\d]* = .*kind=kCustom")


def read(ctx):
    c = ctx.conf
    payload = f"f32[{c['n_slabs']},{c['capacity']},{c['dim']}]"
    runs, _ = ctx.trace.module_runs(SEARCH_MODULE)
    k = ctx.trace.ops_in_module(SEARCH_MODULE, KERNEL, payload)
    return k / runs * 1e3 if runs and k > 0 else None
