"""Device time of one add batch's commit, in ms: ``jit_insert_fn(...)``
runs on the trace's ``XLA Modules`` line, mean over the traced window."""
MODULE = r"^jit_insert_fn\("


def read(ctx):
    runs, total = ctx.trace.module_runs(MODULE)
    return total / runs * 1e3 if runs else None
