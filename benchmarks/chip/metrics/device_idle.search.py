"""Share of the traced window in which no operation ran on the chip,
under search traffic: 1 - (union of op intervals) / window."""


def read(ctx):
    w = ctx.trace.window_s
    return 1.0 - ctx.trace.busy_s() / w if w > 0 else None
