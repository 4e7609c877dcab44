"""Rows acknowledged per second, added plus removed, by every pair the
writer started in the window, over the time from the window's start to the
last acknowledgement (host clock)."""


def read(ctx):
    w = ctx.window
    return w.mut_rows / (w.mut_last_ack - w.t0) if w.mut_pairs else None
