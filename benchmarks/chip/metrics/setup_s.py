"""Set-up time, in s: process start to the window's start (generation,
k-means, the base ingest, warm-up of the window's shapes), by the host's
clock."""


def read(ctx):
    return ctx.setup_s
