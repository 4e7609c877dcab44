"""Device memory peak per byte of live row data: the fullest chip's
``peak_bytes_in_use`` since process start, read as the window closes and
before the check allocates, over live rows x dim x 4."""


def read(ctx):
    return ctx.peak_bytes / (ctx.live_rows * ctx.conf["dim"] * 4) \
        if ctx.live_rows and ctx.peak_bytes else None
