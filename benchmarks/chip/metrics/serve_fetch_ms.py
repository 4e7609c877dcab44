"""Median time the serve loop takes to copy a finished tile's distances and
labels from the device to the host, in ms: the durations of the program's
``sivf.serve.resolve.fetch`` spans that start in the traced window. None
where the trace holds none."""
import numpy as np

import spans


def read(ctx):
    fetch = spans.spans(ctx.trace, "serve.resolve.fetch")
    return float(np.median(fetch.dur)) * 1e-6 if len(fetch) else None
