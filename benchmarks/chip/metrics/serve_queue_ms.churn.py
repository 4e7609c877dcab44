"""Median wait of a search in the serve engine's queue while a writer
churns, in ms: ``ServeSearchResult.queue_s`` (submit to dispatch) of the
searches answered inside the traced window."""
import numpy as np


def read(ctx):
    q = [a.result.queue_s for a in ctx.answers]
    return float(np.median(q)) * 1e3 if q else None
