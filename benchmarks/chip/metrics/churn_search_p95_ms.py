"""95th percentile of every search answered in the window while a writer
churns, in ms: each from its scheduled arrival to its result in the client,
by the host's clock."""
import numpy as np


def read(ctx):
    lat = ctx.window.lat_s
    return float(np.percentile(np.asarray(lat, np.float64), 95)) * 1e3 \
        if lat else None
