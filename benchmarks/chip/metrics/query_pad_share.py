"""Query rows the index handle pads a coalesced tile with, per live row:
sum over tiles of (``padded_to`` - ``coalesced``) over the sum of
``coalesced``, from the engine's per-request fields. Each request of ``n``
rows carries ``n / coalesced`` of its tile, so the sum runs over requests."""


def read(ctx):
    pad = live = 0.0
    for a in ctx.answers:
        res, n = a.result, a.rows
        pad += n * (res.padded_to - res.coalesced) / res.coalesced
        live += n
    return pad / live if live else None
