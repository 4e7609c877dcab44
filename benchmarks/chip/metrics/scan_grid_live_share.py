"""Share of the fused scan's grid steps that do real work: over the tiles
the serve loop resolved in the traced window (``sivf.serve.resolve``
spans), the sum of ``live_steps`` (non-empty slab-table entries of the
tile's live query rows, counted inside the search executable) over the sum
of ``grid_steps`` (the launch's padded rows x table entries). None where no
span carries both."""
import spans


def read(ctx):
    live = grid = 0
    for a in spans.args(ctx.trace, "serve.resolve"):
        if "live_steps" in a and "grid_steps" in a:
            live += a["live_steps"]
            grid += a["grid_steps"]
    return live / grid if grid else None
