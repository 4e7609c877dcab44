"""Share of the traced window in which the chip runs no op while the serve
loop's host work is under way: the first device idle and a
``sivf.serve.dispatch``, ``sivf.serve.flush`` or ``sivf.serve.resolve``
span of the program open, over the window. Idle under ``sivf.serve.wait``
(the loop waiting for requests) is idle the load caused, and not counted.
None where the trace holds none of the program's serve spans."""
import spans

SERVE = ("serve.dispatch", "serve.flush", "serve.resolve")


def read(ctx):
    tr = ctx.trace
    w = tr.window_s
    if w <= 0 or not len(spans.spans(tr, *SERVE)):
        return None
    return spans.idle_under(tr, *SERVE) / w
