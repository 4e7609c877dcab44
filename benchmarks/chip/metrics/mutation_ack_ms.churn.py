"""Mean time from a mutation batch's submit to its acknowledgement, in ms,
over the flushes the serve loop ran in the traced window: the sum of the
``wait_ms`` args of the program's ``sivf.serve.flush`` spans over the sum
of their ``batches``. None where the trace holds no flush."""
import spans


def read(ctx):
    wait = batches = 0
    for a in spans.args(ctx.trace, "serve.flush"):
        if "wait_ms" in a:
            wait += a["wait_ms"]
            batches += a["batches"]
    return wait / batches if batches else None
