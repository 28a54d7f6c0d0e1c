"""Share of the traced window the training loop spent waiting for its
next batch: the loop's own ``data_wait`` spans."""


def read(ctx):
    waits = ctx.spans_named("data_wait")
    if not waits:
        return None
    return 100.0 * sum(e - s for s, e in waits) / ctx.window_ns
