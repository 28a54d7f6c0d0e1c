"""Device time of one step: the union of the intervals in which an
operation ran on a chip, over the traced steps, mean over the chips."""


def read(ctx):
    if not ctx.busy_s:
        return None
    return 1e3 * ctx.busy_s / ctx.steps
