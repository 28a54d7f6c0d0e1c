"""Share of the traced window in which no operation ran on a chip, mean
over the cell's chips."""


def read(ctx):
    if not ctx.busy_s:
        return None
    return 100.0 * (1.0 - ctx.busy_s / ctx.window_s)
