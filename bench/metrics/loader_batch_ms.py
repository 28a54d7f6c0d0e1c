"""Mean time a loader worker takes for one batch (gather plus MLM
masking): the loader's ``batch_fetch`` spans that end in the window."""


def read(ctx):
    fetches = ctx.spans_named("batch_fetch", whole=True)
    if not fetches:
        return None
    return sum(e - s for s, e in fetches) / len(fetches) / 1e6
