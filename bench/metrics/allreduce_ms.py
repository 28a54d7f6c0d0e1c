"""Device time per step of the gradient all-reduces (the collectives
under the ``gradsync_bucket_*`` scopes), mean over the chips."""


def read(ctx):
    t = ctx.op_seconds(ctx.is_grad_allreduce)
    if not t:
        return None
    return 1e3 * t / ctx.steps
