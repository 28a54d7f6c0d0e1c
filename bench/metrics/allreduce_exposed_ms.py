"""The part of ``allreduce_ms`` during which no compute operation runs
on that chip: the all-reduce time backward does not hide."""


def read(ctx):
    if not ctx.op_seconds(ctx.is_grad_allreduce):
        return None
    return 1e3 * ctx.exposed_seconds(ctx.is_grad_allreduce) / ctx.steps
