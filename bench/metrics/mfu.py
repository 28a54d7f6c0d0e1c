"""The whole step's share of the chips' peak: the model's matmul FLOPs
per step (forward and backward, no recomputation; its module's
``model_flops_per_step``) times the steps, over the traced window's wall
time, the chips and their bf16 peak."""

from bench.harness import model_of


def read(ctx):
    c = ctx.config
    per_step = model_of(c).model_flops_per_step(c, ctx.batch, c["seq_len"])
    need = per_step * ctx.steps
    return 100.0 * need / (ctx.window_s * ctx.chips * ctx.peak["bf16_flops"])
