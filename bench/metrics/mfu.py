"""The whole step's share of the chips' peak: the model's matmul FLOPs
per step (forward and backward, no recomputation) times the steps, over
the traced window's wall time, the chips and their bf16 peak."""

from bench import flops


def read(ctx):
    c = ctx.config
    need = flops.model_flops_per_step(c, ctx.batch, c["seq_len"]) * ctx.steps
    return 100.0 * need / (ctx.window_s * ctx.chips * ctx.peak["bf16_flops"])
