"""The matmuls' share of their roofline: the least time the chip could
take for the step's matmul FLOPs and bytes (the model module's
``step_matmuls``, with the recomputation the device really does), over
the device time of the operations that are, or fuse, a convolution or a
dot.  ``bound`` says which of the FLOP and byte bounds is the larger
(FLOPs, in every cell so far)."""

from bench.harness import model_of


def bound(ctx):
    """(seconds the roofline allows, 'flops' or 'bytes'), per chip."""
    c = ctx.config
    f, b = model_of(c).step_matmuls(c, ctx.batch // ctx.chips, c["seq_len"])
    t_f = f * ctx.steps / ctx.peak["bf16_flops"]
    t_b = b * ctx.steps / ctx.peak["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")


def read(ctx):
    t = ctx.op_seconds(ctx.is_matmul)
    if not t:
        return None
    return 100.0 * bound(ctx)[0] / t
