"""Device time per step of the loss head (MLM transform, unembedding,
log-softmax and NLL) in every pass: the operations under
``loss_head`` (``bench/scopes.py``), mean over the chips."""

from bench import scopes


def read(ctx):
    return scopes.part_ms(ctx, "loss_head")
