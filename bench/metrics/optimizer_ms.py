"""Device time per step of the optimizer, clipping norm and AdamW
update: the operations under ``optimizer`` (``bench/scopes.py``), mean
over the chips."""

from bench import scopes


def read(ctx):
    return scopes.phase_ms(ctx, "optimizer")
