"""Device time per step of the attention sublayers, norm and
projections included, in every pass: the operations under
``attention`` (``bench/scopes.py``), mean over the chips."""

from bench import scopes


def read(ctx):
    return scopes.part_ms(ctx, "attention")
