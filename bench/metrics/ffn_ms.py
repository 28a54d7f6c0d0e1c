"""Device time per step of the feed-forward sublayers, norm included,
in every pass: the operations under ``ffn`` (``bench/scopes.py``),
mean over the chips."""

from bench import scopes


def read(ctx):
    return scopes.part_ms(ctx, "ffn")
