"""Device time per step of what the backward pass recomputes: the
operations under ``rematted_computation`` (``bench/scopes.py``), mean
over the chips."""

from bench import scopes


def read(ctx):
    return scopes.phase_ms(ctx, "recompute")
