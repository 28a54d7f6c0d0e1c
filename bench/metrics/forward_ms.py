"""Device time per step of the forward pass: the operations under
``jvp(step_forward)`` and in no phase before it (``bench/scopes.py``),
mean over the chips."""

from bench import scopes


def read(ctx):
    return scopes.phase_ms(ctx, "forward")
