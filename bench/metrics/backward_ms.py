"""Device time per step of the backward pass: the operations under
``transpose(jvp(step_forward))``, less what it recomputes
(``bench/scopes.py``), mean over the chips."""

from bench import scopes


def read(ctx):
    return scopes.phase_ms(ctx, "backward")
