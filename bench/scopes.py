"""The step program's parts, as its named scopes mark the device's
operations.

The program runs what it differentiates under ``jax.named_scope(
"step_forward")``, each layer's sublayers under ``attention`` and
``ffn``, the loss head under ``loss_head``, AdamW under ``optimizer``
and each gradient bucket's all-reduce under ``gradsync_bucket_*``.
Each compiled operation's ``op_name`` (``Op.scope``) is the path of
scopes it was traced under, with JAX's transformations around them:
``jvp(step_forward)`` on the forward pass,
``transpose(jvp(step_forward))`` on the backward pass and
``.../rematted_computation/...`` on what the backward pass recomputes.

An operation's phase is the first of :data:`PHASES` whose mark its
scope holds, so the phases are disjoint.  A part is any named scope
(:data:`PARTS` lists those of the transformer layers and the loss head);
parts are not phases: an ``attention`` operation counts in its pass too.
"""
from __future__ import annotations

import functools
import re
from typing import Callable, Optional

from bench.trace import Context, Op, on_core


def _component(name: str) -> Callable[[str], bool]:
    """A test for ``name`` as one whole component of an op_name path."""
    return re.compile(rf"(?:^|/){re.escape(name)}(?:/|$)").search


# (phase, test on the op_name), in order of precedence
PHASES = (
    ("gradsync", lambda s: "gradsync_bucket" in s),
    ("optimizer", _component("optimizer")),
    ("recompute", _component("rematted_computation")),
    ("backward", lambda s: "transpose(jvp(step_forward))" in s),
    ("forward", lambda s: "jvp(step_forward)" in s),
)
PARTS = {name: _component(name)
         for name in ("attention", "ffn", "loss_head")}


@functools.lru_cache(maxsize=None)
def phase_of(scope: str) -> Optional[str]:
    """The phase of an operation whose op_name is ``scope``, or None."""
    return next((name for name, has in PHASES if has(scope)), None)


def _per_step_ms(ctx: Context, pred: Callable[[Op], bool]
                 ) -> Optional[float]:
    t = ctx.op_seconds(lambda o: on_core(o) and pred(o))
    return 1e3 * t / ctx.steps if t else None


def phase_ms(ctx: Context, phase: str) -> Optional[float]:
    """Device time a step of the operations in ``phase``, mean over the
    chips; None where no operation is in it."""
    return _per_step_ms(ctx, lambda o: phase_of(o.scope) == phase)


def part_ms(ctx: Context, part: str) -> Optional[float]:
    """Device time a step of the operations under the scope named
    ``part``, in any phase, mean over the chips; None where none is."""
    has = _component(part)
    return _per_step_ms(ctx, lambda o: has(o.scope) is not None)
