"""Pallas TPU kernels (interpret-mode validated on CPU; see ops.py)."""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Whether a Pallas kernel runs in the interpreter.

    An explicit ``interpret`` wins.  ``None`` asks the default backend:
    the CPU interprets, the TPU compiles, and any other backend raises
    rather than silently running the interpreter on an accelerator.
    """
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"no Pallas kernel mode for the {backend!r} backend "
                       "(only cpu interprets and tpu compiles)")
