"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.  The single-pod mesh is a 16×16 (256-chip TPU
v5e pod); the multi-pod mesh adds a leading "pod" axis (2 pods = 512
chips) used for pure data parallelism over DCN.
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(
        jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False,
                         pipeline_stages: int = 0,
                         expert_parallel: int = 0):
    """The 256-chip pod mesh (16x16 data x model), optionally with a
    leading ``pod`` DCN axis (2 pods), a ``pipe`` axis carved out of the
    data dimension (``pipeline_stages`` stages; the per-stage dp width
    shrinks by the same factor, total chips unchanged), and/or an
    ``expert`` axis carved from data the same way (``expert_parallel``
    shards; experts spread over it, the batch shards over data x
    expert jointly)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    if expert_parallel and expert_parallel > 1:
        e = expert_parallel
        if shape[-2] % e != 0:
            raise ValueError(
                f"expert_parallel={e} must divide the "
                f"{shape[-2]}-wide data axis")
        shape = shape[:-2] + (shape[-2] // e, e, shape[-1])
        axes = axes[:-1] + ("expert",) + axes[-1:]
    if pipeline_stages and pipeline_stages > 1:
        s = pipeline_stages
        d_pos = axes.index("data")
        if shape[d_pos] % s != 0:
            raise ValueError(
                f"pipeline_stages={s} must divide the "
                f"{shape[d_pos]}-wide data axis")
        shape = (s,) + shape[:d_pos] + (shape[d_pos] // s,) \
            + shape[d_pos + 1:]
        axes = ("pipe",) + axes
    return _make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1, pipe: int = 0,
                   expert: int = 0):
    """Small mesh over however many (virtual) devices exist — tests.
    ``pipe > 0`` prepends the pipeline axis; ``expert > 0`` inserts the
    expert axis between data and model: ``(pipe, data, expert, model)``
    with the zero-valued axes dropped."""
    shape: tuple = (data,)
    axes: tuple = ("data",)
    if expert and expert > 0:
        shape, axes = shape + (expert,), axes + ("expert",)
    shape, axes = shape + (model,), axes + ("model",)
    if pipe and pipe > 0:
        shape, axes = (pipe,) + shape, ("pipe",) + axes
    return _make_mesh(shape, axes)
