"""Training loop facade: wires the data loader, the sharding-aware
StepRunner and the async TrainLoop together.  This is the driver
``examples/`` and ``launch/train.py`` use.

The execution machinery lives in ``repro.train.runner``: the step is
compiled once with explicit shardings and donated state buffers, batches
are device-prefetched, metrics are fetched asynchronously and checkpoints
are written on a background thread.  ``train()`` keeps the seed repo's
call signature so existing callers and tests keep working.
"""
from __future__ import annotations

from typing import Any, Dict, Iterable, Optional

from repro.configs.base import RunConfig
from repro.models.model import Model
from repro.train.optimizer import AdamWConfig
from repro.train.runner import (StepRunner, TrainerLog,  # noqa: F401
                                TrainLoop, resume)


def train(model: Model, run: RunConfig, opt: AdamWConfig,
          data: Iterable[Dict[str, Any]], *, steps: int,
          seed: int = 0, mesh=None, log_every: int = 10,
          ckpt_path: Optional[str] = None, ckpt_every: int = 0,
          ckpt_dir: Optional[str] = None, start_step: int = 0,
          process_index: int = 0, process_count: int = 1,
          state=None, runner: Optional[StepRunner] = None,
          device_prefetch: bool = True, async_checkpoint: bool = True,
          aot_compile: bool = True, donate: bool = True,
          peak_flops: Optional[float] = None) -> tuple:
    """Returns (state, TrainerLog).  ``ckpt_dir`` selects the sharded
    resumable layout (``data`` may be a ``DataPipeline``; its position is
    checkpointed alongside the state — see train/checkpoint.py)."""
    if runner is None:
        runner = StepRunner(model, run, opt, mesh, donate=donate)
    if state is not None and runner.donate:
        # seed-trainer compat: donation consumes the state buffers in
        # place, but a caller-provided tree must stay usable after we
        # return — train on a copy, not on the caller's arrays
        import jax
        import jax.numpy as jnp

        state = jax.tree_util.tree_map(jnp.array, state)
    loop = TrainLoop(runner, log_every=log_every, ckpt_path=ckpt_path,
                     ckpt_every=ckpt_every, ckpt_dir=ckpt_dir,
                     process_index=process_index,
                     process_count=process_count,
                     async_checkpoint=async_checkpoint,
                     device_prefetch=device_prefetch, aot_compile=aot_compile,
                     peak_flops=peak_flops)
    return loop.run(data, steps, state=state, seed=seed,
                    start_step=start_step)
