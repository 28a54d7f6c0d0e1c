"""AdamW in pure JAX (no optax dependency), with warmup+cosine schedule.

Moments are f32 regardless of param dtype (TPU-idiomatic mixed precision;
see DESIGN.md §7.4).  The optimizer state tree mirrors the param tree, so
parameter sharding rules apply verbatim to the state (ZeRO falls out of
FSDP sharding for free).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_ratio: float = 0.1
    grad_clip: float = 1.0


def lr_at(c: AdamWConfig, step):
    step = step.astype(jnp.float32) if hasattr(step, "astype") else float(step)
    warm = jnp.minimum(1.0, (step + 1) / max(1, c.warmup_steps))
    prog = jnp.clip((step - c.warmup_steps)
                    / max(1, c.total_steps - c.warmup_steps), 0.0, 1.0)
    cos = c.min_lr_ratio + (1 - c.min_lr_ratio) * 0.5 * (1 + jnp.cos(jnp.pi * prog))
    return c.lr * warm * cos


def init_opt_state(params) -> Dict[str, Any]:
    zeros = lambda t: jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, jnp.float32), t)
    return {"mu": zeros(params), "nu": zeros(params),
            "step": jnp.zeros((), jnp.int32)}


def _global_norm(tree) -> jnp.ndarray:
    sq = jax.tree_util.tree_reduce(
        lambda a, x: a + jnp.sum(jnp.square(x.astype(jnp.float32))), tree, 0.0)
    return jnp.sqrt(sq)


def adamw_update(c: AdamWConfig, grads, opt_state, params, *,
                 grad_norm=None
                 ) -> Tuple[Any, Dict[str, Any], Dict[str, jnp.ndarray]]:
    """One AdamW step; returns (new_params, new_opt_state, metrics).

    The update is elementwise, so it runs unchanged on sharded leaves —
    the fsdp ``scatter_overlap`` step calls it on per-device param/grad/
    moment SHARDS.  The one cross-leaf quantity is the clipping norm:
    pass ``grad_norm`` when the leaves don't span the whole gradient
    (e.g. ``gradsync.fsdp_global_norm``, which psums shard contributions
    across the dp axes); left None, it is the local ``_global_norm``.
    """
    with jax.named_scope("optimizer"):
        step = opt_state["step"]
        gnorm = grad_norm if grad_norm is not None else _global_norm(grads)
        scale = jnp.minimum(1.0, c.grad_clip / jnp.maximum(gnorm, 1e-9)) \
            if c.grad_clip else 1.0
        lr = lr_at(c, step)
        t = (step + 1).astype(jnp.float32)
        bc1 = 1 - c.b1 ** t
        bc2 = 1 - c.b2 ** t

        def upd(p, g, mu, nu):
            g = g.astype(jnp.float32) * scale
            mu = c.b1 * mu + (1 - c.b1) * g
            nu = c.b2 * nu + (1 - c.b2) * jnp.square(g)
            mhat = mu / bc1
            nhat = nu / bc2
            step_vec = mhat / (jnp.sqrt(nhat) + c.eps)
            pf = p.astype(jnp.float32)
            if p.ndim >= 2:  # decay matrices only (norms/bias excluded)
                step_vec = step_vec + c.weight_decay * pf
            return (pf - lr * step_vec).astype(p.dtype), mu, nu

        flat_p, treedef = jax.tree_util.tree_flatten(params)
        flat_g = treedef.flatten_up_to(grads)
        flat_mu = treedef.flatten_up_to(opt_state["mu"])
        flat_nu = treedef.flatten_up_to(opt_state["nu"])
        out = [upd(p, g, m, n)
               for p, g, m, n in zip(flat_p, flat_g, flat_mu, flat_nu)]
        new_p = jax.tree_util.tree_unflatten(treedef, [o[0] for o in out])
        new_mu = jax.tree_util.tree_unflatten(treedef, [o[1] for o in out])
        new_nu = jax.tree_util.tree_unflatten(treedef, [o[2] for o in out])
        new_state = {"mu": new_mu, "nu": new_nu, "step": step + 1}
        return new_p, new_state, {"grad_norm": gnorm, "lr": lr}
