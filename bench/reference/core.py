"""What every plain reference shares, whatever its model: the PRNG key
from a seed, gradients in blocks of rows, the program's AdamW and its
schedule, leaf norms, and a reference followed through its first steps.

A model module (``bench/reference/<reference>.py``) gives the loss sum
and the weights; this module never names a model.  Like the model
modules, it imports nothing of the program and takes nothing the
program made.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A PRNG key from any seed below 2**62, wider than 32 bits too.
    Make it outside ``jax.jit`` and pass it in: a seed baked into a
    program as a constant makes a new program, compiled anew, per seed."""
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def is_shape(x) -> bool:
    """A leaf of a model's ``param_shapes`` tree: ``(shape, init)``."""
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


# ---------------------------------------------------------------------------
# gradients in blocks of rows, and AdamW
# ---------------------------------------------------------------------------


class Reference:
    """Gradients and AdamW steps of the reference, computed in blocks of
    ``block_rows`` rows so that a batch of any size fits.  ``devices``
    spreads each block's rows over several chips (weights replicated).

    ``nll_sum(params, batch, c, dtype)`` is the model's loss: the sum of
    the negative log-likelihood over the positions the batch selects,
    and their count.  ``init_params(c, key)`` makes its weights."""

    def __init__(self, c, nll_sum: Callable, init_params: Callable, *,
                 dtype=jnp.float32, block_rows: int,
                 devices: Sequence[Any], precision: str = "highest"):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        if block_rows % len(devices):
            raise ValueError(f"{block_rows} rows a block do not split over "
                             f"{len(devices)} devices")
        self.c, self.dtype, self.block_rows = c, dtype, block_rows
        self.init_params = init_params
        mesh = Mesh(np.array(devices), ("rows",))
        self.rep = NamedSharding(mesh, P())
        self.rows = NamedSharding(mesh, P("rows"))
        if dtype != jnp.float32:
            precision = "default"

        def grad_block(params, blk):
            with jax.default_matmul_precision(precision):
                (s, n), g = jax.value_and_grad(
                    lambda p: nll_sum(p, blk, c, dtype), has_aux=True)(params)
            g = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), g)
            return s, n, g

        self._grad_block = jax.jit(grad_block, out_shardings=self.rep)
        self._add = jax.jit(lambda a, b: jax.tree_util.tree_map(
            jnp.add, a, b), donate_argnums=(0,))
        self._scale = jax.jit(lambda g, k: jax.tree_util.tree_map(
            lambda x: x * k, g), donate_argnums=(0,))

    def params(self, seed: int):
        return jax.jit(lambda k: self.init_params(self.c, k),
                       out_shardings=self.rep)(seed_key(seed))

    def loss_and_grads(self, params, batch, rows: slice = slice(None),
                       den=None):
        """Mean NLL over the selected positions of ``batch[rows]`` and its
        gradient.  ``den`` overrides the count it is divided by."""
        batch = {k: np.asarray(v)[rows] for k, v in batch.items()}
        n_rows = batch["tokens"].shape[0]
        step = min(self.block_rows, n_rows)
        total, count, grads = 0.0, 0.0, None
        for lo in range(0, n_rows, step):
            blk = {k: jax.device_put(v[lo:lo + step], self.rows)
                   for k, v in batch.items()}
            s, n, g = self._grad_block(params, blk)
            total += float(s)
            count += float(n)
            grads = g if grads is None else self._add(grads, g)
        den = count if den is None else den
        return total / den, self._scale(grads, 1.0 / den), count


def lr_at(o, step: int) -> float:
    warm = min(1.0, (step + 1) / max(1, o["warmup_steps"]))
    prog = min(1.0, max(0.0, (step - o["warmup_steps"])
                        / max(1, o["total_steps"] - o["warmup_steps"])))
    cos = o["min_lr_ratio"] + (1 - o["min_lr_ratio"]) * 0.5 * (
        1 + math.cos(math.pi * prog))
    return o["lr"] * warm * cos


def _adamw(o, step, params, grads, mu, nu):
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                         for g in jax.tree_util.tree_leaves(grads)))
    scale = jnp.minimum(1.0, o["grad_clip"] / jnp.maximum(gnorm, 1e-9))
    lr = lr_at(o, step)
    t = step + 1
    bc1, bc2 = 1 - o["b1"] ** t, 1 - o["b2"] ** t

    def one(p, g, m, v):
        g = g * scale
        m = o["b1"] * m + (1 - o["b1"]) * g
        v = o["b2"] * v + (1 - o["b2"]) * g * g
        u = (m / bc1) / (jnp.sqrt(v / bc2) + o["eps"])
        if p.ndim >= o["decay_min_ndim"]:
            u = u + o["weight_decay"] * p
        return p - lr * u, m, v

    out = jax.tree_util.tree_map(one, params, grads, mu, nu)
    pick = lambda i: jax.tree_util.tree_map(
        lambda _, t: t[i], params, out)
    return pick(0), pick(1), pick(2)


@jax.jit
def _norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree_util.tree_leaves(tree)]


def leaf_norms(tree) -> List[float]:
    """The 2-norm of every leaf, in tree order."""
    return [float(x) for x in _norms(tree)]


def follow(c, seed: int, batches: Sequence[Dict[str, np.ndarray]], *,
           ref: Reference, rows: slice = slice(None), global_den=False
           ) -> Dict[str, Any]:
    """Run the reference through ``len(batches)`` AdamW steps from the
    weights of ``seed``.  Returns each step's loss, the norm of every leaf
    of the first gradient as the optimizer gets it (before clipping), and
    the norm of every leaf's change over all the steps.

    ``rows`` restricts each step to those rows of its batch; with
    ``global_den`` the loss is still divided by the whole batch's count
    (what one replica computes when the exchange of gradients is left
    out)."""
    o = c["optimizer"]
    p0 = ref.params(seed)
    params = p0
    zeros = jax.jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t),
                    out_shardings=ref.rep)
    mu, nu = zeros(p0), zeros(p0)
    step = jax.jit(lambda s, p, g, m, v: _adamw(o, s, p, g, m, v),
                   static_argnums=0, donate_argnums=(2, 3, 4))
    losses, first = [], None
    for i, batch in enumerate(batches):
        den = float(np.sum(batch["loss_mask"])) if global_den else None
        loss, grads, _ = ref.loss_and_grads(params, batch, rows, den)
        losses.append(loss)
        if i == 0:
            first = leaf_norms(grads)
        new, mu, nu = step(i, params, grads, mu, nu)
        if params is not p0:
            del params
        params = new
    change = leaf_norms(jax.jit(lambda a, b: jax.tree_util.tree_map(
        jnp.subtract, a, b))(params, p0))
    return {"losses": losses, "grad_norms": first, "change_norms": change}
