"""Plain BERT-MLM in jax.numpy: weights, masking, forward, loss, gradients
and AdamW.  It imports nothing of the program and takes nothing the
program made.

The architecture is the one the configuration file states, which departs
from BERT (arXiv:1810.04805) where the program does: LayerNorm before
each sublayer (``pre_layer_norm``) and once more after the last layer
(``final_layer_norm``), no LayerNorm and no segment embedding on the
input, tanh-approximated GELU, bidirectional attention over the whole
packed row (no padding mask).  Weights follow BERT's initialisation:
normal with ``initializer_range`` for matrices and embeddings (not
truncated), zeros for biases, ones and zeros for LayerNorm.

Parameters use the layout the program's train state has (a dict tree
with the layers stacked on a leading axis), so that leaves compare one
to one.  ``dtype`` is the compute precision: float32 runs every matmul
at ``precision`` (``highest`` unless given); bfloat16 casts the weights
and every intermediate to bfloat16 (the control), with float32 master
weights and optimizer.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Sequence

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


def _dims(c):
    d, h = c["hidden_size"], c["num_attention_heads"]
    return (c["num_hidden_layers"], d, h, d // h, c["intermediate_size"],
            c["vocab_size"], c["max_position_embeddings"])


def param_shapes(c) -> Dict[str, Any]:
    """(shape, init) of every leaf, in the train state's layout."""
    L, d, H, D, f, V, P = _dims(c)
    n, z, o = "normal", "zeros", "ones"
    ln = lambda *lead: {"scale": ((*lead, d), o), "bias": ((*lead, d), z)}
    block = {
        "ln1": ln(L),
        "mixer": {"wq": ((L, d, H, D), n), "wk": ((L, d, H, D), n),
                  "wv": ((L, d, H, D), n), "wo": ((L, H, D, d), n),
                  "bq": ((L, H, D), z), "bk": ((L, H, D), z),
                  "bv": ((L, H, D), z)},
        "ln2": ln(L),
        "mlp": {"wi": ((L, d, f), n), "bi": ((L, f), z),
                "wo": ((L, f, d), n), "bo": ((L, d), z)},
    }
    return {"embed": {"tokens": ((V, d), n), "positions": ((P, d), n)},
            "final_norm": ln(),
            "groups": [[block]],
            "mlm": {"dense": ((d, d), n), "bias": ((d,), z), "ln": ln(),
                    "out_bias": ((V,), z)}}


def _is_shape(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


def init_params(c, key):
    """BERT initialisation from ``key`` (``seed_key``'s); call it under
    ``jax.jit``."""
    shapes = param_shapes(c)
    leaves, treedef = jax.tree_util.tree_flatten(shapes, is_leaf=_is_shape)
    std = c["initializer_range"]
    out = []
    for i, (shape, kind) in enumerate(leaves):
        if kind == "zeros":
            out.append(jnp.zeros(shape, jnp.float32))
        elif kind == "ones":
            out.append(jnp.ones(shape, jnp.float32))
        else:
            k = jax.random.fold_in(key, i)
            out.append(std * jax.random.normal(k, shape, jnp.float32))
    return jax.tree_util.tree_unflatten(treedef, out)


def mask_tokens(key, tokens, c):
    """BERT's masking recipe: ``mask_rate`` of the maskable tokens are
    selected; of those 80% become the mask id, 10% a random token and 10%
    stay.  Returns (inputs, labels, selected) with ``selected`` float32."""
    m = c["mask"]
    k1, k2, k3 = jax.random.split(key, 3)
    maskable = tokens >= m["special_boundary"]
    sel = (jax.random.uniform(k1, tokens.shape) < m["rate"]) & maskable
    r = jax.random.uniform(k2, tokens.shape)
    rand = jax.random.randint(k3, tokens.shape, m["special_boundary"],
                              c["vocab_size"])
    inputs = jnp.where(sel & (r < 0.8), m["mask_id"], tokens)
    inputs = jnp.where(sel & (r >= 0.8) & (r < 0.9), rand, inputs)
    return inputs, tokens, sel.astype(jnp.float32)


# ---------------------------------------------------------------------------
# forward and loss
# ---------------------------------------------------------------------------


def _layer_norm(p, x, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def nll_sum(params, batch, c, dtype=jnp.float32):
    """Sum over the selected positions of the negative log-likelihood of
    the label, and the number of selected positions."""
    eps = c["layer_norm_eps"]
    p = jax.tree_util.tree_map(lambda x: x.astype(dtype), params)
    tok = batch["tokens"]
    S = tok.shape[1]
    h = p["embed"]["tokens"][tok] + p["embed"]["positions"][:S][None]

    def layer(h, w):
        x = _layer_norm(w["ln1"], h, eps)
        a = w["mixer"]
        q = jnp.einsum("bsd,dhe->bshe", x, a["wq"]) + a["bq"]
        k = jnp.einsum("bsd,dhe->bshe", x, a["wk"]) + a["bk"]
        v = jnp.einsum("bsd,dhe->bshe", x, a["wv"]) + a["bv"]
        s = jnp.einsum("bqhe,bkhe->bhqk", q, k) / math.sqrt(q.shape[-1])
        s = s - jnp.max(s, axis=-1, keepdims=True)
        e = jnp.exp(s)
        pr = e / jnp.sum(e, axis=-1, keepdims=True)
        o = jnp.einsum("bhqk,bkhe->bqhe", pr, v)
        h = h + jnp.einsum("bshe,hed->bsd", o, a["wo"])
        x = _layer_norm(w["ln2"], h, eps)
        m = w["mlp"]
        h = h + _gelu(x @ m["wi"] + m["bi"]) @ m["wo"] + m["bo"]
        return h, None

    h, _ = jax.lax.scan(jax.checkpoint(layer), h, p["groups"][0][0])
    h = _layer_norm(p["final_norm"], h, eps)
    m = p["mlm"]
    x = _layer_norm(m["ln"], _gelu(h @ m["dense"] + m["bias"]), eps)
    logits = x @ p["embed"]["tokens"].T + m["out_bias"]
    z = logits - jnp.max(logits, axis=-1, keepdims=True)
    logp = z - jnp.log(jnp.sum(jnp.exp(z), axis=-1, keepdims=True))
    nll = -jnp.take_along_axis(logp, batch["labels"][..., None], axis=-1)
    sel = batch["loss_mask"].astype(dtype)
    return (jnp.sum(nll[..., 0] * sel).astype(jnp.float32),
            jnp.sum(batch["loss_mask"]))


# ---------------------------------------------------------------------------
# gradients in blocks of rows, and AdamW
# ---------------------------------------------------------------------------


class Reference:
    """Gradients and AdamW steps of the reference, computed in blocks of
    ``block_rows`` rows so that a batch of any size fits.  ``devices``
    spreads each block's rows over several chips (weights replicated)."""

    def __init__(self, c, *, dtype=jnp.float32, block_rows: int,
                 devices: Sequence[Any], precision: str = "highest"):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        if block_rows % len(devices):
            raise ValueError(f"{block_rows} rows a block do not split over "
                             f"{len(devices)} devices")
        self.c, self.dtype, self.block_rows = c, dtype, block_rows
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
        return jax.jit(lambda k: init_params(self.c, k),
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
