"""Plain BERT-MLM in jax.numpy: weights, masking, forward and loss, with
the program's config and the step's counts for the same configuration
file.  The reference imports nothing of the program and takes nothing
the program made; ``program_config`` alone names the program, to build
its side of the comparison.  Gradients in blocks of rows and AdamW are
``bench.reference.core``'s, as for every model.

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

import dataclasses
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from bench.flops import model_flops_per_step, step_matmuls  # noqa: F401
from bench.reference.core import is_shape, seed_key  # noqa: F401


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


def init_params(c, key):
    """BERT initialisation from ``key`` (``seed_key``'s); call it under
    ``jax.jit``."""
    shapes = param_shapes(c)
    leaves, treedef = jax.tree_util.tree_flatten(shapes, is_leaf=is_shape)
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
# the program's side
# ---------------------------------------------------------------------------


def program_config(c: Dict[str, Any]):
    """The program's ModelConfig for configuration file ``c``: its
    published config with the file's sizes."""
    from repro.configs import get_config
    from repro.configs.base import ATTN, LayerSpec, uniform_schedule

    base = get_config(c["program_arch"])
    d, h = c["hidden_size"], c["num_attention_heads"]
    return dataclasses.replace(
        base, d_model=d, n_heads=h, n_kv_heads=h, head_dim=d // h,
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        schedule=uniform_schedule(c["num_hidden_layers"], LayerSpec(ATTN)),
        max_position=c["max_position_embeddings"],
        norm_eps=c["layer_norm_eps"])
