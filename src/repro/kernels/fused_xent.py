"""Fused softmax cross-entropy over the vocabulary as a Pallas TPU kernel.

For 256k-vocab models (gemma2/3) the (T, V) logit softmax is the memory
hot-spot of the loss: XLA materializes log-probs (T·V f32).  This kernel
streams vocab tiles through VMEM with an online max/denominator and picks
the label logit on the fly, so HBM traffic is one read of the logits and
a (T,) write — no (T, V) temporary.

Grid: (n_token_blocks, n_vocab_blocks) — vocab innermost (running scratch).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -2.0e38


def _kernel(logits_ref, labels_ref, nll_ref, m_scr, l_scr, pick_scr, *,
            bt: int, bv: int, nv: int):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        pick_scr[...] = jnp.zeros_like(pick_scr)

    x = logits_ref[...].astype(jnp.float32)          # (BT, BV)
    labels = labels_ref[...]                         # (BT, 1)
    v0 = vi * bv
    m_prev = m_scr[...]                              # (BT, 1)
    m_cur = jnp.maximum(m_prev, jnp.max(x, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(jnp.exp(x - m_cur), axis=1,
                                              keepdims=True)
    m_scr[...] = m_cur
    # pick the label logit if it lives in this tile
    cols = v0 + jax.lax.broadcasted_iota(jnp.int32, (bt, bv), 1)
    hit = cols == labels
    pick_scr[...] = pick_scr[...] + jnp.sum(jnp.where(hit, x, 0.0), axis=1,
                                            keepdims=True)

    @pl.when(vi == nv - 1)
    def _finish():
        nll_ref[...] = (jnp.log(l_scr[...]) + m_scr[...] - pick_scr[...]
                        ).astype(nll_ref.dtype)


def fused_xent(logits, labels, *, block_t: int = 128, block_v: int = 512,
               interpret: Optional[bool] = None):
    """logits:(T,V), labels:(T,) int32 -> nll:(T,) f32.

    Labels and the result travel as ``(T, 1)`` columns: a 1-D ``(bt,)``
    block has a different tiling in XLA and in Mosaic, which the chip's
    compiler refuses."""
    T, V = logits.shape
    bt = min(block_t, T)
    bv = min(block_v, V)
    padT = (-T) % bt
    padV = (-V) % bv
    if padT or padV:
        logits = jnp.pad(logits, ((0, padT), (0, padV)),
                         constant_values=NEG_INF / 2)
        labels = jnp.pad(labels, (0, padT))
    Tp, Vp = logits.shape
    nt, nv = Tp // bt, Vp // bv
    out = pl.pallas_call(
        functools.partial(_kernel, bt=bt, bv=bv, nv=nv),
        grid=(nt, nv),
        in_specs=[
            pl.BlockSpec((bt, bv), lambda t, v: (t, v)),
            pl.BlockSpec((bt, 1), lambda t, v: (t, 0)),
        ],
        out_specs=pl.BlockSpec((bt, 1), lambda t, v: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((Tp, 1), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bt, 1), jnp.float32),
            pltpu.VMEM((bt, 1), jnp.float32),
            pltpu.VMEM((bt, 1), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(logits, labels.astype(jnp.int32).reshape(Tp, 1))
    return out[:T, 0]
