"""Flash attention (forward) as a Pallas TPU kernel.

TPU adaptation (DESIGN.md §2): the online-softmax tiling is blocked for
VMEM — (BQ, D) query tiles × (BK, D) key/value tiles with f32 accumulators
in VMEM scratch — and the (BQ, BK) score tile feeds the MXU with
hardware-aligned 128-multiples.  Supports GQA (kv-head index derived in the
BlockSpec index_map), causal masking, sliding windows and gemma-style logit
softcap.  Causal/window block skipping is done with `pl.when` so skipped
tiles cost no MXU work.

Grid: (B, H, n_q_blocks, n_k_blocks) — k innermost so the running
(m, l, acc) scratch carries across k iterations of one q tile.
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
DEFAULT_BQ = 512  # (bq, D) + (bk, D) + (bq, bk) f32 tiles fit 16MB VMEM
DEFAULT_BK = 512


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: Optional[int],
            softcap: float, bq: int, bk: int, nk: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * bq
    k_start = ik * bk
    # block-level skip: entirely-masked tiles do no work
    relevant = True
    if causal:
        relevant = k_start <= q_start + bq - 1
    if window is not None:
        relevant = jnp.logical_and(
            relevant, k_start + bk - 1 > q_start - window)

    @pl.when(relevant)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (BQ, D)
        k = k_ref[0, 0].astype(jnp.float32)            # (BK, D)
        v = v_ref[0, 0].astype(jnp.float32)            # (BK, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (BQ, BK)
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        qi = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kj = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        ok = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            ok = jnp.logical_and(ok, kj <= qi)
        if window is not None:
            ok = jnp.logical_and(ok, kj > qi - window)
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_scr[...]                            # (BQ,)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_cur

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-37)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, causal: bool = True,
                        window: Optional[int] = None, softcap: float = 0.0,
                        scale: Optional[float] = None,
                        block_q: int = DEFAULT_BQ, block_k: int = DEFAULT_BK,
                        interpret: Optional[bool] = None):
    """q:(B,S,H,D), k/v:(B,S,Hkv,D) -> (B,S,H,D)."""
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    rep = H // Hkv
    scale = D**-0.5 if scale is None else scale
    bq = min(block_q, S)
    bk = min(block_k, S)
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    nq, nk = S // bq, S // bk

    qt = q.transpose(0, 2, 1, 3)   # (B,H,S,D)
    kt = k.transpose(0, 2, 1, 3)   # (B,Hkv,S,D)
    vt = v.transpose(0, 2, 1, 3)

    grid = (B, H, nq, nk)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, window=window,
                          softcap=softcap, bq=bq, bk=bk, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h // rep, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h // rep, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        scratch_shapes=[
            # running max / denominator / accumulator — f32 VMEM scratch
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
