"""Paged-attention decode as a Pallas TPU kernel.

One query token per sequence attends to K/V scattered across fixed-size
pages of a preallocated pool, addressed through a per-sequence block
table.  The kernel mirrors the blocking/VMEM discipline of
``kernels/flash_attention.py``: an online-softmax accumulator in f32
VMEM scratch carried across the innermost grid axis, with `pl.when`
skipping pages that lie entirely outside the valid (causal ∩ window)
key range.

Grid: ``(B, max_pages)`` — pages innermost so the running (m, l, acc)
scratch carries across one sequence's pages.  The block table and
sequence lengths ride in as **scalar-prefetch** operands
(``pltpu.PrefetchScalarGridSpec``), so the K/V BlockSpec index maps can
dereference ``table[b, j]`` to pick the physical page each grid step
streams into VMEM.  One grid step fetches the whole page, every kv head
at once (one contiguous DMA), and scores each kv head in turn.  GQA
costs nothing extra: all ``rep = H // Hkv`` query heads of a kv head
score that head's keys as one ``(rep, P)`` tile.

A skipped page's DMA is still issued (the BlockSpec gather runs before
the body) — table slots past a sequence's allocation point at the
reserved page 0, so the wasted fetch is one bounded trash page, never
an out-of-range read.
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


def _kernel(tbl_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *, scale: float,
            window: Optional[int], softcap: float, page: int, npages: int,
            hkv: int, rep: int):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = lens_ref[b, 0]
    page_start = j * page
    # page-level skip: pages fully beyond the query position (or fully
    # behind the sliding window) do no MXU work
    relevant = page_start <= pos
    if window is not None:
        relevant = jnp.logical_and(relevant, page_start + page - 1
                                   > pos - window)

    @pl.when(relevant)
    def _compute():
        kp = page_start + jax.lax.broadcasted_iota(jnp.int32, (rep, page), 1)
        ok = kp <= pos
        if window is not None:
            ok = jnp.logical_and(ok, kp > pos - window)
        for h in range(hkv):                           # static: Hkv is small
            q = q_ref[0, h].astype(jnp.float32)            # (rep, D)
            k = k_ref[0, :, h, :].astype(jnp.float32)      # (P, D)
            v = v_ref[0, :, h, :].astype(jnp.float32)      # (P, D)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (rep, P)
            if softcap:
                s = jnp.tanh(s / softcap) * softcap
            s = jnp.where(ok, s, NEG_INF)
            m_prev = m_scr[h]                              # (rep, 1)
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_cur)
            p = jnp.exp(s - m_cur)
            l_scr[h] = l_scr[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[h] = m_cur

    @pl.when(j == npages - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-37)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def paged_attention_fwd(q, k_pages, v_pages, block_tables, seq_lens, *,
                        window: Optional[int] = None, softcap: float = 0.0,
                        scale: Optional[float] = None,
                        interpret: Optional[bool] = None):
    """q:(B,H,D), k_pages/v_pages:(NP,P,Hkv,D), block_tables:(B,maxp)
    int32, seq_lens:(B,) int32 (current query position per sequence;
    keys 0..seq_lens[b] are live) -> (B,H,D)."""
    B, H, D = q.shape
    NP, P, Hkv, _ = k_pages.shape
    maxp = block_tables.shape[1]
    rep = H // Hkv
    assert H == rep * Hkv, (H, Hkv)
    scale = D**-0.5 if scale is None else scale

    qt = q.reshape(B, Hkv, rep, D)
    lens2 = seq_lens.reshape(B, 1).astype(jnp.int32)  # 2D for SMEM
    tables = block_tables.astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, maxp),
        in_specs=[
            pl.BlockSpec((1, Hkv, rep, D),
                         lambda b, j, tbl, lens: (b, 0, 0, 0)),
            # the whole page, every kv head: a (1, P, 1, D) block would
            # break the TPU's (8, 128) rule on the Hkv axis
            pl.BlockSpec((1, P, Hkv, D),
                         lambda b, j, tbl, lens: (tbl[b, j], 0, 0, 0)),
            pl.BlockSpec((1, P, Hkv, D),
                         lambda b, j, tbl, lens: (tbl[b, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Hkv, rep, D),
                               lambda b, j, tbl, lens: (b, 0, 0, 0)),
        scratch_shapes=[
            # running max / denominator / accumulator per kv head — f32
            # VMEM scratch
            pltpu.VMEM((Hkv, rep, 1), jnp.float32),
            pltpu.VMEM((Hkv, rep, 1), jnp.float32),
            pltpu.VMEM((Hkv, rep, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, window=window,
                          softcap=softcap, page=P, npages=maxp, hkv=Hkv,
                          rep=rep),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, rep, D), q.dtype),
        interpret=resolve_interpret(interpret),
    )(tables, lens2, qt, k_pages, v_pages)
    return out.reshape(B, H, D)
