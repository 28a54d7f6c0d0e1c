"""Mamba2 SSD chunk scan as a Pallas TPU kernel.

TPU adaptation (DESIGN.md §2): the chunked dual form maps naturally onto
the MXU — per chunk, three (L×N)/(L×L)/(L×P) matmuls — while the O(1)
inter-chunk recurrence is carried in a (N, P) f32 VMEM scratch across the
innermost (sequential) grid axis.  This replaces the GPU kernel's
warp-level associative scan with TPU's sequential-grid + scratch carry
idiom.

Grid: (B, H, n_chunks) — chunks innermost so the state scratch carries.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _tri_dot(lhs, rhs, dims):
    """Prefix sums as matmuls against a 0/1 triangle (Mosaic has no
    cumsum).  HIGHEST keeps the f32 sums exact enough to exponentiate."""
    return jax.lax.dot_general(
        lhs, rhs, (dims, ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _kernel(a_ref, x_ref, dtr_ref, dtc_ref, b_ref, c_ref, y_ref, fs_ref,
            state_scr, *, L: int, nc: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, 0, 0].astype(jnp.float32)      # (L, P)
    dt_r = dtr_ref[0, 0, 0].astype(jnp.float32)  # (1, L) row
    dt_c = dtc_ref[0, 0, 0].astype(jnp.float32)  # (L, 1) column
    A = a_ref[pl.program_id(1)]                 # () scalar from SMEM
    Bm = b_ref[0, 0, 0].astype(jnp.float32)     # (L, N)
    Cm = c_ref[0, 0, 0].astype(jnp.float32)     # (L, N)
    state = state_scr[...]                      # (N, P)
    Pd = state.shape[1]

    a_r = dt_r * A                              # (1, L) negative
    li = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    causal = si <= li                           # [l, s]: s <= l
    # cumulative decay acs[l] = sum_{s<=l} a[s], as a row and as a
    # column; the suffix sum_{s>l} a[s] = acs[-1] - acs[l] and the chunk
    # total acs[-1] come from the same row of a
    acs_r = _tri_dot(a_r, (li <= si).astype(jnp.float32), ((1,), (0,)))
    acs_c = _tri_dot(causal.astype(jnp.float32), a_r, ((1,), (1,)))
    suf_c = _tri_dot((si > li).astype(jnp.float32), a_r, ((1,), (1,)))
    tot_r = _tri_dot(a_r, jnp.ones((L, Pd), jnp.float32), ((1,), (0,)))

    # inter-chunk contribution: y_prev = exp(acs) * (C @ state)
    y_prev = jax.lax.dot_general(
        Cm, state, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * jnp.exp(acs_c)

    # intra-chunk dual form
    scores = jax.lax.dot_general(
        Cm, Bm, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)     # (L, L)
    diff = acs_c - acs_r
    # mask the exponent, not the product: exp(diff) overflows for s > l
    # and 0*inf poisons interpret-mode gradients (same fix as ssd_chunked)
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    seg = scores * decay * dt_r
    y_intra = jax.lax.dot_general(
        seg, x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)     # (L, P)

    y_ref[0, 0, 0] = (y_prev + y_intra).astype(y_ref.dtype)

    # state update: S' = exp(acs[-1]) S + B^T diag(exp(acs[-1]-acs) dt) x
    w = jnp.exp(suf_c) * dt_c                   # (L, 1)
    upd = jax.lax.dot_general(
        Bm * w, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)     # (N, P)
    state_scr[...] = jnp.exp(tot_r) * state + upd

    @pl.when(ci == nc - 1)
    def _final():
        fs_ref[0, 0] = state_scr[...]


def ssd_scan(x, dt, A, B, C, chunk: int, *,
             interpret: Optional[bool] = None):
    """x:(B,S,H,P) dt:(B,S,H) A:(H,) B,C:(B,S,G,N) ->
    (y:(B,S,H,P), final_state:(B,H,N,P)) — matches ``ref.ssd_ref``.

    ``dt`` enters twice, as (1, L) rows and (L, 1) columns per chunk, so
    that both blocks meet the TPU's (8, 128) tiling rule; ``A`` is read
    as a scalar from SMEM."""
    Bb, S, H, Pd = x.shape
    G, N = B.shape[2], B.shape[3]
    rep = H // G
    L = chunk
    pad = (-S) % L
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Sp = S + pad
    nc = Sp // L

    xt = x.transpose(0, 2, 1, 3).reshape(Bb, H, nc, L, Pd)
    dtt = dt.transpose(0, 2, 1).reshape(Bb, H, nc, L)
    Bt = B.transpose(0, 2, 1, 3).reshape(Bb, G, nc, L, N)
    Ct = C.transpose(0, 2, 1, 3).reshape(Bb, G, nc, L, N)

    y, fs = pl.pallas_call(
        functools.partial(_kernel, L=L, nc=nc),
        grid=(Bb, H, nc),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, 1, L, Pd), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, L), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, L, 1), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, L, N), lambda b, h, c: (b, h // rep, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, L, N), lambda b, h, c: (b, h // rep, c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, L, Pd), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, N, Pd), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bb, H, nc, L, Pd), x.dtype),
            jax.ShapeDtypeStruct((Bb, H, N, Pd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, Pd), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(A.astype(jnp.float32), xt, dtt.reshape(Bb, H, nc, 1, L),
      dtt.reshape(Bb, H, nc, L, 1), Bt, Ct)

    y = y.reshape(Bb, H, Sp, Pd).transpose(0, 2, 1, 3)[:, :S]
    return y, fs
