"""jit'd public wrappers for the Pallas kernels.

Each op is differentiable: forward runs the Pallas kernel, backward is the
``jax.vjp`` of the pure-jnp oracle (recompute — matches the usual flash
backward strategy of not storing the score matrix).  Whether a kernel
compiles or runs in the interpreter follows the default backend
(:func:`repro.kernels.resolve_interpret`): interpreted on the CPU,
compiled on the TPU.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref as kref
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.fused_xent import fused_xent as _fused_xent
from repro.kernels.paged_attention import paged_attention_fwd
from repro.kernels.ssd_scan import ssd_scan as _ssd_scan


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool = True,
                    window: Optional[int] = None, softcap: float = 0.0,
                    scale: Optional[float] = None):
    return flash_attention_fwd(q, k, v, causal=causal, window=window,
                               softcap=softcap, scale=scale)


def _fa_fwd(q, k, v, causal, window, softcap, scale):
    return flash_attention(q, k, v, causal, window, softcap, scale), (q, k, v)


def _fa_bwd(causal, window, softcap, scale, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: kref.flash_attention_ref(
            q_, k_, v_, causal=causal, window=window, softcap=softcap,
            scale=scale),
        q, k, v)
    return vjp(g)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


# ---------------------------------------------------------------------------
# paged decode attention
# ---------------------------------------------------------------------------


def paged_attention(q, k_pages, v_pages, block_tables, seq_lens,
                    window: Optional[int] = None, softcap: float = 0.0,
                    scale: Optional[float] = None):
    """Decode-only paged attention (no vjp: serving never differentiates
    through it).  q:(B,H,D) against (NP,P,Hkv,D) pools via (B,maxp)
    block tables; see ``kernels/paged_attention.py``."""
    return paged_attention_fwd(q, k_pages, v_pages, block_tables, seq_lens,
                               window=window, softcap=softcap, scale=scale)


# ---------------------------------------------------------------------------
# SSD chunk scan
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def ssd(x, dt, A, B, C, chunk: int = 256):
    return _ssd_scan(x, dt, A, B, C, chunk)


def _ssd_fwd(x, dt, A, B, C, chunk):
    return ssd(x, dt, A, B, C, chunk), (x, dt, A, B, C)


def _ssd_bwd(chunk, res, g):
    x, dt, A, B, C = res
    _, vjp = jax.vjp(
        lambda *a: kref.ssd_ref(*a, chunk=chunk), x, dt, A, B, C)
    return vjp(g)


ssd.defvjp(_ssd_fwd, _ssd_bwd)


# ---------------------------------------------------------------------------
# fused vocab cross-entropy
# ---------------------------------------------------------------------------


@jax.custom_vjp
def xent(logits, labels):
    return _fused_xent(logits, labels)


def _xe_fwd(logits, labels):
    return xent(logits, labels), (logits, labels)


def _xe_bwd(res, g):
    logits, labels = res
    _, vjp = jax.vjp(lambda l: kref.xent_ref(l, labels), logits)
    return vjp(g) + (None,)


xent.defvjp(_xe_fwd, _xe_bwd)
