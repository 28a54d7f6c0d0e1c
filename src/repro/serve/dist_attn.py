"""Sequence-sharded decode attention ("flash decoding" adapted to TPU ICI).

For decode shapes the KV cache's *sequence* dimension is sharded over the
``model`` mesh axis (and over ``data`` too when batch=1, e.g. long_500k).
Each chip computes attention of the (replicated) single-token query against
its local cache chunk, then the partial results are combined with a
numerically-stable log-sum-exp reduction over the sequence axes
(``pmax`` + two ``psum``s — this is the collective schedule the roofline
§collective term sees for decode).

The new token's K/V is written by the one chip that owns the target slot.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import softcap

NEG_INF = -2.0e38


def _axis_size(mesh, axes):
    s = 1
    for a in axes:
        s *= mesh.shape[a]
    return s


def dist_decode_attend(q, k_new, v_new, cache, pos, cfg, dist):
    """q:(B,1,H,D) k_new/v_new:(B,1,Hkv,D) cache{k,v}:(B,S,Hkv,D) global.

    dist.axes: mesh axes the cache seq dim is sharded over.
    dist.batch_axes: mesh axes the batch dim is sharded over.
    Returns (o:(B,1,H,Dv), new_cache).
    """
    mesh = dist.mesh
    seq_axes = tuple(dist.axes)
    bax = tuple(dist.batch_axes)
    b_entry = (bax if len(bax) != 1 else bax[0]) if bax else None
    qspec = P(b_entry, None, None, None)
    cspec = P(b_entry, seq_axes if len(seq_axes) != 1 else seq_axes[0],
              None, None)
    scale = cfg.query_scale if cfg.query_scale else q.shape[-1] ** -0.5
    cap = cfg.attn_logit_softcap

    from jax import shard_map

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(qspec, qspec, qspec, {"k": cspec, "v": cspec}, P()),
        out_specs=(qspec, {"k": cspec, "v": cspec}),
        check_vma=False,
    )
    def run(ql, knl, vnl, cl, posl):
        kloc, vloc = cl["k"], cl["v"]
        B, S_loc, Hkv, D = kloc.shape
        n_seq = 1
        idx = jnp.zeros((), jnp.int32)
        for a in seq_axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
            n_seq *= mesh.shape[a]
        offset = idx * S_loc
        # -- write the new token into the owning shard: one-slot
        # read-modify-write (a full-buffer select would copy the cache) ----
        local_pos = jnp.clip(posl - offset, 0, S_loc - 1)
        owns = (posl >= offset) & (posl < offset + S_loc)
        k_old = jax.lax.dynamic_slice_in_dim(kloc, local_pos, 1, axis=1)
        v_old = jax.lax.dynamic_slice_in_dim(vloc, local_pos, 1, axis=1)
        kloc = jax.lax.dynamic_update_slice_in_dim(
            kloc, jnp.where(owns, knl.astype(kloc.dtype), k_old),
            local_pos, axis=1)
        vloc = jax.lax.dynamic_update_slice_in_dim(
            vloc, jnp.where(owns, vnl.astype(vloc.dtype), v_old),
            local_pos, axis=1)
        # -- local partial attention --------------------------------------
        H = ql.shape[2]
        rep = H // Hkv
        qr = ql.reshape(B, 1, Hkv, rep, D)
        s = jnp.einsum("bqhrd,bkhd->bhrqk", qr, kloc).astype(jnp.float32)
        s = softcap(s * scale, cap)
        valid = (offset + jnp.arange(S_loc)) <= posl
        s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)
        m_loc = jnp.max(s, axis=-1, keepdims=True)
        m = jax.lax.pmax(m_loc, seq_axes)
        p = jnp.exp(s - m)
        l_loc = jnp.sum(p, axis=-1, keepdims=True)          # (B,Hkv,rep,1,1)
        num_loc = jnp.einsum("bhrqk,bkhd->bqhrd", p.astype(vloc.dtype), vloc)
        l = jax.lax.psum(l_loc, seq_axes)
        num = jax.lax.psum(num_loc, seq_axes)
        o = num / jnp.maximum(l, 1e-37).astype(num.dtype).transpose(0, 3, 1, 2, 4)
        o = o.reshape(B, 1, H, vloc.shape[-1])
        return o, {"k": kloc, "v": vloc}

    return run(q, k_new, v_new, cache, jnp.asarray(pos, jnp.int32))
