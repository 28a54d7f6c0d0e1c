"""Attention: MHA/GQA (+bias, softcap, qk-norm, sliding window) and
DeepSeek-style MLA with a compressed-latent KV cache.

Modes:
  train   — full sequence, causal (or bidirectional for encoder family)
  prefill — like train, additionally fills and returns the KV cache
  decode  — single query token against the cache

Decode against a *sequence-sharded* cache is delegated to
``repro.serve.dist_attn`` via the ``dist`` argument (a DistDecode config);
locally the math is identical.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from repro.models.layers import apply_rope, rms_normalize, softcap
from repro.models.params import ParamSpec

NEG_INF = -2.0e38


@dataclass(frozen=True)
class DistDecode:
    """How decode-time attention is distributed (see serve/dist_attn.py)."""

    axes: tuple = ()          # mesh axes the cache sequence dim is sharded over
    batch_axes: tuple = ()    # mesh axes the cache batch dim is sharded over
    mesh: object = None       # jax.sharding.Mesh


# ---------------------------------------------------------------------------
# Param specs
# ---------------------------------------------------------------------------


def attn_specs(cfg: ModelConfig, cross: bool = False):
    H, Hkv, D, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_model
    out = {
        "wq": ParamSpec((d, H, D), ("embed", "heads", "head_dim")),
        "wk": ParamSpec((d, Hkv, D), ("embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((d, Hkv, D), ("embed", "kv_heads", "head_dim")),
        "wo": ParamSpec((H, D, d), ("heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias:
        out["bq"] = ParamSpec((H, D), ("heads", "head_dim"), init="zeros")
        out["bk"] = ParamSpec((Hkv, D), ("kv_heads", "head_dim"), init="zeros")
        out["bv"] = ParamSpec((Hkv, D), ("kv_heads", "head_dim"), init="zeros")
    if getattr(cfg, "qk_norm", False):
        out["q_norm"] = ParamSpec((D,), ("head_dim",), init="ones")
        out["k_norm"] = ParamSpec((D,), ("head_dim",), init="ones")
    return out


def mla_specs(cfg: ModelConfig):
    m = cfg.mla
    H, d = cfg.n_heads, cfg.d_model
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "wq": ParamSpec((d, H, qk), ("embed", "heads", "head_dim")),
        "wdkv": ParamSpec(
            (d, m.kv_lora_rank + m.qk_rope_head_dim), ("embed", None)
        ),
        "kv_ln": ParamSpec((m.kv_lora_rank,), (None,), init="ones"),
        "wuk": ParamSpec(
            (m.kv_lora_rank, H, m.qk_nope_head_dim), (None, "heads", "head_dim")
        ),
        "wuv": ParamSpec(
            (m.kv_lora_rank, H, m.v_head_dim), (None, "heads", "head_dim")
        ),
        "wo": ParamSpec((H, m.v_head_dim, d), ("heads", "head_dim", "embed")),
    }


# ---------------------------------------------------------------------------
# Masks
# ---------------------------------------------------------------------------


def build_mask(sq: int, sk: int, *, causal: bool, window: Optional[int],
               q_offset: int = 0):
    """Additive mask (1, 1, sq, sk) in f32; q position i maps to i+q_offset."""
    qi = jnp.arange(sq)[:, None] + q_offset
    kj = jnp.arange(sk)[None, :]
    ok = jnp.ones((sq, sk), bool)
    if causal:
        ok &= kj <= qi
    if window is not None:
        ok &= kj > qi - window
    return jnp.where(ok, 0.0, NEG_INF)[None, None].astype(jnp.float32)


# ---------------------------------------------------------------------------
# Core scaled-dot-product with GQA
# ---------------------------------------------------------------------------


def _scale(cfg: ModelConfig, qk_dim: int) -> float:
    return cfg.query_scale if cfg.query_scale else qk_dim**-0.5


ATTN_CHUNK = 512  # q-block size for the XLA memory-bounded attention path


def _attend_block(q, k, v, mask, cfg: ModelConfig):
    """q: (B,Sq,H,D) k,v: (B,Sk,Hkv,D) mask: (B or 1,1,Sq,Sk) additive."""
    B, Sq, H, D = q.shape
    Hkv = k.shape[2]
    rep = H // Hkv
    qr = q.reshape(B, Sq, Hkv, rep, D)
    s = jnp.einsum("bqhrd,bkhd->bhrqk", qr, k).astype(jnp.float32)
    s = s * _scale(cfg, D)
    s = softcap(s, cfg.attn_logit_softcap)
    s = s + mask[:, :, None] if mask.ndim == 4 else s + mask
    w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    o = jnp.einsum("bhrqk,bkhd->bqhrd", w, v)
    return o.reshape(B, Sq, H, v.shape[-1])


def gqa_attend(q, k, v, mask, cfg: ModelConfig, *, use_pallas: bool = False,
               causal_hint: bool = False, window: Optional[int] = None,
               q_offset: int = 0, causal: bool = True):
    """q: (B,Sq,H,D) k,v: (B,Sk,Hkv,D).

    ``mask`` may be None when (causal, window, q_offset) describe it — then
    long sequences take a q-chunked path that never materializes the full
    (Sq, Sk) score matrix (the XLA analogue of the Pallas flash kernel,
    which is used instead when ``use_pallas``).  Returns (B,Sq,H,Dv).
    """
    Sq = q.shape[1]
    if use_pallas and causal_hint and Sq == k.shape[1] and Sq >= 128:
        from repro.kernels import ops as kops

        return kops.flash_attention(
            q, k, v, causal=True, window=window,
            softcap=cfg.attn_logit_softcap, scale=_scale(cfg, q.shape[-1]),
        )
    if mask is not None:
        return _attend_block(q, k, v, mask, cfg)
    if Sq <= ATTN_CHUNK:
        mask = build_mask(Sq, k.shape[1], causal=causal, window=window,
                          q_offset=q_offset)
        return _attend_block(q, k, v, mask, cfg)
    # q-chunked: peak score memory = (B, H, CHUNK, Sk) per step
    nq = Sq // ATTN_CHUNK
    rem = Sq - nq * ATTN_CHUNK

    @jax.checkpoint  # map's backward keeps only chunk outputs, not scores
    def one(i):
        off = i * ATTN_CHUNK
        qb = jax.lax.dynamic_slice_in_dim(q, off, ATTN_CHUNK, axis=1)
        # mask rows shifted by the (traced) block offset
        qi = off + q_offset + jnp.arange(ATTN_CHUNK)[:, None]
        kj = jnp.arange(k.shape[1])[None, :]
        ok = jnp.ones((ATTN_CHUNK, k.shape[1]), bool)
        if causal:
            ok &= kj <= qi
        if window is not None:
            ok &= kj > qi - window
        m = jnp.where(ok, 0.0, NEG_INF)[None, None].astype(jnp.float32)
        return _attend_block(qb, k, v, m, cfg)

    blocks = jax.lax.map(one, jnp.arange(nq))          # (nq,B,C,H,Dv)
    out = blocks.transpose(1, 0, 2, 3, 4).reshape(
        q.shape[0], nq * ATTN_CHUNK, q.shape[2], v.shape[-1])
    if rem:
        mrem = build_mask(rem, k.shape[1], causal=causal, window=window,
                          q_offset=nq * ATTN_CHUNK + q_offset)
        tail = _attend_block(q[:, -rem:], k, v, mrem, cfg)
        out = jnp.concatenate([out, tail], axis=1)
    return out


# ---------------------------------------------------------------------------
# GQA attention block
# ---------------------------------------------------------------------------


def _project_qkv(p, h, cfg: ModelConfig):
    q = jnp.einsum("bsd,dhe->bshe", h, p["wq"].astype(h.dtype))
    k = jnp.einsum("bsd,dhe->bshe", h, p["wk"].astype(h.dtype))
    v = jnp.einsum("bsd,dhe->bshe", h, p["wv"].astype(h.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(h.dtype)
        k = k + p["bk"].astype(h.dtype)
        v = v + p["bv"].astype(h.dtype)
    if getattr(cfg, "qk_norm", False):
        q = rms_normalize(q) * p["q_norm"].astype(h.dtype)
        k = rms_normalize(k) * p["k_norm"].astype(h.dtype)
    return q, k, v


def _theta(cfg: ModelConfig, spec: LayerSpec) -> float:
    if spec.window is not None and cfg.rope_local_theta:
        return cfg.rope_local_theta
    return cfg.rope_theta


def apply_attn(p, h, cfg: ModelConfig, spec: LayerSpec, *, positions,
               mode: str, cache=None, pos=None, causal: bool = True,
               use_pallas: bool = False, dist: Optional[DistDecode] = None,
               kv_override=None, shard_ctx=None, paged=None):
    """Returns (out, new_cache).  ``kv_override=(k,v)`` is used for
    cross-attention (keys/values from the encoder, no rope, no cache write).

    ``paged`` routes the serving engine's paged-KV paths
    (serve/paged_cache.py).  In decode it is ``{"tables": (B,maxp) int32,
    "page": P, "use_pallas": bool}`` with ``pos`` a per-slot (B,) array:
    the layer's cache leaves are page POOLS (NP,P,Hkv,D) written through
    the block table, and sliding-window layers use per-slot dense ring
    buffers (``pos`` leaf shaped (B,W)).  In prefill it is ``{"length":
    L}`` — the true (unpadded) prompt length, so the ring fill stays
    correct under right-padded prompt buckets.

    ``shard_ctx`` = {"q": fn, "kv": fn} enables context-parallel attention:
    q is sequence-sharded, k/v replicated over the model axis, so the score
    matrix is sharded by sequence instead of being replicated (the
    head-sharding fallback when kv_heads < model axis replicates the whole
    (S,S) score computation and psums it — see EXPERIMENTS.md §Perf)."""
    B = h.shape[0]
    if mode in ("train", "prefill"):
        q, k, v = _project_qkv(p, h, cfg)
        if kv_override is not None:
            k, v = kv_override
        elif cfg.pos_type == "rope":
            th = _theta(cfg, spec)
            q = apply_rope(q, positions, th)
            k = apply_rope(k, positions, th)
        if kv_override is not None:
            o = gqa_attend(q, k, v, None, cfg, causal=False)
        elif shard_ctx is not None and "flash" in shard_ctx:
            # shard_map'd Pallas flash attention: scores stay in VMEM
            o = shard_ctx["flash"](
                q, k, v, causal=causal, window=spec.window,
                softcap=cfg.attn_logit_softcap,
                scale=_scale(cfg, q.shape[-1]))
        elif shard_ctx is not None and "q" in shard_ctx:
            # pin dtypes before the k/v all-gathers: without the barrier XLA
            # sinks the f32->bf16 convert past the gather, doubling traffic
            q, k, v = jax.lax.optimization_barrier((q, k, v))
            q = shard_ctx["q"](q)
            k = shard_ctx["kv"](k)
            v = shard_ctx["kv"](v)
            S = q.shape[1]
            mask = build_mask(S, S, causal=causal, window=spec.window)
            o = _attend_block(q, k, v, mask, cfg)
            o = shard_ctx["q"](o)
        else:
            o = gqa_attend(q, k, v, None, cfg, use_pallas=use_pallas,
                           causal_hint=causal, causal=causal,
                           window=spec.window)
        new_cache = None
        if mode == "prefill" and kv_override is None:
            length = paged.get("length") if paged else None
            new_cache = _fill_cache(k, v, spec, cfg, length=length)
        out = jnp.einsum("bshe,hed->bsd", o, p["wo"].astype(h.dtype))
        return out, new_cache

    # ------------------------------------------------------------- decode
    q, k_new, v_new = _project_qkv(p, h, cfg)  # (B,1,H,D) / (B,1,Hkv,D)
    if kv_override is not None:  # cross-attention: static keys, no cache
        k, v = kv_override
        mask = jnp.zeros((1, 1, 1, k.shape[1]), jnp.float32)
        o = gqa_attend(q, k, v, mask, cfg)
        out = jnp.einsum("bshe,hed->bsd", o, p["wo"].astype(h.dtype))
        return out, cache
    assert cache is not None and pos is not None
    if cfg.pos_type == "rope":
        th = _theta(cfg, spec)
        if paged is not None:
            pos_arr = pos.reshape(B, 1)       # per-slot positions
        else:
            pos_arr = jnp.full((B, 1), pos, jnp.int32)
        q = apply_rope(q, pos_arr, th)
        k_new = apply_rope(k_new, pos_arr, th)

    if paged is not None and "tables" in paged:
        if spec.window is not None:
            # per-slot dense ring buffer — a fixed-size pool row per slot
            new_cache, mask, k_all, v_all = _sliding_update_paged(
                cache, k_new, v_new, pos, spec.window)
            o = gqa_attend(q, k_all, v_all, mask, cfg)
        else:
            o, new_cache = _paged_attend(
                q, k_new, v_new, cache, pos, cfg, paged)
        out = jnp.einsum("bshe,hed->bsd", o, p["wo"].astype(h.dtype))
        return out, new_cache

    if spec.window is not None:
        new_cache, mask, k_all, v_all = _sliding_update(
            cache, k_new, v_new, pos, spec.window
        )
        o = gqa_attend(q, k_all, v_all, mask, cfg)
    elif dist is not None and dist.axes:
        from repro.serve.dist_attn import dist_decode_attend

        o, new_cache = dist_decode_attend(q, k_new, v_new, cache, pos, cfg, dist)
    else:
        k_all = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new.astype(cache["k"].dtype), pos, axis=1)
        v_all = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new.astype(cache["v"].dtype), pos, axis=1)
        S = k_all.shape[1]
        mask = jnp.where(jnp.arange(S)[None, None, None] <= pos, 0.0, NEG_INF)
        o = gqa_attend(q, k_all, v_all, mask.astype(jnp.float32), cfg)
        new_cache = {"k": k_all, "v": v_all}
    out = jnp.einsum("bshe,hed->bsd", o, p["wo"].astype(h.dtype))
    return out, new_cache


def _paged_attend(q, k_new, v_new, cache, pos, cfg: ModelConfig, paged):
    """Scatter the new token's K/V into the page pool through the block
    table, then attend the (B,1,H,D) query over all live pages.

    ``cache`` = {"k": (NP,P,Hkv,D), "v": ...} — this layer's pools.
    ``pos`` (B,) per-slot positions.  Distinct active slots hold distinct
    pages (the allocator's invariant), so the scatter is race-free;
    inactive slots write to the reserved trash page 0.
    """
    P = paged["page"]
    tables = paged["tables"]
    B = q.shape[0]
    b_idx = jnp.arange(B)
    page = tables[b_idx, pos // P]                 # (B,) physical pages
    off = pos % P
    kp = cache["k"].at[page, off].set(k_new[:, 0].astype(cache["k"].dtype))
    vp = cache["v"].at[page, off].set(v_new[:, 0].astype(cache["v"].dtype))
    if paged.get("use_pallas"):
        from repro.kernels import ops as kops

        o = kops.paged_attention(
            q[:, 0], kp, vp, tables, pos, window=None,
            softcap=cfg.attn_logit_softcap, scale=_scale(cfg, q.shape[-1]))
    else:
        from repro.kernels.ref import paged_attention_ref

        o = paged_attention_ref(
            q[:, 0], kp, vp, tables, pos, window=None,
            softcap=cfg.attn_logit_softcap, scale=_scale(cfg, q.shape[-1]))
    return o[:, None], {"k": kp, "v": vp}


def _sliding_update_paged(cache, k_new, v_new, pos, window: int):
    """Per-slot ring update: like :func:`_sliding_update` but every slot
    carries its own position (continuous batching), so the ``pos`` leaf
    is (B, W) and the ring write index differs per row."""
    B = k_new.shape[0]
    b_idx = jnp.arange(B)
    slot = pos % window
    k = cache["k"].at[b_idx, slot].set(k_new[:, 0].astype(cache["k"].dtype))
    v = cache["v"].at[b_idx, slot].set(v_new[:, 0].astype(cache["v"].dtype))
    pos_ids = cache["pos"].at[b_idx, slot].set(pos)
    p = pos[:, None]
    valid = (pos_ids >= 0) & (pos_ids <= p) & (pos_ids > p - window)
    mask = jnp.where(valid, 0.0, NEG_INF)[:, None, None].astype(jnp.float32)
    return {"k": k, "v": v, "pos": pos_ids}, mask, k, v


def _fill_cache(k, v, spec: LayerSpec, cfg: ModelConfig, length=None):
    if spec.window is not None and length is not None:
        # ragged fill: the prompt really ends at ``length`` (traced), the
        # buffer is right-padded to S.  Ring slot s gets the largest
        # position p <= length-1 with p % W == s (and >= length-W); pad
        # positions never enter the ring.
        W = spec.window
        s_ids = jnp.arange(W, dtype=jnp.int32)
        p_ids = (length - 1) - ((length - 1 - s_ids) % W)
        ok = p_ids >= 0
        idx = jnp.clip(p_ids, 0, k.shape[1] - 1)
        kc = jnp.take(k, idx, axis=1)
        vc = jnp.take(v, idx, axis=1)
        zero = jnp.zeros((), k.dtype)
        kc = jnp.where(ok[None, :, None, None], kc, zero)
        vc = jnp.where(ok[None, :, None, None], vc, zero)
        return {"k": kc, "v": vc,
                "pos": jnp.where(ok, p_ids, jnp.int32(-1))}
    if spec.window is not None:
        W = spec.window
        S = k.shape[1]
        if S >= W:
            kc, vc = k[:, S - W:], v[:, S - W:]
            pos_ids = jnp.arange(S - W, S, dtype=jnp.int32)
            # ring layout: slot = position % W
            slot = pos_ids % W
            inv = jnp.argsort(slot)
            return {
                "k": kc[:, inv], "v": vc[:, inv], "pos": pos_ids[inv],
            }
        pad = W - S
        kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        pos_ids = jnp.concatenate(
            [jnp.arange(S, dtype=jnp.int32), jnp.full((pad,), -1, jnp.int32)]
        )
        return {"k": kc, "v": vc, "pos": pos_ids}
    return {"k": k, "v": v}


def _sliding_update(cache, k_new, v_new, pos, window: int):
    slot = pos % window
    k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new.astype(cache["k"].dtype), slot, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new.astype(cache["v"].dtype), slot, axis=1)
    pos_ids = jax.lax.dynamic_update_slice_in_dim(
        cache["pos"], jnp.full((1,), pos, jnp.int32), slot, axis=0
    )
    valid = (pos_ids >= 0) & (pos_ids <= pos) & (pos_ids > pos - window)
    mask = jnp.where(valid, 0.0, NEG_INF)[None, None, None].astype(jnp.float32)
    return {"k": k, "v": v, "pos": pos_ids}, mask, k, v


# ---------------------------------------------------------------------------
# MLA (DeepSeek V2)
# ---------------------------------------------------------------------------


def _mla_q(p, h, cfg: ModelConfig, positions):
    m = cfg.mla
    q = jnp.einsum("bsd,dhe->bshe", h, p["wq"].astype(h.dtype))
    q_nope = q[..., : m.qk_nope_head_dim]
    q_rope = apply_rope(q[..., m.qk_nope_head_dim:], positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_ckv(p, h, cfg: ModelConfig, positions):
    m = cfg.mla
    ckv_full = h @ p["wdkv"].astype(h.dtype)  # (B,S,r+rope)
    ckv = ckv_full[..., : m.kv_lora_rank]
    ckv = rms_normalize(ckv) * p["kv_ln"].astype(h.dtype)
    k_rope = ckv_full[..., m.kv_lora_rank:][:, :, None]  # (B,S,1,rope)
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta)[:, :, 0]
    return ckv, k_rope


def apply_mla(p, h, cfg: ModelConfig, spec: LayerSpec, *, positions,
              mode: str, cache=None, pos=None, use_pallas: bool = False,
              dist: Optional[DistDecode] = None, paged=None):
    m = cfg.mla
    B = h.shape[0]
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    if mode in ("train", "prefill"):
        S = h.shape[1]
        q_nope, q_rope = _mla_q(p, h, cfg, positions)
        ckv, k_rope = _mla_ckv(p, h, cfg, positions)
        # expanded form for long-sequence compute
        k_nope = jnp.einsum("bsr,rhe->bshe", ckv, p["wuk"].astype(h.dtype))
        v = jnp.einsum("bsr,rhe->bshe", ckv, p["wuv"].astype(h.dtype))

        def blk(qn, qr, off):
            sq = qn.shape[1]
            s = (
                jnp.einsum("bqhe,bkhe->bhqk", qn, k_nope)
                + jnp.einsum("bqhe,bke->bhqk", qr, k_rope)
            ).astype(jnp.float32) * scale
            qi = off + jnp.arange(sq)[:, None]
            kj = jnp.arange(S)[None, :]
            ok = kj <= qi
            if spec.window is not None:
                ok &= kj > qi - spec.window
            s = jnp.where(ok[None, None], s, NEG_INF)
            w = jax.nn.softmax(s, axis=-1).astype(h.dtype)
            return jnp.einsum("bhqk,bkhe->bqhe", w, v)

        if S <= ATTN_CHUNK:
            o = blk(q_nope, q_rope, 0)
        else:  # q-chunked: never materialize the (S, S) score matrix
            nq = S // ATTN_CHUNK
            rem = S - nq * ATTN_CHUNK

            @jax.checkpoint
            def one(i):
                off = i * ATTN_CHUNK
                qn = jax.lax.dynamic_slice_in_dim(q_nope, off, ATTN_CHUNK, 1)
                qr = jax.lax.dynamic_slice_in_dim(q_rope, off, ATTN_CHUNK, 1)
                return blk(qn, qr, off)

            blocks = jax.lax.map(one, jnp.arange(nq))
            o = blocks.transpose(1, 0, 2, 3, 4).reshape(
                B, nq * ATTN_CHUNK, cfg.n_heads, m.v_head_dim)
            if rem:
                o = jnp.concatenate(
                    [o, blk(q_nope[:, -rem:], q_rope[:, -rem:],
                            nq * ATTN_CHUNK)], axis=1)
        out = jnp.einsum("bshe,hed->bsd", o, p["wo"].astype(h.dtype))
        new_cache = {"ckv": ckv, "kr": k_rope} if mode == "prefill" else None
        return out, new_cache

    # ------------------------------------------------------------- decode
    assert cache is not None and pos is not None
    if paged is not None and "tables" in paged:
        pos_arr = pos.reshape(B, 1)
    else:
        pos_arr = jnp.full((B, 1), pos, jnp.int32)
    q_nope, q_rope = _mla_q(p, h, cfg, pos_arr)       # (B,1,H,·)
    ckv_new, kr_new = _mla_ckv(p, h, cfg, pos_arr)    # (B,1,r) (B,1,rope)
    if paged is not None and "tables" in paged:
        # latent cache through the page pool: scatter the new (ckv, kr)
        # at (page, offset), gather all live pages per slot, score in the
        # absorbed form with a per-slot causal mask
        P = paged["page"]
        tables = paged["tables"]
        maxp = tables.shape[1]
        b_idx = jnp.arange(B)
        page = tables[b_idx, pos // P]
        off = pos % P
        ckv_p = cache["ckv"].at[page, off].set(
            ckv_new[:, 0].astype(cache["ckv"].dtype))
        kr_p = cache["kr"].at[page, off].set(
            kr_new[:, 0].astype(cache["kr"].dtype))
        ckv = ckv_p[tables].reshape(B, maxp * P, -1)
        kr = kr_p[tables].reshape(B, maxp * P, -1)
        q_eff = jnp.einsum("bqhe,rhe->bqhr", q_nope, p["wuk"].astype(h.dtype))
        s = (
            jnp.einsum("bqhr,bkr->bhqk", q_eff, ckv)
            + jnp.einsum("bqhe,bke->bhqk", q_rope, kr)
        ).astype(jnp.float32) * scale
        kpos = jnp.arange(maxp * P)[None, None, None]
        s = s + jnp.where(kpos <= pos[:, None, None, None], 0.0, NEG_INF)
        w = jax.nn.softmax(s, axis=-1).astype(h.dtype)
        o_lat = jnp.einsum("bhqk,bkr->bqhr", w, ckv)
        o = jnp.einsum("bqhr,rhe->bqhe", o_lat, p["wuv"].astype(h.dtype))
        out = jnp.einsum("bshe,hed->bsd", o, p["wo"].astype(h.dtype))
        return out, {"ckv": ckv_p, "kr": kr_p}
    ckv = jax.lax.dynamic_update_slice_in_dim(cache["ckv"], ckv_new.astype(cache["ckv"].dtype), pos, axis=1)
    kr = jax.lax.dynamic_update_slice_in_dim(cache["kr"], kr_new.astype(cache["kr"].dtype), pos, axis=1)
    # absorbed form: score against the latent directly
    q_eff = jnp.einsum("bqhe,rhe->bqhr", q_nope, p["wuk"].astype(h.dtype))
    s = (
        jnp.einsum("bqhr,bkr->bhqk", q_eff, ckv)
        + jnp.einsum("bqhe,bke->bhqk", q_rope, kr)
    ).astype(jnp.float32) * scale
    S = ckv.shape[1]
    s = s + jnp.where(jnp.arange(S)[None, None, None] <= pos, 0.0, NEG_INF)
    w = jax.nn.softmax(s, axis=-1).astype(h.dtype)
    o_lat = jnp.einsum("bhqk,bkr->bqhr", w, ckv)
    o = jnp.einsum("bqhr,rhe->bqhe", o_lat, p["wuv"].astype(h.dtype))
    out = jnp.einsum("bshe,hed->bsd", o, p["wo"].astype(h.dtype))
    return out, {"ckv": ckv, "kr": kr}
