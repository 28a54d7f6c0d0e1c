"""Residual blocks + the BlockSchedule scan machinery.

A ``ScheduleGroup`` is (pattern × repeats); parameters and KV caches for a
group are *stacked* along a leading ``layers`` axis of size ``repeats`` and
the group is executed with ``jax.lax.scan`` — this keeps HLO size and
compile time O(pattern) instead of O(n_layers), which matters when lowering
an 80-layer model for a 512-device mesh.

Weight-shared blocks (zamba2) take their parameters from ``shared`` banks
that are closed over (broadcast into the scan) instead of scanned.
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import (ATTN, MAMBA, MLA, SHARED_ATTN, LayerSpec,
                                ModelConfig, ScheduleGroup)
from repro.models.attention import apply_attn, apply_mla, attn_specs, mla_specs
from repro.models.layers import apply_mlp, apply_norm, mlp_specs, norm_specs
from repro.models.moe import apply_moe, moe_specs
from repro.models.params import stack_specs
from repro.models.ssm import apply_mamba, ssm_specs


# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------


def block_specs(cfg: ModelConfig, spec: LayerSpec, *, cross: bool = False):
    if spec.kind == SHARED_ATTN:
        return {}  # params come from the shared bank
    out = {"ln1": norm_specs(cfg)}
    if spec.kind == ATTN:
        out["mixer"] = attn_specs(cfg)
    elif spec.kind == MLA:
        out["mixer"] = mla_specs(cfg)
    elif spec.kind == MAMBA:
        out["mixer"] = ssm_specs(cfg)
    else:
        raise ValueError(spec.kind)
    if cfg.post_norms and spec.kind != MAMBA:
        out["post1"] = norm_specs(cfg)
    if cross:
        out["ln_cross"] = norm_specs(cfg)
        out["cross"] = attn_specs(cfg, cross=True)
    if spec.has_mlp:
        out["ln2"] = norm_specs(cfg)
        if spec.moe:
            out["moe"] = moe_specs(cfg)
        else:
            out["mlp"] = mlp_specs(cfg)
        if cfg.post_norms:
            out["post2"] = norm_specs(cfg)
    return out


def shared_block_specs(cfg: ModelConfig):
    """zamba2 shared transformer block (attention + MLP)."""
    return {
        "ln1": norm_specs(cfg),
        "mixer": attn_specs(cfg),
        "ln2": norm_specs(cfg),
        "mlp": mlp_specs(cfg),
    }


def group_specs(cfg: ModelConfig, group: ScheduleGroup, *, cross: bool = False):
    per_layer = [block_specs(cfg, s, cross=cross) for s in group.pattern]
    return stack_specs(per_layer, group.repeats)


# ---------------------------------------------------------------------------
# Apply
# ---------------------------------------------------------------------------


def _cross_kv(p, enc, cfg: ModelConfig):
    k = jnp.einsum("bsd,dhe->bshe", enc, p["wk"].astype(enc.dtype))
    v = jnp.einsum("bsd,dhe->bshe", enc, p["wv"].astype(enc.dtype))
    if cfg.qkv_bias:
        k = k + p["bk"].astype(enc.dtype)
        v = v + p["bv"].astype(enc.dtype)
    return k, v


def apply_block(bp, shared, h, cfg: ModelConfig, spec: LayerSpec, *,
                positions, mode: str, cache=None, pos=None,
                encoder_out=None, causal: bool = True,
                use_pallas: bool = False, dist=None, moe_ctx=None,
                shard_ctx=None, paged=None, tp_ctx=None):
    """Returns (h, new_cache, aux).

    ``tp_ctx`` is the explicitly-scheduled tensor-parallel context
    (``train/train_step.py`` builds it inside the shard_map'd tp step):
    ``h`` arrives SEQUENCE-SHARDED over the model axis — (B, S/ms, d) —
    and each sublayer's parallel region is entered with one
    ``tp_ctx["gather"]`` (all_gather of the normed activations back to
    full sequence) and left with one ``tp_ctx["scatter"]``
    (psum_scatter of the sublayer's partial (B, S, d) output back to
    the sequence shard), so the residual stream between blocks never
    materializes the full sequence per rank.  Attention runs with its
    local head slice, the MLP with its local d_ff slice — their outputs
    are partial sums over the model axis, which is exactly what the
    psum_scatter reduces.
    """
    aux = jnp.zeros((), jnp.float32)
    new_cache: dict = {}
    cache = cache or {}
    p = shared[spec.shared_bank] if spec.kind == SHARED_ATTN else bp

    # ---- mixer ----  (each sublayer runs under a named scope, which the
    # device trace's op_name carries through grad and remat)
    with jax.named_scope("ssm" if spec.kind == MAMBA else "attention"):
        x = apply_norm(p["ln1"], h, cfg)
        if tp_ctx is not None:
            x = tp_ctx["gather"](x)
        if spec.kind == MAMBA:
            mx, mc = apply_mamba(p["mixer"], x, cfg, mode=mode,
                                 cache=cache.get("mixer"),
                                 use_pallas=use_pallas)
        elif spec.kind == MLA:
            mx, mc = apply_mla(p["mixer"], x, cfg, spec, positions=positions,
                               mode=mode, cache=cache.get("mixer"), pos=pos,
                               use_pallas=use_pallas, dist=dist, paged=paged)
        else:  # ATTN / SHARED_ATTN
            mx, mc = apply_attn(p["mixer"], x, cfg, spec,
                                positions=positions, mode=mode,
                                cache=cache.get("mixer"), pos=pos,
                                causal=causal, use_pallas=use_pallas,
                                dist=dist, shard_ctx=shard_ctx, paged=paged)
        if mc is not None:
            new_cache["mixer"] = mc
        if tp_ctx is not None:
            mx = tp_ctx["scatter"](mx)
        if cfg.post_norms and spec.kind != MAMBA and spec.kind != SHARED_ATTN:
            mx = apply_norm(bp["post1"], mx, cfg)
        h = h + mx

    # ---- cross attention (enc-dec decoders) ----
    if "cross" in (bp or {}):
        with jax.named_scope("attention"):
            x = apply_norm(bp["ln_cross"], h, cfg)
            if mode == "decode":
                kv = (cache["cross"]["k"], cache["cross"]["v"])
            else:
                kv = _cross_kv(bp["cross"], encoder_out, cfg)
            cx, _ = apply_attn(bp["cross"], x, cfg, spec, positions=positions,
                               mode=mode, cache=None, pos=pos,
                               kv_override=kv, causal=False)
            if mode == "decode":
                new_cache["cross"] = cache["cross"]
            elif mode == "prefill":
                new_cache["cross"] = {"k": kv[0], "v": kv[1]}
            h = h + cx

    # ---- mlp / moe ----
    has_mlp = spec.has_mlp or spec.kind == SHARED_ATTN
    if has_mlp:
        with jax.named_scope("ffn"):
            x = apply_norm(p["ln2"], h, cfg)
            if spec.moe:
                ctx = moe_ctx or {}
                mx, moe_aux = apply_moe(p["moe"], x, cfg, **ctx)
                aux = aux + moe_aux
            elif tp_ctx is not None:
                # column-parallel up (local d_ff slice) / row-parallel down:
                # the output bias is deferred past the psum_scatter so it is
                # added once, not once per model rank
                mx = apply_mlp(p["mlp"], tp_ctx["gather"](x), cfg,
                               bias_out=False)
                mx = tp_ctx["scatter"](mx)
                if "bo" in p["mlp"]:
                    mx = mx + p["mlp"]["bo"].astype(mx.dtype)
            else:
                mx = apply_mlp(p["mlp"], x, cfg)
            if cfg.post_norms and spec.kind != SHARED_ATTN:
                mx = apply_norm(bp["post2"], mx, cfg)
            h = h + mx
    return h, new_cache, aux


def apply_group(pg, shared, h, cfg: ModelConfig, group: ScheduleGroup, *,
                positions, mode: str, cache_g=None, pos=None,
                encoder_out=None, causal: bool = True, remat: bool = False,
                use_pallas: bool = False, dist=None, moe_ctx=None,
                constrain: Optional[Callable] = None, shard_ctx=None,
                paged=None, tp_ctx=None):
    """Scan the group over its ``repeats`` axis.

    Returns (h, new_cache_g, aux_sum).
    """

    def one_block(pi, hc, pl_pi, cl_pi):
        out = apply_block(
            pl_pi, shared, hc, cfg, group.pattern[pi], positions=positions,
            mode=mode, cache=cl_pi, pos=pos,
            encoder_out=encoder_out, causal=causal,
            use_pallas=use_pallas, dist=dist, moe_ctx=moe_ctx,
            shard_ctx=shard_ctx, paged=paged, tp_ctx=tp_ctx,
        )
        if constrain is not None:
            out = (constrain(out[0]), out[1], out[2])
        return out

    if remat and mode == "train":
        # checkpoint each LAYER (not the whole pattern): the backward then
        # recomputes one layer at a time, bounding peak activation memory
        # to a single layer's working set
        one_block = jax.checkpoint(one_block, prevent_cse=False,
                                   static_argnums=(0,))

    def body(hc, xs):
        pl, cl = xs
        new_caches = []
        aux_tot = jnp.zeros((), jnp.float32)
        for pi in range(len(group.pattern)):
            hc, nc, aux = one_block(
                pi, hc, pl[pi], cl[pi] if cl is not None else None)
            new_caches.append(nc)
            aux_tot = aux_tot + aux
        return hc, (new_caches, aux_tot)

    xs = (pg, cache_g)  # cache_g None => broadcast None per step
    if cache_g is None:
        # scan needs concrete xs; replicate None via a dummy per-step tree
        xs = (pg, None)

        def body_nocache(hc, pl):
            return body(hc, (pl, None))

        h, (new_cache_g, auxs) = jax.lax.scan(body_nocache, h, pg)
    else:
        h, (new_cache_g, auxs) = jax.lax.scan(body, h, (pg, cache_g))
    return h, new_cache_g, jnp.sum(auxs)
