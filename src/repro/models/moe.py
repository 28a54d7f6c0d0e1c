"""Mixture-of-Experts: top-k router, shared experts, and two dispatch paths.

* ``dense`` — one-hot einsum dispatch.  Simple, correct, used as the oracle
  in tests and for tiny smoke configs.
* ``ep`` — expert-parallel capacity dispatch: tokens are scattered into a
  per-expert capacity buffer, exchanged with ``all_to_all`` over the mesh
  axis the experts are sharded on, processed by the local experts, and
  combined back.  This is the TPU-idiomatic adaptation of the GPU
  grouped-GEMM pattern most MoE papers use (see DESIGN.md §2).

The ``ep`` path is written with ``shard_map`` so the collective schedule is
explicit (it shows up as real ``all-to-all`` ops in the dry-run HLO, which
the roofline analysis parses).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.params import ParamSpec


# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------


def moe_specs(cfg: ModelConfig):
    m = cfg.moe
    d, f, E = cfg.d_model, m.expert_ff, m.n_experts
    out = {
        "router": ParamSpec((d, E), ("embed", None), scale=0.02),
        "wi": ParamSpec((E, d, f), ("experts", "embed", "ff")),
        "wg": ParamSpec((E, d, f), ("experts", "embed", "ff")),
        "wo": ParamSpec((E, f, d), ("experts", "ff", "embed")),
    }
    if m.n_shared:
        fs = m.expert_ff * m.n_shared
        out["shared_wi"] = ParamSpec((d, fs), ("embed", "ff"))
        out["shared_wg"] = ParamSpec((d, fs), ("embed", "ff"))
        out["shared_wo"] = ParamSpec((fs, d), ("ff", "embed"))
    return out


# ---------------------------------------------------------------------------
# Router
# ---------------------------------------------------------------------------


def route(p, x, cfg: ModelConfig, stat_axes=None):
    """x: (T, d) -> (weights (T,k), idx (T,k), aux_loss scalar).

    ``stat_axes`` (a mesh axis name or tuple) pmean's the router's batch
    statistics ``me``/``ce`` before they enter the aux loss.  The Switch
    aux is *nonlinear* in those batch means, so inside a shard_map'd
    step the per-shard aux only matches the global one when the stats
    themselves are global.  With the pmean in place, sum-of-local-grads
    == global-grad holds (pmean is self-transpose up to the 1/n the
    per-shard ``aux / dp_size`` contract already applies), which is what
    lets MoE ride the bucketed/scatter/ep overlap paths instead of
    falling back to ``xla_fused``.  See tests/test_moe_router_stats.py.
    """
    m = cfg.moe
    logits = (x.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                      # (T, E)
    w, idx = jax.lax.top_k(probs, m.top_k)                       # (T, k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance aux loss
    me = probs.mean(0)                                           # (E,)
    one_hot = jax.nn.one_hot(idx, m.n_experts).sum(1)            # (T, E)
    ce = one_hot.mean(0)
    if stat_axes:
        me = jax.lax.pmean(me, stat_axes)
        ce = jax.lax.pmean(ce, stat_axes)
    aux = m.n_experts * jnp.sum(me * ce) * m.router_aux_coef
    return w.astype(x.dtype), idx, aux


def _expert_ffn(wi, wg, wo, x, cfg: ModelConfig):
    h = jax.nn.silu(x @ wg) * (x @ wi)
    return h @ wo


def _shared_ffn(p, x, cfg: ModelConfig):
    h = jax.nn.silu(x @ p["shared_wg"].astype(x.dtype)) * (x @ p["shared_wi"].astype(x.dtype))
    return h @ p["shared_wo"].astype(x.dtype)


# ---------------------------------------------------------------------------
# Dense (oracle) dispatch
# ---------------------------------------------------------------------------


def apply_moe_dense(p, x, cfg: ModelConfig, stat_axes=None):
    """x: (B,S,d).  Computes every expert on every token, combines by gate."""
    m = cfg.moe
    B, S, d = x.shape
    xt = x.reshape(-1, d)
    w, idx, aux = route(p, xt, cfg, stat_axes=stat_axes)
    gates = jnp.zeros((xt.shape[0], m.n_experts), x.dtype)
    gates = gates.at[jnp.arange(xt.shape[0])[:, None], idx].set(w)  # (T,E)
    h = jnp.einsum("td,edf->tef", xt, p["wi"].astype(x.dtype))
    g = jnp.einsum("td,edf->tef", xt, p["wg"].astype(x.dtype))
    y = jnp.einsum("tef,efd->ted", jax.nn.silu(g) * h, p["wo"].astype(x.dtype))
    out = jnp.einsum("ted,te->td", y, gates)
    if m.n_shared:
        out = out + _shared_ffn(p, xt, cfg)
    return out.reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# Expert-parallel capacity dispatch (shard_map + all_to_all)
# ---------------------------------------------------------------------------


def _capacity(n_tokens: int, cfg: ModelConfig) -> int:
    m = cfg.moe
    c = int(m.capacity_factor * m.top_k * n_tokens / m.n_experts)
    return max(8, ((c + 7) // 8) * 8)  # pad to multiple of 8 lanes


def _ep_local(p, xt, cfg: ModelConfig, axis: str, n_shards: int, *,
              stat_axes=None, overlap: bool = True):
    """Runs on each shard: xt (T_loc, d); expert weights already local
    (E_loc = E / n_shards).

    ``overlap=True`` (the default) runs the shared-expert FFN *between*
    the dispatch ``all_to_all`` and the expert FFN — the shared FFN
    reads only ``xt``, so it is independent compute the scheduler can
    run while the dispatch exchange is in flight, the same trick
    ``gradsync.py`` plays with psums against the backward.
    ``overlap=False`` serializes it after the combine (the benchmark's
    sequential reference); both orders compute identical values."""
    m = cfg.moe
    T = xt.shape[0]
    d = xt.shape[-1]
    E = m.n_experts
    C = _capacity(T, cfg)
    w, idx, aux = route(p, xt, cfg, stat_axes=stat_axes)  # router replicated

    # scatter tokens into per-expert capacity buffers -----------------------
    flat_e = idx.reshape(-1)                           # (T*k,)
    flat_t = jnp.repeat(jnp.arange(T), m.top_k)        # (T*k,)
    flat_w = w.reshape(-1)
    # position of each (token,slot) within its expert
    one_hot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)        # (T*k, E)
    pos_in_e = jnp.cumsum(one_hot, axis=0) * one_hot            # (T*k, E)
    slot = (pos_in_e.sum(-1) - 1)                               # (T*k,)
    keep = slot < C                                             # capacity drop
    dest = flat_e * C + jnp.where(keep, slot, C)                # overflow -> C
    buf = jnp.zeros((E * C + 1, d), xt.dtype).at[dest].set(xt[flat_t])
    buf = buf[:-1].reshape(E, C, d)

    # all_to_all: (E, C, d) -> (E_loc, n_shards*C, d) on each shard.
    # tiled=True keeps the VJP well-formed (the untiled transpose rule
    # produces axis-swapped cotangents under shard_map).
    buf = jax.lax.all_to_all(buf, axis, split_axis=0, concat_axis=1,
                             tiled=True)

    # shared-expert FFN, issued while the dispatch exchange is in flight
    shared = None
    if m.n_shared and overlap:
        shared = _shared_ffn(p, xt, cfg)

    # local expert FFN -------------------------------------------------------
    h = jnp.einsum("ecd,edf->ecf", buf, p["wi"].astype(xt.dtype))
    g = jnp.einsum("ecd,edf->ecf", buf, p["wg"].astype(xt.dtype))
    y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h, p["wo"].astype(xt.dtype))

    # return trip ------------------------------------------------------------
    y = jax.lax.all_to_all(y, axis, split_axis=1, concat_axis=0, tiled=True)
    y = y.reshape(E * C, d)                                     # my tokens back

    # combine ----------------------------------------------------------------
    gathered = jnp.where(keep[:, None], y[jnp.where(keep, dest, 0)], 0.0)
    out = jnp.zeros((T, d), xt.dtype).at[flat_t].add(gathered * flat_w[:, None])
    if m.n_shared:
        out = out + (shared if shared is not None else _shared_ffn(p, xt, cfg))
    return out, aux


def apply_moe_ep(p, x, cfg: ModelConfig, mesh, *, batch_axes, expert_axis):
    """Expert-parallel MoE.  x (B,S,d) sharded over ``batch_axes`` on B;
    expert weights sharded over ``expert_axis`` on E."""
    m = cfg.moe
    B, S, d = x.shape
    n_shards = 1
    for a in (expert_axis,):
        n_shards *= mesh.shape[a]

    bspec = P(batch_axes if batch_axes else None)
    wspec = jax.tree_util.tree_map(lambda _: P(), p)
    wspec = dict(wspec)
    for k in ("wi", "wg", "wo"):
        wspec[k] = P(expert_axis)

    from jax import shard_map

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(wspec, P(bspec[0] if bspec else None, None, None)),
        out_specs=(P(bspec[0] if bspec else None, None, None), P()),
        check_vma=False,
    )
    def run(pl, xl):
        T = xl.shape[0] * xl.shape[1]
        out, aux = _ep_local(pl, xl.reshape(T, d), cfg, expert_axis, n_shards,
                             stat_axes=batch_axes if batch_axes else None)
        if batch_axes:
            aux = jax.lax.pmean(aux, batch_axes)
        if expert_axis:
            aux = jax.lax.pmean(aux, expert_axis)
        return out.reshape(xl.shape), aux

    return run(p, x)


def apply_moe(p, x, cfg: ModelConfig, *, impl: str = "dense", mesh=None,
              batch_axes=(), expert_axis: Optional[str] = None,
              stat_axes=None, n_shards: int = 1, overlap: bool = True):
    if impl == "ep_shard":
        # Already inside the train step's shard_map: the expert leaves of
        # ``p`` are local (E/ep on their ``experts`` dim) and ``x`` is the
        # per-shard batch, so dispatch directly — no nested shard_map.
        B, S, d = x.shape
        out, aux = _ep_local(p, x.reshape(-1, d), cfg, expert_axis, n_shards,
                             stat_axes=stat_axes, overlap=overlap)
        return out.reshape(B, S, d), aux
    if impl == "ep" and mesh is not None and expert_axis is not None \
            and cfg.moe.n_experts % mesh.shape[expert_axis] == 0:
        return apply_moe_ep(p, x, cfg, mesh, batch_axes=batch_axes,
                            expert_axis=expert_axis)
    return apply_moe_dense(p, x, cfg, stat_axes=stat_axes)
