"""Logical-axis -> mesh-axis sharding rules, and the ParallelPlan.

**Mesh-axis naming convention** (stated once here; every other module —
``gradsync``, ``train/runner``, the launchers — uses these names):

  ``pod``    leading DCN axis of a multi-pod mesh; pure data parallelism.
  ``pipe``   the pipeline axis: the block stack is cut into contiguous
             stages, one per ``pipe`` coordinate, and microbatches
             stream through (``distributed/pipeline.py``).
  ``data``   the data-parallel / ZeRO axis inside a pod: batches shard
             over it in every mode, params + optimizer state shard over
             it under fsdp (``scatter_overlap``).
  ``expert`` the expert-parallel axis (carved from ``data``, like
             ``pipe``): MoE expert weights shard over it on their
             leading ``experts`` dim and tokens move by ``all_to_all``
             capacity dispatch (``models/moe.py``); the batch shards
             over ``data`` x ``expert`` jointly, so for non-expert
             leaves it is just more data parallelism.
  ``model``  the tensor-parallel axis (Megatron-style): heads/ff/vocab/
             expert dims shard over it under tp / fsdp_tp.

Modes (DESIGN.md §5; full treatment in ``docs/parallelism.md``):
  ddp      — paper-faithful pure data parallelism: params replicated,
             batch sharded over every available mesh axis.
  fsdp     — params (and optimizer state) sharded over "data" (ZeRO-3
             analogue); batch over ("pod","data").
  tp       — Megatron-style tensor parallelism over "model" (serving).
  fsdp_tp  — both (default for >=7B training).
  pp       — pipeline parallelism alone: stages over "pipe", whole
             batch per stage column.
  pp_dp    — pipeline x data: stages over "pipe", batch sharded over
             ("pod","data") within each stage; within-stage gradient
             sync reuses the ddp bucket machinery.

Rules are *candidate lists*: the first mesh axis that (a) exists, (b) is not
already used by another dim of the same tensor and (c) divides the dim size
is chosen; otherwise the dim is replicated.  This gives graceful fallback
for e.g. kv_heads=8 on a model axis of 16 (falls back to head_dim).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

__all__ = [
    "ParallelPlan",
    "GRAD_SYNC_BUCKETED", "GRAD_SYNC_SCATTER", "GRAD_SYNC_PIPE",
    "GRAD_SYNC_EP", "GRAD_SYNC_TP", "GRAD_SYNC_XLA", "GRAD_SYNC_NONE",
    "RULES", "TP_LEAF_AXES", "tp_compatible",
    "spec_for", "tree_shardings", "batch_axes", "batch_spec",
    "activation_sharding",
    "local_batch_size", "process_batch_slice",
    "flash_attn_ctx", "flash_shard_shapes", "flash_analytic_cost",
    "ssd_analytic_cost", "attn_shard_ctx",
    "cache_rules", "cache_seq_axes", "cache_batch_axes",
]

Candidate = Union[str, Tuple[str, ...]]


# ---------------------------------------------------------------------------
# Per-host batch / example slicing (multi-host data parallelism)
# ---------------------------------------------------------------------------


def local_batch_size(global_batch: int, process_count: int) -> int:
    """Per-host batch size; the global batch must divide evenly so every
    host dispatches the same program shape."""
    if global_batch % max(1, process_count) != 0:
        raise ValueError(
            f"global_batch={global_batch} not divisible by "
            f"process_count={process_count}")
    return global_batch // max(1, process_count)


def process_batch_slice(global_batch: int, process_index: int,
                        process_count: int) -> slice:
    """Contiguous slice of a global batch owned by ``process_index``.
    Hosts own disjoint, covering slices: host p takes rows
    [p*b_loc, (p+1)*b_loc) of every global batch."""
    b_loc = local_batch_size(global_batch, process_count)
    if not 0 <= process_index < process_count:
        raise ValueError(
            f"process_index={process_index} out of range "
            f"[0, {process_count})")
    return slice(process_index * b_loc, (process_index + 1) * b_loc)

# rule tables: logical axis -> candidates (tried in order)
_TP = {
    "heads": ("model",),
    "kv_heads": ("model",),
    "head_dim": ("model",),       # fallback when kv_heads isn't divisible
    "ff": ("model",),
    "vocab": ("model",),
    "experts": ("model",),
    "ssm_heads": ("model",),
    "ssm_hd": ("model",),
}
_FSDP = {"embed": ("data",)}

RULES: Dict[str, Dict[str, Tuple[Candidate, ...]]] = {
    "ddp": {},
    "fsdp": dict(_FSDP),
    "tp": dict(_TP),
    "fsdp_tp": {**_FSDP, **_TP},
    # pipeline modes: no logical-axis rules — the block stack is sharded
    # over 'pipe' EXPLICITLY (ParallelPlan.pipe_param_specs); everything
    # else is replicated, exactly like ddp within a stage
    "pp": {},
    "pp_dp": {},
}


def _axis_size(mesh: Mesh, cand: Candidate) -> int:
    if isinstance(cand, str):
        return mesh.shape[cand]
    return int(np.prod([mesh.shape[a] for a in cand]))


def _cand_axes(cand: Candidate) -> Tuple[str, ...]:
    return (cand,) if isinstance(cand, str) else tuple(cand)


def spec_for(axes: Optional[Sequence[Optional[str]]], shape: Sequence[int],
             rules: Dict[str, Tuple[Candidate, ...]], mesh: Mesh) -> P:
    """PartitionSpec for one tensor: each logical axis name in ``axes``
    is resolved through ``rules`` to the first mesh axis that exists, is
    unused by this tensor, and divides the dim — else replicated.
    ``axes=None`` (no logical annotation) replicates the whole leaf."""
    if axes is None:
        return P()
    used: set = set()
    out = []
    for name, dim in zip(axes, shape):
        assigned = None
        for cand in rules.get(name, ()):  # type: ignore[arg-type]
            cand_axes = _cand_axes(cand)
            if not all(a in mesh.axis_names for a in cand_axes):
                continue
            if any(a in used for a in cand_axes):
                continue
            if dim % _axis_size(mesh, cand) != 0:
                continue
            # normalize 1-tuples to the bare axis name (the canonical
            # PartitionSpec spelling; matches batch_spec's unwrapping)
            assigned = cand if isinstance(cand, str) else (
                cand[0] if len(cand) == 1 else tuple(cand))
            used.update(cand_axes)
            break
        out.append(assigned)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def tree_shardings(axes_tree, shape_tree, mesh: Mesh, mode: str,
                   drop_axes: Tuple[str, ...] = ()):
    """NamedSharding tree for a (logical-axes, shapes) pair of pytrees."""
    rules = {k: v for k, v in RULES[mode].items() if k not in drop_axes}

    def one(axes, leaf):
        return NamedSharding(mesh, spec_for(axes, leaf.shape, rules, mesh))

    return jax.tree_util.tree_map(
        one, axes_tree, shape_tree,
        is_leaf=lambda x: isinstance(x, tuple) or x is None,
    )


# ---------------------------------------------------------------------------
# Batch / activation sharding
# ---------------------------------------------------------------------------


def batch_axes(mesh: Mesh, global_batch: int, mode: str) -> Tuple[str, ...]:
    """Largest prefix of the DP axis list that divides the global batch."""
    # 'expert' rides in every prefer list: from the batch's point of view
    # the expert axis is just more data parallelism (tokens shard over
    # data x expert jointly; the EP dispatch moves them to their experts
    # with all_to_all inside the step)
    if mode == "ddp":
        prefer = [a for a in ("pod", "data", "expert", "model")
                  if a in mesh.axis_names]
    elif mode in ("pp", "pp_dp"):
        # module-level callers see the pp FALLBACK semantics (pipelining
        # off: 'pipe' demoted to a plain data axis).  An ENGAGED pipeline
        # plan computes its dp axes over ("pod","data") only, inside
        # ParallelPlan.make — batch replicates across stages there.
        prefer = [a for a in ("pod", "pipe", "data", "expert")
                  if a in mesh.axis_names]
    else:
        prefer = [a for a in ("pod", "data", "expert")
                  if a in mesh.axis_names]
    chosen: list = []
    size = 1
    for a in prefer:
        if global_batch % (size * mesh.shape[a]) == 0:
            chosen.append(a)
            size *= mesh.shape[a]
    return tuple(chosen)


def batch_spec(mesh: Mesh, global_batch: int, mode: str, ndim: int = 2) -> P:
    """PartitionSpec for a batch array: leading (batch) dim over the
    mode's dp axes (see :func:`batch_axes`), trailing dims replicated."""
    ax = batch_axes(mesh, global_batch, mode)
    lead = ax if len(ax) != 1 else ax[0]
    return P(lead if ax else None, *([None] * (ndim - 1)))


def activation_sharding(mesh: Mesh, global_batch: int, mode: str,
                        seq_axis: Optional[str] = None):
    """Constraint applied to hidden states (B, S, d) between blocks.
    ``seq_axis='model'`` enables Megatron-style sequence parallelism."""
    ax = batch_axes(mesh, global_batch, mode)
    lead = ax if len(ax) != 1 else ax[0]
    spec = P(lead if ax else None, seq_axis, None)

    def constrain(h):
        return jax.lax.with_sharding_constraint(h, NamedSharding(mesh, spec))

    return constrain


def flash_attn_ctx(cfg, mesh: Mesh, mode: str, global_batch: int,
                   seq_len: int):
    """shard_map wrapper around the Pallas flash-attention kernel.

    Batch is sharded over the DP axes; q heads are sharded over 'model'
    when divisible (each shard slices the kv heads its q-head block maps
    to — GQA block structure guarantees the slice is one contiguous kv
    group when Hl | rep or rep | Hl).  Returns fn(q,k,v,causal,window) or
    None when the kernel can't be mapped onto this mesh.
    """
    import jax.numpy as jnp

    H, Hkv = cfg.n_heads, cfg.n_kv_heads
    if not H or cfg.mla is not None:
        return None
    ms = mesh.shape.get("model", 1)
    bax = batch_axes(mesh, global_batch, mode)
    if mode in ("tp", "fsdp_tp") and H % ms == 0 and ms > 1:
        Hl = H // ms
        rep = H // Hkv
        if not (rep % Hl == 0 or Hl % rep == 0):
            return None
        head_axis = "model"
        kv_len = max(1, Hl // rep)
    elif mode == "ddp":
        head_axis = None
        kv_len = Hkv
        Hl, rep = H, H // Hkv
    else:
        return None
    if seq_len % 512 and seq_len % 128:
        return None
    lead = (bax if len(bax) != 1 else bax[0]) if bax else None
    qspec = P(lead, None, head_axis, None)
    kvspec = P(lead, None, None, None)

    def fn(q, k, v, *, causal, window, softcap, scale):
        from repro.kernels import ops as kops

        def body(ql, kl, vl):
            if head_axis is not None:
                idx = jax.lax.axis_index(head_axis)
                kv_start = (idx * Hl) // rep
                kl_ = jax.lax.dynamic_slice_in_dim(kl, kv_start, kv_len, 2)
                vl_ = jax.lax.dynamic_slice_in_dim(vl, kv_start, kv_len, 2)
            else:
                kl_, vl_ = kl, vl
            with jax.named_scope("pallas_flash"):
                return kops.flash_attention(ql, kl_, vl_, causal, window,
                                            softcap, scale)

        return jax.shard_map(
            body, mesh=mesh, in_specs=(qspec, kvspec, kvspec),
            out_specs=qspec, check_vma=False)(q, k, v)

    return fn


def flash_shard_shapes(cfg, mesh: Mesh, mode: str, global_batch: int):
    """Per-shard (B_loc, Hl, kv_len) the flash ctx will see, or None."""
    H, Hkv = cfg.n_heads, cfg.n_kv_heads
    if not H or cfg.mla is not None:
        return None
    ms = mesh.shape.get("model", 1)
    bax = batch_axes(mesh, global_batch, mode)
    bsz = 1
    for a in bax:
        bsz *= mesh.shape[a]
    B_loc = global_batch // bsz
    if mode in ("tp", "fsdp_tp") and H % ms == 0 and ms > 1:
        Hl = H // ms
        rep = H // Hkv
        if not (rep % Hl == 0 or Hl % rep == 0):
            return None
        return B_loc, Hl, max(1, Hl // rep)
    if mode == "ddp":
        return B_loc, H, Hkv
    return None


def flash_analytic_cost(cfg, mesh: Mesh, mode: str, global_batch: int,
                        seq_len: int, *, causal: bool = True, bq: int = 512,
                        dtype_bytes: int = 2):
    """Per-call (per-device) analytic flash-kernel cost: q/o move once,
    k/v stream once per q block; scores never leave VMEM.  Used as the
    pallas_cost substitution in the roofline (hlocost.HloCostModel)."""
    from repro.analysis.hlocost import Cost

    shapes = flash_shard_shapes(cfg, mesh, mode, global_batch)
    if shapes is None:
        return None
    B_loc, Hl, kvl = shapes
    S = seq_len
    D = cfg.head_dim
    factor = 0.5 if causal else 1.0
    flops = 4.0 * B_loc * Hl * S * S * D * factor
    passes = max(1, S // min(bq, S))
    byts = dtype_bytes * B_loc * (
        2 * S * Hl * D + 2 * S * kvl * D * passes * factor)
    return Cost(flops=flops, bytes=float(byts))


def ssd_analytic_cost(cfg, mesh: Mesh, mode: str, global_batch: int,
                      seq_len: int, dtype_bytes: int = 2):
    """Per-call (per-device) analytic SSD chunk-scan kernel cost: x/dt/B/C
    read once, y written once, the (L,L) decay tile and (N,P) state stay
    in VMEM.  flops per chunk: C·Bᵀ (L²N) + seg·x (L²P) + two (L,N,P)
    state contractions."""
    from repro.analysis.hlocost import Cost
    from repro.models.ssm import ssm_dims

    if cfg.ssm is None:
        return None
    d_inner, H, Pd, G, N = ssm_dims(cfg)
    ms = mesh.shape.get("model", 1)
    bax = batch_axes(mesh, global_batch, mode)
    bsz = 1
    for a in bax:
        bsz *= mesh.shape[a]
    B_loc = max(1, global_batch // bsz)
    H_loc = H // ms if (mode in ("tp", "fsdp_tp") and H % ms == 0) else H
    S = seq_len
    L = cfg.ssm.chunk
    flops = 2.0 * B_loc * H_loc * S * (L * (N + Pd) + 2.0 * N * Pd)
    byts = dtype_bytes * B_loc * S * (
        2 * H_loc * Pd          # x read + y write
        + H_loc                 # dt
        + 4 * G * N)            # B, C read (+ conv outputs)
    return Cost(flops=flops, bytes=float(byts))


def attn_shard_ctx(cfg, mesh: Mesh, mode: str, global_batch: int,
                   seq_len: int):
    """Context-parallel attention constraints.

    When kv-head sharding over the model axis is impossible
    (kv_heads % model != 0), the propagation fallback shards head_dim,
    which replicates the whole (S,S) score computation on every model-axis
    chip and psums it.  Instead: shard q (and the scores) over the
    *sequence*, keep k/v replicated on the model axis.  Returns None when
    head-parallel attention is fine.
    """
    if mode not in ("tp", "fsdp_tp") or "model" not in mesh.axis_names:
        return None
    ms = mesh.shape["model"]
    if cfg.mla is not None:
        return None  # MLA: heads shard cleanly (16 % 16 == 0)
    if cfg.n_kv_heads and cfg.n_kv_heads % ms == 0:
        return None  # head-parallel attention already shards the scores
    if seq_len % ms != 0:
        return None
    bax = batch_axes(mesh, global_batch, mode)
    lead = bax if len(bax) != 1 else bax[0]
    qspec = P(lead if bax else None, "model", None, None)
    kvspec = P(lead if bax else None, None, None, None)

    def cq(x):
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, qspec))

    def ckv(x):
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, kvspec))

    return {"q": cq, "kv": ckv}


# ---------------------------------------------------------------------------
# Cache sharding (decode)
# ---------------------------------------------------------------------------


def cache_rules(mesh: Mesh, global_batch: int, mode: str):
    """Sequence-sharded decode caches: cache_seq over 'model', and over
    ('data','model') when the batch can't use the data axis (long-context
    batch=1)."""
    rules = dict(RULES[mode])
    bax = batch_axes(mesh, global_batch, "fsdp")  # ('pod','data') prefix
    rules["batch"] = (tuple(bax),) if bax else ()
    if bax and "data" in bax:
        rules["cache_seq"] = ("model",)
    else:
        seq_ax = tuple(a for a in ("pod", "data", "model")
                       if a in mesh.axis_names)
        rules["cache_seq"] = (seq_ax, "model")
    # decode-time TP for cache heads is impossible together with seq
    # sharding on the same axis; spec_for's used-set handles the conflict.
    return rules


def cache_seq_axes(mesh: Mesh, global_batch: int) -> Tuple[str, ...]:
    """Mesh axes the decode cache's sequence dim shards over (every axis
    the batch can't use; see :func:`cache_rules`)."""
    bax = batch_axes(mesh, global_batch, "fsdp")
    if bax and "data" in bax:
        return ("model",)
    return tuple(a for a in ("pod", "data", "model") if a in mesh.axis_names)


def cache_batch_axes(mesh: Mesh, global_batch: int) -> Tuple[str, ...]:
    """Mesh axes the decode cache's batch dim shards over (the fsdp
    (`pod`,`data`) prefix that divides the batch)."""
    return batch_axes(mesh, global_batch, "fsdp")


# ---------------------------------------------------------------------------
# ParallelPlan — the one queryable description of a parallelism mode
# ---------------------------------------------------------------------------

# grad-sync strategies (ParallelPlan.grad_sync):
#   bucketed_overlap — explicit per-bucket psum inside a shard_map'd step,
#                      issued as cotangents become ready (ddp, dp>1)
#   scatter_overlap  — fsdp/fsdp_tp: params + optimizer state sharded over
#                      the dp axes (ZeRO-3); the shard_map'd step issues
#                      one all_gather per bucket in forward-layer order
#                      (param prefetch) and one psum_scatter per bucket in
#                      reverse-layer order during backward (grad wire
#                      bytes halve vs the ddp all-reduce)
#   pipe_overlap     — pp/pp_dp: the staged microbatch pipeline
#                      (distributed/pipeline.py); block stack sharded
#                      over 'pipe', activations/cotangents move by
#                      ppermute, within-stage grads reuse bucketed_psum
#                      over the data axes
#   ep_overlap       — ddp MoE on a mesh with an 'expert' axis: expert
#                      weights shard over 'expert' on their leading
#                      experts dim, tokens move by capacity-bucketed
#                      all_to_all (models/moe.py) with the shared-expert
#                      FFN computed while the dispatch is in flight;
#                      expert-leaf grads psum over the data axes only,
#                      replicated leaves over (expert,) + data — the
#                      same split as pipe_overlap's stage/replicated
#                      buckets
#   tp_overlap       — tp/fsdp_tp on a mesh with a >1 'model' axis:
#                      Megatron column/row-parallel attention + FFN with
#                      the activation collectives explicitly scheduled
#                      inside the shard_map'd step — sequence-parallel
#                      layout between blocks (activations sharded over
#                      'model' on the seq dim), one all_gather entering
#                      each block's parallel region and one
#                      psum_scatter leaving it.  tp-sharded leaf grads
#                      psum over the data axes only, dense leaves over
#                      ('model',) + data — the same stage/replicated
#                      split as pipe_overlap/ep_overlap.  Under fsdp_tp
#                      the dense leaves additionally run the ZeRO-3
#                      scatter layout over 'data' (gather forward,
#                      psum_scatter backward), composed via
#                      :meth:`ParallelPlan.tp_scatter_plan`.
#   xla_fused        — the partitioner inserts collectives from the sharded
#                      param/grad specs (the tp fallbacks: indivisible
#                      heads/ff/seq, MoE, overlap off)
#   none             — single data-parallel shard: nothing to synchronize
GRAD_SYNC_BUCKETED = "bucketed_overlap"
GRAD_SYNC_SCATTER = "scatter_overlap"
GRAD_SYNC_PIPE = "pipe_overlap"
GRAD_SYNC_EP = "ep_overlap"
GRAD_SYNC_TP = "tp_overlap"
GRAD_SYNC_XLA = "xla_fused"
GRAD_SYNC_NONE = "none"

# logical axes the tp_overlap path shards over 'model' (column/row
# parallel attention + FFN).  Deliberately narrower than the _TP rule
# table: vocab/head_dim/experts stay dense — the explicit schedule only
# partitions the dims whose collectives it places by hand.
TP_LEAF_AXES = ("heads", "kv_heads", "ff")


def tp_compatible(model_cfg) -> Tuple[bool, str]:
    """(ok, reason) — whether the explicitly-scheduled tp step supports
    this model's structure.  The tp_ctx gather/scatter schedule assumes
    every sublayer is attention or a dense MLP (partial-sum outputs the
    psum_scatter reduces); SSM and MLA mixers and the encoder-decoder
    assembly need their own partition story and fall back to the
    partitioner-scheduled tp specs instead."""
    from repro.configs.base import ATTN, SHARED_ATTN

    if getattr(model_cfg, "is_encoder_decoder", False):
        return False, "encoder-decoder"
    for g in model_cfg.schedule:
        for s in g.pattern:
            if s.kind not in (ATTN, SHARED_ATTN):
                return False, f"{s.kind} mixer"
    return True, ""


@dataclass(frozen=True)
class ParallelPlan:
    """Unified, queryable parallelism plan for one (mesh, mode) pair.

    The seed scattered mode-string dispatch (``run.sharding in (...)``)
    across five files; the plan centralizes every question those call
    sites asked:

    * which mesh axes shard the batch (``dp_axes`` / ``batch_spec``),
    * which logical-axis rules shard params (``rules`` /
      ``tree_shardings``),
    * whether/how gradients are synchronized (``grad_sync`` — see the
      strategy constants above) and at what bucket granularity,
    * how activations between blocks are constrained
      (``activation_constrain``).

    Construct via :meth:`make` (or ``for_run``); the dataclass is frozen
    so a plan can be closed over by traced functions.
    """

    mode: str                      # ddp | fsdp | tp | fsdp_tp | pp | pp_dp
    mesh: Optional[Mesh] = None
    global_batch: int = 0
    grad_bucket_mb: float = 25.0
    overlap: bool = True           # False forces the fused-tail baseline
                                   # (xla_fused) for ddp AND fsdp modes
    microbatch: int = 1            # grad-accumulation count (the overlap
                                   # paths split the LOCAL shard into
                                   # microbatches; under pp modes this is
                                   # the PIPELINE microbatch count M)
    has_moe: bool = False          # MoE model: the router's batch-mean
                                   # statistics are psum'd inside the
                                   # shard_map'd step (models/moe.py
                                   # route(stat_axes=...)), so MoE rides
                                   # the overlap paths; see grad_sync
    n_experts: int = 0             # routed expert count (feeds the
                                   # ep_overlap engagement predicate)
    ep_overlap_dispatch: bool = True  # ep_overlap: compute the shared-
                                   # expert FFN between the dispatch
                                   # all_to_all and the combine (False
                                   # serializes it after the combine —
                                   # the moe_overlap bench baseline)
    donate_gather: bool = True     # scatter_overlap: free the gathered
                                   # full-param buffers after forward and
                                   # re-gather in backward (remat of the
                                   # per-bucket all_gathers) — peak
                                   # memory drops by ~the full param
                                   # tree at the cost of 2x gather wire;
                                   # fsdp_overlap reports the delta
    free_after_use: bool = False   # scatter_overlap: per-bucket regather
                                   # — each bucket's forward all_gather
                                   # is wrapped in jax.checkpoint so the
                                   # gathered buffer is freed after its
                                   # layers consume it and re-gathered in
                                   # backward (peak memory holds one
                                   # bucket's full params instead of the
                                   # whole tree, at 2x gather wire);
                                   # fsdp_overlap measures the trade
    n_heads: int = 0               # tp_overlap engagement: attention
                                   # q heads (0 = not known, gate passes)
    n_kv_heads: int = 0            # ... kv heads (GQA groups must split)
    d_ff: int = 0                  # ... FFN hidden width
    seq_len: int = 0               # ... sequence length (the sequence-
                                   # parallel layout shards it)
    tp_ok: bool = True             # model structure admits the explicit
                                   # tp schedule (sharding.tp_compatible:
                                   # attention + dense-MLP blocks only)
    pp_schedule: str = "1f1b"      # gpipe | 1f1b (pp modes only)
    n_layers: int = 0              # depth of the block stack (pp modes:
                                   # must divide by the pipe axis)
    stageable: bool = True         # model structure admits equal SPMD
                                   # stages (pipeline.stage_compatible)
    _dp_axes: Tuple[str, ...] = field(default=())
    _pipe_ok: bool = field(default=False)

    @classmethod
    def make(cls, mesh: Optional[Mesh], mode: str, global_batch: int, *,
             grad_bucket_mb: float = 25.0, overlap: bool = True,
             microbatch: int = 1, has_moe: bool = False,
             n_experts: int = 0, ep_overlap_dispatch: bool = True,
             donate_gather: bool = True, free_after_use: bool = False,
             n_heads: int = 0, n_kv_heads: int = 0, d_ff: int = 0,
             seq_len: int = 0, tp_ok: bool = True,
             pp_schedule: str = "1f1b", n_layers: int = 0,
             stageable: bool = True) -> "ParallelPlan":
        """Build a plan for one (mesh, mode, global_batch) triple.

        ``overlap=False`` pins the fused ``xla_fused`` baseline (the knob
        the grad_overlap/fsdp_overlap benchmarks flip); ``microbatch``
        feeds the fallback predicate of :attr:`grad_sync`.  For the
        pipeline modes, ``n_layers`` / ``stageable`` / ``pp_schedule``
        feed the static engagement test (:attr:`pipe_engaged`); when
        pipelining cannot engage, ``pipe`` is demoted to a plain data
        axis and the ddp dispatch applies.  ``has_moe`` + ``n_experts``
        feed the ``ep_overlap`` engagement test (:attr:`ep_engaged`);
        when expert parallelism cannot engage, ``expert`` stays a plain
        data axis and the MoE runs dense dispatch under the mode's
        normal strategy.  Raises ``KeyError`` on an unknown mode.
        """
        if mode not in RULES:
            raise KeyError(f"unknown sharding mode {mode!r}; "
                           f"known: {sorted(RULES)}")
        microbatch = max(1, microbatch)
        pipe_ok = False
        if mode in ("pp", "pp_dp") and mesh is not None:
            pp = mesh.shape["pipe"] if "pipe" in mesh.axis_names else 1
            # batch axes of an ENGAGED pipeline: the ("pod","data")
            # prefix — batch replicates across stages
            dp = batch_axes(mesh, global_batch, "fsdp")
            dp_size = int(np.prod([mesh.shape[a] for a in dp])) if dp \
                else 1
            local = global_batch // dp_size
            pipe_ok = (pp > 1 and overlap and stageable and not has_moe
                       and n_layers > 0 and n_layers % pp == 0
                       and local % microbatch == 0
                       and local >= microbatch)
        if not pipe_ok:
            dp = batch_axes(mesh, global_batch, mode) if mesh is not None \
                else ()
        return cls(mode=mode, mesh=mesh, global_batch=global_batch,
                   grad_bucket_mb=grad_bucket_mb, overlap=overlap,
                   microbatch=microbatch, has_moe=has_moe,
                   n_experts=n_experts,
                   ep_overlap_dispatch=ep_overlap_dispatch,
                   donate_gather=donate_gather,
                   free_after_use=free_after_use,
                   n_heads=n_heads, n_kv_heads=n_kv_heads, d_ff=d_ff,
                   seq_len=seq_len, tp_ok=tp_ok,
                   pp_schedule=pp_schedule, n_layers=n_layers,
                   stageable=stageable, _dp_axes=dp, _pipe_ok=pipe_ok)

    @classmethod
    def for_run(cls, run, mesh: Optional[Mesh], *,
                grad_bucket_mb: float = 25.0,
                overlap: bool = True,
                donate_gather: bool = True,
                free_after_use: bool = False,
                ep_overlap_dispatch: bool = True) -> "ParallelPlan":
        """Plan derived from a ``RunConfig`` (mode, global batch,
        microbatch count, MoE-ness, layer depth and stage compatibility
        all read off ``run``).  ``ep_overlap_dispatch=False`` serializes
        the MoE shared-expert FFN after the all_to_all combine — the
        moe_overlap benchmark's sequential reference."""
        from repro.distributed.pipeline import stage_compatible

        moe = run.model.moe
        return cls.make(mesh, run.sharding, run.shape.global_batch,
                        grad_bucket_mb=grad_bucket_mb,
                        overlap=overlap,
                        donate_gather=donate_gather,
                        free_after_use=free_after_use,
                        ep_overlap_dispatch=ep_overlap_dispatch,
                        microbatch=run.microbatch or 1,
                        has_moe=moe is not None,
                        n_experts=moe.n_experts if moe is not None else 0,
                        n_heads=run.model.n_heads,
                        n_kv_heads=run.model.n_kv_heads
                        or run.model.n_heads,
                        d_ff=run.model.d_ff,
                        seq_len=run.shape.seq_len,
                        tp_ok=tp_compatible(run.model)[0],
                        pp_schedule=getattr(run, "pp_schedule", "1f1b"),
                        n_layers=run.model.n_layers,
                        stageable=stage_compatible(run.model)[0])

    # -- axes ------------------------------------------------------------
    @property
    def dp_axes(self) -> Tuple[str, ...]:
        """Mesh axes the batch is sharded over."""
        return self._dp_axes

    @property
    def dp_size(self) -> int:
        if self.mesh is None:
            return 1
        return int(np.prod([self.mesh.shape[a] for a in self._dp_axes])) \
            if self._dp_axes else 1

    @property
    def model_axis(self) -> Optional[str]:
        """The tensor-parallel axis, when this mode uses one."""
        if self.mesh is not None and self.mode in ("tp", "fsdp_tp") \
                and "model" in self.mesh.axis_names:
            return "model"
        return None

    # -- pipeline axis ---------------------------------------------------
    @property
    def pipe_engaged(self) -> bool:
        """True when this plan actually pipelines: a pp mode on a mesh
        with a >1 ``pipe`` axis, a stage-divisible block stack, no MoE,
        and a microbatch count that divides the per-shard batch.  When
        False the pp modes demote ``pipe`` to a plain data axis and the
        ddp dispatch below applies (see docs/parallelism.md)."""
        return self._pipe_ok

    @property
    def pp_size(self) -> int:
        """Pipeline stage count (1 when not pipelining)."""
        if not self._pipe_ok:
            return 1
        return self.mesh.shape["pipe"]

    @property
    def pipe_axis(self) -> Optional[str]:
        return "pipe" if self._pipe_ok else None

    @property
    def n_micro(self) -> int:
        """Pipeline microbatch count M (the grad-accumulation split)."""
        return max(1, self.microbatch)

    @property
    def stage_layers(self) -> int:
        """Blocks per stage (the whole stack when not pipelining)."""
        return self.n_layers // self.pp_size if self.n_layers else 0

    # -- expert axis -----------------------------------------------------
    @property
    def ep_size(self) -> int:
        """Width of the mesh's ``expert`` axis (1 when absent)."""
        if self.mesh is not None and "expert" in self.mesh.axis_names:
            return self.mesh.shape["expert"]
        return 1

    @property
    def ep_engaged(self) -> bool:
        """True when this plan runs expert-parallel MoE dispatch: a ddp
        plan for an MoE model on a mesh with a >1 ``expert`` axis the
        batch divides over, an expert count the axis divides
        (capacity dispatch needs whole local expert groups), overlap
        on, and a microbatch count that divides the per-shard batch.
        When False the ``expert`` axis stays a plain data axis and the
        MoE runs dense dispatch under the mode's normal strategy."""
        if self._pipe_ok or self.mesh is None:
            return False
        if self.mode != "ddp" or not self.overlap or not self.has_moe:
            return False
        if self.ep_size <= 1 or "expert" not in self._dp_axes:
            return False
        if self.n_experts <= 0 or self.n_experts % self.ep_size != 0:
            return False
        return self.local_batch % self.microbatch == 0 \
            and self.local_batch >= self.microbatch

    @property
    def ep_axis(self) -> Optional[str]:
        return "expert" if self.ep_engaged else None

    @property
    def ep_data_axes(self) -> Tuple[str, ...]:
        """The dp axes minus ``expert`` — the sync group of the
        expert-sharded grad leaves (each expert-axis coordinate owns a
        distinct expert slice, so their grads must NOT sum over it)."""
        return tuple(a for a in self._dp_axes if a != "expert")

    # -- specs -----------------------------------------------------------
    @property
    def rules(self) -> Dict[str, Tuple[Candidate, ...]]:
        return RULES[self.mode]

    def batch_spec(self, ndim: int = 2) -> P:
        # built from the plan's OWN dp axes (not the module-level
        # recompute): an engaged pipeline shards the batch over
        # ("pod","data") only and replicates it across stages
        if self.mesh is None:
            return P(*([None] * ndim))
        ax = self._dp_axes
        lead = ax if len(ax) != 1 else ax[0]
        return P(lead if ax else None, *([None] * (ndim - 1)))

    def tree_shardings(self, axes_tree, shape_tree,
                       drop_axes: Tuple[str, ...] = ()):
        assert self.mesh is not None, "tree_shardings needs a mesh"
        return tree_shardings(axes_tree, shape_tree, self.mesh, self.mode,
                              drop_axes=drop_axes)

    def activation_constrain(self, seq_axis: Optional[str] = None):
        if self.mesh is None:
            return None
        return activation_sharding(self.mesh, self.global_batch, self.mode,
                                   seq_axis=seq_axis)

    # -- gradient synchronization ----------------------------------------
    @property
    def local_batch(self) -> int:
        """Per-dp-shard batch rows inside the shard_map'd step."""
        return self.global_batch // self.dp_size if self.dp_size else \
            self.global_batch

    # -- tensor-parallel axis --------------------------------------------
    @property
    def tp_size(self) -> int:
        """Width of the mesh's ``model`` axis (1 when absent)."""
        if self.mesh is not None \
                and "model" in getattr(self.mesh, "axis_names", ()):
            return self.mesh.shape["model"]
        return 1

    @property
    def tp_engaged(self) -> bool:
        """True when this plan runs the explicitly-scheduled tensor-
        parallel step (``tp_overlap``): a tp-carrying mode on a mesh
        with a >1 ``model`` axis, overlap on, no MoE (the ep dispatch
        owns the model axis there), a microbatch count that divides the
        per-shard batch, and head/ff/sequence dims the model axis
        divides (``n_heads``/``n_kv_heads``/``d_ff``/``seq_len``; a 0
        means "not known", which passes — :meth:`for_run` always fills
        them).  When False the tp modes fall back to the partitioner-
        scheduled ``xla_fused`` step (tp specs applied, collectives
        implicit) or, for fsdp_tp on a model-axis-1 mesh, to
        ``scatter_overlap`` with vacuous tp specs."""
        if self.mesh is None or self.mode not in ("tp", "fsdp_tp"):
            return False
        ms = self.tp_size
        if ms <= 1 or not self.overlap or self.has_moe \
                or not self.tp_ok:
            return False
        if self.local_batch % self.microbatch != 0 \
                or self.local_batch < self.microbatch:
            return False
        for dim in (self.n_heads, self.n_kv_heads, self.d_ff,
                    self.seq_len):
            if dim and dim % ms != 0:
                return False
        return True

    @property
    def tp_axis(self) -> Optional[str]:
        return "model" if self.tp_engaged else None

    @property
    def grad_sync(self) -> str:
        """Which strategy keeps data-parallel replicas in sync.

        The overlap paths split the LOCAL shard into microbatches (the
        standard ddp accumulation semantics), so they require
        ``local_batch % microbatch == 0``; otherwise the plan falls back
        to the partitioner-scheduled fused path rather than failing.
        MoE models ride the overlap paths: the Switch aux loss is a
        nonlinear function of batch-MEAN router statistics, and a pmean
        of equal-size shard means IS the global mean, so the per-shard
        step pmeans the router's me/ce over the dp axes
        (``models/moe.py`` ``route(stat_axes=...)``) and
        sum-of-local-grads == global-grad holds exactly (the psum
        transpose re-psums the cotangent; see
        ``tests/test_moe_router_stats.py``).  On a mesh with an
        ``expert`` axis an MoE ddp plan upgrades to ``ep_overlap``
        (:attr:`ep_engaged`).  The tp modes return ``tp_overlap`` when
        :attr:`tp_engaged` — note this is checked BEFORE the
        ``dp_size <= 1`` gate: a pure-tp mesh (data=1, model=ms) has no
        data parallelism yet still needs the explicitly-scheduled tp
        step.  The pp modes return ``pipe_overlap`` when
        :attr:`pipe_engaged`; otherwise ``pipe`` has been demoted to a
        data axis (see :meth:`make`) and they dispatch exactly like
        ddp.  The full mode x condition table lives in
        ``docs/parallelism.md`` and is asserted in
        ``tests/test_gradsync.py``; :attr:`fallback_reason` names the
        gate that declined a better strategy."""
        if self._pipe_ok:
            return GRAD_SYNC_PIPE
        if self.mesh is None:
            return GRAD_SYNC_NONE
        if self.tp_engaged:
            return GRAD_SYNC_TP
        if self.dp_size <= 1:
            return GRAD_SYNC_NONE
        divisible = self.local_batch % self.microbatch == 0 \
            and self.local_batch >= self.microbatch
        if self.overlap and divisible:
            if self.ep_engaged:
                return GRAD_SYNC_EP
            if self.mode in ("ddp", "pp", "pp_dp"):
                return GRAD_SYNC_BUCKETED
            if self.mode == "fsdp" or (self.mode == "fsdp_tp"
                                       and self.tp_size <= 1):
                return GRAD_SYNC_SCATTER
        return GRAD_SYNC_XLA

    @property
    def fallback_reason(self) -> Optional[str]:
        """Why this plan declined a better strategy (None when the
        preferred strategy for its mode engaged).  Answers "why did my
        run silently fall back" from the plan print / telemetry:
        ``xla_fused`` gets the gate that blocked every overlap path; a
        pp mode that demoted ``pipe`` to a data axis, or an MoE plan
        whose ``expert`` axis stayed a data axis, gets the demotion
        reason even though an overlap strategy still engaged."""
        gs = self.grad_sync
        if self.mesh is None or gs == GRAD_SYNC_NONE:
            return None
        divisible = self.local_batch % self.microbatch == 0 \
            and self.local_batch >= self.microbatch
        if gs == GRAD_SYNC_XLA:
            if not self.overlap:
                return "overlap disabled"
            if self.mode in ("tp", "fsdp_tp") and self.tp_size > 1:
                ms = self.tp_size
                if self.has_moe:
                    return "moe (tp has no ep composition)"
                if not self.tp_ok:
                    return "tp-incompatible model structure"
                if self.n_heads and self.n_heads % ms != 0:
                    return "tp-indivisible heads"
                if self.n_kv_heads and self.n_kv_heads % ms != 0:
                    return "tp-indivisible kv heads"
                if self.d_ff and self.d_ff % ms != 0:
                    return "tp-indivisible d_ff"
                if self.seq_len and self.seq_len % ms != 0:
                    return "tp-indivisible seq_len"
                return "indivisible microbatch"
            if not divisible:
                return "indivisible microbatch"
            return "tp mode without a model axis"
        if self.mode in ("pp", "pp_dp") and not self._pipe_ok:
            why = "moe" if self.has_moe else \
                "unstageable model" if not self.stageable else \
                "no pipe axis" if ("pipe" not in self.mesh.axis_names
                                   or self.mesh.shape["pipe"] <= 1) else \
                "stage-indivisible depth" if (self.n_layers <= 0
                                              or self.n_layers
                                              % self.mesh.shape["pipe"]
                                              != 0) else \
                "indivisible microbatch"
            return f"{why} (pipe demoted to data axis)"
        if self.has_moe and self.ep_size > 1 and gs != GRAD_SYNC_EP:
            why = "ep-indivisible experts" \
                if self.n_experts % self.ep_size != 0 else \
                "batch-indivisible expert axis" \
                if "expert" not in self._dp_axes else \
                f"mode {self.mode!r} has no ep path"
            return f"{why} (dense dispatch, expert axis stays data)"
        return None

    def _grad_leaves(self, abstract_params):
        """Grad-tree leaves at sync width: f32 accumulators when
        ``microbatch > 1``, param dtype otherwise."""
        import jax.numpy as jnp

        leaves = jax.tree_util.tree_leaves(abstract_params)
        if self.microbatch > 1:
            leaves = [jax.ShapeDtypeStruct(l.shape, jnp.float32)
                      for l in leaves]
        return leaves

    def grad_buckets(self, abstract_params):
        """Reverse-layer size-targeted buckets over the grad tree, or None
        when this plan doesn't bucket (see :attr:`grad_sync`).

        With accumulation (``microbatch > 1``) the synced gradients are
        the f32 accumulators, not param-dtype arrays, so buckets are
        sized — and comm telemetry reported — at f32 widths."""
        if self.grad_sync != GRAD_SYNC_BUCKETED:
            return None
        from repro.distributed import gradsync

        return gradsync.partition_buckets(
            self._grad_leaves(abstract_params),
            bucket_mb=self.grad_bucket_mb)

    def scatter_plan(self, abstract_params):
        """The :class:`~repro.distributed.gradsync.FsdpBucketPlan` for a
        ``scatter_overlap`` run (all_gather/psum_scatter bucket layout +
        per-leaf shard dims), or None for every other strategy.  Sized at
        grad width like :meth:`grad_buckets`."""
        if self.grad_sync != GRAD_SYNC_SCATTER:
            return None
        from repro.distributed import gradsync

        return gradsync.partition_fsdp_buckets(
            self._grad_leaves(abstract_params), self.dp_size,
            bucket_mb=self.grad_bucket_mb)

    def scatter_param_specs(self, abstract_params):
        """Per-leaf ``PartitionSpec`` tree for the ``scatter_overlap``
        state layout: each leaf sharded over the dp axes on its
        :func:`~repro.distributed.gradsync.shard_dim` (first dim the dp
        size divides), replicated when no dim divides.  Used both as the
        ``shard_map`` in/out specs of the scatter step and (as
        ``NamedSharding``) for the runner's state placement — the two
        must agree, which is why they share this one builder."""
        from repro.distributed import gradsync

        axis = self._dp_axes if len(self._dp_axes) > 1 else \
            (self._dp_axes[0] if self._dp_axes else None)

        def one(leaf):
            d = gradsync.shard_dim(leaf, self.dp_size)
            if d is None or axis is None:
                return P()
            return P(*([None] * d), axis)

        return jax.tree_util.tree_map(one, abstract_params)

    # -- pipeline layout -------------------------------------------------
    def pipe_param_specs(self, abstract_params):
        """Per-leaf ``PartitionSpec`` tree of the pipeline state layout
        (block stack over ``pipe`` on the leading layers dim, everything
        else replicated); None for non-pipeline plans.  Shared between
        the staged step's shard_map specs and the runner's state
        placement — same single-builder rule as
        :meth:`scatter_param_specs`."""
        if not self._pipe_ok:
            return None
        from repro.distributed import pipeline

        return pipeline.stage_param_specs(abstract_params)

    def pipe_sync_plan(self, abstract_params):
        """The :class:`~repro.distributed.pipeline.PipeSyncPlan` of a
        ``pipe_overlap`` run: stage-local vs replicated grad buckets,
        sized at the STAGE-LOCAL f32 accumulator shapes (the executor
        always accumulates grads in f32), or None otherwise."""
        if not self._pipe_ok:
            return None
        import jax.numpy as jnp

        from repro.distributed import pipeline

        stage = set(pipeline.stage_param_leaf_indices(abstract_params))
        S = self.pp_size
        leaves = []
        for i, l in enumerate(jax.tree_util.tree_leaves(abstract_params)):
            shape = tuple(l.shape)
            if i in stage:
                shape = (shape[0] // S,) + shape[1:]
            leaves.append(jax.ShapeDtypeStruct(shape, jnp.float32))
        return pipeline.partition_pipe_buckets(
            leaves, sorted(stage & set(range(len(leaves)))),
            bucket_mb=self.grad_bucket_mb)

    # -- expert-parallel layout ------------------------------------------
    def _ep_expert_dims(self, axes_tree, abstract_params):
        """Tree (same structure as the params) of the per-leaf position
        of the ``experts`` logical dim the expert axis shards, or -1 for
        replicated leaves.  Driven by the logical-axes tree, same as
        :func:`tree_shardings` — the scan-stacked block leaves carry a
        leading ``layers`` dim, which ``axes.index`` skips naturally."""
        ep = self.ep_size

        def one(axes, leaf):
            if axes is not None and "experts" in axes:
                d = axes.index("experts")
                if leaf.shape[d] % ep == 0:
                    return d
            return -1

        return jax.tree_util.tree_map(
            one, axes_tree, abstract_params,
            is_leaf=lambda x: isinstance(x, tuple) or x is None)

    def ep_param_specs(self, axes_tree, abstract_params):
        """Per-leaf ``PartitionSpec`` tree of the ``ep_overlap`` state
        layout: each leaf with an ``experts`` logical dim sharded over
        ``expert`` on that dim, everything else replicated; None for
        non-ep plans.  Shared between the EP step's shard_map specs and
        the runner's state placement — same single-builder rule as
        :meth:`scatter_param_specs`."""
        if not self.ep_engaged:
            return None
        dims = self._ep_expert_dims(axes_tree, abstract_params)

        def one(d, leaf):
            if d < 0:
                return P()
            return P(*([None] * d), "expert")

        return jax.tree_util.tree_map(one, dims, abstract_params)

    def ep_sync_plan(self, axes_tree, abstract_params):
        """The grad-sync bucket layout of an ``ep_overlap`` run, reusing
        :class:`~repro.distributed.pipeline.PipeSyncPlan` with
        ``expert`` in the role of ``pipe``: expert-sharded leaves (at
        their LOCAL ``E/ep`` shapes) bucket separately and psum over the
        data axes only, replicated leaves psum over ``(expert,) +
        data``.  Sized at grad width like :meth:`grad_buckets`; None for
        non-ep plans."""
        if not self.ep_engaged:
            return None
        import jax.numpy as jnp

        from repro.distributed import pipeline

        ep = self.ep_size
        dims = jax.tree_util.tree_leaves(
            self._ep_expert_dims(axes_tree, abstract_params))
        leaves, expert_idx = [], []
        for i, (l, d) in enumerate(zip(
                jax.tree_util.tree_leaves(abstract_params), dims)):
            shape = tuple(l.shape)
            if d >= 0:
                shape = shape[:d] + (shape[d] // ep,) + shape[d + 1:]
                expert_idx.append(i)
            dt = jnp.float32 if self.microbatch > 1 else l.dtype
            leaves.append(jax.ShapeDtypeStruct(shape, dt))
        return pipeline.partition_pipe_buckets(
            leaves, expert_idx, bucket_mb=self.grad_bucket_mb)

    # -- tensor-parallel layout ------------------------------------------
    def _tp_shard_dims(self, axes_tree, abstract_params):
        """Tree (same structure as the params) of the per-leaf position
        of the tp-sharded logical dim (first of :data:`TP_LEAF_AXES`
        the model axis divides), or -1 for dense leaves.  Driven by the
        logical-axes tree like :meth:`_ep_expert_dims` — scan-stacked
        block leaves carry a leading ``layers`` dim, which the
        enumerate skips naturally."""
        ms = self.tp_size

        def one(axes, leaf):
            if axes is None:
                return -1
            for d, name in enumerate(axes):
                if name in TP_LEAF_AXES and leaf.shape[d] % ms == 0:
                    return d
            return -1

        return jax.tree_util.tree_map(
            one, axes_tree, abstract_params,
            is_leaf=lambda x: isinstance(x, tuple) or x is None)

    def tp_param_specs(self, axes_tree, abstract_params):
        """Per-leaf ``PartitionSpec`` tree of the ``tp_overlap`` state
        layout: leaves with a heads/kv_heads/ff logical dim sharded over
        ``model`` on that dim; under fsdp_tp the dense leaves are
        additionally ZeRO-3-sharded over the dp axes on their
        :func:`~repro.distributed.gradsync.shard_dim` (moments follow
        params); None for non-tp plans.  Shared between the tp step's
        shard_map specs and the runner's state placement — same
        single-builder rule as :meth:`scatter_param_specs`."""
        if not self.tp_engaged:
            return None
        from repro.distributed import gradsync

        dims = self._tp_shard_dims(axes_tree, abstract_params)
        fsdp = self.mode == "fsdp_tp" and self.dp_size > 1
        axis = self._dp_axes if len(self._dp_axes) > 1 else \
            (self._dp_axes[0] if self._dp_axes else None)

        def one(d, leaf):
            if d >= 0:
                return P(*([None] * d), "model")
            if fsdp and axis is not None:
                sd = gradsync.shard_dim(leaf, self.dp_size)
                if sd is not None:
                    return P(*([None] * sd), axis)
            return P()

        return jax.tree_util.tree_map(one, dims, abstract_params)

    def _tp_local_leaves(self, axes_tree, abstract_params):
        """(leaves, tp_indices): flat grad-width leaves at their
        model-LOCAL shapes plus the flat indices of the tp-sharded
        ones."""
        import jax.numpy as jnp

        ms = self.tp_size
        dims = jax.tree_util.tree_leaves(
            self._tp_shard_dims(axes_tree, abstract_params))
        leaves, tp_idx = [], []
        for i, (l, d) in enumerate(zip(
                jax.tree_util.tree_leaves(abstract_params), dims)):
            shape = tuple(l.shape)
            if d >= 0:
                shape = shape[:d] + (shape[d] // ms,) + shape[d + 1:]
                tp_idx.append(i)
            dt = jnp.float32 if self.microbatch > 1 else l.dtype
            leaves.append(jax.ShapeDtypeStruct(shape, dt))
        return leaves, tp_idx

    def tp_sync_plan(self, axes_tree, abstract_params):
        """The grad-sync bucket layout of a ``tp_overlap`` run, reusing
        :class:`~repro.distributed.pipeline.PipeSyncPlan` with
        ``model`` in the role of ``pipe``: tp-sharded leaves (at their
        LOCAL head/ff-sliced shapes) bucket separately and psum over
        the data axes only, dense leaves psum over ``('model',) +
        data``.  Sized at grad width like :meth:`grad_buckets`; None
        for non-tp plans."""
        if not self.tp_engaged:
            return None
        from repro.distributed import pipeline

        leaves, tp_idx = self._tp_local_leaves(axes_tree,
                                               abstract_params)
        return pipeline.partition_pipe_buckets(
            leaves, tp_idx, bucket_mb=self.grad_bucket_mb)

    def tp_scatter_plan(self, axes_tree, abstract_params):
        """The fsdp_tp composition's ZeRO-3 bucket layout: a
        :class:`~repro.distributed.gradsync.FsdpBucketPlan` over the dp
        axes with the tp-sharded leaves PINNED into the psum category —
        their grads are already correct after a plain psum over data
        (each model rank owns a distinct head/ff slice), and
        ``gather_fsdp_params`` passes psum-category leaves through
        untouched, so the model-axis sharding survives the scatter
        machinery.  Dense grads must be psum'd over ``('model',)``
        FIRST (the ``tp_sync_plan`` replicated buckets do that); then
        this plan's scatter/psum schedule over data applies.  None
        unless an fsdp_tp plan with real data parallelism engaged
        tp."""
        if not self.tp_engaged or self.mode != "fsdp_tp" \
                or self.dp_size <= 1:
            return None
        from repro.distributed import gradsync

        leaves, tp_idx = self._tp_local_leaves(axes_tree,
                                               abstract_params)
        return gradsync.partition_fsdp_buckets(
            leaves, self.dp_size, bucket_mb=self.grad_bucket_mb,
            pinned=tp_idx)

    # -- the merged, plan-driven spec builder ----------------------------
    def param_specs(self, axes_tree, abstract_params):
        """THE state-layout builder: one dispatch over the engaged
        strategy replaces the hand-paired ``tree_shardings`` /
        ``scatter_param_specs`` / ``stage_param_specs`` /
        ``ep_param_specs`` call sites — every caller (step shard_map
        specs, runner state placement, checkpoint restore) asks the
        plan once and gets the same per-leaf ``PartitionSpec`` tree:

        * ``pipe_overlap``  — block stack over ``pipe`` (leading
          layers dim), rest replicated;
        * ``ep_overlap``    — expert leaves over ``expert`` on their
          ``experts`` dim, rest replicated;
        * ``tp_overlap``    — heads/kv_heads/ff leaves over ``model``;
          under fsdp_tp dense leaves ZeRO-3 over the dp axes;
        * ``scatter_overlap`` — every dp-divisible leaf over the dp
          axes on its first divisible dim;
        * anything else     — fully replicated.
        """
        if self._pipe_ok:
            return self.pipe_param_specs(abstract_params)
        if self.ep_engaged:
            return self.ep_param_specs(axes_tree, abstract_params)
        if self.tp_engaged:
            return self.tp_param_specs(axes_tree, abstract_params)
        if self.grad_sync == GRAD_SYNC_SCATTER:
            return self.scatter_param_specs(abstract_params)
        return jax.tree_util.tree_map(lambda l: P(), abstract_params)

    def pipe_schedule_obj(self):
        """The static :class:`~repro.distributed.pipeline.PipeSchedule`
        tick table of this plan, or None when not pipelining."""
        if not self._pipe_ok:
            return None
        from repro.distributed import pipeline

        return pipeline.make_schedule(self.pp_schedule, self.pp_size,
                                      self.n_micro)

    def describe(self) -> Dict[str, Any]:
        """Flat summary for logs / telemetry."""
        out = {
            "mode": self.mode,
            "dp_axes": list(self._dp_axes),
            "dp_size": self.dp_size,
            "local_batch": self.local_batch,
            "microbatch": self.microbatch,
            "model_axis": self.model_axis,
            "grad_sync": self.grad_sync,
            "grad_bucket_mb": self.grad_bucket_mb,
        }
        if self.mode in ("pp", "pp_dp"):
            out.update(pp_stages=self.pp_size,
                       pp_schedule=self.pp_schedule if self._pipe_ok
                       else None,
                       pipe_engaged=self._pipe_ok)
        if self.has_moe or self.ep_size > 1:
            out.update(ep_engaged=self.ep_engaged, ep_size=self.ep_size,
                       n_experts=self.n_experts)
        if self.mode in ("tp", "fsdp_tp"):
            out.update(tp_engaged=self.tp_engaged, tp_size=self.tp_size)
        out["fallback_reason"] = self.fallback_reason
        return out
