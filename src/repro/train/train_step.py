"""pjit train / prefill / decode step builders.

``make_train_step`` returns a jit-able function with in/out shardings
derived from the sharding rules (DESIGN.md §5); this is the function the
multi-pod dry-run lowers and the trainer executes.

Gradient synchronization is dispatched through the
:class:`~repro.distributed.sharding.ParallelPlan`
(docs/parallelism.md):

* ``bucketed_overlap`` (ddp, dp>1) — the step runs inside ``shard_map``
  with replicated params and dp-sharded batch; each device computes local
  gradients (accumulated locally over microbatches) and
  ``gradsync.bucketed_psum`` issues one collective per reverse-layer
  bucket, so late-layer reduction overlaps early-layer backward.
* ``scatter_overlap`` (fsdp/fsdp_tp, dp>1) — params and optimizer state
  live sharded over the dp axes (ZeRO-3); the ``shard_map``'d step
  rebuilds full params with one ``all_gather`` per bucket in
  forward-layer order (prefetchable under the previous layer's
  matmuls) and reduces gradients straight back to shards with one
  ``psum_scatter`` per bucket during backward — half the gradient wire
  bytes of the ddp all-reduce.
* ``ep_overlap`` (ddp + MoE + ``expert`` mesh axis) — expert weights and
  their optimizer moments live sharded over ``expert`` on the
  ``experts`` dim; the batch shards over ``(data, expert)`` jointly.
  Inside the ``shard_map``'d step each MoE layer dispatches its tokens
  with a capacity-bucketed ``all_to_all`` over ``expert`` (the
  shared-expert FFN overlaps the exchange), expert-sharded gradients
  psum over the data axes only, and everything else reuses the
  bucketed-psum machinery over all dp axes.
* ``tp_overlap`` (tp / fsdp_tp, ``model`` axis > 1) — Megatron-style
  tensor parallelism with the activation collectives explicitly
  scheduled inside the ``shard_map``'d step: attention heads and the
  FFN hidden dim are column/row-partitioned over ``model``, the
  residual stream rides SEQUENCE-SHARDED between blocks, and each
  block's parallel region is entered with exactly one ``all_gather``
  and left with exactly one ``psum_scatter`` (see ``models/blocks.py``)
  — each collective depending only on its own sublayer, so it overlaps
  the adjacent sublayers' compute the same way the bucketed grad syncs
  overlap backward.  tp-sharded grads psum over data only, dense grads
  over ``('model',) + data`` (the pipeline sync with ``model`` in the
  role of ``pipe``); under fsdp_tp the dense leaves additionally live
  ZeRO-3-sharded over ``data`` and ride the scatter machinery with the
  tp leaves pinned into its psum category.
* ``xla_fused`` / ``none`` — the seed pjit path: the partitioner derives
  any collectives from the param/grad shardings.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, RunConfig, ShapeConfig
from repro.core.accum import accumulate_grads
from repro.core.mlm import lm_loss, mlm_loss
from repro.distributed import gradsync
from repro.distributed import pipeline as pipe
from repro.distributed import sharding as shd
from repro.distributed.sharding import (GRAD_SYNC_BUCKETED, GRAD_SYNC_EP,
                                        GRAD_SYNC_PIPE, GRAD_SYNC_SCATTER,
                                        GRAD_SYNC_TP, ParallelPlan)
from repro.models.attention import DistDecode
from repro.models.model import Model
from repro.train.optimizer import AdamWConfig, adamw_update, init_opt_state


def _act_dtype(run: RunConfig):
    return jnp.dtype(run.activation_dtype)


def _moe_ctx(model: Model, mesh: Optional[Mesh], run: RunConfig,
             global_batch: int):
    if model.cfg.moe is None:
        return None
    if mesh is None or run.sharding not in ("tp", "fsdp_tp") \
            or "model" not in mesh.axis_names:
        return {"impl": "dense"}
    return {
        "impl": "ep",
        "mesh": mesh,
        "batch_axes": shd.batch_axes(mesh, global_batch, run.sharding),
        "expert_axis": "model",
    }


LOSS_TARGET_BYTES = 512e6  # per-device f32 logits per loss block


def loss_chunk_len(global_batch: int, seq: int, vocab: int,
                   n_batch_shards: int) -> int:
    """The most seq positions a loss block may hold so per-device f32
    logits stay ~512MB (``loss_blocks`` tiles the rows with blocks of at
    most this length).  Chunking along SEQ preserves the batch sharding
    (chunking flattened global tokens would serialize the loss across
    devices)."""
    b_loc = max(1, global_batch // max(1, n_batch_shards))
    per_pos = b_loc * vocab * 4.0
    c = int(LOSS_TARGET_BYTES // per_pos)
    return max(8, min(seq, c))


def loss_blocks(rows: int, chunk: int) -> Tuple[int, int]:
    """``(n, c)``: ``n`` loss blocks of ``c <= chunk`` positions for
    ``rows`` positions.  The fewest blocks, up to twice the least number,
    that tile ``rows`` exactly; failing that, the least number of blocks
    of nearly equal length, the last padded by fewer than ``n``."""
    n = -(-rows // max(1, chunk))
    for m in range(n, 2 * n + 1):
        if rows % m == 0:
            return m, rows // m
    return n, -(-rows // n)


def loss_capacities(seq: int) -> Tuple[int, ...]:
    """The row capacities the loss head may run at: S/8, S/4 and S/2,
    each rounded up to a multiple of 8, then S itself."""
    up8 = lambda x: -(-x // 8) * 8
    caps = {min(seq, up8(-(-seq // k))) for k in (8, 4, 2)}
    return tuple(sorted(caps | {seq}))


def _compact_index(loss_mask):
    """Per row, the positions of its nonzero ``loss_mask`` entries in
    order, then the last position repeated, and which slots hold one of
    the row's entries."""
    S = loss_mask.shape[1]
    cs = jnp.cumsum(loss_mask != 0, axis=1, dtype=jnp.int32)
    slot = jnp.arange(S, dtype=jnp.int32)
    # slot k holds the position p with cs[p] == k + 1: the count of
    # positions before it, which all have cs <= k (S past the row's last)
    idx = (cs[:, None, :] <= slot[None, :, None]).sum(-1, dtype=jnp.int32)
    return jnp.minimum(idx, S - 1), idx < S


def chunked_xent(params, h, labels, loss_mask, cfg, *, chunk: int = 512,
                 use_pallas: bool = False):
    """Streaming loss: unembed + log-softmax one seq block at a time, never
    materializing the full (B, S, V) logits.  With ``use_pallas`` the
    per-block nll comes from the fused_xent Pallas kernel (no (c, V)
    log-prob temp at all); otherwise the jnp analogue.

    Only positions with a nonzero ``loss_mask`` count, so the head runs
    on those alone: each row's masked positions are gathered to its
    front, in order (the gather's transpose scatters the gradient back),
    and the rows are cut at ``loss_capacities(S)`` into segments, each a
    scan over ``loss_blocks`` of at most ``chunk``.  The segments up to
    the smallest capacity that holds every row's count run; those past
    it hold weight 0 only and are skipped (``lax.cond``).  When a count
    passes S/2 every segment runs: the sums are the same for any mask.
    Returns (sum_nll, sum_correct, denom, rows), ``rows`` the capacity
    taken over S."""
    from repro.models.transformer import head_apply

    B, S, d = h.shape
    caps = loss_capacities(S)
    zeros = (jnp.zeros((), jnp.float32),) * 3

    @jax.checkpoint
    def one(carry, xs):
        hb, lb, mb = xs
        logits = head_apply(params, hb, cfg)
        if use_pallas:
            from repro.kernels import ops as kops

            V = logits.shape[-1]
            with jax.named_scope("pallas_xent"):
                nll = kops.xent(logits.reshape(-1, V),
                                lb.reshape(-1)).reshape(lb.shape)
        else:
            lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            nll = -jnp.take_along_axis(lp, lb[..., None], axis=-1)[..., 0]
        acc = (logits.argmax(-1) == lb) * mb
        s_nll, s_acc, s_den = carry
        return (s_nll + (nll * mb).sum(), s_acc + acc.sum(),
                s_den + mb.sum()), None

    def segment(h, labels, loss_mask):
        n, c = loss_blocks(h.shape[1], chunk)
        pad = n * c - h.shape[1]
        if pad:
            h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
            labels = jnp.pad(labels, ((0, 0), (0, pad)))
            loss_mask = jnp.pad(loss_mask, ((0, 0), (0, pad)))
        xs = (
            h.reshape(B, n, c, d).transpose(1, 0, 2, 3),
            labels.reshape(B, n, c).transpose(1, 0, 2),
            loss_mask.reshape(B, n, c).transpose(1, 0, 2),
        )
        return jax.lax.scan(one, zeros, xs)[0]

    with jax.named_scope("loss_head"):
        need = (loss_mask != 0).sum(axis=1, dtype=jnp.int32).max()
        case = (need > jnp.asarray(caps[:-1], jnp.int32)).sum(
            dtype=jnp.int32)
        idx, held = _compact_index(loss_mask)
        take = lambda x: jnp.take_along_axis(
            x, idx if x.ndim == 2 else idx[..., None], axis=1,
            mode="promise_in_bounds")
        h, labels = take(h), take(labels)
        loss_mask = jnp.where(held, take(loss_mask), 0)
        sums = zeros
        for i, (a, b) in enumerate(zip((0,) + caps[:-1], caps)):
            part = (h[:, a:b], labels[:, a:b], loss_mask[:, a:b])
            got = segment(*part) if i == 0 else jax.lax.cond(
                case >= i, segment, lambda *_: zeros, *part)
            sums = tuple(x + y for x, y in zip(sums, got))
        rows = jnp.asarray(caps, jnp.float32)[case] / S
    return (*sums, rows)


def build_attn_ctx(cfg, mesh, run: RunConfig, global_batch: int,
                   seq_len: int):
    """Merged attention context: Pallas flash (when run.use_pallas) with
    context-parallel constraint fallback."""
    if mesh is None:
        return None
    ctx = {}
    if run.use_pallas:
        flash = shd.flash_attn_ctx(cfg, mesh, run.sharding, global_batch,
                                   seq_len)
        if flash is not None:
            ctx["flash"] = flash
    if "flash" not in ctx:
        # context-parallel q/score sharding (tp modes whose kv heads do
        # not divide the model axis); tests/test_multidevice.py checks it
        # against one device on CPU virtual devices
        cp = shd.attn_shard_ctx(cfg, mesh, run.sharding, global_batch,
                                seq_len)
        if cp is not None:
            ctx.update(cp)
    return ctx or None


def loss_for(model: Model, params, batch, *, run: RunConfig,
             mesh: Optional[Mesh] = None, constrain=None, shard_ctx=None,
             axis_names=None, dp_size: int = 1, moe_ctx=None,
             tp_ctx=None):
    """Loss + metrics.  Two calling modes:

    * Global (default): under pjit the reductions span the full batch —
      XLA inserts whatever collectives the sharding implies.
    * Per-shard (``axis_names`` set, inside ``shard_map``): the model runs
      on this device's batch shard only.  The returned *loss* is this
      shard's contribution ``local_nll / global_den + aux/dp_size``, built
      so that a plain SUM of per-device gradients equals the global-batch
      gradient exactly (the property ``gradsync.bucketed_psum`` relies
      on).  Only the data-dependent denominator is psum'd on the
      differentiated path; param-dependent cross-device reductions appear
      solely in the (undifferentiated) metrics, where their transpose
      never runs.  Metrics are globally reduced and replicated.

    ``moe_ctx`` overrides the derived MoE dispatch context wholesale
    (the ep_overlap step passes its ``ep_shard`` context here).  When
    derived in per-shard mode, the context gains ``stat_axes`` so the
    router's batch statistics are pmean'd to their global values — the
    Switch aux is nonlinear in those means, so this is what keeps
    sum-of-local-grads == global-grad for MoE (see ``route``).

    ``tp_ctx`` (tp_overlap step only, per-shard mode) switches the model
    to the sequence-parallel layout: the returned hidden is
    sequence-LOCAL, so the caller must pass ``labels``/``loss_mask``
    already sliced to this model rank's seq rows, and ``axis_names``
    must include ``model`` so the loss denominator spans the full
    sequence.
    """
    cfg = model.cfg
    if shard_ctx is None and mesh is not None:
        shard_ctx = build_attn_ctx(cfg, mesh, run,
                                   batch["tokens"].shape[0],
                                   batch["tokens"].shape[1])
    if moe_ctx is None:
        moe_ctx = _moe_ctx(model, mesh, run, batch["tokens"].shape[0])
        if moe_ctx is not None and axis_names is not None:
            moe_ctx = {**moe_ctx, "stat_axes": axis_names}
    labels = batch["labels"]
    mask = batch.get("loss_mask")
    if mask is None:
        mask = jnp.ones(labels.shape, jnp.float32)
    n_shards = 1
    if mesh is not None:
        import numpy as _np
        bax = shd.batch_axes(mesh, labels.shape[0], run.sharding)
        n_shards = int(_np.prod([mesh.shape[a] for a in bax])) if bax else 1
    c = loss_chunk_len(labels.shape[0], labels.shape[1], cfg.vocab_size,
                       n_shards)
    # what is differentiated runs under one named scope: on the device its
    # ops read jvp(step_forward) (forward), transpose(jvp(step_forward))
    # (backward) and .../rematted_computation/... (recomputation)
    with jax.named_scope("step_forward"):
        h, _, aux = model.apply(
            params, batch, mode="train", remat=run.remat,
            use_pallas=run.use_pallas, act_dtype=_act_dtype(run),
            moe_ctx=moe_ctx, tp_ctx=tp_ctx,
            constrain=constrain, return_hidden=True, shard_ctx=shard_ctx,
        )
        s_nll, s_acc, s_den, rows = chunked_xent(
            params, h, labels, mask, cfg, chunk=c,
            use_pallas=run.use_pallas)
    if axis_names is not None:
        # global denominator: mask-only, so safe inside value_and_grad
        # (its transpose never touches params)
        g_den = jax.lax.psum(s_den, axis_names)
        den = jnp.maximum(g_den, 1.0)
        loss = s_nll / den + aux / dp_size
        # metric reductions are dead-end branches for the cotangent
        g_nll, g_acc, g_aux, g_rows = jax.lax.psum(
            (s_nll, s_acc, aux, rows), axis_names)
        xent = g_nll / den
        metrics = {"xent": xent, "acc": g_acc / den, "tokens": g_den,
                   "loss_rows": g_rows / jax.lax.psum(1, axis_names),
                   "aux_loss": g_aux / dp_size,
                   "loss": xent + g_aux / dp_size}
        return loss, metrics
    den = jnp.maximum(s_den, 1.0)
    loss = s_nll / den
    metrics = {"xent": loss, "acc": s_acc / den, "tokens": s_den,
               "loss_rows": rows}
    loss = loss + aux
    metrics["aux_loss"] = aux
    metrics["loss"] = loss
    return loss, metrics


def make_train_step(model: Model, run: RunConfig, opt: AdamWConfig,
                    mesh: Optional[Mesh] = None,
                    seq_axis: Optional[str] = None,
                    plan: Optional[ParallelPlan] = None) -> Callable:
    """(state, batch) -> (state, metrics); state = {params, opt}.

    ``seq_axis='model'`` adds Megatron-style sequence parallelism to the
    inter-block activation constraint (fsdp_tp training).  ``plan``
    selects the gradient-sync strategy; by default it is derived from
    (run, mesh), which routes multi-shard ddp onto the
    bucketed/overlapped ``shard_map`` step."""
    if plan is None:
        plan = ParallelPlan.for_run(run, mesh)
    if plan.grad_sync == GRAD_SYNC_BUCKETED:
        return _make_overlap_ddp_step(model, run, opt, plan)
    if plan.grad_sync == GRAD_SYNC_SCATTER:
        return _make_scatter_fsdp_step(model, run, opt, plan)
    if plan.grad_sync == GRAD_SYNC_PIPE:
        return _make_pipeline_step(model, run, opt, plan)
    if plan.grad_sync == GRAD_SYNC_EP:
        return _make_ep_step(model, run, opt, plan)
    if plan.grad_sync == GRAD_SYNC_TP:
        return _make_tp_step(model, run, opt, plan)
    constrain = None
    if mesh is not None:
        constrain = shd.activation_sharding(
            mesh, run.shape.global_batch, run.sharding, seq_axis=seq_axis)

    def step(state, batch):
        def loss_fn(p, b):
            return loss_for(model, p, b, run=run, mesh=mesh,
                            constrain=constrain)

        loss, grads, metrics = accumulate_grads(
            loss_fn, state["params"], batch, run.microbatch or 1)
        new_params, new_opt, opt_metrics = adamw_update(
            opt, grads, state["opt"], state["params"])
        metrics = {**metrics, **opt_metrics}
        return {"params": new_params, "opt": new_opt}, metrics

    return step


def make_grad_fn(model: Model, run: RunConfig,
                 mesh: Optional[Mesh] = None,
                 plan: Optional[ParallelPlan] = None) -> Callable:
    """(params, batch) -> (loss, grads, metrics) under the plan's
    grad-sync strategy — the train step minus the optimizer update.

    This is the surface the equivalence tests and the ``grad_overlap``
    benchmark compare.  The bucketed path reproduces the fused reference
    gradients to float tolerance when the microbatches carry equal loss
    weight (always true for ``microbatch == 1``, and for any microbatch
    count with a uniform ``loss_mask``).  With ``microbatch > 1`` AND a
    ragged mask the two strategies partition rows into microbatches
    differently (global contiguous chunks vs per-shard slices), so the
    per-microbatch denominators — and therefore the 1/n-averaged
    gradients — are different token-weighted estimators of the same
    global batch; neither is "wrong", but they are not bitwise
    comparable.
    """
    if plan is None:
        plan = ParallelPlan.for_run(run, mesh)
    if plan.grad_sync == GRAD_SYNC_BUCKETED:
        accum, axis = _bucketed_accum(model, run, plan)

        def body(params, batch):
            loss, grads, metrics = accum(params, batch)
            # the accumulated loss is this shard's contribution; the
            # declared-replicated output must be the global value
            return jax.lax.psum(loss, axis), grads, metrics

        return jax.shard_map(
            body, mesh=plan.mesh,
            in_specs=(P(), _dp_batch_spec(plan)),
            out_specs=(P(), P(), P()), check_vma=False)
    if plan.grad_sync == GRAD_SYNC_SCATTER:
        accum, axis, _ = _scatter_accum(model, run, plan)
        pspecs = plan.scatter_param_specs(
            model.abstract(jnp.dtype(run.param_dtype)))

        def scatter_body(params, batch):
            loss, grads, metrics = accum(params, batch)
            return jax.lax.psum(loss, axis), grads, metrics

        # grads come out as shards; the P(dp)-on-shard-dim out specs
        # reassemble them into the full summed gradient tree, so callers
        # compare against the fused reference leaf-for-leaf
        return jax.shard_map(
            scatter_body, mesh=plan.mesh,
            in_specs=(pspecs, _dp_batch_spec(plan)),
            out_specs=(P(), pspecs, P()), check_vma=False)
    if plan.grad_sync == GRAD_SYNC_EP:
        accum, axis, _ = _ep_accum(model, run, plan)
        pspecs = plan.ep_param_specs(
            model.param_axes(),
            model.abstract(jnp.dtype(run.param_dtype)))

        def ep_body(params, batch):
            loss, grads, metrics = accum(params, batch)
            return jax.lax.psum(loss, axis), grads, metrics

        # expert grads come out as per-shard E/ep slices; the
        # P('expert')-on-experts out specs reassemble the full expert
        # gradient tree, so callers compare against the dense one-hot
        # oracle leaf-for-leaf
        return jax.shard_map(
            ep_body, mesh=plan.mesh,
            in_specs=(pspecs, _dp_batch_spec(plan)),
            out_specs=(P(), pspecs, P()), check_vma=False)
    if plan.grad_sync == GRAD_SYNC_TP:
        accum, axis, _, _ = _tp_accum(model, run, plan)
        pspecs = plan.param_specs(
            model.param_axes(),
            model.abstract(jnp.dtype(run.param_dtype)))

        def tp_body(params, batch):
            loss, grads, metrics = accum(params, batch)
            return jax.lax.psum(loss, axis), grads, metrics

        # tp grads come out as per-rank head/ff slices (and, under
        # fsdp_tp, dense grads as per-data-rank ZeRO-3 shards); the
        # P('model')/P(data)-on-shard-dim out specs reassemble the full
        # summed gradient tree, so callers compare against the fused
        # reference leaf-for-leaf
        return jax.shard_map(
            tp_body, mesh=plan.mesh,
            in_specs=(pspecs, _dp_batch_spec(plan)),
            out_specs=(P(), pspecs, P()), check_vma=False)
    if plan.grad_sync == GRAD_SYNC_PIPE:
        accum, _ = _pipeline_accum(model, run, plan)
        pspecs = plan.pipe_param_specs(
            model.abstract(jnp.dtype(run.param_dtype)))

        # grads come out stage-local; the P('pipe')-on-layers out specs
        # restack them into the full depth-L gradient tree, so callers
        # compare against the unpipelined reference leaf-for-leaf
        return jax.shard_map(
            accum, mesh=plan.mesh,
            in_specs=(pspecs, _dp_batch_spec(plan)),
            out_specs=(P(), pspecs, P()), check_vma=False)

    def grad_fn(params, batch):
        def loss_fn(p, b):
            return loss_for(model, p, b, run=run, mesh=mesh)

        return accumulate_grads(loss_fn, params, batch,
                                run.microbatch or 1)

    return grad_fn


def _axis_arg(dp_axes: Tuple[str, ...]):
    return dp_axes if len(dp_axes) > 1 else dp_axes[0]


def _dp_batch_spec(plan: ParallelPlan) -> P:
    """shard_map spec prefix for the batch dict: leading (batch) dim over
    the dp axes, everything else replicated (fully replicated for a
    pure-pp plan, whose batch rides whole into every stage column)."""
    if not plan.dp_axes:
        return P()
    return P(_axis_arg(plan.dp_axes))


def _bucketed_accum(model: Model, run: RunConfig, plan: ParallelPlan):
    """Shared core of the bucketed ddp paths (the train step and
    ``make_grad_fn`` must never drift apart): per-shard loss -> local
    microbatch accumulation -> one psum per reverse-layer bucket.
    Returns ``(accum(params, local_batch) -> (loss, grads, metrics),
    axis)``; ``accum`` must be called INSIDE shard_map over the plan's
    mesh, and its loss is this shard's contribution (grads and metrics
    are already globally reduced)."""
    axis = _axis_arg(plan.dp_axes)
    buckets = plan.grad_buckets(model.abstract(jnp.dtype(run.param_dtype)))

    def accum(params, batch):
        def loss_fn(p, b):
            return loss_for(model, p, b, run=run, mesh=None,
                            axis_names=axis, dp_size=plan.dp_size)

        return accumulate_grads(
            loss_fn, params, batch, run.microbatch or 1,
            sync_grads=lambda g: gradsync.bucketed_psum(g, axis, buckets))

    return accum, axis


def _make_overlap_ddp_step(model: Model, run: RunConfig, opt: AdamWConfig,
                           plan: ParallelPlan) -> Callable:
    """The bucketed/backward-overlapped ddp train step.

    The whole step — forward, backward, per-bucket psum, optimizer — runs
    inside one ``shard_map``: params and optimizer state are replicated
    (spec ``P()``), the batch is sharded over the plan's dp axes, and the
    only cross-device traffic is ``len(buckets)`` all-reduces whose
    operands become ready in reverse-layer order during backward.  Each
    device then applies the identical synced gradient, keeping replicas
    bit-equal without broadcasting parameters.
    """
    accum, _ = _bucketed_accum(model, run, plan)

    def body(state, batch):
        _, grads, metrics = accum(state["params"], batch)
        new_params, new_opt, opt_metrics = adamw_update(
            opt, grads, state["opt"], state["params"])
        metrics = {**metrics, **opt_metrics}
        return {"params": new_params, "opt": new_opt}, metrics

    return jax.shard_map(
        body, mesh=plan.mesh, in_specs=(P(), _dp_batch_spec(plan)),
        out_specs=(P(), P()), check_vma=False)


def _scatter_accum(model: Model, run: RunConfig, plan: ParallelPlan):
    """Shared core of the ``scatter_overlap`` (fsdp) paths: per-bucket
    all_gather rebuilds full params, per-shard loss -> local microbatch
    accumulation -> per-bucket psum_scatter back to grad shards.

    Returns ``(accum(local_params, local_batch) -> (loss, grads,
    metrics), axis, scatter_plan)``.  ``accum`` must be called INSIDE
    shard_map over the plan's mesh; ``grads`` come back in the sharded
    state layout (shard-shaped leaves for scatterable indices, full
    synced leaves for the replicated remainder), ``loss`` is this
    shard's contribution, metrics are globally reduced.

    The gather runs once per step, OUTSIDE the microbatch scan — full
    params persist across microbatches, and the scatter runs once, on
    the final accumulated gradients.  ``plan.free_after_use`` flips the
    trade: the (checkpointed) gather moves INSIDE each microbatch's vjp,
    so full-width params are gathered on entry, freed after use, and
    re-gathered during backward instead of held live across the step —
    peak temp memory drops by about the gathered tree, gather wire runs
    ``2 x n_micro`` per step.  The ``fsdp_overlap`` benchmark reports
    both sides so the flip point is measured, not guessed.

    With ``plan.donate_gather`` (default, engages when there is no
    microbatch accumulation) the step differentiates FROM THE SHARDS
    instead: the bucketed gather sits inside the vjp, and its linear
    transpose is exactly one ``psum_scatter`` per bucket — same
    collectives, same reverse-layer overlap order — so backward's
    full-width gradient buffers are handed straight to the scatter as
    each bucket's cotangents complete and the full-size (f32) gradient
    tree is never materialized: peak temp memory drops by about that
    tree.  Wire volume is unchanged (one gather forward, one scatter
    backward).  With accumulation the path is skipped — a per-microbatch
    gather would multiply the forward wire volume by ``n_micro`` (the
    per-layer-regather trade, tracked in ROADMAP).  The ``fsdp_overlap``
    benchmark reports the measured peak-memory delta.
    """
    axis = _axis_arg(plan.dp_axes)
    sp = plan.scatter_plan(model.abstract(jnp.dtype(run.param_dtype)))
    n_micro = run.microbatch or 1
    gather = lambda lp: gradsync.gather_fsdp_params(
        lp, axis, sp, free_after_use=plan.free_after_use)

    if plan.donate_gather and n_micro == 1:
        def accum(local_params, batch):
            def loss_sh(lp, b):
                return loss_for(model, gather(lp), b, run=run, mesh=None,
                                axis_names=axis, dp_size=plan.dp_size)

            (loss, metrics), grads = jax.value_and_grad(
                loss_sh, has_aux=True)(local_params, batch)
            # scatter leaves arrived shard-shaped and summed (the
            # gather's transpose); only the replicated remainder still
            # needs its plain-psum buckets
            grads = gradsync.bucketed_psum(grads, axis, sp.psum)
            return loss, grads, metrics

        return accum, axis, sp

    if plan.free_after_use:
        # per-microbatch regather: differentiate FROM THE SHARDS with
        # the checkpointed gather inside the vjp, so each microbatch
        # gathers its params on entry, re-gathers during backward
        # (``jax.checkpoint`` drops the gathered tree from the residual
        # set), and the gather's transpose psum_scatters the cotangents
        # straight back to shards.  Peak memory holds about one
        # bucket's full params; gather wire runs 2 x n_micro per step.
        def accum(local_params, batch):
            def loss_sh(lp, b):
                return loss_for(model, gather(lp), b, run=run, mesh=None,
                                axis_names=axis, dp_size=plan.dp_size)

            return accumulate_grads(
                loss_sh, local_params, batch, n_micro,
                sync_grads=lambda g: gradsync.bucketed_psum(
                    g, axis, sp.psum))

        return accum, axis, sp

    def accum(local_params, batch):
        full_params = gather(local_params)

        def loss_fn(p, b):
            return loss_for(model, p, b, run=run, mesh=None,
                            axis_names=axis, dp_size=plan.dp_size)

        return accumulate_grads(
            loss_fn, full_params, batch, n_micro,
            sync_grads=lambda g: gradsync.bucketed_psum_scatter(
                g, axis, sp))

    return accum, axis, sp


def _make_scatter_fsdp_step(model: Model, run: RunConfig, opt: AdamWConfig,
                            plan: ParallelPlan) -> Callable:
    """The overlap-scheduled fsdp (ZeRO-3) train step.

    Params and optimizer moments live SHARDED over the dp axes (each
    leaf split on its first dp-divisible dim; see
    ``ParallelPlan.scatter_param_specs``).  Inside one ``shard_map``:
    per-bucket ``all_gather`` rebuilds full params in forward-layer
    order (each gather independent — the layer-ahead prefetch handle),
    backward produces full local grads, and per-bucket ``psum_scatter``
    in reverse-layer order reduces them straight back to shards — half
    the gradient wire bytes of the ddp all-reduce.  The optimizer then
    updates only this device's shard of params/mu/nu (the grad-norm is
    assembled via one scalar psum so clipping matches the fused path).
    """
    accum, axis, sp = _scatter_accum(model, run, plan)
    pspecs = plan.scatter_param_specs(
        model.abstract(jnp.dtype(run.param_dtype)))
    state_spec = {"params": pspecs,
                  "opt": {"mu": pspecs, "nu": pspecs, "step": P()}}

    def body(state, batch):
        _, grads, metrics = accum(state["params"], batch)
        gnorm = gradsync.fsdp_global_norm(grads, axis, sp)
        new_params, new_opt, opt_metrics = adamw_update(
            opt, grads, state["opt"], state["params"], grad_norm=gnorm)
        metrics = {**metrics, **opt_metrics}
        return {"params": new_params, "opt": new_opt}, metrics

    return jax.shard_map(
        body, mesh=plan.mesh,
        in_specs=(state_spec, _dp_batch_spec(plan)),
        out_specs=(state_spec, P()), check_vma=False)


# ---------------------------------------------------------------------------
# Expert-parallel step (ep_overlap: models/moe.py all_to_all dispatch)
# ---------------------------------------------------------------------------


def _ep_accum(model: Model, run: RunConfig, plan: ParallelPlan):
    """Shared core of the ``ep_overlap`` paths (train step and
    ``make_grad_fn``): per-shard loss with ``ep_shard`` MoE dispatch ->
    local microbatch accumulation -> split grad sync.  Expert-sharded
    leaves (local ``E/ep`` slices) psum over the data axes only — their
    expert slice lives on exactly this expert rank — while everything
    else rides the bucketed psum over all dp axes; structurally the
    pipeline sync with ``expert`` in the role of ``pipe``, so it reuses
    :func:`pipe.pipe_grad_sync` wholesale.  Returns ``(accum(params,
    local_batch) -> (loss, grads, metrics), axis, sync_plan)``;
    ``accum`` must run INSIDE shard_map over the plan's mesh."""
    axis = _axis_arg(plan.dp_axes)
    abstract = model.abstract(jnp.dtype(run.param_dtype))
    sp = plan.ep_sync_plan(model.param_axes(), abstract)
    moe_ctx = {"impl": "ep_shard", "expert_axis": "expert",
               "n_shards": plan.ep_size, "stat_axes": axis,
               "overlap": plan.ep_overlap_dispatch}

    def accum(params, batch):
        def loss_fn(p, b):
            return loss_for(model, p, b, run=run, mesh=None,
                            axis_names=axis, dp_size=plan.dp_size,
                            moe_ctx=moe_ctx)

        return accumulate_grads(
            loss_fn, params, batch, run.microbatch or 1,
            sync_grads=lambda g: pipe.pipe_grad_sync(
                g, sp, "expert", plan.ep_data_axes))

    return accum, axis, sp


def _make_ep_step(model: Model, run: RunConfig, opt: AdamWConfig,
                  plan: ParallelPlan) -> Callable:
    """The expert-parallel (ep_overlap) train step.

    Expert weights — and their Adam moments — live SHARDED over
    ``expert`` on the ``experts`` dim (``ParallelPlan.ep_param_specs``;
    router / shared experts / everything else replicated), and the
    batch shards over ``(data, expert)`` jointly, so the expert axis
    pulls double duty: batch width in attention / dense compute, expert
    width inside each MoE layer's ``all_to_all`` dispatch.  Inside one
    ``shard_map``: each MoE layer scatters its local tokens into
    capacity buffers, exchanges them over ``expert`` (overlapping the
    shared-expert FFN), runs its local experts, and combines; the
    optimizer updates only this rank's expert slice with a
    globally-assembled clipping norm.
    """
    accum, _, sp = _ep_accum(model, run, plan)
    pspecs = plan.ep_param_specs(
        model.param_axes(), model.abstract(jnp.dtype(run.param_dtype)))
    state_spec = {"params": pspecs,
                  "opt": {"mu": pspecs, "nu": pspecs, "step": P()}}

    def body(state, batch):
        _, grads, metrics = accum(state["params"], batch)
        gnorm = pipe.pipe_global_norm(grads, sp, "expert")
        new_params, new_opt, opt_metrics = adamw_update(
            opt, grads, state["opt"], state["params"], grad_norm=gnorm)
        metrics = {**metrics, **opt_metrics}
        return {"params": new_params, "opt": new_opt}, metrics

    return jax.shard_map(
        body, mesh=plan.mesh,
        in_specs=(state_spec, _dp_batch_spec(plan)),
        out_specs=(state_spec, P()), check_vma=False)


# ---------------------------------------------------------------------------
# Tensor-parallel step (tp_overlap: models/blocks.py gather/scatter schedule)
# ---------------------------------------------------------------------------


def _tp_ctx(plan: ParallelPlan, seq_len: int):
    """The explicitly-scheduled TP collective context threaded into
    ``apply_block`` (must run inside ``shard_map`` over a mesh carrying
    ``model``).  Activations between blocks are sequence-sharded —
    (B, S/ms, d) — so each parallel region costs exactly one tiled
    ``all_gather`` in (full-seq activations from shards) and one tiled
    ``psum_scatter`` out (reducing the partial sublayer outputs over
    ``model`` AND re-sharding the sequence in the same collective — the
    Megatron sequence-parallel identity that replaces an all-reduce +
    slice).  Returns ``(tp_ctx, slice_seq)``; ``slice_seq`` also cuts
    labels/masks to this rank's rows."""
    s_loc = seq_len // plan.tp_size

    def slice_seq(x):
        start = jax.lax.axis_index("model") * s_loc
        return jax.lax.dynamic_slice_in_dim(x, start, s_loc, axis=1)

    ctx = {
        "gather": lambda x: jax.lax.all_gather(
            x, "model", axis=1, tiled=True),
        "scatter": lambda x: jax.lax.psum_scatter(
            x, "model", scatter_dimension=1, tiled=True),
        "slice_seq": slice_seq,
    }
    return ctx, slice_seq


def _tp_accum(model: Model, run: RunConfig, plan: ParallelPlan):
    """Shared core of the ``tp_overlap`` paths (train step and
    ``make_grad_fn``): sequence-parallel per-shard loss (labels sliced
    to this model rank's rows, denominator psum'd over data AND
    ``model``) -> local microbatch accumulation -> split grad sync.
    tp-sharded leaves (local head/ff slices) psum over the data axes
    only — structurally the pipeline sync with ``model`` in the role of
    ``pipe`` — while dense leaves psum over ``('model',) + data``.

    Under fsdp_tp with real data parallelism the dense leaves
    additionally live ZeRO-3-sharded over data: forward rebuilds them
    with the bucketed ``all_gather`` (tp leaves pass through untouched
    — they are pinned into the scatter plan's psum category), and the
    backward sync composes the model-axis psum (dense leaves) with the
    data-axis ``psum_scatter`` back to shards.

    Returns ``(accum(params, local_batch) -> (loss, grads, metrics),
    axis, tp_sp, fsdp_plan)``; ``fsdp_plan`` is None for the pure-tp
    (replicated-dense) variant.  ``accum`` must run INSIDE shard_map
    over the plan's mesh."""
    axes = plan.dp_axes + ("model",)
    axis = _axis_arg(axes)
    abstract = model.abstract(jnp.dtype(run.param_dtype))
    axes_tree = model.param_axes()
    sp = plan.tp_sync_plan(axes_tree, abstract)
    fsdp = plan.tp_scatter_plan(axes_tree, abstract)
    ctx, slice_seq = _tp_ctx(plan, run.shape.seq_len)
    n_micro = run.microbatch or 1
    # every device (dp x model) adds aux/n once; non-MoE models (the
    # only ones tp engages for) have aux == 0, but keep the count honest
    n_dev = plan.dp_size * plan.tp_size

    def loss_fn(p, b):
        bl = dict(b)
        bl["labels"] = slice_seq(b["labels"])
        if b.get("loss_mask") is not None:
            bl["loss_mask"] = slice_seq(b["loss_mask"])
        return loss_for(model, p, bl, run=run, mesh=None,
                        axis_names=axis, dp_size=n_dev, tp_ctx=ctx)

    if fsdp is None:
        def accum(params, batch):
            return accumulate_grads(
                loss_fn, params, batch, n_micro,
                sync_grads=lambda g: pipe.pipe_grad_sync(
                    g, sp, "model", plan.dp_axes))

        return accum, axis, sp, None

    data_axis = _axis_arg(plan.dp_axes)

    def sync(g):
        # dense grads to their model-summed values first (tp buckets are
        # skipped — empty dp_axes arg), then the ZeRO-3 scatter over
        # data; pinned tp leaves ride its psum buckets, which IS their
        # remaining data-axis sync
        g = pipe.pipe_grad_sync(g, sp, "model", ())
        return gradsync.bucketed_psum_scatter(g, data_axis, fsdp)

    def accum(local_params, batch):
        full = gradsync.gather_fsdp_params(
            local_params, data_axis, fsdp,
            free_after_use=plan.free_after_use)

        return accumulate_grads(loss_fn, full, batch, n_micro,
                                sync_grads=sync)

    return accum, axis, sp, fsdp


def _tp_global_norm(grads, plan: ParallelPlan, sp, fsdp) -> jnp.ndarray:
    """Global L2 norm of a synced ``tp_overlap`` grad tree.  Pure tp is
    exactly the pipeline norm with ``model`` as the pipe axis.  fsdp_tp
    needs the three-way split: ZeRO-3 dense leaves are disjoint shards
    across DATA ranks (psum over data), tp leaves disjoint slices
    across MODEL ranks (psum over model), and the un-shardable dense
    remainder is identical everywhere (counted once)."""
    if fsdp is None:
        return pipe.pipe_global_norm(grads, sp, "model")
    leaves = jax.tree_util.tree_leaves(grads)
    tp = set(sp.stage_indices)
    sc = set(fsdp.scatter_indices)
    sq = lambda x: jnp.sum(jnp.square(x.astype(jnp.float32)))
    z = jnp.zeros((), jnp.float32)
    sq_tp = sum((sq(l) for i, l in enumerate(leaves) if i in tp), z)
    sq_sc = sum((sq(l) for i, l in enumerate(leaves) if i in sc), z)
    sq_rep = sum((sq(l) for i, l in enumerate(leaves)
                  if i not in tp and i not in sc), z)
    data_axis = _axis_arg(plan.dp_axes)
    return jnp.sqrt(jax.lax.psum(sq_tp, "model")
                    + jax.lax.psum(sq_sc, data_axis) + sq_rep)


def _make_tp_step(model: Model, run: RunConfig, opt: AdamWConfig,
                  plan: ParallelPlan) -> Callable:
    """The tensor-parallel (tp_overlap) train step.

    Attention q/k/v/o and the FFN up/down projections live SHARDED over
    ``model`` on their heads / kv_heads / ff logical dims — Adam moments
    alike, so each model rank stores and updates only its slice
    (``ParallelPlan.tp_param_specs``); under fsdp_tp the dense remainder
    is additionally ZeRO-3-sharded over ``data``.  Inside one
    ``shard_map``: activations ride sequence-sharded between blocks,
    each sublayer gathers the full sequence on entry and
    reduce-scatters its partial output on exit (one collective each
    way, overlapping adjacent compute), grads take the split
    model/data psum schedule, and the optimizer updates rank-local
    state with a globally-assembled clipping norm.
    """
    accum, _, sp, fsdp = _tp_accum(model, run, plan)
    pspecs = plan.param_specs(
        model.param_axes(), model.abstract(jnp.dtype(run.param_dtype)))
    state_spec = {"params": pspecs,
                  "opt": {"mu": pspecs, "nu": pspecs, "step": P()}}

    def body(state, batch):
        _, grads, metrics = accum(state["params"], batch)
        gnorm = _tp_global_norm(grads, plan, sp, fsdp)
        new_params, new_opt, opt_metrics = adamw_update(
            opt, grads, state["opt"], state["params"], grad_norm=gnorm)
        metrics = {**metrics, **opt_metrics}
        return {"params": new_params, "opt": new_opt}, metrics

    return jax.shard_map(
        body, mesh=plan.mesh,
        in_specs=(state_spec, _dp_batch_spec(plan)),
        out_specs=(state_spec, P()), check_vma=False)


# ---------------------------------------------------------------------------
# Pipeline-parallel step (pp / pp_dp: distributed/pipeline.py)
# ---------------------------------------------------------------------------


def _pipeline_parts(model: Model, run: RunConfig, plan: ParallelPlan):
    """The model-side callables of the staged executor: ``stage_fwd``
    runs embed (first stage only, selected by the traced flag) plus this
    rank's contiguous slice of the block stack — the same scanned
    ``apply_group`` as the unpipelined forward, over a ``ScheduleGroup``
    whose ``repeats`` is the per-stage depth — and ``stage_loss``
    computes final-norm + chunked xent pieces (real on the last stage,
    masked junk elsewhere).  Returns ``(stage_fwd, stage_loss,
    act_shape, act_dtype)``; ``act_shape`` is the (microbatch, seq,
    d_model) boundary-activation buffer both ppermute directions move.
    """
    from repro.configs.base import ScheduleGroup
    from repro.models.blocks import apply_group
    from repro.models.layers import add_positions, apply_norm, embed_tokens

    cfg = model.cfg
    g0 = cfg.schedule[0]
    local_group = ScheduleGroup(pattern=g0.pattern,
                                repeats=plan.stage_layers)
    act_dtype = _act_dtype(run)
    causal = cfg.family != "encoder"
    chunk = loss_chunk_len(plan.global_batch, run.shape.seq_len,
                           cfg.vocab_size,
                           max(1, plan.dp_size * plan.n_micro))

    def stage_fwd(params, x_recv, mb, is_first):
        toks = mb["tokens"]
        positions = jnp.arange(toks.shape[1], dtype=jnp.int32)[None]
        with jax.named_scope("step_forward"):
            h = embed_tokens(params["embed"], toks, cfg, act_dtype)
            h = add_positions(params["embed"], h, positions, cfg)
            h = jnp.where(is_first, h, x_recv)
            h, _, _ = apply_group(
                params["groups"][0], None, h, cfg, local_group,
                positions=positions, mode="train", causal=causal,
                remat=run.remat, use_pallas=run.use_pallas)
        return h

    def stage_loss(params, y, mb):
        mask = mb.get("loss_mask")
        if mask is None:
            mask = jnp.ones(mb["labels"].shape, jnp.float32)
        with jax.named_scope("step_forward"):
            h = apply_norm(params["final_norm"], y, cfg)
            return chunked_xent(params, h, mb["labels"], mask, cfg,
                                chunk=chunk, use_pallas=run.use_pallas)[:3]

    rows = plan.local_batch // plan.n_micro
    act_shape = (rows, run.shape.seq_len, cfg.d_model)
    return stage_fwd, stage_loss, act_shape, act_dtype


def _pipeline_accum(model: Model, run: RunConfig, plan: ParallelPlan):
    """Shared core of the pipeline paths (train step and
    ``make_grad_fn``): staged executor -> data-axis bucketed sync ->
    pipe-axis replicated sync.  Returns ``(accum(params, local_batch) ->
    (loss, synced_grads, metrics), sync_plan)``; ``accum`` must run
    INSIDE shard_map over the plan's mesh, and its grads are fully
    summed (global) values in the stage-local layout."""
    abstract = model.abstract(jnp.dtype(run.param_dtype))
    sched = plan.pipe_schedule_obj()
    sp = plan.pipe_sync_plan(abstract)
    stage_fwd, stage_loss, act_shape, act_dtype = \
        _pipeline_parts(model, run, plan)

    def accum(params, batch):
        loss, grads, metrics = pipe.pipeline_grads(
            sched, params, batch, stage_fwd=stage_fwd,
            stage_loss=stage_loss, act_shape=act_shape,
            act_dtype=act_dtype, dp_axes=plan.dp_axes)
        grads = pipe.pipe_grad_sync(grads, sp, "pipe", plan.dp_axes)
        return loss, grads, metrics

    return accum, sp


def _make_pipeline_step(model: Model, run: RunConfig, opt: AdamWConfig,
                        plan: ParallelPlan) -> Callable:
    """The pipeline-parallel (GPipe / 1F1B) train step.

    The block stack lives SHARDED over ``pipe`` on its leading layers
    dim — params and Adam moments alike, so each rank stores and
    updates only its stage (``ParallelPlan.pipe_param_specs``; embed /
    final-norm / head replicated).  Inside one ``shard_map``: the
    staged executor streams microbatches through the stages with
    ``ppermute`` activation/cotangent transfers, within-stage gradients
    reuse the bucketed data-axis psum, replicated leaves add one
    pipe-inclusive psum, and the optimizer updates stage-local state
    with a globally-assembled clipping norm.
    """
    accum, sp = _pipeline_accum(model, run, plan)
    abstract = model.abstract(jnp.dtype(run.param_dtype))
    pspecs = plan.pipe_param_specs(abstract)
    state_spec = {"params": pspecs,
                  "opt": {"mu": pspecs, "nu": pspecs, "step": P()}}

    def body(state, batch):
        _, grads, metrics = accum(state["params"], batch)
        gnorm = pipe.pipe_global_norm(grads, sp, "pipe")
        new_params, new_opt, opt_metrics = adamw_update(
            opt, grads, state["opt"], state["params"], grad_norm=gnorm)
        metrics = {**metrics, **opt_metrics}
        return {"params": new_params, "opt": new_opt}, metrics

    return jax.shard_map(
        body, mesh=plan.mesh,
        in_specs=(state_spec, _dp_batch_spec(plan)),
        out_specs=(state_spec, P()), check_vma=False)


# ---------------------------------------------------------------------------
# Sharding trees for jit in/out_shardings
# ---------------------------------------------------------------------------


def param_shardings(model: Model, mesh: Mesh, run: RunConfig):
    drop = ("kv_heads", "head_dim") if run.replicate_kv else ()
    return shd.tree_shardings(
        model.param_axes(), model.abstract(jnp.dtype(run.param_dtype)),
        mesh, run.sharding, drop_axes=drop)


def state_shardings(model: Model, mesh: Mesh, run: RunConfig,
                    plan: Optional[ParallelPlan] = None):
    """NamedSharding tree for the train state ``{params, opt}``.

    Default: the mode's logical-axis rules (``param_shardings``) applied
    to params and moments alike.  Under a ``scatter_overlap`` plan the
    layout is instead the plan's shard-dim split (every dp-divisible
    leaf sharded over the dp axes), matching the shard_map in/out specs
    of the scatter step — optimizer state included, so each device
    stores and updates only its 1/dp slice (ZeRO-3).  Under a
    ``pipe_overlap`` plan it is the stage layout: block-stack leaves
    (and their moments) split over ``pipe`` on the layers dim.  Under an
    ``ep_overlap`` plan it is the expert layout: leaves with an
    ``experts`` logical dim (and their moments) split over ``expert``
    on that dim, the rest replicated.  Under a ``tp_overlap`` plan it
    is the merged tp layout (``ParallelPlan.param_specs``): heads /
    kv_heads / ff leaves split over ``model``, and — for fsdp_tp with
    real data parallelism — the dense remainder ZeRO-3-sharded over
    the dp axes."""
    if plan is not None and plan.grad_sync == GRAD_SYNC_SCATTER:
        specs = plan.scatter_param_specs(
            model.abstract(jnp.dtype(run.param_dtype)))
        p_sh = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), specs)
    elif plan is not None and plan.grad_sync == GRAD_SYNC_PIPE:
        specs = plan.pipe_param_specs(
            model.abstract(jnp.dtype(run.param_dtype)))
        p_sh = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), specs)
    elif plan is not None and plan.grad_sync == GRAD_SYNC_EP:
        specs = plan.ep_param_specs(
            model.param_axes(),
            model.abstract(jnp.dtype(run.param_dtype)))
        p_sh = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), specs)
    elif plan is not None and plan.grad_sync == GRAD_SYNC_TP:
        specs = plan.param_specs(
            model.param_axes(),
            model.abstract(jnp.dtype(run.param_dtype)))
        p_sh = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), specs)
    else:
        p_sh = param_shardings(model, mesh, run)
    return {
        "params": p_sh,
        "opt": {"mu": p_sh, "nu": p_sh,
                "step": NamedSharding(mesh, P())},
    }


def batch_shardings(model: Model, mesh: Mesh, run: RunConfig,
                    shape: ShapeConfig,
                    plan: Optional[ParallelPlan] = None):
    """NamedSharding per batch leaf.  When a ``plan`` is given its own
    dp axes are used (an engaged pipeline replicates the batch across
    stages — the module-level mode-string recompute can't know that)."""
    bspec = plan.batch_spec() if plan is not None \
        else shd.batch_spec(mesh, shape.global_batch, run.sharding)
    ns = lambda ndim: NamedSharding(
        mesh, P(bspec[0], *([None] * (ndim - 1))))
    specs = model.input_specs(shape, act_dtype=_act_dtype(run))
    out = {}
    for k, v in specs.items():
        out[k] = NamedSharding(mesh, P()) if v.ndim == 0 else ns(v.ndim)
    return out


def abstract_state(model: Model, run: RunConfig):
    params = model.abstract(jnp.dtype(run.param_dtype))
    f32 = lambda t: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32), t)
    return {
        "params": params,
        "opt": {"mu": f32(params), "nu": f32(params),
                "step": jax.ShapeDtypeStruct((), jnp.int32)},
    }


def init_state(model: Model, key, run: RunConfig):
    params = model.init(key, jnp.dtype(run.param_dtype))
    return {"params": params, "opt": init_opt_state(params)}


# ---------------------------------------------------------------------------
# Serve steps (prefill / decode)
# ---------------------------------------------------------------------------


def make_prefill_step(model: Model, run: RunConfig,
                      mesh: Optional[Mesh] = None) -> Callable:
    def prefill(params, batch):
        shard_ctx = build_attn_ctx(model.cfg, mesh, run,
                                   batch["tokens"].shape[0],
                                   batch["tokens"].shape[1])
        constrain = None
        if run.seq_parallel_serve and mesh is not None \
                and "model" in mesh.axis_names \
                and batch["tokens"].shape[1] % mesh.shape["model"] == 0:
            constrain = shd.activation_sharding(
                mesh, batch["tokens"].shape[0], run.sharding,
                seq_axis="model")
        logits, cache = model.prefill(
            params, batch, use_pallas=run.use_pallas,
            act_dtype=_act_dtype(run),
            moe_ctx=_moe_ctx(model, mesh, run, batch["tokens"].shape[0]),
            shard_ctx=shard_ctx, constrain=constrain,
        )
        return logits, cache

    return prefill


def make_decode_step(model: Model, run: RunConfig,
                     mesh: Optional[Mesh] = None,
                     dist_cache: bool = False,
                     global_batch: Optional[int] = None) -> Callable:
    dist = None
    if dist_cache and mesh is not None:
        dist = DistDecode(
            axes=shd.cache_seq_axes(mesh, global_batch or 1),
            batch_axes=shd.cache_batch_axes(mesh, global_batch or 1),
            mesh=mesh,
        )

    def decode(params, cache, tokens, pos):
        batch = {"tokens": tokens, "pos": pos}
        logits, new_cache, _ = model.apply(
            params, batch, mode="decode", cache=cache,
            act_dtype=_act_dtype(run), dist=dist,
            moe_ctx=_moe_ctx(model, mesh, run, tokens.shape[0]),
        )
        return logits, new_cache

    return decode


def make_paged_prefill_step(model: Model, run: RunConfig) -> Callable:
    """Bucketed prefill for the paged engine: ``tokens`` is ONE prompt
    right-padded to a bucket length, ``length`` its true length (dynamic,
    so one compile per bucket shape serves every prompt in the bucket).
    Returns (last-real-position logits, prefill cache)."""
    from repro.models.transformer import head_apply

    def prefill(params, tokens, length):
        h, cache, _ = model.apply(
            params, {"tokens": tokens}, mode="prefill",
            use_pallas=run.use_pallas, act_dtype=_act_dtype(run),
            moe_ctx=_moe_ctx(model, None, run, tokens.shape[0]),
            return_hidden=True, paged={"length": length},
        )
        h_last = jax.lax.dynamic_slice_in_dim(h, length - 1, 1, axis=1)
        return head_apply(params, h_last, model.cfg), cache

    return prefill


def make_paged_decode_step(model: Model, run: RunConfig, page: int,
                           use_pallas: Optional[bool] = None) -> Callable:
    """One continuous-batching decode tick at a FIXED batch shape
    (``max_slots`` rows, inactive rows write the trash page): pools are
    the paged KV pools, ``positions`` is (B,) per-slot, ``tables`` the
    (B, max_pages) block tables.  Jit with the pools donated — every
    input shape is constant for the engine's lifetime, so the step never
    recompiles after warmup."""
    up = run.use_pallas if use_pallas is None else use_pallas

    def decode(params, pools, tokens, positions, tables):
        paged = {"tables": tables, "page": page, "use_pallas": up}
        batch = {"tokens": tokens, "pos": positions}
        logits, new_pools, _ = model.apply(
            params, batch, mode="decode", cache=pools,
            act_dtype=_act_dtype(run), paged=paged,
            moe_ctx=_moe_ctx(model, None, run, tokens.shape[0]),
        )
        return logits, new_pools

    return decode
