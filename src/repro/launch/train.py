"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch bert-mlm-120m \
      --steps 200 --batch 16 --seq 128 [--reduced] [--workers 2] \
      [--ckpt-dir runs/ck --ckpt-every 50 --keep-last-k 3] [--resume]

Runs the paper's full pipeline on whatever devices exist, now through the
deterministic ``DataPipeline``: synthesize a binary-function corpus,
tokenize+pack it (R1), stage it node-locally (R2), auto-tune loader
workers and device-prefetch depth off the runner's measured step time
(R3), then pretrain with the sharding-aware async StepRunner/TrainLoop.
``--ckpt-dir`` writes resumable per-process shard checkpoints
(``ckpt-<step>/shard-<pidx>.npz`` + manifest; ``--keep-last-k`` prunes
older committed ones) and ``--resume`` continues bit-exact from the
newest complete one — or from ``--ckpt-step N`` — same step, same next
batch, same loss trajectory.  ``--process-index/--process-count`` set
this host's slice of the deterministic global batch order.

Multi-controller runs: exporting ``REPRO_COORDINATOR`` (or
``JAX_COORDINATOR_ADDRESS``) plus ``*_NUM_PROCESSES``/``*_PROCESS_ID``
makes the launcher call ``jax.distributed.initialize()`` before any
device query; with nothing exported it is a single-process no-op.

On >1 data-parallel shards the runner's ParallelPlan routes the step
onto an overlap-scheduled gradient sync (``--grad-bucket-mb`` sets the
bucket size; docs/parallelism.md has the full strategy table):
``--sharding ddp`` (default) gets the bucketed backward-overlapped
all-reduce; ``--sharding fsdp`` gets scatter_overlap — params and
optimizer state sharded over the dp axes, per-bucket all_gather
prefetch in forward, per-bucket psum_scatter in backward.
``--tensor-parallel N`` carves an N-wide 'model' axis and runs the
explicitly-scheduled tensor-parallel step (``tp_overlap``): attention
heads and FFN columns shard over it, activations stay sequence-sharded
between blocks, and ZeRO-3 over the remaining data axis composes in
under ``--sharding fsdp_tp`` (the implied default).

Resuming from a pinned ``--ckpt-step N`` protects checkpoint N from
``--keep-last-k`` GC for the rest of the run (docs/resume.md).

Elastic restore: ``--elastic-restore`` routes ``--resume`` through the
plan-aware resharding reader (``distributed/reshard.py``), so a
checkpoint written by N processes restores onto THIS topology — any
process count, any ``--sharding`` plan — each host reading only the
stored sub-shards overlapping its new shards.  ``--journal-dir`` (point
it at tmpfs, e.g. ``/dev/shm/run-j``) keeps an every-step last-K
rollback journal in host memory: a transient step failure rolls back
in-process, and a killed-and-restarted worker resumes from the journal
entry — seconds old — instead of the last durable checkpoint
(``--journal-k`` sets K; see docs/resume.md).
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np


def main(argv=None):
    """Run the launcher on ``argv`` (``sys.argv[1:]`` when None) and
    return the final ``(state, TrainerLog)``."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="bert-mlm-120m")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=16,
                    help="per-host batch size")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale model (CPU-friendly)")
    ap.add_argument("--workers", type=int, default=0,
                    help="loader workers; 0 = auto-tune (R3)")
    ap.add_argument("--n-functions", type=int, default=3000)
    ap.add_argument("--data-dir", default=None)
    ap.add_argument("--data-seed", type=int, default=0,
                    help="pipeline order/augmentation seed")
    ap.add_argument("--ckpt", default=None,
                    help="flat single-file checkpoint path (legacy)")
    ap.add_argument("--ckpt-dir", default=None,
                    help="sharded resumable checkpoint directory")
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="background-save every N steps (0 = final only)")
    ap.add_argument("--resume", action="store_true",
                    help="continue from the newest complete checkpoint "
                         "in --ckpt-dir")
    ap.add_argument("--ckpt-step", type=int, default=None,
                    help="with --resume: restore this exact step instead "
                         "of the newest complete one")
    ap.add_argument("--keep-last-k", type=int, default=0,
                    help="prune committed checkpoints beyond the newest "
                         "K after each save (0 = keep all)")
    ap.add_argument("--elastic-restore", action="store_true",
                    help="with --resume: restore through the topology-"
                         "resharding reader, so the checkpoint may have "
                         "been written by a different process count / "
                         "sharding plan (global batch must be unchanged)")
    ap.add_argument("--journal-dir", default=None,
                    help="every-step rollback-journal directory (use "
                         "tmpfs, e.g. /dev/shm/<run>); --resume prefers "
                         "its newest entry over older disk checkpoints")
    ap.add_argument("--journal-k", type=int, default=0,
                    help="rollback-journal depth; >0 without "
                         "--journal-dir keeps the ring in process "
                         "memory only (in-process rollback, no restart "
                         "recovery); 0 with --journal-dir defaults to 2")
    ap.add_argument("--sharding", default="ddp",
                    choices=["ddp", "fsdp", "tp", "fsdp_tp", "pp",
                             "pp_dp"],
                    help="parallelism mode; ddp replicates params, fsdp "
                         "shards params+optimizer over the data axis "
                         "(scatter_overlap), pp/pp_dp pipeline the "
                         "block stack (see docs/parallelism.md)")
    ap.add_argument("--pipeline-stages", type=int, default=0,
                    help="cut the block stack into N pipeline stages "
                         "over a 'pipe' mesh axis (implies --sharding "
                         "pp_dp unless a pp mode was given); devices "
                         "must divide by N")
    ap.add_argument("--expert-parallel", type=int, default=0,
                    help="carve an N-wide 'expert' axis out of the data "
                         "axis for MoE models: experts (and their "
                         "optimizer state) shard over it, tokens "
                         "dispatch with overlapped all_to_all "
                         "(ep_overlap; requires --sharding ddp and "
                         "n_experts divisible by N)")
    ap.add_argument("--tensor-parallel", type=int, default=0,
                    help="carve an N-wide 'model' axis for tensor "
                         "parallelism: attention heads and FFN columns "
                         "shard over it with explicitly-scheduled "
                         "sequence-parallel collectives (tp_overlap; "
                         "implies --sharding fsdp_tp unless a tp mode "
                         "was given; heads/d_ff/seq must divide by N)")
    ap.add_argument("--pp-schedule", default="1f1b",
                    choices=["gpipe", "1f1b"],
                    help="pipeline microbatch schedule: gpipe holds M "
                         "microbatches in flight, 1f1b bounds them at "
                         "the stage count (same bubble)")
    ap.add_argument("--microbatch", type=int, default=0,
                    help="grad-accumulation split of the local batch; "
                         "under pp modes this is the pipeline "
                         "microbatch count M (0 = no split)")
    ap.add_argument("--grad-bucket-mb", type=float, default=25.0,
                    help="gradient collective bucket size (MB); one "
                         "psum (ddp) or psum_scatter+all_gather (fsdp) "
                         "per bucket, overlapped with compute")
    ap.add_argument("--devices", type=int, default=0,
                    help="build the mesh over the first N devices of a "
                         "single-process run (0 = all)")
    ap.add_argument("--process-index", type=int, default=None)
    ap.add_argument("--process-count", type=int, default=None)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--trace-dir", default=None,
                    help="write a Perfetto-loadable span timeline to "
                         "<dir>/trace-<pidx>.json: per-step data-wait/"
                         "dispatch/ckpt/journal lanes plus per-worker "
                         "batch-fetch lanes (docs/observability.md)")
    ap.add_argument("--metrics-jsonl", default=None,
                    help="append a metrics-registry snapshot line per "
                         "log window (and a final one) to this file")
    ap.add_argument("--straggler-every", type=int, default=0,
                    help="every N steps allgather per-rank phase times "
                         "and warn '[straggler] rank=...' when one rank "
                         "exceeds --straggler-ratio x median (0 = off)")
    ap.add_argument("--straggler-ratio", type=float, default=2.0,
                    help="straggler threshold as a multiple of the "
                         "cross-rank median phase time")
    args = ap.parse_args(argv)

    from repro.configs import default_run_config, get_config, \
        reduced as reduce_cfg
    from repro.configs.base import ShapeConfig
    from repro.core.mlm import mask_tokens
    from repro.data import DataPipeline, NetworkFS
    from repro.distributed import maybe_initialize_distributed
    from repro.launch.compile_cache import init_compile_cache
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model
    from repro.train.optimizer import AdamWConfig
    from repro.train.runner import StepRunner, TrainLoop, resume

    init_compile_cache()
    # multi-controller wiring (env-keyed; single-process no-op) — must run
    # before the first jax device/process query below
    if maybe_initialize_distributed():
        print(f"[dist] jax.distributed initialized: process "
              f"{jax.process_index()}/{jax.process_count()}")

    pidx = args.process_index if args.process_index is not None \
        else jax.process_index()
    pcount = args.process_count if args.process_count is not None \
        else jax.process_count()

    # observability: install the tracer BEFORE the pipeline/loop exist so
    # loader workers pick it up; the registry always rides along (it is
    # only written out when --metrics-jsonl is given)
    from repro.observability import MetricsRegistry, Tracer, set_tracer

    tracer = None
    if args.trace_dir or args.straggler_every:
        tracer = Tracer(process_index=pidx)
        set_tracer(tracer)
    registry = MetricsRegistry()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    cfg = dataclasses.replace(cfg, max_position=max(cfg.max_position,
                                                    args.seq))
    is_mlm = cfg.family == "encoder"

    def work(batch, rng):
        if not is_mlm:
            toks = batch["tokens"]
            return {"tokens": toks,
                    "labels": np.roll(toks, -1, axis=1),
                    "loss_mask": batch["attn_mask"]}
        key = jax.random.PRNGKey(int(rng.integers(1 << 30)))
        inputs, labels, mask = mask_tokens(
            key, jnp.asarray(batch["tokens"]), cfg.vocab_size, mask_id=3)
        return {"tokens": np.asarray(inputs), "labels": np.asarray(labels),
                "loss_mask": np.asarray(mask) * batch["attn_mask"]}

    data_dir = args.data_dir or tempfile.mkdtemp(prefix="repro_data_")
    print(f"[data] building pipeline in {data_dir} "
          f"(host {pidx}/{pcount}, per-host batch {args.batch})")
    t0 = time.perf_counter()
    pipeline = DataPipeline.build(
        data_dir, n_functions=args.n_functions, seq_len=args.seq,
        batch_size=args.batch, vocab_size=cfg.vocab_size,
        network=NetworkFS(agg_bw=2e9, readers=8),
        seed=args.data_seed, process_index=pidx, process_count=pcount,
        n_workers=max(1, args.workers), work_fn=work)
    print(f"[R1+R2] packed+staged {pipeline.ds.n_examples} examples "
          f"({pipeline.batches_per_epoch} global batches/epoch) "
          f"in {time.perf_counter() - t0:.2f}s")

    model = build_model(cfg)
    # under a real jax.distributed launch every process cooperates in ONE
    # SPMD computation, so the step sees the global batch (per-host rows
    # are assembled by data.device_prefetch.place_on); the simulated
    # multi-host path (--process-count without a coordinator) keeps each
    # process training independently on its own slice, as before
    gbatch = args.batch * jax.process_count()
    sharding = args.sharding
    if args.pipeline_stages > 1 and sharding not in ("pp", "pp_dp"):
        sharding = "pp_dp"
    if sharding in ("pp", "pp_dp") and args.pipeline_stages < 2:
        # without a pipe axis the plan would silently demote to plain
        # ddp — make the mismatch loud instead
        ap.error(f"--sharding {sharding} needs --pipeline-stages >= 2")
    if args.tensor_parallel > 1 and sharding not in ("tp", "fsdp_tp"):
        sharding = "fsdp_tp"
    if sharding in ("tp", "fsdp_tp") and args.tensor_parallel < 2:
        # same loudness rule: a tp mode on a model-axis-1 mesh would
        # silently fall back (fsdp_tp -> scatter_overlap, tp -> fused)
        ap.error(f"--sharding {sharding} needs --tensor-parallel >= 2")
    run = default_run_config(cfg, ShapeConfig("cli", args.seq, gbatch,
                                              "train"),
                             sharding=sharding,
                             pp_schedule=args.pp_schedule,
                             microbatch=args.microbatch)
    opt = AdamWConfig(lr=args.lr, warmup_steps=max(2, args.steps // 20),
                      total_steps=args.steps)

    # mesh over whatever devices exist (all processes' under
    # jax.distributed): the runner jits ONCE with explicit state/batch
    # shardings + donated state buffers, and its ParallelPlan picks the
    # gradient-sync strategy (bucketed overlapped psum for multi-shard
    # ddp; the staged pipeline when --pipeline-stages carves a pipe axis)
    n_dev = jax.device_count()
    if args.devices:
        if not 0 < args.devices <= n_dev or jax.process_count() > 1:
            ap.error(f"--devices {args.devices} needs a single-process run "
                     f"with at least that many of its {n_dev} devices")
        n_dev = args.devices
    carvers = [n for n, v in (("--pipeline-stages", args.pipeline_stages),
                              ("--expert-parallel", args.expert_parallel),
                              ("--tensor-parallel", args.tensor_parallel))
               if v > 1]
    if len(carvers) > 1:
        ap.error(f"{' and '.join(carvers)} are mutually exclusive (each "
                 "carves its axis out of the data axis; composing them "
                 "is tracked in ROADMAP.md)")
    if args.tensor_parallel > 1:
        tp = args.tensor_parallel
        if n_dev % tp != 0:
            ap.error(f"--tensor-parallel {tp} must divide the device "
                     f"count {n_dev}")
        dp = n_dev // tp
        mesh = make_host_mesh(data=dp if gbatch % max(1, dp) == 0 else 1,
                              model=tp)
    elif args.pipeline_stages > 1:
        stages = args.pipeline_stages
        if n_dev % stages != 0:
            ap.error(f"--pipeline-stages {stages} must divide the "
                     f"device count {n_dev}")
        dp = n_dev // stages
        mesh = make_host_mesh(data=dp if gbatch % max(1, dp) == 0 else 1,
                              pipe=stages)
    elif args.expert_parallel > 1:
        ep = args.expert_parallel
        if n_dev % ep != 0:
            ap.error(f"--expert-parallel {ep} must divide the device "
                     f"count {n_dev}")
        dp = n_dev // ep
        mesh = make_host_mesh(
            data=dp if gbatch % n_dev == 0 else 1, expert=ep)
    else:
        mesh = make_host_mesh(data=n_dev if gbatch % n_dev == 0 else 1)
    runner = StepRunner(model, run, opt, mesh,
                        grad_bucket_mb=args.grad_bucket_mb)
    gs = runner.grad_sync_info()
    print(f"[plan] mode={gs['mode']} dp_axes={gs['dp_axes']} "
          f"dp_size={gs['dp_size']} grad_sync={gs['grad_sync']} "
          f"buckets={gs['n_buckets']} "
          f"comm={gs['comm_bytes']/1e6:.1f}MB/step "
          f"wire={gs['wire_bytes_per_device']/1e6:.1f}MB/dev "
          f"gather={gs['param_gather_bytes']/1e6:.1f}MB")
    if gs.get("fallback_reason"):
        print(f"[plan] fallback: {gs['fallback_reason']}")
    if gs.get("pipe_engaged"):
        print(f"[plan] pipeline: stages={gs['pp_stages']} "
              f"schedule={gs['pp_schedule']} "
              f"micro={gs['microbatch']} "
              f"bubble={gs['bubble_fraction']:.3f} "
              f"(analytic {gs['bubble_analytic']:.3f}) "
              f"act_wire={gs['act_wire_bytes_per_device']/1e6:.1f}MB/dev "
              f"buffer_depth={gs['pp_buffer_depth']}")
    if gs.get("ep_engaged"):
        print(f"[plan] expert-parallel: ep={gs['ep_size']} "
              f"experts={gs['n_experts']} "
              f"expert_buckets={gs['n_expert_buckets']} "
              f"dispatch_wire="
              f"{gs['dispatch_wire_bytes_per_device']/1e6:.1f}MB/dev")
    if gs.get("tp_engaged"):
        print(f"[plan] tensor-parallel: tp={gs['tp_size']} "
              f"tp_buckets={gs['n_tp_buckets']} "
              f"act_wire={gs['tp_wire_bytes_per_device']/1e6:.1f}MB/dev "
              f"gather={gs['param_gather_bytes']/1e6:.1f}MB")

    if args.workers == 0:
        # R3 end-to-end: measure the real compiled step time on a scratch
        # state (so the training trajectory — and resume determinism — is
        # untouched), then grow workers / prefetch depth until the
        # consumer stops stalling, and no more
        from repro.data.device_prefetch import place_on

        scratch = runner.init_state(seed=123)
        probe_batch = {k: place_on(v, runner.batch_shardings.get(k))
                       for k, v in pipeline.peek_batch().items()}
        runner.compile(scratch, probe_batch)
        t0 = time.perf_counter()
        for _ in range(3):
            scratch, _ = runner(scratch, probe_batch)
        jax.block_until_ready(scratch)
        step_time = (time.perf_counter() - t0) / 3
        del scratch
        tuned = pipeline.autotune(step_time_s=step_time, n_batches=12)
        print(f"[R3] step={step_time*1e3:.1f}ms -> auto-tuned "
              f"workers={tuned['n_workers']} "
              f"device_prefetch={tuned['device_prefetch']} "
              f"(stall={tuned['stall_fraction']:.2f})")

    state, start_step = None, 0
    if args.resume:
        if not args.ckpt_dir and not args.journal_dir:
            ap.error("--resume needs --ckpt-dir (or --journal-dir)")
        from repro.train import checkpoint as ckpt

        # newest recoverable state wins: a journal entry (seconds old,
        # in tmpfs) beats an older durable checkpoint — unless the
        # operator pinned an exact --ckpt-step
        ck_step = ckpt.latest_step(args.ckpt_dir) if args.ckpt_dir else None
        j_step = ckpt.latest_step(args.journal_dir) \
            if args.journal_dir else None
        if args.ckpt_step is not None:
            src, step_arg = args.ckpt_dir, args.ckpt_step
        elif j_step is not None and (ck_step is None or j_step > ck_step):
            src, step_arg = args.journal_dir, None
        elif ck_step is not None:
            src, step_arg = args.ckpt_dir, None
        else:
            src = None
        if src is None:
            print(f"[resume] no complete checkpoint in {args.ckpt_dir}; "
                  "starting fresh")
        elif args.elastic_restore:
            from repro.train.runner import resume_resharded

            state, start_step = resume_resharded(src, runner,
                                                 pipeline=pipeline,
                                                 step=step_arg)
            print(f"[resume] host {pidx} reshard-restored step "
                  f"{start_step} from {src} onto {pcount} process(es)")
        else:
            state, start_step = resume(src, runner,
                                       pipeline=pipeline,
                                       process_index=pidx,
                                       step=step_arg)
            print(f"[resume] host {pidx} restored shard at step "
                  f"{start_step} from {src}")

    journal = None
    if args.journal_dir or args.journal_k > 0:
        from repro.train.journal import RollbackJournal

        journal = RollbackJournal(args.journal_k if args.journal_k > 0
                                  else 2,
                                  dir=args.journal_dir,
                                  process_index=pidx,
                                  process_count=pcount)

    # a pinned --ckpt-step is an operator decision (e.g. a rollback
    # point): protect it from keep-last-k GC for the rest of this run
    pins = (args.ckpt_step,) if (args.resume
                                 and args.ckpt_step is not None) else ()
    loop = TrainLoop(runner, log_every=args.log_every,
                     ckpt_path=args.ckpt, ckpt_dir=args.ckpt_dir,
                     ckpt_every=args.ckpt_every
                     if (args.ckpt or args.ckpt_dir) else 0,
                     keep_last_k=args.keep_last_k, pin_steps=pins,
                     process_index=pidx, process_count=pcount,
                     journal=journal,
                     metrics=registry, metrics_jsonl=args.metrics_jsonl,
                     straggler_every=args.straggler_every,
                     straggler_ratio=args.straggler_ratio)
    print(f"[train] {cfg.name}: {model.cfg.n_layers}L d={cfg.d_model} "
          f"on {n_dev} device(s), mesh {dict(mesh.shape)}, "
          f"steps {start_step}->{args.steps}")
    state, log = loop.run(pipeline, args.steps, state=state,
                          start_step=start_step)
    pipeline.close()
    for s, m, sps, tps, mfu in zip(log.steps, log.metrics, log.samples_per_s,
                                   log.tokens_per_s, log.mfu):
        print(f"  step {s:5d} loss={m['loss']:.4f} xent={m['xent']:.4f} "
              f"acc={m.get('acc', float('nan')):.3f} samples/s={sps:.1f} "
              f"tokens/s={tps:.0f} mfu={mfu:.2e}")
    t = log.telemetry
    print(f"[telemetry] step_ema={t['step_time_ema']*1e3:.1f}ms "
          f"tokens/s={t['tokens_per_s']:.0f} "
          f"host_stall={t['stall_fraction']*100:.1f}% "
          f"compiles={t['n_traces']:.0f} "
          f"grad_sync={t['grad_sync']}/{t['grad_buckets']}bkt/"
          f"{t['grad_comm_bytes']/1e6:.1f}MB")
    if args.straggler_every and loop.last_straggler_reports:
        last = loop.last_straggler_reports[-1]["summary"]
        worst = max(last.items(), key=lambda kv: kv[1]["imbalance"])
        print(f"[straggler] checks={len(loop.last_straggler_reports)} "
              f"worst_phase={worst[0]} "
              f"imbalance={worst[1]['imbalance']:.2f}x")
    if args.metrics_jsonl:
        print(f"[metrics] wrote {args.metrics_jsonl}")
    if tracer is not None and args.trace_dir:
        path = tracer.flush(args.trace_dir)
        print(f"[trace] wrote {path} ({len(tracer)} events, "
              f"{tracer.dropped} dropped) — open in ui.perfetto.dev")
    print("[done]")
    return state, log


if __name__ == "__main__":
    main()
