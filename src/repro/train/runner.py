"""Sharding-aware asynchronous training execution: StepRunner + TrainLoop.

The paper's recommendations are about keeping the accelerator busy; this
module applies them to the execution path itself:

  StepRunner  — compiles the train step ONCE with explicit
                ``in_shardings``/``out_shardings`` derived from
                ``state_shardings``/``batch_shardings`` and donates the
                state argument, so params + optimizer buffers are reused
                in place (no per-step state copy, no recompiles).
  TrainLoop   — drives the runner without ever blocking the dispatch
                queue: device batches arrive through the double-buffered
                ``data.device_prefetch`` adapter, metric scalars are
                fetched asynchronously (resolved only once the device has
                produced them), and checkpoint serialization runs on a
                background thread (``checkpoint.AsyncCheckpointer``).

Per-step telemetry (step-time EMA, tokens/s, an MFU estimate from the
``analysis.hlocost`` trip-count-aware HLO cost model, and the host-stall
fraction) rides along in the returned :class:`TrainerLog`.
"""
from __future__ import annotations

import collections
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, List, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import RunConfig
from repro.data.device_prefetch import DevicePrefetch
from repro.models.model import Model
from repro.observability import STEP_TIME_BUCKETS_MS, get_tracer
from repro.train import checkpoint as ckpt
from repro.train.faults import TransientWorkerError, fault_point
from repro.train.optimizer import AdamWConfig
from repro.train.train_step import (batch_shardings, init_state,
                                    make_train_step, state_shardings)

__all__ = ["StepRunner", "TrainLoop", "TrainerLog", "AsyncMetrics",
           "resume", "resume_resharded"]


# ---------------------------------------------------------------------------
# Non-blocking metrics
# ---------------------------------------------------------------------------


class AsyncMetrics:
    """Holds device metric trees and resolves them to host floats lazily.

    ``push`` never blocks.  ``poll`` resolves only entries whose arrays
    the device has already produced (``Array.is_ready``), so the host
    keeps dispatching ahead of the accelerator; a bounded pending window
    (``max_pending``) forces resolution of the oldest entry rather than
    letting unbounded device memory accumulate.  ``drain`` resolves
    everything (end of training).

    Ordering contract: ``poll``/``drain`` yield entries in PUSH order,
    never readiness order — both only ever pop the deque head, and the
    forced-resolve pass runs *before* the ready scan so a ready entry
    behind a slow head is held back until the head resolves.  Consumers
    (``TrainLog.metrics``) therefore see strictly monotone step order.
    """

    def __init__(self, max_pending: int = 8):
        self.max_pending = max_pending
        self._pending: "collections.deque" = collections.deque()
        self.forced_resolves = 0

    @staticmethod
    def _is_ready(metrics: Dict[str, Any]) -> bool:
        for v in metrics.values():
            ready = getattr(v, "is_ready", None)
            if ready is not None and not ready():
                return False
        return True

    @staticmethod
    def _resolve(entry):
        meta, metrics = entry
        return meta, {k: float(v) for k, v in metrics.items()}

    def push(self, meta: Dict[str, Any], metrics: Dict[str, Any]):
        self._pending.append((meta, metrics))

    def poll(self) -> List[tuple]:
        out = []
        # bound the window FIRST: force-resolving the oldest entries
        # before the ready scan keeps emission in push order by
        # construction (resolving head entries can only ever extend the
        # ready prefix, never reorder it)
        while len(self._pending) > self.max_pending:
            self.forced_resolves += 1
            out.append(self._resolve(self._pending.popleft()))
        while self._pending and self._is_ready(self._pending[0][1]):
            out.append(self._resolve(self._pending.popleft()))
        return out

    def drain(self) -> List[tuple]:
        out = []
        while self._pending:
            out.append(self._resolve(self._pending.popleft()))
        return out


# ---------------------------------------------------------------------------
# StepRunner
# ---------------------------------------------------------------------------


class StepRunner:
    """Owns the jitted train step: explicit shardings, donation, AOT
    compilation, and the compiled-program cost model.

    With a ``mesh`` the step is jitted with ``in_shardings`` /
    ``out_shardings`` built from ``state_shardings``/``batch_shardings``
    (the trees the seed repo built but never passed to jit) and
    ``donate_argnums=(0,)`` on the state.  ``n_traces`` counts retraces —
    a steady-state loop must keep it at 1.
    """

    def __init__(self, model: Model, run: RunConfig, opt: AdamWConfig,
                 mesh=None, *, donate: bool = True,
                 seq_axis: Optional[str] = None,
                 plan: Optional["ParallelPlan"] = None,
                 grad_bucket_mb: float = 25.0):
        from repro.distributed.sharding import ParallelPlan

        self.model, self.run, self.opt, self.mesh = model, run, opt, mesh
        self.plan = plan if plan is not None else ParallelPlan.for_run(
            run, mesh, grad_bucket_mb=grad_bucket_mb)
        self.donate = donate
        self.n_traces = 0
        step = make_train_step(model, run, opt, mesh, seq_axis=seq_axis,
                               plan=self.plan)

        def counted(state, batch):
            self.n_traces += 1  # trace-time side effect == compile count
            return step(state, batch)

        self._counted = counted
        self.state_shardings = None
        self.batch_shardings: Dict[str, Any] = {}
        if mesh is not None:
            self.state_shardings = state_shardings(model, mesh, run,
                                                   plan=self.plan)
            self.batch_shardings = batch_shardings(model, mesh, run,
                                                   run.shape,
                                                   plan=self.plan)
        self._jit = None        # built on first use: the batch half of
        self.compiled = None    # in_shardings must mirror the actual
        self._cost = None       # batch pytree structure

    def _get_jit(self, batch):
        if self._jit is None:
            kw: Dict[str, Any] = {}
            if self.donate:
                kw["donate_argnums"] = (0,)
            if self.mesh is not None:
                b_sh = {k: self.batch_shardings.get(k) for k in batch} \
                    if isinstance(batch, dict) else None
                kw["in_shardings"] = (self.state_shardings, b_sh)
                kw["out_shardings"] = (self.state_shardings, None)
            self._jit = jax.jit(self._counted, **kw)
        return self._jit

    # -- state -----------------------------------------------------------
    def init_state(self, seed: int = 0):
        state = init_state(self.model, jax.random.PRNGKey(seed), self.run)
        return self.place_state(state)

    def place_state(self, state):
        """Commit the state onto its sharded layout (so the donated-buffer
        fast path applies from the very first step).

        A sharding spanning other processes' devices (real
        multi-controller fsdp) can't go through ``device_put`` on a host
        buffer; those leaves are committed via
        ``make_array_from_callback``, which reads only this process's
        slices — the counterpart of the sub-shard checkpoint layout
        (``train/checkpoint.py``), whose restore zero-fills exactly the
        regions this path never touches."""
        if self.state_shardings is None:
            return state

        def put(x, s):
            if getattr(s, "is_fully_addressable", True):
                return jax.device_put(x, s)
            import numpy as np

            host = np.asarray(x)
            return jax.make_array_from_callback(
                host.shape, s, lambda idx: host[idx])

        return jax.tree_util.tree_map(put, state, self.state_shardings)

    # -- compilation -----------------------------------------------------
    def lower(self, state=None, batch=None):
        """Lower the step with explicit shardings.  With no arguments it
        lowers against the run's abstract state / input specs — the path
        ``launch/dryrun.py`` (via ``lowering.lower_train``) analyzes."""
        from repro.train.train_step import abstract_state

        if batch is None:
            batch = self.model.input_specs(
                self.run.shape,
                act_dtype=jnp.dtype(self.run.activation_dtype))
        if state is None:
            state = abstract_state(self.model, self.run)
        return self._get_jit(batch).lower(state, batch)

    def compile(self, state, batch) -> "StepRunner":
        """AOT lower+compile against the concrete (state, batch) shapes.
        Subsequent calls run the stored executable — compilation happens
        exactly once, by construction, and the optimized HLO feeds the
        hlocost MFU estimate."""
        def one(x):
            sharding = getattr(x, "sharding", None)
            kw = {"sharding": sharding} if sharding is not None else {}
            return jax.ShapeDtypeStruct(jnp.shape(x),
                                        getattr(x, "dtype", jnp.float32),
                                        **kw)

        spec = lambda t: jax.tree_util.tree_map(one, t)
        self.compiled = self.lower(spec(state), spec(batch)).compile()
        return self

    def __call__(self, state, batch):
        if self.compiled is not None:
            return self.compiled(state, batch)
        return self._get_jit(batch)(state, batch)

    # -- gradient-sync telemetry -----------------------------------------
    def grad_sync_info(self) -> Dict[str, Any]:
        """The plan's grad-sync shape plus per-step communication volume.

        Always present: strategy, bucket count, per-bucket payload bytes
        (``bucket_bytes``), and the per-device gradient wire bytes per
        step (``wire_bytes_per_device`` — ring all-reduce volume for
        ``bucketed_overlap``, reduce-scatter + remainder all-reduce for
        ``scatter_overlap``).  Under ``scatter_overlap`` the forward
        param all-gather volume rides along as ``param_gather_bytes`` /
        ``gather_wire_bytes_per_device`` so operators can see both
        halves of the decomposed all-reduce."""
        from repro.distributed import gradsync

        info = dict(self.plan.describe())
        abstract = self.model.abstract(jnp.dtype(self.run.param_dtype))
        info.update(n_buckets=0, comm_bytes=0, bucket_bytes=[],
                    wire_bytes_per_device=0.0, param_gather_bytes=0,
                    gather_wire_bytes_per_device=0.0)
        pp = self.plan.pipe_sync_plan(abstract)
        if pp is not None:
            from repro.distributed import pipeline

            sched = self.plan.pipe_schedule_obj()
            n_dp = self.plan.dp_size
            n_all = n_dp * self.plan.pp_size
            buckets = pp.buckets
            info.update(gradsync.bucket_plan_stats(buckets))
            info["bucket_bytes"] = [b.nbytes for b in buckets]
            info["n_stage_buckets"] = len(pp.stage)
            info["n_replicated_buckets"] = len(pp.replicated)
            # stage grads ring over data only; replicated leaves ring
            # over the whole (pipe x data) sync group
            info["wire_bytes_per_device"] = (
                gradsync.ring_allreduce_bytes(pp.stage_bytes, n_dp)
                + gradsync.ring_allreduce_bytes(pp.replicated_bytes,
                                                n_all))
            rows = self.plan.local_batch // self.plan.n_micro
            act = pipeline.activation_wire_bytes(
                sched, (rows, self.run.shape.seq_len,
                        self.model.cfg.d_model),
                jnp.dtype(self.run.activation_dtype))
            info.update(act)
            info["bubble_fraction"] = sched.bubble_fraction()
            info["bubble_analytic"] = pipeline.analytic_bubble(
                sched.n_stages, sched.n_micro)
            info["pp_buffer_depth"] = sched.buffer_depth
            return info
        ep = self.plan.ep_sync_plan(self.model.param_axes(), abstract)
        if ep is not None:
            from repro.analysis.hlocost import ep_dispatch_bytes

            n_dp = self.plan.dp_size
            n_data = max(1, n_dp // self.plan.ep_size)
            buckets = ep.buckets
            info.update(gradsync.bucket_plan_stats(buckets))
            info["bucket_bytes"] = [b.nbytes for b in buckets]
            info["n_expert_buckets"] = len(ep.stage)
            info["n_replicated_buckets"] = len(ep.replicated)
            # expert-sharded grads ring over data only; the replicated
            # rest rings over the whole (data x expert) sync group
            info["wire_bytes_per_device"] = (
                gradsync.ring_allreduce_bytes(ep.stage_bytes, n_data)
                + gradsync.ring_allreduce_bytes(ep.replicated_bytes,
                                                n_dp))
            n_micro = self.plan.n_micro
            rows = self.plan.local_batch // n_micro
            info["dispatch_wire_bytes_per_device"] = \
                n_micro * ep_dispatch_bytes(
                    self.model.cfg, rows * self.run.shape.seq_len,
                    self.plan.ep_size,
                    dtype_bytes=jnp.dtype(
                        self.run.activation_dtype).itemsize)
            return info
        tp = self.plan.tp_sync_plan(self.model.param_axes(), abstract)
        if tp is not None:
            from repro.analysis.hlocost import tp_activation_bytes

            ms = self.plan.tp_size
            n_dp = self.plan.dp_size
            fsdp = self.plan.tp_scatter_plan(self.model.param_axes(),
                                             abstract)
            if fsdp is None:
                # pure tp: tp-sharded grads ring over data only, the
                # dense rest over the whole (model x data) sync group
                buckets = tp.buckets
                info["wire_bytes_per_device"] = (
                    gradsync.ring_allreduce_bytes(tp.stage_bytes, n_dp)
                    + gradsync.ring_allreduce_bytes(tp.replicated_bytes,
                                                    n_dp * ms))
            else:
                # fsdp_tp: dense grads psum over model (tp.replicated),
                # then the ZeRO-3 scatter over data; pinned tp leaves
                # ride the fsdp psum buckets
                buckets = tp.replicated + fsdp.buckets
                info["wire_bytes_per_device"] = (
                    gradsync.ring_allreduce_bytes(tp.replicated_bytes,
                                                  ms)
                    + gradsync.reduce_scatter_bytes(fsdp.scatter_bytes,
                                                    n_dp)
                    + gradsync.ring_allreduce_bytes(fsdp.psum_bytes,
                                                    n_dp))
                sc = set(fsdp.scatter_indices)
                leaves, _ = self.plan._tp_local_leaves(
                    self.model.param_axes(), abstract)
                gather = sum(gradsync.leaf_nbytes(l)
                             for i, l in enumerate(leaves) if i in sc)
                info["param_gather_bytes"] = int(gather)
                info["gather_wire_bytes_per_device"] = \
                    gradsync.all_gather_bytes(gather, n_dp)
            info.update(gradsync.bucket_plan_stats(buckets))
            info["bucket_bytes"] = [b.nbytes for b in buckets]
            info["n_tp_buckets"] = len(tp.stage)
            info["n_replicated_buckets"] = len(tp.replicated)
            n_micro = self.plan.n_micro
            rows = self.plan.local_batch // n_micro
            # the activation-path collectives (2 ag + 2 rs per block)
            # are the cost the sequence-parallel layout pays for never
            # materializing full-seq activations between blocks
            info["tp_wire_bytes_per_device"] = tp_activation_bytes(
                self.model.cfg, rows, self.run.shape.seq_len, ms,
                dtype_bytes=jnp.dtype(
                    self.run.activation_dtype).itemsize,
                n_micro=n_micro)
            return info
        sp = self.plan.scatter_plan(abstract)
        if sp is not None:
            n = self.plan.dp_size
            buckets = sp.buckets
            info.update(gradsync.bucket_plan_stats(buckets))
            info["bucket_bytes"] = [b.nbytes for b in buckets]
            info["n_scatter_buckets"] = len(sp.scatter)
            info["n_psum_buckets"] = len(sp.psum)
            info["wire_bytes_per_device"] = (
                gradsync.reduce_scatter_bytes(sp.scatter_bytes, n)
                + gradsync.ring_allreduce_bytes(sp.psum_bytes, n))
            sc = set(sp.scatter_indices)
            gather = sum(
                gradsync.leaf_nbytes(l) for i, l in enumerate(
                    jax.tree_util.tree_leaves(abstract)) if i in sc)
            info["param_gather_bytes"] = int(gather)
            info["gather_wire_bytes_per_device"] = \
                gradsync.all_gather_bytes(gather, n)
            return info
        buckets = self.plan.grad_buckets(abstract)
        if buckets is None:
            return info
        stats = gradsync.bucket_plan_stats(buckets)
        info.update(stats)
        info["bucket_bytes"] = [b.nbytes for b in buckets]
        info["wire_bytes_per_device"] = gradsync.ring_allreduce_bytes(
            stats["comm_bytes"], self.plan.dp_size)
        return info

    # -- cost / MFU ------------------------------------------------------
    def step_cost(self):
        """Per-device hlocost Cost of the compiled step (trip-count-aware
        flops/bytes), or None before :meth:`compile`."""
        if self._cost is None and self.compiled is not None:
            from repro.analysis.hlocost import analyze_text

            self._cost = analyze_text(self.compiled.as_text())
        return self._cost

    def flops_per_step(self, tokens_per_step: int) -> float:
        """Per-device flops of one step: the compiled program's cost when
        available, else the analytic 6ND model."""
        cost = self.step_cost()
        if cost is not None and cost.flops > 0:
            return cost.flops
        from repro.core.scaling import model_flops

        n_dev = self.mesh.size if self.mesh is not None else 1
        return model_flops(self.model.cfg, tokens_per_step) / n_dev

    def mfu(self, step_time_s: float, tokens_per_step: int,
            peak_flops: float) -> float:
        if step_time_s <= 0:
            return float("nan")
        return self.flops_per_step(tokens_per_step) / (
            step_time_s * peak_flops)


# ---------------------------------------------------------------------------
# TrainLoop
# ---------------------------------------------------------------------------


@dataclass
class TrainerLog:
    steps: List[int] = field(default_factory=list)
    metrics: List[Dict[str, float]] = field(default_factory=list)
    samples_per_s: List[float] = field(default_factory=list)
    tokens_per_s: List[float] = field(default_factory=list)
    step_time_ema: List[float] = field(default_factory=list)
    mfu: List[float] = field(default_factory=list)
    telemetry: Dict[str, float] = field(default_factory=dict)

    def last(self) -> Dict[str, float]:
        return self.metrics[-1] if self.metrics else {}


class TrainLoop:
    """Asynchronous driver around a :class:`StepRunner`.

    The loop's only synchronous points are (a) the host->device snapshot
    before an async checkpoint (required: the next dispatched step reuses
    the donated state buffers in place) and (b) the final drain.  Host
    time spent blocked is accounted in ``telemetry['host_blocked_s']`` /
    ``['stall_fraction']`` — the figure of merit the ``train_overlap``
    benchmark compares against the seed-style loop.

    Checkpointing has two shapes: the seed's flat single-file
    ``ckpt_path``, and the resumable sharded layout ``ckpt_dir`` — each
    process writes only its own ``ckpt-<step>/shard-<pidx>.npz``, and
    when ``data`` is a :class:`repro.data.pipeline.DataPipeline` the
    serialized input position rides along, so a later ``run(...,
    start_step=s)`` on a restored state replays the exact uninterrupted
    trajectory (the pipeline position for step ``s`` is analytic —
    device-prefetch read-ahead can never skew the resume point).
    """

    def __init__(self, runner: StepRunner, *, log_every: int = 10,
                 ckpt_path: Optional[str] = None, ckpt_every: int = 0,
                 ckpt_dir: Optional[str] = None, keep_last_k: int = 0,
                 pin_steps: tuple = (),
                 process_index: int = 0, process_count: int = 1,
                 async_checkpoint: bool = True, device_prefetch: bool = True,
                 prefetch_size: int = 2, aot_compile: bool = True,
                 metrics_lag: int = 8,
                 journal=None, max_rollbacks: int = 2,
                 peak_flops: Optional[float] = None,
                 tracer=None, metrics=None,
                 metrics_jsonl: Optional[str] = None,
                 straggler_every: int = 0,
                 straggler_ratio: float = 2.0):
        """``pin_steps`` lists checkpoint steps ``keep_last_k`` GC must
        never prune — the resume path pins the ``--ckpt-step`` it
        restored from, so the operator's rollback point survives
        subsequent saves (see docs/resume.md).

        ``journal`` is an optional
        :class:`repro.train.journal.RollbackJournal`: the loop records
        every completed step into it, and a
        :class:`~repro.train.faults.TransientWorkerError` raised during
        a step (an injected fault, or a caller-detected flaky step)
        rolls state + data cursor back to the newest journal entry and
        replays — no disk checkpoint is read.  At most ``max_rollbacks``
        recoveries per ``run()``; past that the error propagates (a
        'transient' fault that keeps firing isn't transient).

        Observability (all optional, all off by default):  ``tracer``
        overrides the process-wide :func:`repro.observability.get_tracer`
        — every phase the loop already times for stall accounting
        (data wait, dispatch, metrics resolve, journal snapshot,
        checkpoint commit, final drain) is recorded as a span with the
        SAME clock readings, plus a per-iteration ``step`` span and
        rollback instants.  ``metrics`` is a
        :class:`~repro.observability.MetricsRegistry` populated with a
        step-time histogram, per-window throughput gauges and the final
        telemetry/grad-sync series; ``metrics_jsonl`` appends a registry
        snapshot per log window.  ``straggler_every`` > 0 runs the
        cross-host phase allgather every that many steps and logs
        ``[straggler] rank=...`` when a rank exceeds
        ``straggler_ratio`` x median (see observability.aggregate)."""
        if ckpt_path and ckpt_dir:
            raise ValueError("pass ckpt_path (flat) or ckpt_dir (sharded), "
                             "not both")
        self.runner = runner
        self.log_every = max(1, log_every)
        self.ckpt_path, self.ckpt_every = ckpt_path, ckpt_every
        self.ckpt_dir = ckpt_dir
        self.keep_last_k = keep_last_k
        self.pin_steps = tuple(pin_steps)
        self.process_index = process_index
        self.process_count = process_count
        self.async_checkpoint = async_checkpoint
        self.device_prefetch = device_prefetch
        self.prefetch_size = prefetch_size
        self.aot_compile = aot_compile
        self.metrics_lag = metrics_lag
        self.journal = journal
        self.max_rollbacks = max_rollbacks
        if peak_flops is None:
            from repro.core.scaling import device_peak_flops

            # resolved up front: an accelerator with no peak on record
            # fails before training, not at the first log line
            mesh = runner.mesh
            peak_flops = device_peak_flops(
                mesh.devices.flat[0] if mesh is not None
                else jax.devices()[0])
        self.peak_flops = peak_flops
        self.tracer = tracer
        self.metrics = metrics
        self.metrics_jsonl = metrics_jsonl
        self.straggler_every = straggler_every
        self.straggler_ratio = straggler_ratio

    def run(self, data: Iterable[Dict[str, Any]], steps: int, *,
            state=None, seed: int = 0, start_step: int = 0):
        """Run steps ``[start_step, steps)``; returns (state, TrainerLog).

        ``start_step`` > 0 is the resume path: ``state`` should be the
        restored checkpoint and, when ``data`` is a DataPipeline, its
        ``restore()`` must have been aimed at the same step (or simply
        at ``pipeline.start_step`` — asserted below)."""
        from repro.data.pipeline import DataPipeline

        runner = self.runner
        if state is None:
            state = runner.init_state(seed)
        else:
            state = runner.place_state(state)

        pipeline: Optional[DataPipeline] = None
        pipeline_loader = None
        if isinstance(data, DataPipeline):
            pipeline = data
            if pipeline.start_step != start_step:
                raise ValueError(
                    f"pipeline positioned at step {pipeline.start_step} "
                    f"but loop starts at {start_step}")
            if self.device_prefetch:
                it = pipeline.device_batches(runner.batch_shardings)
            else:
                it = iter(pipeline.host_batches())
            pipeline_loader = pipeline.last_loader  # owned by this run
        elif self.device_prefetch:
            it = iter(DevicePrefetch(data, shardings=runner.batch_shardings,
                                     size=self.prefetch_size))
        else:
            it = iter(data)

        log = TrainerLog()
        async_metrics = AsyncMetrics(max_pending=self.metrics_lag)
        saver = None
        if self.ckpt_dir and self.async_checkpoint:
            saver = ckpt.AsyncCheckpointer(
                self.ckpt_dir, sharded=True,
                process_index=self.process_index,
                process_count=self.process_count,
                keep_last_k=self.keep_last_k,
                pin_steps=self.pin_steps)
        elif self.ckpt_path and self.async_checkpoint:
            saver = ckpt.AsyncCheckpointer(self.ckpt_path)

        tracer = self.tracer if self.tracer is not None else get_tracer()
        step_hist = self.metrics.histogram(
            "train_step_time_ms", STEP_TIME_BUCKETS_MS,
            help="per-step wall time") if self.metrics is not None else None
        monitor = None
        if self.straggler_every:
            from repro.observability import StragglerMonitor

            monitor = StragglerMonitor(
                tracer, every=self.straggler_every,
                ratio=self.straggler_ratio, registry=self.metrics)
        self.last_straggler_reports = []

        blocked = 0.0          # host time spent waiting (stalls)
        drain_s = 0.0          # end-of-run metric drain (NOT steady stall)
        ema = None
        tokens_per_step = None
        t_start = time.perf_counter()
        t_last_log = t_start
        last_logged = start_step - 1

        def resolve_into_log(entries):
            for meta, m in entries:
                log.steps.append(meta["step"])
                log.metrics.append(m)
                log.samples_per_s.append(meta["samples_per_s"])
                log.tokens_per_s.append(meta["tokens_per_s"])
                log.step_time_ema.append(meta["step_time_ema"])
                log.mfu.append(meta["mfu"])
                if self.metrics is not None:
                    # the step's own metrics (train_xent, train_loss_rows)
                    self.metrics.set_gauges(m, prefix="train_")

        last_saved = -1

        def write_ckpt(st, step_no):
            pstate = pipeline.state_at(step_no).to_json() \
                if pipeline is not None else None
            if saver is not None:
                saver.save(st, step=step_no, pipeline_state=pstate)
            elif self.ckpt_dir:
                ckpt.save_sharded(self.ckpt_dir, st, step=step_no,
                                  process_index=self.process_index,
                                  process_count=self.process_count,
                                  pipeline_state=pstate,
                                  keep_last_k=self.keep_last_k,
                                  pin_steps=self.pin_steps)
            else:
                ckpt.save(self.ckpt_path, st, step=step_no)

        rollbacks = 0
        try:
            t_iter = time.perf_counter()
            i = start_step
            while i < steps:
                try:
                    # the stall accounting reads the timed spans' own
                    # ends, so the trace is bit-identical to it
                    with tracer.step(i):
                        with tracer.timed("data_wait", "data") as waited:
                            batch = next(it)
                        blocked += waited.seconds

                        if i == start_step:
                            if tokens_per_step is None:
                                tok = batch["tokens"]
                                tokens_per_step = int(tok.shape[0]
                                                      * tok.shape[1])
                            if self.aot_compile and runner.compiled is None:
                                runner.compile(state, batch)

                        with tracer.span("dispatch", "compute"):
                            state, metrics = runner(state, batch)
                        # the host-kill window: step i dispatched, device
                        # possibly still mid-backward
                        fault_point("step", i)

                        now = time.perf_counter()
                        dt = now - t_iter
                        t_iter = now
                        # the first iteration is dominated by compile
                        if i > start_step:
                            ema = dt if ema is None else 0.9 * ema + 0.1 * dt

                        if (i + 1) % self.log_every == 0 or i == start_step \
                                or i == steps - 1:
                            n = i - last_logged
                            window = max(now - t_last_log, 1e-9)
                            bsz = batch["tokens"].shape[0]
                            step_t = ema if ema is not None else dt
                            meta = {
                                "step": i + 1,
                                "samples_per_s": n * bsz / window,
                                "tokens_per_s": n * tokens_per_step / window,
                                "step_time_ema": step_t,
                                "mfu": runner.mfu(step_t, tokens_per_step,
                                                  self.peak_flops),
                            }
                            async_metrics.push(meta, metrics)
                            last_logged = i
                            t_last_log = now
                            # poll may force-resolve past the lag window,
                            # which blocks on the device — stall time
                            with tracer.timed("metrics_resolve",
                                              "metrics") as resolved:
                                resolve_into_log(async_metrics.poll())
                            blocked += resolved.seconds
                            if self.metrics is not None:
                                self.metrics.set_gauges(meta, prefix="train_")
                                if self.metrics_jsonl:
                                    self.metrics.write_jsonl(
                                        self.metrics_jsonl, step=i + 1)

                        if self.journal is not None:
                            # device->host snapshot of the completed step —
                            # must happen before the next dispatch reuses
                            # the donated buffers; the sync is the price of
                            # single-step rollback granularity
                            with tracer.timed("journal_snapshot", "ckpt",
                                              step=i + 1) as snap:
                                self.journal.record(
                                    state, i + 1,
                                    pipeline.state_at(i + 1)
                                    if pipeline is not None else None)
                            blocked += snap.seconds

                        if (self.ckpt_path or self.ckpt_dir) \
                                and self.ckpt_every \
                                and (i + 1) % self.ckpt_every == 0:
                            with tracer.timed("ckpt_commit", "ckpt",
                                              step=i + 1) as commit:
                                write_ckpt(state, i + 1)
                            blocked += commit.seconds
                            last_saved = i + 1

                    if step_hist is not None and i > start_step:
                        step_hist.observe(dt * 1e3)
                    if monitor is not None:
                        # deterministic schedule: every rank reaches this
                        # allgather at the same completed-step count
                        monitor.maybe_check(i + 1)
                except TransientWorkerError:
                    if self.journal is None or pipeline is None \
                            or self.journal.latest() is None \
                            or rollbacks >= self.max_rollbacks:
                        raise
                    rollbacks += 1
                    from repro.train.train_step import abstract_state

                    tracer.instant("rollback", "loop", step=i)

                    like = abstract_state(runner.model, runner.run)
                    tree, jpstate, jstep = self.journal.restore(like)
                    state = runner.place_state(tree)
                    # the old loader may have prefetched past the fault;
                    # stop it and re-aim a fresh one at the journal entry
                    if pipeline_loader is not None:
                        pipeline_loader.stop()
                    pipeline.restore(jpstate if jpstate is not None
                                     else pipeline.state_at(jstep))
                    if self.device_prefetch:
                        it = pipeline.device_batches(runner.batch_shardings)
                    else:
                        it = iter(pipeline.host_batches())
                    pipeline_loader = pipeline.last_loader
                    tracer.instant("replay", "loop", from_step=jstep)
                    i = jstep
                    t_iter = time.perf_counter()
                    continue
                i += 1

            # the end-of-run drain is NOT steady-state stall: it resolves
            # every still-pending metric window at once, a cost paid once
            # at exit.  Account it separately (telemetry['drain_s']) so
            # stall_fraction keeps meaning "host blocked per steady step".
            with tracer.timed("metrics_drain", "metrics") as drained:
                resolve_into_log(async_metrics.drain())
            drain_s = drained.seconds
            with tracer.timed("device_block", "compute") as waited:
                jax.block_until_ready(state)
            blocked += waited.seconds
            # steps > start_step: a resumed run that had nothing to do must
            # not rewrite (or mislabel) an existing checkpoint with the
            # restored state under a different step number
            final_ckpt = (self.ckpt_path or self.ckpt_dir) \
                and last_saved != steps and steps > start_step
            if final_ckpt or saver is not None:
                with tracer.timed("ckpt_commit", "ckpt",
                                  step=steps) as commit:
                    if final_ckpt:
                        write_ckpt(state, steps)
                    if saver is not None:
                        saver.close()
                        saver = None
                blocked += commit.seconds
        finally:
            if saver is not None:  # exception path: still flush the queue
                saver.close()
            if pipeline_loader is not None:  # this run started it: stop it
                pipeline_loader.stop()

        total = time.perf_counter() - t_start
        n_steps = steps - start_step
        gs = runner.grad_sync_info()
        log.telemetry = {
            "total_s": total,
            "host_blocked_s": blocked,
            "stall_fraction": blocked / max(total, 1e-9),
            # end-of-run metric drain, kept OUT of host_blocked_s /
            # stall_fraction: it is a one-time exit cost, not per-step
            # dispatch stall (the train_overlap figure of merit)
            "drain_s": drain_s,
            "step_time_ema": ema if ema is not None else float("nan"),
            "tokens_per_s": n_steps * (tokens_per_step or 0)
                            / max(total, 1e-9),
            "n_traces": runner.n_traces,
            "forced_metric_resolves": async_metrics.forced_resolves,
            # rollback-journal recovery telemetry (0 without a journal)
            "rollbacks": rollbacks,
            "journal_records": self.journal.n_recorded
                               if self.journal is not None else 0,
            # per-bucket comm volume rides with the MFU/stall telemetry so
            # the grad_overlap benchmark (and operators) can attribute
            # step-time differences to communication
            "grad_sync": gs["grad_sync"],
            "grad_buckets": gs["n_buckets"],
            "grad_comm_bytes": gs["comm_bytes"],
            "grad_wire_bytes_per_device": gs["wire_bytes_per_device"],
            # scatter_overlap only (0 otherwise): the forward-side param
            # all-gather volume — the other half of the decomposed
            # all-reduce, hidden under forward compute
            "param_gather_bytes": gs["param_gather_bytes"],
            # pipe_overlap only (0 otherwise): schedule-level idle
            # fraction and per-step boundary-activation transfer volume
            "pp_bubble_fraction": gs.get("bubble_fraction", 0.0),
            "act_wire_bytes_per_device":
                gs.get("act_wire_bytes_per_device", 0.0),
        }
        if monitor is not None:
            self.last_straggler_reports = monitor.reports
        if self.metrics is not None:
            # telemetry + per-plan comm volume as named series — the
            # stable surface the autotuner/scrapers consume
            from repro.distributed import gradsync

            self.metrics.set_gauges(log.telemetry, prefix="train_")
            self.metrics.set_gauges(gradsync.metric_series(gs),
                                    prefix="grad_")
            self.metrics.counter(
                "train_rollbacks_total",
                help="journal rollback recoveries").inc(rollbacks)
            if self.metrics_jsonl:
                self.metrics.write_jsonl(self.metrics_jsonl, step=steps,
                                         extra={"final": True})
        return state, log


def resume(ckpt_dir: str, runner: StepRunner, *,
           pipeline=None, process_index: int = 0,
           step: Optional[int] = None):
    """Restore this process's latest (or given) sharded checkpoint.

    Returns ``(state, start_step)`` with ``state`` placed on the runner's
    sharded layout, ready for ``TrainLoop.run(pipeline, total_steps,
    state=state, start_step=start_step)``.  When ``pipeline`` is given it
    is re-aimed at the checkpoint's input position (and the stored
    layout is validated against the pipeline's).  Restores through the
    run's *abstract* state spec, so no throwaway init_state allocation.
    """
    from repro.train.train_step import abstract_state

    like = abstract_state(runner.model, runner.run)
    state, pstate, manifest = ckpt.restore_sharded(
        ckpt_dir, like, step=step, process_index=process_index)
    if pipeline is not None:
        if pstate is None:
            raise ValueError(
                f"checkpoint step {manifest['step']} has no pipeline state")
        pipeline.restore(pstate)
    return runner.place_state(state), manifest["step"]


def resume_resharded(ckpt_dir: str, runner: StepRunner, *,
                     pipeline=None, step: Optional[int] = None):
    """Elastic :func:`resume`: restore a checkpoint written by ANY
    number of processes onto this runner's topology and plan.

    Target regions come from ``runner.state_shardings`` (the
    ``ParallelPlan`` made concrete on the current mesh), so each process
    reads only the stored sub-shards overlapping its new shards — see
    :mod:`repro.distributed.reshard`.  The pipeline is re-aimed
    elastically (global position; the global batch must be unchanged).
    Works on the plain same-topology case too, so ``--elastic-restore``
    is safe to leave on.

    Returns ``(state, start_step)`` like :func:`resume`.
    """
    from repro.distributed.reshard import restore_resharded
    from repro.train.train_step import abstract_state

    like = abstract_state(runner.model, runner.run)
    state, pstate, manifest = restore_resharded(
        ckpt_dir, like, step=step, shardings=runner.state_shardings)
    if pipeline is not None:
        if pstate is None:
            raise ValueError(
                f"checkpoint step {manifest['step']} has no pipeline state")
        pipeline.restore(pstate, elastic=True)
    return runner.place_state(state), manifest["step"]
