"""Cross-host phase-time aggregation + straggler detection.

Hybrid-parallel steps run at the speed of the slowest rank: the
Frontier scaling study (arXiv 2312.12705) attributes most step-time
variance at scale to a handful of straggling hosts, and the
distributed-training survey (arXiv 2407.20018) lists cross-host
timing aggregation as the monitoring baseline.  This module is that
baseline over the tracer's phase windows:

every K steps each rank contributes its per-phase seconds since the
last check (``Tracer.take_window()``) to a ``process_allgather``; the
result is summarized per phase as min/median/max and an **imbalance
factor** ``max / median`` (1.0 = perfectly balanced), and any rank
whose phase time exceeds ``ratio x median`` is reported as a
straggler::

    [straggler] rank=3 phase=data_wait 2.41x median (0.482s vs 0.200s)

Single-process runs skip the collective and still produce the summary
(trivially balanced), so the code path is identical everywhere.  The
collective is called from the SAME step on every rank (the monitor
fires on a deterministic step schedule), which is what makes it safe
to issue from the training loop.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

__all__ = ["PHASES", "allgather_phase_times", "summarize_phases",
           "find_stragglers", "StragglerMonitor"]

# the train-loop span names worth comparing across ranks (a subset of
# the taxonomy in docs/observability.md; "step" anchors the total)
PHASES = ("step", "data_wait", "dispatch", "metrics_resolve",
          "journal_snapshot", "ckpt_commit")


def phase_vector(window: Dict[str, float],
                 phases: Sequence[str] = PHASES) -> np.ndarray:
    return np.asarray([float(window.get(p, 0.0)) for p in phases],
                      np.float64)


# per-process sequence number for the KV-store gather: every rank calls
# allgather_phase_times on the same deterministic step schedule, so the
# counters agree across ranks and each exchange gets a fresh key space
_kv_seq = 0


def _kv_allgather(vec: np.ndarray) -> np.ndarray:
    """Collective-free allgather through the jax.distributed KV store.

    The CPU backend refuses to compile multi-process XLA computations,
    which rules ``process_allgather`` out for multi-controller CPU runs
    (tests, the CI observability job).  Phase timings are a few dozen
    bytes per rank every K steps, so the coordinator's key-value store
    — already up, it bootstrapped the cluster — is a perfectly sized
    transport: set ``obs/gather/<seq>/<rank>``, blocking-get every
    rank's key.
    """
    global _kv_seq
    import jax
    from jax._src import distributed

    client = distributed.global_state.client
    seq, _kv_seq = _kv_seq, _kv_seq + 1
    pidx = jax.process_index()
    client.key_value_set(
        f"obs/gather/{seq}/{pidx}",
        ",".join(repr(float(x)) for x in np.asarray(vec).ravel()))
    rows = []
    for r in range(jax.process_count()):
        val = client.blocking_key_value_get(f"obs/gather/{seq}/{r}",
                                            60_000)
        rows.append([float(x) for x in val.split(",")])
    return np.asarray(rows, np.float64)


def allgather_phase_times(vec: np.ndarray) -> np.ndarray:
    """(n_phases,) per-rank seconds -> (n_processes, n_phases) matrix.

    Multi-controller runs go through
    ``jax.experimental.multihost_utils.process_allgather`` (every rank
    must call this at the same step) — except on the CPU backend, which
    cannot compile multi-process computations and uses the KV-store
    gather instead; single-process runs return the vector as a 1-row
    matrix without touching jax collectives.
    """
    import jax

    if jax.process_count() == 1:
        return np.asarray(vec, np.float64)[None, :]
    if jax.devices()[0].platform == "cpu":
        return _kv_allgather(vec)
    from jax.experimental import multihost_utils

    out = multihost_utils.process_allgather(np.asarray(vec, np.float32))
    return np.asarray(out, np.float64).reshape(jax.process_count(), -1)


def summarize_phases(mat: np.ndarray,
                     phases: Sequence[str] = PHASES
                     ) -> Dict[str, Dict[str, float]]:
    """Per-phase min/median/max seconds + imbalance (max/median)."""
    out: Dict[str, Dict[str, float]] = {}
    for j, p in enumerate(phases):
        col = mat[:, j]
        med = float(np.median(col))
        out[p] = {"min": float(col.min()), "median": med,
                  "max": float(col.max()),
                  "imbalance": float(col.max() / med) if med > 0 else 1.0}
    return out


def find_stragglers(mat: np.ndarray, phases: Sequence[str] = PHASES,
                    ratio: float = 2.0, min_seconds: float = 1e-3
                    ) -> List[Dict[str, Any]]:
    """Ranks whose phase time exceeds ``ratio x median`` (and is at
    least ``min_seconds`` — microsecond phases aren't stragglers)."""
    found: List[Dict[str, Any]] = []
    for j, p in enumerate(phases):
        col = mat[:, j]
        med = float(np.median(col))
        if med <= 0:
            continue
        for r in np.nonzero((col > ratio * med)
                            & (col >= min_seconds))[0]:
            found.append({"rank": int(r), "phase": p,
                          "seconds": float(col[r]), "median": med,
                          "factor": float(col[r] / med)})
    return found


class StragglerMonitor:
    """Every-K-steps cross-host phase comparison over a tracer's
    accumulation window.

    ``maybe_check(step)`` is called once per completed step on every
    rank; on ``step % every == 0`` it takes the tracer window, runs the
    allgather, logs ``[straggler] ...`` lines through ``log`` and
    mirrors the summary into ``registry`` gauges
    (``phase_<name>_imbalance`` / ``_median_s`` / ``_max_s`` and the
    ``straggler_events`` counter).  Reports accumulate on
    ``self.reports`` for programmatic use (tests, the launcher's final
    summary).
    """

    def __init__(self, tracer, *, every: int, ratio: float = 2.0,
                 phases: Sequence[str] = PHASES,
                 registry=None, log: Callable[[str], None] = print,
                 min_seconds: float = 1e-3):
        if every < 1:
            raise ValueError(f"check interval must be >= 1, got {every}")
        self.tracer = tracer
        self.every = every
        self.ratio = ratio
        self.phases = tuple(phases)
        self.registry = registry
        self.log = log
        self.min_seconds = min_seconds
        self.reports: List[Dict[str, Any]] = []

    def maybe_check(self, step: int) -> Optional[Dict[str, Any]]:
        """The check at a multiple of ``every``, as a ``straggler_check``
        span on the comm lane; None between checks."""
        if step % self.every:
            return None
        with self.tracer.span("straggler_check", "comm", step=step):
            return self.check(step)

    def check(self, step: int) -> Dict[str, Any]:
        vec = phase_vector(self.tracer.take_window(), self.phases)
        mat = allgather_phase_times(vec)
        summary = summarize_phases(mat, self.phases)
        stragglers = find_stragglers(mat, self.phases, self.ratio,
                                     self.min_seconds)
        report = {"step": step, "summary": summary,
                  "stragglers": stragglers}
        self.reports.append(report)
        for s in stragglers:
            self.log(f"[straggler] rank={s['rank']} phase={s['phase']} "
                     f"{s['factor']:.2f}x median "
                     f"({s['seconds']:.3f}s vs {s['median']:.3f}s) "
                     f"step={step}")
        if self.registry is not None:
            for p, st in summary.items():
                self.registry.gauge(f"phase_{p}_imbalance").set(
                    st["imbalance"])
                self.registry.gauge(f"phase_{p}_median_s").set(
                    st["median"])
                self.registry.gauge(f"phase_{p}_max_s").set(st["max"])
            self.registry.counter(
                "straggler_events",
                help="rank-phase pairs flagged over ratio x median",
            ).inc(len(stragglers))
        return report
