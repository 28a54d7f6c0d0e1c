"""The sharding-aware async training subsystem (train.runner):

- the jitted step compiles exactly once, with explicit shardings, and the
  donated state buffers are actually reused (old state deleted);
- an async checkpoint snapshotted mid-training (while donation keeps
  rewriting the live buffers) round-trips identical to a synchronous save;
- the device-prefetch adapter preserves batch order and content;
- the PrefetchLoader shutdown race (stop() after the queue drained) ends
  iteration instead of hanging;
- the trailing samples/s log window is the true number of steps since the
  last log entry (seed bug: always ``log_every``).
"""
import dataclasses
import threading
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.configs.base import RunConfig, ShapeConfig
from repro.data.device_prefetch import DevicePrefetch
from repro.data.loader import PrefetchLoader
from repro.launch.mesh import make_host_mesh
from repro.models import build_model
from repro.train import checkpoint as ckpt
from repro.train.optimizer import AdamWConfig
from repro.train.runner import AsyncMetrics, StepRunner, TrainLoop

B, S, VOCAB = 4, 32, 256


def _fixture(d_model=64):
    cfg = dataclasses.replace(
        reduced(get_config("bert-mlm-120m"), d_model=d_model),
        vocab_size=VOCAB, max_position=S)
    model = build_model(cfg)
    run = RunConfig(model=cfg, shape=ShapeConfig("t", S, B, "train"),
                    sharding="ddp", param_dtype="float32",
                    activation_dtype="float32")
    opt = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=50)
    return model, run, opt


def _batches(seed=0, sleep_s=0.0):
    rng = np.random.default_rng(seed)
    while True:
        if sleep_s:
            time.sleep(sleep_s)
        toks = rng.integers(4, VOCAB, (B, S)).astype(np.int32)
        yield {"tokens": toks, "labels": toks,
               "loss_mask": np.ones((B, S), np.float32)}


# ---------------------------------------------------------------------------
# StepRunner: compile-once, explicit shardings, donation
# ---------------------------------------------------------------------------


def test_step_runner_compiles_once_with_shardings_and_donates():
    model, run, opt = _fixture()
    mesh = make_host_mesh(1, 1)
    runner = StepRunner(model, run, opt, mesh)
    assert runner.state_shardings is not None
    assert set(runner.batch_shardings) >= {"tokens", "labels", "loss_mask"}

    state = runner.init_state(0)
    old_leaves = jax.tree_util.tree_leaves(state)
    it = _batches()
    for i in range(4):
        state, metrics = runner(state, it.__next__())
    # exactly one trace across 4 steps
    assert runner.n_traces == 1
    # donated: the original state buffers were consumed in place
    assert all(leaf.is_deleted() for leaf in old_leaves)
    # outputs land on the explicit state shardings
    jax.tree_util.tree_map(
        lambda x, sh: None if x.sharding == sh else pytest.fail(
            f"{x.sharding} != {sh}"),
        state, runner.state_shardings)
    assert float(metrics["loss"]) == float(metrics["loss"])  # not NaN-free
                                                             # check, just
                                                             # resolvable


def test_step_runner_aot_compile_once_and_cost():
    model, run, opt = _fixture()
    runner = StepRunner(model, run, opt, make_host_mesh(1, 1))
    state = runner.init_state(0)
    it = _batches()
    first = next(it)
    runner.compile(state, first)
    assert runner.compiled is not None
    n_after_compile = runner.n_traces
    assert n_after_compile == 1
    for _ in range(3):
        state, _ = runner(state, next(it))
    assert runner.n_traces == 1  # no retrace after AOT compile
    cost = runner.step_cost()   # hlocost over the optimized HLO
    assert cost is not None and cost.flops > 0
    assert runner.mfu(0.1, B * S, peak_flops=197e12) > 0


def test_trainloop_telemetry_reports_single_compile():
    model, run, opt = _fixture()
    runner = StepRunner(model, run, opt, make_host_mesh(1, 1))
    _, log = TrainLoop(runner, log_every=3).run(_batches(), 7)
    assert log.telemetry["n_traces"] == 1
    assert log.steps == [1, 3, 6, 7]
    assert len(log.metrics) == len(log.steps)
    assert len(log.mfu) == len(log.steps)
    assert 0.0 <= log.telemetry["stall_fraction"] <= 1.0


# ---------------------------------------------------------------------------
# Async checkpointing
# ---------------------------------------------------------------------------


def test_async_checkpoint_mid_training_matches_sync_save(tmp_path):
    model, run, opt = _fixture()
    runner = StepRunner(model, run, opt, make_host_mesh(1, 1))
    state = runner.init_state(0)
    it = _batches()
    state, _ = runner(state, next(it))
    state, _ = runner(state, next(it))

    sync_path = str(tmp_path / "sync")
    async_path = str(tmp_path / "async")
    jax.block_until_ready(state)
    ckpt.save(sync_path, state, step=2)
    with ckpt.AsyncCheckpointer(async_path) as saver:
        saver.save(state, step=2)
        # keep training immediately: donation reuses state's buffers while
        # the async write is (possibly) still serializing its snapshot
        for _ in range(3):
            state, _ = runner(state, next(it))
        saver.wait()
        assert saver.n_saved == 1

    a = ckpt.restore(async_path, state)
    b = ckpt.restore(sync_path, state)
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def test_trainloop_async_checkpoint_restorable(tmp_path):
    model, run, opt = _fixture()
    runner = StepRunner(model, run, opt, make_host_mesh(1, 1))
    path = str(tmp_path / "ck")
    state, _ = TrainLoop(runner, log_every=2, ckpt_path=path,
                         ckpt_every=3).run(_batches(), 6)
    back = ckpt.restore(path, state)  # final background save, flushed
    for la, lb in zip(jax.tree_util.tree_leaves(state["params"]),
                      jax.tree_util.tree_leaves(back["params"])):
        np.testing.assert_array_equal(np.float32(la), np.float32(lb))


# ---------------------------------------------------------------------------
# Device prefetch
# ---------------------------------------------------------------------------


def test_device_prefetch_preserves_order_and_content():
    batches = [{"tokens": np.full((2, 3), i, np.int32)} for i in range(7)]
    pf = DevicePrefetch(iter(batches), size=2)
    out = list(pf)
    assert len(out) == 7
    assert pf.puts == 7
    for i, b in enumerate(out):
        assert isinstance(b["tokens"], jax.Array)
        np.testing.assert_array_equal(np.asarray(b["tokens"]),
                                      batches[i]["tokens"])


def test_device_prefetch_deterministic_and_short_iterators():
    def gen():
        rng = np.random.default_rng(3)
        for _ in range(5):
            yield {"x": rng.integers(0, 99, (4,)).astype(np.int32)}

    a = [np.asarray(b["x"]) for b in DevicePrefetch(gen(), size=3)]
    b = [np.asarray(b["x"]) for b in DevicePrefetch(gen(), size=3)]
    for xa, xb in zip(a, b):
        np.testing.assert_array_equal(xa, xb)
    # iterator shorter than the buffer
    short = [{"x": np.arange(2, dtype=np.int32)}]
    assert len(list(DevicePrefetch(iter(short), size=4))) == 1
    # sharded placement
    mesh = make_host_mesh(1, 1)
    from jax.sharding import NamedSharding, PartitionSpec as P

    sh = NamedSharding(mesh, P("data"))
    out = list(DevicePrefetch(iter([{"x": np.zeros((4, 2), np.float32),
                                     "extra": np.zeros((3,), np.float32)}]),
                              shardings={"x": sh}))
    assert out[0]["x"].sharding == sh  # extra key: default placement, no err


# ---------------------------------------------------------------------------
# PrefetchLoader shutdown race
# ---------------------------------------------------------------------------


class _StubDS:
    shards = [0]

    def read_shard(self, _i):
        return (np.zeros((8, 4), np.int32), np.ones((8, 4), np.float32))


def test_prefetch_loader_stop_terminates_blocked_consumer():
    loader = PrefetchLoader(_StubDS(), batch_size=8, n_workers=1, prefetch=2)
    it = iter(loader)
    next(it)

    done = threading.Event()

    def consume_rest():
        for _ in it:
            pass
        done.set()

    t = threading.Thread(target=consume_rest, daemon=True)
    t.start()
    time.sleep(0.1)     # let the consumer drain the queue / block on get
    loader.stop()
    assert done.wait(timeout=5.0), \
        "consumer hung after stop() — shutdown race regression"


# ---------------------------------------------------------------------------
# Non-blocking metrics + samples/s window accounting
# ---------------------------------------------------------------------------


class _NeverReady:
    dtype = np.float32

    def is_ready(self):
        return False

    def __float__(self):
        return 7.0


def test_async_metrics_polls_only_ready_entries():
    am = AsyncMetrics(max_pending=10)
    am.push({"step": 1}, {"loss": np.float32(1.0)})   # plain scalar: ready
    am.push({"step": 2}, {"loss": _NeverReady()})
    resolved = am.poll()
    assert [m["step"] for m, _ in resolved] == [1]
    assert resolved[0][1]["loss"] == 1.0
    drained = am.drain()
    assert [m["step"] for m, _ in drained] == [2]
    assert drained[0][1]["loss"] == 7.0


def test_async_metrics_bounds_pending_window():
    am = AsyncMetrics(max_pending=2)
    for i in range(6):
        am.push({"step": i}, {"loss": _NeverReady()})
    out = am.poll()
    assert len(out) == 4 and am.forced_resolves == 4  # kept window of 2


def test_async_metrics_interleaved_preserves_push_order():
    """The poll() contract: entries resolve in PUSH order, never around
    an unready head.  A ready step-3 behind an unready step-2 is held
    back, so consumers of ``TrainLog.metrics`` see monotone steps."""
    am = AsyncMetrics(max_pending=10)
    am.push({"step": 1}, {"loss": np.float32(1.0)})
    am.push({"step": 2}, {"loss": _NeverReady()})
    am.push({"step": 3}, {"loss": np.float32(3.0)})   # ready, but queued
    assert [m["step"] for m, _ in am.poll()] == [1]
    assert [m["step"] for m, _ in am.drain()] == [2, 3]


def test_async_metrics_forced_resolves_keep_push_order():
    """When the pending window overflows, the forced-resolve pass runs
    BEFORE the ready scan — the oldest (blocking) entries come out
    first, so the stream stays in push order even under pressure."""
    am = AsyncMetrics(max_pending=1)
    am.push({"step": 1}, {"loss": _NeverReady()})
    am.push({"step": 2}, {"loss": _NeverReady()})
    am.push({"step": 3}, {"loss": np.float32(3.0)})
    assert [m["step"] for m, _ in am.poll()] == [1, 2, 3]
    assert am.forced_resolves == 2


def test_async_metrics_random_interleave_monotone():
    am = AsyncMetrics(max_pending=3)
    seen = []
    for step in range(1, 21):
        loss = _NeverReady() if step % 3 == 0 else np.float32(step)
        am.push({"step": step}, {"loss": loss})
        seen += [m["step"] for m, _ in am.poll()]
    seen += [m["step"] for m, _ in am.drain()]
    assert seen == list(range(1, 21))   # strictly monotone, no gaps


def test_drain_excluded_from_stall_fraction():
    """Seed bug: the end-of-run ``drain()`` (waiting out the metrics
    lag window) was lumped into ``host_blocked_s``, inflating
    ``stall_fraction`` on short runs.  With a drain forced to take
    0.25s on an otherwise fast loop, the drain must surface in
    ``telemetry['drain_s']`` and NOT in the stall accounting."""
    import repro.train.runner as runner_mod

    class _SlowDrain(AsyncMetrics):
        def drain(self):
            time.sleep(0.25)
            return super().drain()

    model, run, opt = _fixture(d_model=32)
    runner = StepRunner(model, run, opt, make_host_mesh(1, 1))
    orig = runner_mod.AsyncMetrics
    runner_mod.AsyncMetrics = _SlowDrain
    try:
        loop = TrainLoop(runner, log_every=4, device_prefetch=False)
        _, log = loop.run(_batches(), 8)
    finally:
        runner_mod.AsyncMetrics = orig
    t = log.telemetry
    assert t["drain_s"] >= 0.25
    # the old accounting would have put the 0.25s sleep in here too
    assert t["host_blocked_s"] < 0.25, t
    assert t["stall_fraction"] == pytest.approx(
        t["host_blocked_s"] / t["total_s"], rel=1e-6)


def test_final_log_window_not_inflated():
    """Seed bug: the last log entry divided ``log_every`` steps' samples by
    a window of fewer steps, inflating throughput.  With a loader-bound
    loop (20ms/batch), correct accounting makes the final short-window
    entry agree with the steady-state entry; the old code overstated it
    ~log_every/actual_window times."""
    model, run, opt = _fixture(d_model=32)
    runner = StepRunner(model, run, opt, make_host_mesh(1, 1))
    loop = TrainLoop(runner, log_every=10, device_prefetch=False)
    _, log = loop.run(_batches(sleep_s=0.03), 12)
    assert log.steps == [1, 10, 12]
    steady, final = log.samples_per_s[1], log.samples_per_s[2]
    # old accounting reported ~5x here (10-step numerator over a 2-step
    # window); the bound stays loose enough for scheduler jitter
    assert final < 3.5 * steady, (steady, final)
