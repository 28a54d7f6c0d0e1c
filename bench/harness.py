"""Phases of one benchmark run: set-up, the measured window, the
comparison with the reference, and the result line.

``run.py`` calls them in order; the tests call them one by one on the
CPU at small sizes.  Everything that belongs to one configuration, one
traffic mix, one cell or one per-layer metric is a file of its own under
``bench/``, found by the name ``BENCHMARK.json`` gives it; a
configuration's model (reference, program config and counts) is the
module its file's ``reference`` key names (:func:`model_of`).
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import gc
import importlib.util
import json
import math
import time
from pathlib import Path
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import core

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# what to run
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Spec:
    name: str
    chips: int
    config: Dict[str, Any]      # bench/configs/<config>.json
    traffic: Dict[str, Any]     # bench/traffic/<traffic>.json
    cell: Dict[str, Any]        # bench/workloads/<name>.json
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_spec(workload: str, root: Path = ROOT) -> Spec:
    """The cell ``workload`` of ``<root>/BENCHMARK.json`` with its files."""
    bench = _json(root / "BENCHMARK.json")
    entry = {w["name"]: w for w in bench["workloads"]}.get(workload)
    if entry is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    has = lambda m: workload in m.get("workloads", [workload])
    return Spec(
        name=workload, chips=entry["chips"],
        config=_json(root / "bench" / "configs" / f"{entry['config']}.json"),
        traffic=_json(root / "bench" / "traffic" / f"{entry['traffic']}.json"),
        cell=_json(root / "bench" / "workloads" / f"{workload}.json"),
        end_to_end=[m for m in bench["end_to_end"] if has(m)],
        per_layer=[m for m in bench["per_layer"] if has(m)])


def chips(n: int) -> List[Any]:
    """The first ``n`` TPU chips; raises :class:`NoChip` otherwise."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devices[0].platform!r})")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX finds {len(devices)}")
    return devices[:n]


@functools.lru_cache(maxsize=None)
def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (metric readers, sources,
    models), loaded once a process."""
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('-', '_').replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model_of(c: Dict[str, Any]):
    """The model module of configuration file ``c``:
    ``bench/reference/<c["reference"]>.py``, whose interface
    ``bench/reference/__init__.py`` states."""
    return load_module("reference", c["reference"])


def program_config(c: Dict[str, Any]):
    """The program's ModelConfig for configuration file ``c``."""
    return model_of(c).program_config(c)


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------


class Feed:
    """Hands one underlying batch iterator to successive ``TrainLoop.run``
    calls without losing what a run's device prefetch read ahead: after a
    run, :meth:`settle` puts the batches it did not consume back in
    front.  Batches of the global steps in ``keep`` are kept for the
    comparison."""

    def __init__(self, it, keep: Sequence[int] = ()):
        self._it = it
        self._back: collections.deque = collections.deque()
        self._handed: List[Any] = []
        self.keep = set(keep)
        self.kept: Dict[int, Any] = {}
        self.step = 0

    def take(self):
        self._handed = []

        def gen():
            while True:
                b = self._back.popleft() if self._back else next(self._it)
                self._handed.append(b)
                yield b

        return gen()

    def settle(self, consumed: int):
        for i, b in enumerate(self._handed[:consumed]):
            if self.step + i in self.keep:
                self.kept[self.step + i] = b
        self._back.extendleft(reversed(self._handed[consumed:]))
        self._handed = []
        self.step += consumed


@dataclasses.dataclass
class Setup:
    spec: Spec
    seed: int
    devices: List[Any]
    runner: Any
    loop: Any
    source: Any
    feed: Feed
    state: Any
    tokens_per_step: int
    step: int = 0
    readings: Dict[str, Any] = dataclasses.field(default_factory=dict)


def adamw(c):
    from repro.train.optimizer import AdamWConfig

    o = c["optimizer"]
    return AdamWConfig(**{f.name: o[f.name]
                          for f in dataclasses.fields(AdamWConfig)})


def make_mesh(devices):
    from jax.sharding import AxisType, Mesh

    return Mesh(np.array(devices).reshape(len(devices), 1),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)


def init_state(c, seed: int, shardings):
    """The train state from ``seed``, made on the device in one call: the
    reference's weights, zero moments, step 0."""
    init_params = model_of(c).init_params

    def make(key):
        p = init_params(c, key)
        z = jax.tree_util.tree_map(jnp.zeros_like, p)
        return {"params": p, "opt": {"mu": z, "nu": z,
                                     "step": jnp.zeros((), jnp.int32)}}

    return jax.jit(make, out_shardings=shardings)(core.seed_key(seed))


def make_runner(spec: Spec, devices):
    """The step runner, as ``launch/train.py`` builds it: f32
    ``default_run_config``, AdamW, the host mesh, 25 MB grad buckets."""
    from repro.configs import default_run_config
    from repro.configs.base import ShapeConfig
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model
    from repro.train.runner import StepRunner

    c, n = spec.config, len(devices)
    cfg = program_config(c)
    model = build_model(cfg)
    batch = spec.cell["batch_per_chip"] * n
    run = default_run_config(
        cfg, ShapeConfig(spec.name, c["seq_len"], batch, "train"),
        sharding=spec.traffic["sharding"])
    mesh = make_host_mesh(data=n) if n == jax.device_count() \
        else make_mesh(devices)
    want = jax.tree_util.tree_structure(model.abstract())
    got = jax.tree_util.tree_structure(
        model_of(c).param_shapes(c), is_leaf=core.is_shape)
    if want != got:
        raise ValueError(f"the reference's weights do not have the "
                         f"program's layout:\n{got}\n!=\n{want}")
    return StepRunner(model, run, adamw(c), mesh,
                      grad_bucket_mb=spec.traffic["grad_bucket_mb"])


def build(spec: Spec, devices, seed: int, *, peak_flops: float,
          root: Path = ROOT, keep_steps: Sequence[int] = (),
          runner=None) -> Setup:
    """Runner (``make_runner``'s, unless one is given), loop, data source
    and state from ``seed``."""
    from repro.train.runner import TrainLoop

    c = spec.config
    if runner is None:
        runner = make_runner(spec, devices)
    batch = runner.run.shape.global_batch
    state = init_state(c, seed, runner.state_shardings)
    source = load_module("sources", spec.traffic["source"]).open_source(
        spec, runner, seed, root)
    loop = TrainLoop(runner, log_every=spec.traffic["log_every"],
                     device_prefetch=source.device_prefetch,
                     prefetch_size=spec.cell.get("device_prefetch", 2),
                     peak_flops=peak_flops)
    return Setup(spec, seed, list(devices), runner, loop, source,
                 Feed(source.batches(), keep_steps), state,
                 batch * c["seq_len"])


def run_steps(s: Setup, n: int):
    """Drive ``n`` steps through the loop; returns its TrainerLog."""
    s.state, log = s.loop.run(s.feed.take(), s.step + n, state=s.state,
                              start_step=s.step)
    s.feed.settle(n)
    s.step += n
    return log


def first_grad_norms(o, mu, grad_norm: float) -> List[float]:
    """Each leaf's norm of the first gradient as the optimizer got it,
    before clipping, from its first moment after one step: ``mu`` is
    ``(1 - b1)`` times the gradient scaled by ``min(1, clip / norm)``,
    with ``norm`` the global norm the program clipped by."""
    scale = min(1.0, o["grad_clip"] / max(grad_norm, 1e-9)) \
        if o["grad_clip"] else 1.0
    return [x / (1 - o["b1"]) / scale for x in core.leaf_norms(mu)]


def setup_steps(s: Setup, n_ref: int, n_warm: int):
    """The first ``n_ref`` steps one at a time, with the program's
    readings for the comparison, then ``n_warm`` more as warm-up."""
    c = s.spec.config
    o = c["optimizer"]
    init_params = model_of(c).init_params
    losses = []
    for i in range(n_ref):
        log = run_steps(s, 1)
        losses.append(log.metrics[-1]["loss"])
        if i == 0:
            s.readings["grad_norms"] = first_grad_norms(
                o, s.state["opt"]["mu"], log.metrics[-1]["grad_norm"])
    s.readings["change_norms"] = core.leaf_norms(jax.jit(
        lambda p, key: jax.tree_util.tree_map(
            jnp.subtract, p, init_params(c, key)))(
                s.state["params"], core.seed_key(s.seed)))
    s.readings["losses"] = losses
    if n_warm:
        run_steps(s, n_warm)
    jax.block_until_ready(s.state)


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Window:
    steps: int
    seconds: float
    t0: float
    t1: float
    losses: List[float]
    compiles: int
    traces: int


class _CompileCounter:
    """Counts compilations, and programs loaded from the persistent
    cache, while it is installed (JAX's monitoring events)."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.n = 0

    def _duration(self, name, secs, **kw):
        self.n += name == self.COMPILE

    def _event(self, name, **kw):
        self.n += name == self.CACHE_HIT

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)
        return False


def window_steps(seconds: float, step_s: float) -> int:
    """A whole number of steps that lasts about ``seconds``."""
    return max(1, round(seconds / step_s))


def measure(s: Setup, n_steps: int, annotate=None) -> Window:
    """The measured window: ``n_steps`` steps through the loop, ending at
    ``block_until_ready`` on the state (``TrainLoop.run`` waits on it)."""
    traces = s.runner.n_traces
    with _CompileCounter() as counter:
        t0 = time.perf_counter()
        log = run_steps(s, n_steps)
        t1 = time.perf_counter()
    if annotate is not None:
        annotate(t0, t1)
    return Window(n_steps, t1 - t0, t0, t1,
                  [m["loss"] for m in log.metrics], counter.n,
                  s.runner.n_traces - traces)


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def release(s: Setup):
    """Free the program's state before the reference runs."""
    s.source.close()
    s.state = None
    s.loop = s.runner = None
    gc.collect()


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------


def reference_readings(spec: Spec, seed: int, batches, devices, *,
                       dtype=jnp.float32, rows=slice(None),
                       global_den=False, precision=None
                       ) -> Dict[str, Any]:
    """The reference followed through ``batches`` from ``seed``, its
    float32 matmuls at ``precision``: unless given, the one the
    configuration states (``matmul_precision``), as the program runs."""
    m = model_of(spec.config)
    ref = core.Reference(spec.config, m.nll_sum, m.init_params, dtype=dtype,
                         devices=devices,
                         block_rows=spec.cell["reference_block_rows"],
                         precision=precision
                         or spec.config["matmul_precision"])
    return core.follow(spec.config, seed, batches, ref=ref, rows=rows,
                       global_den=global_den)


# a leaf whose reference gradient is under this share of the median
# leaf's moves by round-off alone (a key bias under softmax), so it is
# left out of the gradient and change comparisons
NEGLIGIBLE = 1e-3


def gaps(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    """The numbers compared: the largest relative gap of the step losses;
    and of the norms of the first gradient and of the change over the
    followed steps, each by the worst leaf, against the larger of the
    reference leaf's norm and the median leaf's."""
    loss = max(abs(a - b) / abs(b)
               for a, b in zip(prog["losses"], ref["losses"]))
    rg = np.asarray(ref["grad_norms"])
    live = rg >= NEGLIGIBLE * np.median(rg)
    out = {"loss_gap": float(loss)}
    for key, name in (("grad_norms", "grad_gap"),
                      ("change_norms", "change_gap")):
        p = np.asarray(prog[key])[live]
        r = np.asarray(ref[key])[live]
        floor = np.maximum(r, np.median(r))
        out[name] = float(np.max(np.abs(p - r) / floor))
    return out


def checks(values: Dict[str, float], limits: Dict[str, float]
           ) -> Dict[str, Dict[str, float]]:
    return {k: {"value": v, "limit": limits[k]} for k, v in values.items()}


def is_correct(chk: Dict[str, Dict[str, float]]) -> bool:
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in chk.values())


# ---------------------------------------------------------------------------
# the result
# ---------------------------------------------------------------------------


def device_info(devices) -> Dict[str, Any]:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}
