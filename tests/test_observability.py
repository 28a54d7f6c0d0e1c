"""Unified tracing + metrics subsystem (``repro.observability``):

- Tracer semantics: span nesting/reentrancy, thread-default lanes, the
  drop-oldest ring buffer, async-event pairing, window accumulation;
- Chrome-trace export schema (``ph``/``ts``/``dur``/``pid``/``tid``)
  validated on a flushed file, strict-JSON parseable;
- the metrics registry: typed series, kind-mismatch rejection,
  histogram quantiles, the telemetry gauge bridge, JSONL + Prometheus
  exporters;
- straggler detection: synthetic matrices, the monitor's deterministic
  step schedule, registry mirroring;
- ``tools/trace_summary.py`` merging multiple ranks' files;
- end-to-end: a traced ``TrainLoop`` whose spans cover >=95% of the
  wall window AND sum to the stall telemetry (trace == telemetry), a
  traced ``PagedServeEngine`` with per-request async intervals + TTFT,
  and a REAL 2-process ``jax.distributed`` run (``tests/_faults.py``
  harness) whose per-rank trace files merge into one coherent timeline
  and whose straggler monitor flags the slow rank on BOTH ranks.
"""
import dataclasses
import importlib.util
import json
import math
import os
import re
import time

import numpy as np
import pytest

import jax

from _faults import run_workers

from repro.configs import get_config, reduced
from repro.configs.base import RunConfig, ShapeConfig
from repro.launch.mesh import make_host_mesh
from repro.models import build_model
from repro.observability import (NULL_TRACER, MetricsRegistry, NullTracer,
                                 StragglerMonitor, Tracer,
                                 find_stragglers, get_tracer, set_tracer,
                                 summarize_phases)
from repro.observability.trace import DEFAULT_LANES
from repro.train.optimizer import AdamWConfig
from repro.train.runner import StepRunner, TrainLoop

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# Tracer semantics
# ---------------------------------------------------------------------------


def test_span_nesting_and_reentrancy():
    tr = Tracer()

    def walk(depth):
        with tr.span("walk", "loop", depth=depth):
            if depth:
                walk(depth - 1)

    with tr.span("outer", "loop"):
        with tr.span("inner", "data"):
            pass
        walk(3)
    xs = [e for e in tr.chrome_events() if e["ph"] == "X"]
    names = [e["name"] for e in xs]
    # children exit (and record) before their parents
    assert names == ["inner"] + ["walk"] * 4 + ["outer"]
    depths = [e["args"]["depth"] for e in xs if e["name"] == "walk"]
    assert depths == [0, 1, 2, 3]
    # nesting is containment: every walk span sits inside "outer"
    # (1us slop: float64 us-since-epoch resolution is ~0.5us)
    outer = xs[-1]
    for e in xs[:-1]:
        assert e["ts"] >= outer["ts"] - 1.0
        assert e["ts"] + e["dur"] <= outer["ts"] + outer["dur"] + 1.0


def test_thread_lane_default_resolves_none():
    tr = Tracer()
    tr.thread_lane("fetch-w3")
    with tr.span("batch_fetch"):          # lane=None -> thread default
        pass
    tr.thread_lane(None)
    with tr.span("bare"):                 # no default -> "compute"
        pass
    xs = [e for e in tr.chrome_events() if e["ph"] == "X"]
    assert [e["cat"] for e in xs] == ["fetch-w3", "compute"]
    # the dynamic lane got an id past the default taxonomy
    assert xs[0]["tid"] >= len(DEFAULT_LANES)


def test_ring_buffer_drops_oldest():
    tr = Tracer(capacity=8)
    for i in range(25):
        tr.complete("ev", "loop", 0.0, 1e-6, i=i)
    assert len(tr) == 8
    assert tr.dropped == 17
    xs = [e for e in tr.chrome_events() if e["ph"] == "X"]
    # the survivors are exactly the NEWEST 8, in order
    assert [e["args"]["i"] for e in xs] == list(range(17, 25))
    # the straggler window still accounts every event (not ring-bound)
    assert tr.take_window()["ev"] == pytest.approx(25e-6)


def test_async_events_pair_and_instants():
    tr = Tracer()
    tr.begin_async("request", 7, "serve", prompt=3)
    tr.instant("first_token", "serve", rid=7)
    tr.end_async("request", 7, "serve", new_tokens=4)
    evs = [e for e in tr.chrome_events() if e["ph"] in ("b", "e", "i")]
    assert [e["ph"] for e in evs] == ["b", "i", "e"]
    b, i, e = evs
    assert b["id"] == e["id"] == "7"
    assert b["name"] == e["name"] == "request"
    assert i["s"] == "t" and i["args"]["rid"] == 7
    assert b["ts"] <= i["ts"] + 1.0 and i["ts"] <= e["ts"] + 1.0


def test_take_window_accumulates_and_resets():
    tr = Tracer()
    tr.complete("data_wait", "data", 0.0, 0.25)
    tr.complete("data_wait", "data", 0.0, 0.25)
    tr.complete("dispatch", "compute", 0.0, 0.125)
    w = tr.take_window()
    assert w == {"data_wait": pytest.approx(0.5),
                 "dispatch": pytest.approx(0.125)}
    assert tr.take_window() == {}            # reset


def test_null_tracer_is_inert_and_default():
    prev = set_tracer(None)
    try:
        t = get_tracer()
        assert isinstance(t, NullTracer) and not t.enabled
        with t.span("x", "loop"):
            t.complete("y", None, 0.0, 1.0)
            t.instant("z")
            t.begin_async("a", 1)
            t.end_async("a", 1)
        assert len(t) == 0 and t.take_window() == {}
        assert t.chrome_events() == []
        # span() hands back one shared object: no per-call allocation
        assert t.span("a") is t.span("b") is NULL_TRACER.span("c")
    finally:
        set_tracer(prev)


def test_set_tracer_returns_previous():
    a, b = Tracer(), Tracer()
    prev0 = set_tracer(a)
    try:
        assert get_tracer() is a
        assert set_tracer(b) is a
        assert get_tracer() is b
    finally:
        set_tracer(prev0)


# ---------------------------------------------------------------------------
# Chrome-trace JSON schema
# ---------------------------------------------------------------------------


def test_flushed_trace_schema(tmp_path):
    tr = Tracer(process_index=3)
    with tr.span("step", "loop", step=0):
        with tr.span("data_wait", "data"):
            time.sleep(0.001)
    tr.instant("rollback", "loop", step=0)
    tr.begin_async("request", 1, "serve")
    tr.end_async("request", 1, "serve")
    path = tr.flush(str(tmp_path))
    assert os.path.basename(path) == "trace-3.json"

    with open(path) as f:
        doc = json.load(f, parse_constant=pytest.fail)  # strict: no NaN
    assert doc["displayTimeUnit"] == "ms"
    assert doc["otherData"]["process_index"] == 3
    assert doc["otherData"]["dropped"] == 0
    evs = doc["traceEvents"]
    assert all(e["pid"] == 3 for e in evs)

    meta = [e for e in evs if e["ph"] == "M"]
    lanes = {e["args"]["name"]: e["tid"] for e in meta
             if e["name"] == "thread_name"}
    assert set(DEFAULT_LANES) <= set(lanes)
    assert any(e["name"] == "process_name"
               and e["args"]["name"] == "host3" for e in meta)

    for e in evs:
        assert e["ph"] in ("M", "X", "i", "b", "e"), e
        if e["ph"] == "M":
            continue
        assert isinstance(e["ts"], float) and e["ts"] > 0
        assert isinstance(e["tid"], int) and e["cat"] in lanes
        assert lanes[e["cat"]] == e["tid"]
        if e["ph"] == "X":
            assert e["dur"] >= 0
        if e["ph"] == "i":
            assert e["s"] == "t"
        if e["ph"] in ("b", "e"):
            assert e["id"] == "1"
    # metadata precedes data events, and flush is idempotent
    assert [e["ph"] for e in evs[:len(meta)]] == ["M"] * len(meta)
    assert tr.flush(str(tmp_path)) == path
    with open(path) as f:
        assert json.load(f)["traceEvents"] == evs


def test_trace_timestamps_are_wall_anchored():
    before = time.time() * 1e6
    tr = Tracer()
    tr.complete("x", "loop", time.perf_counter(), time.perf_counter())
    after = time.time() * 1e6
    ts = [e["ts"] for e in tr.chrome_events() if e["ph"] == "X"][0]
    assert before - 1e6 <= ts <= after + 1e6   # within 1s of wall clock


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_registry_typed_series_and_mismatch():
    reg = MetricsRegistry()
    c = reg.counter("reqs", help="requests")
    c.inc()
    c.inc(2)
    assert reg.counter("reqs") is c and c.value == 3
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("util")
    g.set(0.5)
    g.inc(0.25)
    assert reg["util"].value == pytest.approx(0.75)
    with pytest.raises(TypeError):
        reg.gauge("reqs")                 # kind mismatch
    with pytest.raises(ValueError):
        reg.counter("bad name")           # not Prometheus-safe
    assert reg.names() == ["reqs", "util"]


def test_histogram_quantiles_and_snapshot():
    reg = MetricsRegistry()
    h = reg.histogram("lat_ms", (1, 5, 10, 50))
    for v in (0.2, 0.4, 3, 7, 7, 120):
        h.observe(v)
    assert h.count == 6 and h.sum == pytest.approx(137.6)
    assert h.quantile(0.5) == 5            # bucket-resolution median
    assert h.quantile(1.0) == 50           # +inf clamps to last bound
    snap = h.snapshot()
    assert snap["buckets"] == {"1.0": 2, "5.0": 3, "10.0": 5, "50.0": 5}
    with pytest.raises(ValueError):
        reg.histogram("unsorted", (5, 1))


def test_set_gauges_bridges_only_finite_numbers():
    reg = MetricsRegistry()
    reg.set_gauges({"stall_fraction": 0.25, "n_traces": 1,
                    "grad_sync": "bucketed_overlap",   # str: skipped
                    "ok": True,                        # bool: skipped
                    "mfu": float("nan")},              # NaN: skipped
                   prefix="train_")
    assert reg.names() == ["train_stall_fraction", "train_n_traces"]
    assert reg["train_stall_fraction"].value == 0.25


def test_jsonl_and_prometheus_export(tmp_path):
    reg = MetricsRegistry()
    reg.counter("rollbacks", help="journal rollbacks").inc(2)
    reg.gauge("util").set(0.5)
    reg.histogram("lat_ms", (1, 10)).observe(3)
    p = str(tmp_path / "m.jsonl")
    reg.write_jsonl(p, step=4)
    reg.write_jsonl(p, step=8, extra={"final": True})
    lines = [json.loads(x) for x in open(p)]
    assert [ln["step"] for ln in lines] == [4, 8]
    assert lines[1]["final"] is True
    assert lines[0]["metrics"]["rollbacks"] == 2
    assert lines[0]["metrics"]["lat_ms"]["count"] == 1

    prom_path = str(tmp_path / "metrics.prom")
    reg.write_prometheus(prom_path)
    text = open(prom_path).read()
    assert "# HELP rollbacks journal rollbacks" in text
    assert "# TYPE rollbacks counter" in text
    assert "# TYPE lat_ms histogram" in text
    assert 'lat_ms_bucket{le="10.0"} 1' in text
    assert 'lat_ms_bucket{le="+Inf"} 1' in text
    assert "lat_ms_sum 3.0" in text
    assert not os.path.exists(prom_path + ".tmp")  # atomic rename


# ---------------------------------------------------------------------------
# straggler detection
# ---------------------------------------------------------------------------


def test_find_stragglers_synthetic_matrix():
    # 4 ranks x 2 phases; rank 2 is 4x the data_wait median
    mat = np.array([[1.0, 0.10], [1.0, 0.11], [1.0, 0.40], [1.0, 0.09]])
    phases = ("step", "data_wait")
    s = find_stragglers(mat, phases, ratio=2.0)
    assert len(s) == 1
    assert s[0]["rank"] == 2 and s[0]["phase"] == "data_wait"
    assert s[0]["factor"] == pytest.approx(0.40 / np.median(mat[:, 1]))
    # below the min_seconds floor nothing is a straggler
    assert find_stragglers(mat * 1e-4, phases, ratio=2.0) == []
    summary = summarize_phases(mat, phases)
    assert summary["step"]["imbalance"] == pytest.approx(1.0)
    assert summary["data_wait"]["max"] == pytest.approx(0.40)


def test_monitor_schedule_registry_and_log():
    tr = Tracer()
    reg = MetricsRegistry()
    lines = []
    mon = StragglerMonitor(tr, every=3, ratio=2.0, registry=reg,
                           log=lines.append)
    for step in range(1, 7):
        tr.complete("data_wait", "data", 0.0, 0.01)
        fired = mon.maybe_check(step)
        assert (fired is not None) == (step % 3 == 0)
    assert len(mon.reports) == 2
    # single process: trivially balanced, no straggler lines
    assert lines == [] and reg["straggler_events"].value == 0
    assert reg["phase_data_wait_imbalance"].value == pytest.approx(1.0)
    # each check consumed the window: 3 steps x 10ms per report
    for r in mon.reports:
        assert r["summary"]["data_wait"]["median"] == pytest.approx(0.03)
    with pytest.raises(ValueError):
        StragglerMonitor(tr, every=0)


# ---------------------------------------------------------------------------
# trace_summary tool
# ---------------------------------------------------------------------------


def test_trace_summary_merges_ranks(tmp_path, capsys):
    ts = _load_tool("trace_summary")
    for pidx in (0, 1):
        tr = Tracer(process_index=pidx)
        for i in range(3):
            with tr.span("step", "loop", step=i):
                time.sleep(0.001 * (1 + 2 * pidx))
        tr.flush(str(tmp_path))
    events = ts.load_events([str(tmp_path)])
    xs = ts.spans(events)
    assert len(xs) == 6 and {e["pid"] for e in xs} == {0, 1}
    rows = ts.flame_rows(events)
    assert rows[0]["name"] == "step" and rows[0]["count"] == 6
    by_rank = ts.flame_rows(events, by_rank=True)
    assert {(r["rank"], r["name"]) for r in by_rank} \
        == {(0, "step"), (1, "step")}
    top = ts.top_spans(events, 2)
    assert len(top) == 2 and all(e["pid"] == 1 for e in top)  # slower rank
    # bare-list files (no traceEvents wrapper) load too
    bare = tmp_path / "bare.json"
    bare.write_text(json.dumps(xs))
    assert len(ts.spans(ts.load_events([str(bare)]))) == 6
    assert ts.main([str(tmp_path), "-n", "3"]) == 0
    out = capsys.readouterr().out
    assert "2 rank(s)" in out and "step" in out


# ---------------------------------------------------------------------------
# end-to-end: traced TrainLoop — coverage + trace == telemetry
# ---------------------------------------------------------------------------

B, S, VOCAB = 4, 32, 256


def _fixture(d_model=32):
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


def _union_seconds(intervals):
    total, end = 0.0, -math.inf
    for a, b in sorted(intervals):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def test_trainloop_trace_covers_wall_and_matches_telemetry():
    """The two acceptance numbers: spans account for >=95% of the wall
    window between first and last step, and the traced stall regions
    reproduce ``host_blocked_s`` (same perf_counter readings) so the
    data_wait share of the trace matches ``stall_fraction`` within 2%
    on a loader-bound run."""
    model, run, opt = _fixture()
    runner = StepRunner(model, run, opt, make_host_mesh(1, 1))
    tracer = Tracer()
    reg = MetricsRegistry()
    STEPS = 10
    loop = TrainLoop(runner, log_every=3, tracer=tracer, metrics=reg,
                     device_prefetch=False)
    _, log = loop.run(_batches(sleep_s=0.02), STEPS)
    t = log.telemetry
    xs = [e for e in tracer.chrome_events() if e["ph"] == "X"]
    by = {}
    for e in xs:
        by.setdefault(e["name"], []).append(e)

    assert len(by["step"]) == STEPS
    assert len(by["data_wait"]) == STEPS
    assert {"dispatch", "metrics_resolve", "metrics_drain",
            "device_block"} <= set(by)

    # -- coverage: union of all spans over the first->last-step window
    w0 = min(e["ts"] for e in by["step"])
    w1 = max(e["ts"] + e["dur"] for e in by["step"])
    union = _union_seconds(
        [(max(e["ts"], w0), min(e["ts"] + e["dur"], w1)) for e in xs
         if e["ts"] + e["dur"] > w0 and e["ts"] < w1])
    coverage = union / (w1 - w0)
    assert coverage >= 0.95, f"trace covers only {coverage:.1%} of wall"

    # -- trace == telemetry: the blocked-region spans carry the SAME
    # perf_counter readings as the stall accounting, so their sum IS
    # host_blocked_s (tolerance: an untraced saver-close sliver)
    blocked_names = ("data_wait", "metrics_resolve", "journal_snapshot",
                     "ckpt_commit", "device_block")
    traced_blocked = sum(e["dur"] for n in blocked_names
                         for e in by.get(n, [])) / 1e6
    assert traced_blocked == pytest.approx(t["host_blocked_s"],
                                           rel=0.02, abs=1e-4)
    # the acceptance cross-check: data_wait share vs stall_fraction
    data_wait_s = sum(e["dur"] for e in by["data_wait"]) / 1e6
    assert abs(data_wait_s / t["total_s"] - t["stall_fraction"]) <= 0.02
    # the end-of-run drain span is exactly telemetry['drain_s']
    drain = sum(e["dur"] for e in by["metrics_drain"]) / 1e6
    assert drain == pytest.approx(t["drain_s"], abs=1e-5)

    # -- the metrics registry saw the run too
    assert reg["train_step_time_ms"].count == STEPS - 1
    assert reg["train_stall_fraction"].value \
        == pytest.approx(t["stall_fraction"])
    assert any(n.startswith("grad_") for n in reg.names())


LOOP_SPANS = ("step", "data_wait", "dispatch", "metrics_resolve",
              "metrics_drain", "device_block")


def _profiled_loop(runner, tracer, trace_dir, steps=4):
    """``steps`` loop iterations under a profiler session; the host
    plane's loop-span events."""
    loop = TrainLoop(runner, log_every=2, tracer=tracer,
                     device_prefetch=False)
    jax.profiler.start_trace(str(trace_dir))
    try:
        loop.run(_batches(), steps)
    finally:
        jax.profiler.stop_trace()
    sc = _load_tool("span_clock")
    return sc, sc.host_events(str(trace_dir), LOOP_SPANS + ("train_step",))


def test_loop_spans_mirrored_on_the_profilers_clock(tmp_path):
    """With a Tracer installed each loop span is also a profiler
    annotation: once on the host plane, of the same duration, at one
    offset between the two clocks (what lines the benchmark's host
    spans up with the device's operations).  With the NullTracer the
    profiler sees none of them."""
    model, run, opt = _fixture()
    runner = StepRunner(model, run, opt, make_host_mesh(1, 1))
    TrainLoop(runner, device_prefetch=False).run(_batches(), 1)  # compile
    tracer = Tracer()
    sc, host = _profiled_loop(runner, tracer, tmp_path / "on")
    spans = sc.tracer_spans(tracer.chrome_events())
    assert set(spans) == set(LOOP_SPANS)
    for name in LOOP_SPANS:
        assert len(host[name]) == len(spans[name]), name
    # each iteration is also the profiler's step, numbered from 0
    assert len(host["train_step"]) == len(spans["step"]) == 4
    pairs = sc.pair({n: host[n] for n in LOOP_SPANS}, spans)
    offsets = []
    for name in LOOP_SPANS:
        assert len(pairs[name]) == len(spans[name]), name
        for offset, gap in pairs[name]:
            assert abs(gap) <= 50.0, (name, gap)          # us
            offsets.append(offset)
    assert max(offsets) - min(offsets) <= 100.0             # us

    prev = set_tracer(None)
    try:
        _, host = _profiled_loop(runner, None, tmp_path / "off")
    finally:
        set_tracer(prev)
    assert not any(host.values()), {n: len(v) for n, v in host.items()}


def test_untraced_loop_reads_the_clock_three_times_a_step(monkeypatch):
    """The NullTracer's loop enters no profiler annotation and reads
    ``perf_counter`` three times an iteration between log steps: the
    data wait's two ends (the stall accounting) and the step clock."""
    import repro.observability.trace as trace_mod
    import repro.train.runner as runner_mod

    class Clock:
        n = 0

        def __getattr__(self, name):
            return getattr(time, name)

        def perf_counter(self):
            Clock.n += 1
            return time.perf_counter()

    def refuse(*a, **k):
        raise AssertionError("a profiler annotation with tracing off")

    model, run, opt = _fixture()
    runner = StepRunner(model, run, opt, make_host_mesh(1, 1))
    TrainLoop(runner, device_prefetch=False).run(_batches(), 1)  # compile
    monkeypatch.setattr(runner_mod, "time", Clock())
    monkeypatch.setattr(trace_mod, "time", Clock())
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", refuse)
    prev = set_tracer(None)
    try:
        reads = []
        for steps in (3, 7):
            Clock.n = 0
            TrainLoop(runner, log_every=100, tracer=NULL_TRACER,
                      device_prefetch=False).run(_batches(), steps)
            reads.append(Clock.n)
    finally:
        set_tracer(prev)
    assert (reads[1] - reads[0]) / 4 == 3


def test_trainloop_straggler_monitor_single_process():
    model, run, opt = _fixture()
    runner = StepRunner(model, run, opt, make_host_mesh(1, 1))
    tracer = Tracer()
    loop = TrainLoop(runner, log_every=2, tracer=tracer,
                     straggler_every=2)
    loop.run(_batches(), 6)
    reports = loop.last_straggler_reports
    assert [r["step"] for r in reports] == [2, 4, 6]
    for r in reports:
        assert r["stragglers"] == []          # one rank: balanced
        assert r["summary"]["step"]["median"] > 0
    # the checks themselves were traced on the comm lane
    checks = [e for e in tracer.chrome_events()
              if e["ph"] == "X" and e["name"] == "straggler_check"]
    assert len(checks) == 3


# ---------------------------------------------------------------------------
# end-to-end: traced paged serve engine
# ---------------------------------------------------------------------------


def test_paged_serve_engine_traced_and_metered():
    from repro.serve import PagedServeEngine

    cfg = reduced(get_config("starcoder2-3b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    run = RunConfig(model=cfg, shape=ShapeConfig("s", 16, 2, "decode"),
                    sharding="ddp", param_dtype="float32",
                    activation_dtype="float32")
    tracer = Tracer()
    reg = MetricsRegistry()
    eng = PagedServeEngine(model=model, run=run, page=8, n_pages=64,
                           max_slots=2, use_pallas_decode=False,
                           tracer=tracer, metrics=reg)
    prompts = [list(np.random.RandomState(i + 1).randint(
        4, cfg.vocab_size, n)) for i, n in enumerate((13, 7))]
    rids = [eng.submit(p, 4) for p in prompts]
    out = eng.serve(params)
    assert set(out) == set(rids)

    evs = tracer.chrome_events()
    xs = [e for e in evs if e["ph"] == "X"]
    prefills = [e for e in xs if e["name"] == "prefill"]
    assert sorted(e["args"]["rid"] for e in prefills) == sorted(rids)
    assert all(e["cat"] == "serve" for e in prefills)
    assert len([e for e in xs if e["name"] == "prefill_commit"]) == 2
    ticks = [e for e in xs if e["name"] == "decode_tick"]
    assert len(ticks) >= 1 and all("active" in e["args"] for e in ticks)
    # request lifetime: one async begin/end pair per rid, TTFT instant
    for rid in rids:
        bs = [e for e in evs if e["ph"] == "b" and e["id"] == str(rid)]
        es = [e for e in evs if e["ph"] == "e" and e["id"] == str(rid)]
        assert len(bs) == 1 and len(es) == 1
        assert bs[0]["ts"] <= es[0]["ts"]
    firsts = [e for e in evs if e["ph"] == "i"
              and e["name"] == "first_token"]
    assert sorted(e["args"]["rid"] for e in firsts) == sorted(rids)

    assert reg["serve_requests_submitted"].value == 2
    assert reg["serve_requests_finished"].value == 2
    assert reg["serve_ttft_ms"].count == 2
    assert reg["serve_ttft_ms"].quantile(0.5) > 0
    assert reg["serve_decode_tick_ms"].count == len(ticks)
    assert reg["serve_kv_utilization"].value == 0.0   # all released
    assert reg["serve_active_slots"].value == 0


# ---------------------------------------------------------------------------
# 2-process merge + cross-host straggler detection (real jax.distributed)
# ---------------------------------------------------------------------------

TWO_PROC_BODY = """
    import os, sys, time
    import numpy as np
    from repro.distributed import maybe_initialize_distributed
    maybe_initialize_distributed()
    import jax
    assert jax.process_count() == 2
    from repro.observability import StragglerMonitor, Tracer

    TMP = os.environ["TRACE_TMP"]
    pidx = jax.process_index()
    tr = Tracer(process_index=pidx)
    # rank 1's data_wait is ~10x rank 0's: the deterministic straggler
    mon = StragglerMonitor(tr, every=2, ratio=1.5, min_seconds=1e-3)
    for i in range(4):
        t0 = time.perf_counter()
        with tr.span("data_wait", "data"):
            time.sleep(0.005 + 0.045 * pidx)
        with tr.span("dispatch", "compute"):
            np.dot(np.ones((64, 64)), np.ones((64, 64)))
        tr.complete("step", "loop", t0, time.perf_counter(), step=i)
        mon.maybe_check(i + 1)
    path = tr.flush(TMP)
    n_strag = sum(len(r["stragglers"]) for r in mon.reports)
    print(f"rank={pidx} events={len(tr)} checks={len(mon.reports)} "
          f"stragglers={n_strag} path={path}", flush=True)
"""


def test_two_process_traces_merge_and_straggler_flagged(tmp_path):
    outs = run_workers(TWO_PROC_BODY, 2, timeout=300,
                       extra_env={"TRACE_TMP": str(tmp_path)})
    for pidx, (rc, out, err) in enumerate(outs):
        assert rc == 0, (rc, out, err)
        assert f"rank={pidx}" in out and "checks=2" in out
        # the KV-store allgather gave BOTH ranks the same view: each
        # flags rank 1's data_wait in both check windows (rank 1 may
        # additionally be flagged on the "step" phase it dominates)
        n_strag = int(re.search(r"stragglers=(\d+)", out).group(1))
        assert n_strag >= 2, out
        assert "[straggler] rank=1 phase=data_wait" in out, out

    ts = _load_tool("trace_summary")
    events = ts.load_events([str(tmp_path)])
    xs = ts.spans(events)
    assert {e["pid"] for e in xs} == {0, 1}
    # one coherent timeline: wall-anchored timestamps mean the two
    # ranks' windows overlap (they ran concurrently)
    span_of = lambda pid: (
        min(e["ts"] for e in xs if e["pid"] == pid),
        max(e["ts"] + e["dur"] for e in xs if e["pid"] == pid))
    (a0, a1), (b0, b1) = span_of(0), span_of(1)
    assert max(a0, b0) < min(a1, b1), "rank timelines do not overlap"
    rows = {(r["rank"], r["name"]): r
            for r in ts.flame_rows(events, by_rank=True)}
    assert rows[(0, "step")]["count"] == rows[(1, "step")]["count"] == 4
    # the straggling rank's data_wait dominates the merged flame view
    assert rows[(1, "data_wait")]["total_ms"] \
        > 3 * rows[(0, "data_wait")]["total_ms"]
