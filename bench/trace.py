"""From a profiler trace of the window, and the loop's and loader's spans,
to per-layer metrics and the breakdown.

The device side is the JAX profiler's xplane: one plane per chip, whose
``XLA Ops`` line holds every operation the chip ran.  Its events carry
only the instruction, so each is classified through the compiled step's
text (``hlo_index``).  The host side is the program's own
tracer (``repro.observability.Tracer``): the loop's ``data_wait`` /
``dispatch`` / ``metrics_resolve`` spans and the loader's
``batch_fetch`` spans.  The two clocks are lined up by one span recorded
on both: ``bench_window``, a profiler ``TraceAnnotation`` around the
window and a tracer span with the same ends.
"""
from __future__ import annotations

import bisect
import contextlib
import dataclasses
import glob
import re
from pathlib import Path
from typing import Any, Callable, Dict, List, Sequence, Tuple

Interval = Tuple[int, int]
WINDOW = "bench_window"


# ---------------------------------------------------------------------------
# interval arithmetic (integer nanoseconds)
# ---------------------------------------------------------------------------


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals: Sequence[Interval]) -> int:
    return sum(e - s for s, e in union(intervals))


def clip(intervals: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def minus(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The points of ``a`` not in ``b``."""
    b = union(b)
    out = []
    for s, e in union(a):
        cur = s
        for bs, be in b:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def gaps(intervals: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """The idle intervals of ``[lo, hi)`` between ``intervals``."""
    return minus([(lo, hi)], intervals)


# ---------------------------------------------------------------------------
# the records
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Op:
    """One operation on one chip, in profiler nanoseconds."""
    name: str
    start: int
    end: int
    category: str
    scope: str


@dataclasses.dataclass
class Trace:
    """What the reduction reads: the window on the profiler's clock, each
    chip's operations in it, and the host spans on the same clock."""
    window: Interval
    ops: Dict[str, List[Op]]
    spans: List[Tuple[str, str, int, int]]   # (name, lane, start, end)


COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
CONTROL = ("while", "conditional", "call")


def is_matmul(op: Op) -> bool:
    return op.category in ("convolution", "dot", "fusion:matmul")


def is_collective(op: Op) -> bool:
    return op.category.split(":")[-1].startswith(COLLECTIVES)


def on_core(op: Op) -> bool:
    """An operation that occupies the chip's core while it runs: not an
    async transfer, and not a loop or call, whose body's operations are
    events of their own."""
    return not op.category.startswith("async:") and op.category not in CONTROL


def is_compute(op: Op) -> bool:
    return on_core(op) and not is_collective(op)


def is_grad_allreduce(op: Op) -> bool:
    return "all-reduce" in op.category and "gradsync_bucket" in op.scope


@dataclasses.dataclass
class Context:
    """Everything a per-layer metric reader sees (``bench/metrics``)."""
    config: Dict[str, Any]
    batch: int              # global batch, rows
    chips: int
    steps: int
    peak: Dict[str, float]
    trace: Trace

    is_matmul = staticmethod(is_matmul)
    is_grad_allreduce = staticmethod(is_grad_allreduce)

    @property
    def window_ns(self) -> int:
        return self.trace.window[1] - self.trace.window[0]

    @property
    def window_s(self) -> float:
        return self.window_ns / 1e9

    def _ops(self, pred=None) -> Dict[str, List[Interval]]:
        lo, hi = self.trace.window
        return {d: clip([(o.start, o.end) for o in ops
                         if pred is None or pred(o)], lo, hi)
                for d, ops in self.trace.ops.items()}

    @property
    def busy_s(self) -> float:
        """Union of the intervals in which an operation ran on a chip
        (collectives included, async transfers and loop events not), in
        the window, mean over chips."""
        per = [length(v) for v in self._ops(on_core).values()]
        return sum(per) / max(1, len(per)) / 1e9

    def op_seconds(self, pred: Callable[[Op], bool]) -> float:
        """Time covered by the operations ``pred`` picks, mean over chips."""
        per = [length(v) for v in self._ops(pred).values()]
        return sum(per) / max(1, len(per)) / 1e9

    def exposed_seconds(self, pred: Callable[[Op], bool]) -> float:
        """Time of the ``pred`` operations during which no compute
        operation runs on the same chip, mean over chips."""
        picked = self._ops(pred)
        compute = self._ops(is_compute)
        per = [length(minus(picked[d], compute[d])) for d in picked]
        return sum(per) / max(1, len(per)) / 1e9

    def spans_named(self, name: str, whole: bool = False
                    ) -> List[Interval]:
        """Host spans called ``name``, clipped to the window; with
        ``whole`` unclipped, those that end inside it."""
        lo, hi = self.trace.window
        got = [(s, e) for n, _, s, e in self.trace.spans if n == name]
        if whole:
            return [(s, e) for s, e in got if lo < e <= hi]
        return clip(got, lo, hi)


# ---------------------------------------------------------------------------
# recording
# ---------------------------------------------------------------------------


class HostTracer:
    """The program's span tracer, installed for a traced run, plus the
    ``bench_window`` span that lines its clock up with the profiler's."""

    def __init__(self):
        from repro.observability import Tracer, set_tracer

        self.tracer = Tracer(capacity=1 << 20)
        set_tracer(self.tracer)

    def window(self, t0: float, t1: float):
        self.tracer.complete(WINDOW, "bench", t0, t1)

    def spans(self) -> List[Tuple[str, str, float, float]]:
        """(name, lane, start, end) in the tracer's microseconds."""
        return [(e["name"], e.get("cat", ""), e["ts"], e["ts"] + e["dur"])
                for e in self.tracer.chrome_events() if e["ph"] == "X"]


def start(trace_dir: Path):
    """Start the profiler, without its Python function tracer."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)


def stop():
    import jax

    jax.profiler.stop_trace()


def annotation(on: bool):
    import jax

    return jax.profiler.TraceAnnotation(WINDOW) if on \
        else contextlib.nullcontext()


def hlo_index(hlo_text: str) -> Dict[str, Tuple[str, str]]:
    """Instruction name -> (category, op_name scope) from the compiled
    module's text.  The category is the opcode, or ``fusion:matmul`` for a
    fusion whose computations hold a convolution or dot: the profiler's
    events carry neither, only the instruction."""
    comps: Dict[str, List[Tuple[str, str, List[str], str]]] = {}
    cur = None
    for line in hlo_text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head and not line.startswith(" "):
            cur = comps.setdefault(head.group(1), [])
            continue
        m = _INSTR.match(line)
        if m and cur is not None:
            calls = re.findall(r"(?:calls|to_apply|body|condition)="
                               r"%([\w.\-]+)", line)
            scope = re.search(r'op_name="([^"]*)"', line)
            cur.append((m.group(1), m.group(2), calls,
                        scope.group(1) if scope else ""))
    memo: Dict[str, bool] = {}

    def has_mm(comp: str) -> bool:
        if comp not in memo:
            memo[comp] = False
            memo[comp] = any(op in ("convolution", "dot")
                             or (op == "fusion" and any(map(has_mm, calls)))
                             for _, op, calls, _ in comps.get(comp, []))
        return memo[comp]

    out = {}
    for instrs in comps.values():
        for name, op, calls, scope in instrs:
            if op == "fusion" and any(map(has_mm, calls)):
                op = "fusion:matmul"
            out[name] = (op, scope)
    return out


_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = .*?\b([a-z][a-z\-]*)\(")
_EVENT = re.compile(r"^%([\w.\-]+) = ")


def _short(event_name: str) -> str:
    """An op event's instruction name (its event name is the whole HLO
    line); other events keep their name."""
    m = _EVENT.match(event_name)
    return m.group(1) if m else event_name


# the lines of a chip's plane that the reduction reads
DEVICE_LINES = ("XLA Modules", "XLA Ops", "Async XLA Ops")


def read_profile(trace_dir: Path) -> Dict[str, Any]:
    """The newest profile under ``trace_dir`` as plain data: the
    ``bench_window`` annotation's (start, end), and for each chip the
    events of :data:`DEVICE_LINES` as (name, start, end), all in the
    profiler's nanoseconds."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no profile under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    window, chips = None, {}
    for plane in pd.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW:
                        window = [int(ev.start_ns), int(ev.end_ns)]
        elif plane.name.startswith("/device:TPU:"):
            chips[plane.name] = {
                line.name: [[_short(ev.name), int(ev.start_ns),
                             int(ev.end_ns)] for ev in line.events]
                for line in plane.lines if line.name in DEVICE_LINES}
    if window is None:
        raise ValueError(f"no {WINDOW} annotation in {paths[-1]}")
    return {"window": window, "chips": chips}


def reduce(profile: Dict[str, Any], spans, hlo_text: str) -> Trace:
    """The ``Trace`` of ``profile`` (``read_profile``'s): each chip's
    ``XLA Ops`` events (and, as ``async:<opcode>``, its ``Async XLA Ops``).
    Those that ran inside the step's module (``XLA Modules``) are
    classified by its compiled text ``hlo_text``; others (such as the
    loader's eager masking) keep an empty category.  ``spans`` are the
    host tracer's (name, lane, start, end) in microseconds, moved onto
    the profiler's clock by the ``bench_window`` span both recorded."""
    index = hlo_index(hlo_text)
    module = re.match(r"HloModule ([^\s,]+)", hlo_text).group(1)
    window = tuple(profile["window"])
    ops: Dict[str, List[Op]] = {}
    for chip, lines in profile["chips"].items():
        steps = union([(t0, t1) for name, t0, t1
                       in lines.get("XLA Modules", [])
                       if name.split("(")[0] == module])
        starts = [a for a, _ in steps]
        got = ops.setdefault(chip, [])
        for line_name, prefix in (("XLA Ops", ""),
                                  ("Async XLA Ops", "async:")):
            for name, t0, t1 in lines.get(line_name, []):
                i = bisect.bisect_right(starts, t0) - 1
                inside = i >= 0 and t0 < steps[i][1]
                cat, scope = index.get(name, ("", "")) if inside \
                    else ("", "")
                got.append(Op(name, t0, t1, prefix + cat, scope))
    w_host = next(s for s in spans if s[0] == WINDOW)
    to_ns = lambda us: int(round(window[0] + (us - w_host[2]) * 1e3))
    return Trace(window, ops, [(n, lane, to_ns(s), to_ns(e))
                               for n, lane, s, e in spans if n != WINDOW])


def save(path: Path, profile, spans, hlo_text: str):
    """What ``reduce`` reads, as one gzipped JSON file."""
    import gzip
    import json

    Path(path).parent.mkdir(parents=True, exist_ok=True)
    with gzip.open(path, "wt") as f:
        json.dump({"profile": profile, "spans": spans, "hlo": hlo_text}, f)


def load_saved(path: Path):
    """(profile, spans, hlo_text) as :func:`save` wrote them."""
    import gzip
    import json

    with gzip.open(path, "rt") as f:
        d = json.load(f)
    return d["profile"], [tuple(x) for x in d["spans"]], d["hlo"]


def context(spec, setup, win, peak, trace: Trace) -> Context:
    return Context(config=spec.config, batch=setup.tokens_per_step
                   // spec.config["seq_len"], chips=len(setup.devices),
                   steps=win.steps, peak=peak, trace=trace)


def per_layer(spec, ctx: Context) -> Dict[str, Dict[str, Any]]:
    """Each per-layer metric of the cell whose reader finds something."""
    from bench.harness import load_module

    out = {}
    for m in spec.per_layer:
        v = load_module("metrics", m["name"]).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


# the loop's spans that name what the host was doing in a device gap
HOST_PHASES = ("data_wait", "dispatch", "metrics_resolve", "metrics_drain",
               "device_block")


def breakdown(ctx: Context, top: int = 10) -> Dict[str, List[List[Any]]]:
    """The device operations that took most time (seconds summed over the
    window, mean over chips), and the longest idle gaps of the first chip,
    each named by the loop span that covered most of it."""
    lo, hi = ctx.trace.window
    tot: Dict[str, float] = {}
    for ops in ctx.trace.ops.values():
        for o in ops:
            if not on_core(o):
                continue
            s, e = max(o.start, lo), min(o.end, hi)
            if e > s:
                tot[o.name] = tot.get(o.name, 0.0) + (e - s) / 1e9
    n = max(1, len(ctx.trace.ops))
    device_ops = sorted(([k, v / n] for k, v in tot.items()),
                        key=lambda kv: -kv[1])[:top]
    idle = []
    if ctx.trace.ops:
        first = sorted(ctx.trace.ops)[0]
        busy = [(o.start, o.end) for o in ctx.trace.ops[first]
                if on_core(o)]
        host = [(nm, s, e) for nm, _, s, e in ctx.trace.spans
                if nm in HOST_PHASES]
        for s, e in sorted(gaps(busy, lo, hi), key=lambda g: g[0] - g[1])[:top]:
            cover = {}
            for nm, hs, he in host:
                ov = min(e, he) - max(s, hs)
                if ov > 0:
                    cover[nm] = cover.get(nm, 0) + ov
            name = max(cover, key=cover.get) if cover else "host_other"
            idle.append([name, (e - s) / 1e9])
    return {"device_ops": device_ops, "idle_gaps": idle}
