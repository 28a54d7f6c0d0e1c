"""One run of one benchmark cell on the chips of this machine.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the program's step runner and training loop for the cell,
its data source and its state from ``--seed``, compiles (served from
JAX's persistent cache in ``<checkout>/.jax_cache`` unless
``JAX_COMPILATION_CACHE_DIR`` names another), and drives the first steps,
whose readings the reference checks.  The window then runs a whole
number of steps that lasts about ``--seconds``.  With ``--trace 1`` a
profiler trace of the window gives the cell's per-layer metrics instead
of its end-to-end ones.  The reference runs after the window.

The last line of standard output is one JSON object; the numbers
compared with the reference are the last lines of standard error.  With
no TPU, or fewer chips than the cell asks for, the run prints no result
and exits 2.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", type=Path, default=None,
                    help="with --trace 1, also write the reduced profile, "
                    "the host spans and the step's compiled text here")
    return ap.parse_args(argv)


def use_compile_cache():
    """JAX's persistent compilation cache at a fixed path in the checkout
    (the path is part of the key), every program in it."""
    import jax

    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def main(argv=None) -> int:
    args = parse(argv)
    import numpy as np

    from bench import harness, peaks
    from bench import trace as tr

    spec = harness.load_spec(args.workload, ROOT)
    try:
        devices = harness.chips(spec.chips)
    except harness.NoChip as e:
        print(f"bench: {e}; nothing run", file=sys.stderr)
        return 2
    use_compile_cache()
    peak = peaks.peaks_for(devices[0].device_kind)

    t = spec.traffic
    n_ref, n_warm = t["reference_steps"], t["warmup_steps"]
    n_win = harness.window_steps(args.seconds, spec.cell["step_s"])
    first = n_ref + n_warm
    rng = np.random.default_rng([args.seed, 1])
    sample = rng.choice(np.arange(first, first + n_win),
                        size=min(t["check_window_batches"], n_win),
                        replace=False)
    tracer = tr.HostTracer() if args.trace else None

    marks = [("start", T_START), ("chips", time.perf_counter())]
    runner = harness.make_runner(spec, devices)
    marks.append(("runner", time.perf_counter()))
    s = harness.build(spec, devices, args.seed, peak_flops=peak["bf16_flops"],
                      root=ROOT, runner=runner,
                      keep_steps=[*range(n_ref), *map(int, sample)])
    marks.append(("state and source", time.perf_counter()))
    harness.setup_steps(s, n_ref, n_warm)
    marks.append(("first steps", time.perf_counter()))
    setup_s = time.perf_counter() - T_START
    print("set-up " + ", ".join(f"{name} {t - t0:.3f} s" for (_, t0), (
        name, t) in zip(marks, marks[1:])), file=sys.stderr, flush=True)

    trace_dir = ROOT / "runs" / "bench" / "trace" / args.workload
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        tr.start(trace_dir)
    with tr.annotation(bool(args.trace)):
        win = harness.measure(
            s, n_win, annotate=tracer.window if tracer is not None else None)
    if args.trace:
        tr.stop()
    if win.compiles or win.traces:
        raise RuntimeError(f"{win.compiles} compilation(s) and {win.traces} "
                           f"trace(s) inside the measured window")
    memory = harness.memory_peak(devices)
    result = {"correct": False, "attempted": n_win,
              "failed": sum(not math.isfinite(x) for x in win.losses),
              "metrics": {}, "device": {**harness.device_info(devices),
                                        "memory_peak_bytes": memory}}
    if args.trace:
        profile, spans = tr.read_profile(trace_dir), tracer.spans()
        hlo = s.runner.compiled.as_text()
        shutil.rmtree(trace_dir, ignore_errors=True)
        if args.keep_trace:
            tr.save(args.keep_trace, profile, spans, hlo)
        ctx = tr.context(spec, s, win, peak, tr.reduce(profile, spans, hlo))
        result["metrics"] = tr.per_layer(spec, ctx)
        result["device"].update(busy_s=ctx.busy_s, window_s=ctx.window_s)
        result["breakdown"] = tr.breakdown(ctx)
    else:
        result["metrics"] = {
            "tokens_per_s": {"value": n_win * s.tokens_per_step / win.seconds,
                             "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}

    ref_batches = s.source.reference_batches(n_ref)
    values = s.source.check(s.feed.kept)
    prog = s.readings
    harness.release(s)
    t_ref = time.perf_counter()
    ref = harness.reference_readings(spec, args.seed, ref_batches, devices)
    values = {**harness.gaps(prog, ref), **values}
    checks = harness.checks(values, spec.cell["limits"])
    result["correct"] = harness.is_correct(checks) and not result["failed"]
    result["reference_s"] = time.perf_counter() - t_ref
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
