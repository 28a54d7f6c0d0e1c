"""Readings that set the limits of the comparison
(``bench/workloads/<cell>.json``): the program's own, the control's and
the planted faults', each against the float32 reference on the cell's
own batches and sizes.  The benchmark's own runs never run this.

    python bench/control.py --workload <name> --seeds 1 2 3 \
        [--variants program bf16 half_batch no_exchange]

* ``program``: the program's step runner and loop, built as a run builds
  them (once, for every seed), driven through the first steps from the
  seed: the sound runs that give the lower reading;
* ``bf16``: the reference put in the program's place, computed in
  bfloat16 (float32 master weights and optimizer), the nearest precision
  below the configuration's float32: the control;
* ``half_batch``: the reference with half the batch left out, the mean
  taken over the rest;
* ``no_exchange``: what one of the cell's chips computes when the
  gradient exchange between chips is left out (its own rows, divided by
  the whole batch's count).

``--truths`` names the matmul precisions of the float32 reference that
each variant is read against; by default the configuration's own
(``matmul_precision``), the one the benchmark's runs compare with.  Each
line also names the leaf that gives the gradient and change gaps.

A step that returns its state unchanged reads 1 on the gradient and
change gaps by construction and needs no run.  One JSON line per seed
and variant.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--variants", nargs="+",
                    default=["program", "bf16", "half_batch"])
    ap.add_argument("--truths", nargs="+", default=None,
                    help="matmul precisions of the float32 reference that "
                    "each variant is compared with (default: the "
                    "configuration's own)")
    args = ap.parse_args(argv)
    import jax.numpy as jnp

    from bench import harness
    from bench.run import use_compile_cache

    spec = harness.load_spec(args.workload, ROOT)
    truths = args.truths or [spec.config["matmul_precision"]]
    names = leaf_names(spec.config)
    try:
        devices = harness.chips(spec.chips)
    except harness.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    use_compile_cache()
    batch = spec.cell["batch_per_chip"] * spec.chips
    n = spec.traffic["reference_steps"]
    source = harness.load_module("sources", spec.traffic["source"])
    variants = {
        "bf16": dict(dtype=jnp.bfloat16),
        "half_batch": dict(rows=slice(0, batch // 2)),
        "no_exchange": dict(rows=slice(0, batch // spec.chips),
                            global_den=True)}
    runner = harness.make_runner(spec, devices) \
        if "program" in args.variants else None
    for seed in args.seeds:
        got = {}
        if runner is not None:
            s = harness.build(spec, devices, seed, peak_flops=float("nan"),
                              root=ROOT, runner=runner)
            harness.setup_steps(s, n, 0)
            got["program"] = s.readings
            batches = s.source.reference_batches(n)
            harness.release(s)
        else:
            batches = source.control_batches(spec, batch, seed, ROOT, n)
        readings = {}
        for p in truths:
            t = time.perf_counter()
            readings[p] = harness.reference_readings(spec, seed, batches,
                                                     devices, precision=p)
            t_ref = time.perf_counter() - t
        for v in args.variants:
            if v != "program":
                got[v] = harness.reference_readings(spec, seed, batches,
                                                    devices, **variants[v])
            for p, truth in readings.items():
                print(json.dumps({"workload": spec.name, "seed": seed,
                                  "variant": v, "truth": p,
                                  "reference_s": t_ref,
                                  **harness.gaps(got[v], truth),
                                  **worst(got[v], truth, names)}),
                      flush=True)
    return 0


def leaf_names(c):
    import jax

    from bench import harness
    from bench.reference import core

    flat, _ = jax.tree_util.tree_flatten_with_path(
        harness.model_of(c).param_shapes(c), is_leaf=core.is_shape)
    return [jax.tree_util.keystr(k) for k, _ in flat]


def worst(prog, ref, names):
    """The leaf behind each of the gradient and change gaps."""
    import numpy as np

    from bench import harness

    rg = np.asarray(ref["grad_norms"])
    live = rg >= harness.NEGLIGIBLE * np.median(rg)
    out = {}
    for key, name in (("grad_norms", "grad"), ("change_norms", "change")):
        p, r = np.asarray(prog[key]), np.asarray(ref[key])
        gap = np.where(live, np.abs(p - r) / np.maximum(
            r, np.median(r[live])), -1.0)
        out[f"{name}_worst"] = names[int(np.argmax(gap))]
        out[f"{name}_median_leaf_gap"] = float(np.median(gap[live]))
        # signed: the program's global norm over the reference's, less 1
        out[f"{name}_global_ratio"] = float(
            np.sqrt(np.sum(p[live] ** 2) / np.sum(r[live] ** 2)) - 1)
    return out


if __name__ == "__main__":
    sys.exit(main())
